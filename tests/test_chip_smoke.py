"""`chip_smoke.py`'s phases on the CPU at a tiny size.

The script runs on the chip at the paper cluster's widths; here the
same phase functions run with the kernels in interpret mode on a
two-member fleet, a 1,024-key space and a 16-slot digest tier, so
tier-1 covers the smoke's control flow and its pallas-vs-XLA checks.
"""
import importlib.util
import pathlib

import pytest

from repro.core.cluster_config import ClusterConfig, SiteConfig

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    sites = tuple(
        SiteConfig(f"smoke-s{i}", followers=f, rtt_intra=1,
                   rtt_inter=6 + 2 * i, on_demand_price=0.0416,
                   spot_price_mean=0.0125)
        for i, f in enumerate((2, 2, 1)))
    return ClusterConfig(name="smoke", sites=sites, max_log=512,
                         key_space=1024, max_secretaries=4,
                         max_observers=8, period_ticks=40)


@pytest.fixture(scope="module")
def clock():
    from repro import compile_cache
    return compile_cache.clock()


def test_device_check_refuses_cpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.check_device()
    assert "'cpu'" in str(exc.value)
    assert "platform=cpu" in capsys.readouterr().out


def test_fleet_phases_agree_across_backends(smoke, cfg, clock):
    kw = dict(epochs=2, seed=0, clock=clock)
    xla = smoke.run_fleet(cfg, [0.0, 0.05], [8.0], backend="xla", **kw)
    pallas = smoke.run_fleet(cfg, [0.0, 0.05], [8.0], backend="pallas",
                             **kw)
    for path in ("managed", "fixed"):
        r = xla[path]
        assert r["B"] == 2 and r["cluster_ticks_per_s"] > 0
        assert r["d2h_bytes_per_epoch"] < r["state_bytes"]
    assert xla["managed"]["compile_count"] == 1       # one epoch program
    assert xla["fixed"]["compile_count"] == 1         # only the scan is new
    smoke.compare_fleets(xla, pallas, "fleet")


def test_compare_digests_rejects_an_integer_difference(smoke):
    import numpy as np
    a = {"n": np.arange(4, dtype=np.int32), "x": np.ones(2, np.float32)}
    b = {"n": np.arange(4, dtype=np.int32) + (np.arange(4) == 3),
         "x": np.ones(2, np.float32)}
    assert smoke.compare_digests(a, a, "same") == 0
    with pytest.raises(smoke.SmokeFailure, match="integer leaf n"):
        smoke.compare_digests(a, b, "off-by-one")


def test_compare_digests_float_rule(smoke):
    """Float leaves are exact unless a tolerance is given (the grouped
    reduction's), and then still bounded by it."""
    import numpy as np
    a = {"g": {"x": np.ones(2, np.float32)}}
    b = {"g": {"x": np.float32([1.0, 1.0 + 2**-20])}}
    with pytest.raises(smoke.SmokeFailure, match="float leaf x"):
        smoke.compare_digests(a, b, "exact")
    assert smoke.compare_digests(a, b, "group", float_rtol=1e-4) == 1
    with pytest.raises(smoke.SmokeFailure, match="float leaf x"):
        smoke.compare_digests(a, b, "group", float_rtol=1e-7)


def test_multiraft_phase_agrees_across_backends(smoke, cfg):
    ref = smoke.run_multiraft(cfg, shards=2, epochs=2, seed=0,
                              backend="xla")
    got = smoke.run_multiraft(cfg, shards=2, epochs=2, seed=0,
                              backend="pallas")
    assert smoke.compare_multiraft(ref, got) == 0


def test_kv_phase_reads_back_and_survives_revocation(smoke, cfg):
    kv = smoke.run_kv(cfg, n_observers=16, n_keys=12, seed=0,
                      backend="pallas")
    assert kv["read_back"] == kv["gets"]
    assert kv["spot_killed"] > 0 and kv["dobs_killed"] > 0
