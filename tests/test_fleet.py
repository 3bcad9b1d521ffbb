"""The batched fleet contract (DESIGN.md §7): a vmapped B-cluster sweep is
element-wise identical to sequential single-cluster runs at the same
padded shapes and seeds, padding is inert, and one static shape costs one
compile.  Plus the §7.1 epoch-digest contract: the device-resident
(fused/donated) pipeline reproduces the PR-1 host-marshalling reports,
the multi-epoch scan equals the epoch-by-epoch loop, and per-epoch
device→host traffic stays O(digest)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import state as SM
from repro.core import step as step_mod
from repro.core.cluster_config import ClusterConfig, SiteConfig
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim, CountingJit, hist_percentile
from repro.core.state import DEAD

_INT_FIELDS = ("reads_arrived", "writes_arrived", "reads_served",
               "writes_committed", "n_secretaries", "n_observers",
               "leader_changes", "no_leader_ticks", "killed")
_FLOAT_FIELDS = ("read_lat_mean", "read_lat_max", "write_lat_mean",
                 "write_lat_p95", "write_lat_p99", "cost")


def _small_cluster(name="small", followers=(2, 2, 1), max_log=1024):
    sites = tuple(
        SiteConfig(f"{name}-s{i}", followers=f, rtt_intra=1,
                   rtt_inter=6 + 2 * i, on_demand_price=0.0416,
                   spot_price_mean=0.0125)
        for i, f in enumerate(followers))
    return ClusterConfig(name=name, sites=sites, max_log=max_log,
                         key_space=256, max_secretaries=4,
                         max_observers=8, period_ticks=60)


def _assert_reports_equal(a, b, ctx=""):
    for f in _INT_FIELDS:
        assert getattr(a, f) == getattr(b, f), \
            f"{ctx}: {f}: fleet={getattr(a, f)} solo={getattr(b, f)}"
    for f in _FLOAT_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        if np.isnan(x) and np.isnan(y):
            continue
        assert np.isclose(x, y, rtol=1e-4, equal_nan=True), \
            f"{ctx}: {f}: fleet={x} solo={y}"


def test_batched_equals_sequential():
    """B=3 vmapped sweep == three sequential BWRaftSim runs, same seeds."""
    cfg = _small_cluster()
    knobs = [dict(write_rate=6.0, read_rate=24.0, phi=0.0, seed=0),
             dict(write_rate=12.0, read_rate=12.0, phi=0.05, seed=1),
             dict(write_rate=3.0, read_rate=48.0, phi=0.02, seed=2)]
    fleet = FleetSim([MemberSpec(cfg=cfg, **k) for k in knobs])
    fleet_reports = fleet.run(3)
    for i, k in enumerate(knobs):
        solo_reports = BWRaftSim(cfg, **k).run(3)
        for e, (a, b) in enumerate(zip(fleet_reports[i], solo_reports)):
            _assert_reports_equal(a, b, ctx=f"member {i} epoch {e}")
            # control plane decided identically too
            if a.decision is not None or b.decision is not None:
                assert (a.decision.dk_s, a.decision.dk_o) == \
                    (b.decision.dk_s, b.decision.dk_o)


def test_heterogeneous_fleet_matches_padded_solo():
    """A small cluster batched next to a bigger one (so it gets padded on
    every axis) reproduces a solo run at the same padded shapes."""
    small = _small_cluster("padded-small", followers=(2, 1), max_log=512)
    big = _small_cluster("big", followers=(3, 3, 2, 2), max_log=1024)
    fleet = FleetSim([
        MemberSpec(cfg=small, write_rate=6.0, read_rate=24.0, seed=4),
        MemberSpec(cfg=big, write_rate=12.0, read_rate=24.0, seed=5,
                   mode="raft"),
    ])
    pads = fleet.pads_for(0)
    assert pads["pad_nodes"] > 0 and pads["pad_sites"] > 0 \
        and pads["pad_log"] > 0
    fleet_reports = fleet.run(2)
    solo = BWRaftSim(small, write_rate=6.0, read_rate=24.0, seed=4,
                     **pads).run(2)
    for e, (a, b) in enumerate(zip(fleet_reports[0], solo)):
        _assert_reports_equal(a, b, ctx=f"epoch {e}")


def test_padding_and_masking_inert():
    """Padded slots never wake up, padded sites never host instances, and
    the padded cluster still does its job."""
    small = _small_cluster("inert-small", followers=(2, 1), max_log=512)
    big = _small_cluster("inert-big", followers=(3, 3, 2, 2))
    fleet = FleetSim([
        MemberSpec(cfg=small, write_rate=6.0, read_rate=24.0, seed=7),
        MemberSpec(cfg=big, write_rate=6.0, read_rate=24.0, seed=8),
    ])
    reports = fleet.run(2)
    st = {k: np.asarray(v) for k, v in fleet.state.items()}
    n_real = small.max_nodes
    assert (st["role"][0, n_real:] == DEAD).all(), \
        "padded slots must stay DEAD"
    assert not st["alive"][0, n_real:].any(), \
        "padded slots must never come alive"
    site = fleet.members[0].static["site"]
    assert (site < small.num_sites).all(), \
        "no node may map to a padded site"
    last = reports[0][-1]
    assert last.no_leader_ticks == 0 and last.writes_committed > 0, \
        "padded cluster must still reach steady state"

    # padding shifts the RNG sample path but not the regime: an unpadded
    # solo run of the same cluster lands in the same goodput band
    unpadded = BWRaftSim(small, write_rate=6.0, read_rate=24.0,
                         seed=7).run(2)[-1]
    assert unpadded.writes_committed > 0
    ratio = last.goodput / max(unpadded.goodput, 1)
    assert 0.5 < ratio < 2.0, (last.goodput, unpadded.goodput)


def test_one_compile_per_static_shape():
    """Different sweep grids at one static shape share one compilation."""
    cfg = _small_cluster("compile", followers=(1, 1), max_log=256)
    a = FleetSim.from_sweep(cfg, {"phi": [0.0, 0.1]}, write_rate=4.0,
                            read_rate=8.0, seed=0)
    a.run(2)
    assert a.compile_count == 1
    b = FleetSim.from_sweep(cfg, {"write_rate": [2.0, 16.0]},
                            read_rate=8.0, seed=3)
    b.run(1)
    # same shapes -> same cached program; new knobs are just jit arguments
    assert b._epoch_fn is a._epoch_fn
    assert b.compile_count == 1


def test_digest_pipeline_matches_host_pipeline():
    """§7.1 equivalence: the fused/donated digest epoch reproduces the
    PR-1 host-marshalling EpochReports — exact counters, histogram-exact
    latency stats — including the control-plane decisions of a managing
    member."""
    cfg = _small_cluster("digest")
    specs = [MemberSpec(cfg=cfg, write_rate=6.0, read_rate=24.0, phi=0.02,
                        seed=0),
             MemberSpec(cfg=cfg, mode="raft", write_rate=12.0,
                        read_rate=12.0, seed=1, manage_resources=False)]
    dev = FleetSim(specs)                       # pipeline="device" default
    host = FleetSim(specs, pipeline="host")
    dev_reports, host_reports = dev.run(3), host.run(3)
    for i in range(len(specs)):
        for e, (a, b) in enumerate(zip(dev_reports[i], host_reports[i])):
            _assert_reports_equal(a, b, ctx=f"member {i} epoch {e}")
            if a.decision is not None or b.decision is not None:
                assert (a.decision.dk_s, a.decision.dk_o) == \
                    (b.decision.dk_s, b.decision.dk_o)
    # the point of the digest: per-epoch D2H is O(digest), not O(B*N*(L+K))
    assert dev.d2h_bytes < host.d2h_bytes / 100, \
        (dev.d2h_bytes, host.d2h_bytes)


def test_multi_epoch_scan_equals_epoch_by_epoch():
    """§7.1 fast path: a fixed-role fleet run as ONE scan-of-scans
    dispatch equals the same fleet stepped epoch by epoch at the same
    seeds/shapes."""
    cfg = _small_cluster("scan")
    specs = [MemberSpec(cfg=cfg, write_rate=6.0, read_rate=24.0, phi=0.02,
                        seed=3, manage_resources=False, prelease=(2, 4)),
             MemberSpec(cfg=cfg, mode="raft", write_rate=8.0,
                        read_rate=16.0, seed=4, manage_resources=False)]
    fast = FleetSim(specs)
    slow = FleetSim(specs)
    assert fast.single_dispatch_eligible
    fast_reports = fast.run(4)                  # auto single dispatch
    slow_reports = slow.run(4, single_dispatch=False)
    for i in range(len(specs)):
        for e, (a, b) in enumerate(zip(fast_reports[i], slow_reports[i])):
            _assert_reports_equal(a, b, ctx=f"member {i} epoch {e}")

    # a managing fleet must refuse the forced fast path
    with pytest.raises(AssertionError):
        FleetSim([MemberSpec(cfg=cfg, seed=0)]).run(2, single_dispatch=True)


def test_preleased_fleet_matches_solo():
    """Fixed-role members (prelease) stay trajectory-equal to a solo
    BWRaftSim wired the same way at the same seed."""
    cfg = _small_cluster("pre")
    spec = dict(write_rate=6.0, read_rate=24.0, phi=0.0, seed=5,
                manage_resources=False, prelease=(2, 4))
    fleet_reports = FleetSim([MemberSpec(cfg=cfg, **spec)]).run(3)
    solo_reports = BWRaftSim(cfg, **spec).run(3)
    for e, (a, b) in enumerate(zip(fleet_reports[0], solo_reports)):
        _assert_reports_equal(a, b, ctx=f"epoch {e}")
    # observers survive a fixed-role run; preleased secretaries are
    # stopped by the FIRST election (paper Step 1) and — manager off —
    # never re-provisioned, so only the observer complement persists
    assert fleet_reports[0][-1].n_observers > 0


def test_lease_fixed_matches_solo_recipe():
    """The fixed-role sweep recipe (stabilize -> lease_fixed -> single
    dispatch, as in fig12/fig13) equals the sequential run/_lease/run."""
    cfg = _small_cluster("fixed")
    spec = dict(write_rate=6.0, read_rate=24.0, phi=0.02, seed=9,
                manage_resources=False)
    fleet = FleetSim([MemberSpec(cfg=cfg, **spec)])
    fleet.run(1)
    fleet.lease_fixed(2, 4)
    solo = BWRaftSim(cfg, **spec)
    solo.run(1)
    solo.lease_fixed(2, 4)
    # the comparison is not vacuous: both wired the same live spot
    # complement before the single dispatch (checked here, because the
    # phi kills of the next 60 ticks may revoke all of it)
    wired = np.isin(np.asarray(solo.state["role"]),
                    (SM.SECRETARY, SM.OBSERVER)) & \
        np.asarray(solo.state["alive"])
    assert wired.sum() > 0
    np.testing.assert_array_equal(
        np.asarray(fleet.state["role"])[0], np.asarray(solo.state["role"]))
    fleet_reports = fleet.run(3)                # ONE dispatch
    solo_reports = solo.run(3)
    for e, (a, b) in enumerate(zip(fleet_reports[0], solo_reports)):
        _assert_reports_equal(a, b, ctx=f"epoch {e}")


def test_hist_percentile_matches_numpy():
    """The digest recovers np.percentile exactly: integer latencies in
    unit bins fully determine the sorted sample."""
    rng = np.random.default_rng(0)
    for size in (1, 2, 7, 100):
        sample = rng.integers(0, 60, size)
        hist = np.bincount(sample, minlength=61)
        for q in (50, 95, 99):
            assert np.isclose(hist_percentile(hist, q),
                              np.percentile(sample, q)), (size, q)
    assert np.isnan(hist_percentile(np.zeros(5, int), 95))


def test_apply_step_last_wins_scatter():
    """The vectorized apply scatter preserves log order: for duplicate
    keys inside one apply window the LAST committed entry wins."""
    N, L, K, A = 2, 8, 4, 4
    state = {
        "log_term": jnp.zeros((N, L), jnp.int32),
        "log_key": jnp.asarray([[1, 1, 2, 1, 0, 0, 0, 0],
                                [3, 3, 3, 3, 0, 0, 0, 0]], jnp.int32),
        "log_val": jnp.asarray([[10, 20, 30, 40, 0, 0, 0, 0],
                                [5, 6, 7, 8, 0, 0, 0, 0]], jnp.int32),
        "applied_len": jnp.zeros((N,), jnp.int32),
        "commit_len": jnp.asarray([4, 3], jnp.int32),
        "alive": jnp.asarray([True, True]),
        "kv": jnp.full((N, K), -1, jnp.int32),
    }
    out = step_mod.apply_step(state, {"max_apply": A}, {})
    kv = np.asarray(out["kv"])
    # row 0 commits keys [1,1,2,1]: key1 -> 40 (last), key2 -> 30
    assert kv[0, 1] == 40 and kv[0, 2] == 30 and kv[0, 0] == -1
    # row 1 commits only 3 of the 4 entries for key3 -> third value wins
    assert kv[1, 3] == 7
    assert np.asarray(out["applied_len"]).tolist() == [4, 3]


def test_compile_count_fallback_without_cache_size():
    """CountingJit counts the jit cache's own compilations, and a jax
    without the private `_cache_size` fails loudly instead of falling
    back to a guessed count."""
    fn = CountingJit(lambda x: x * 2)
    fn(jnp.zeros((4,)))
    fn(jnp.ones((4,)))                  # same shape: no new compile
    fn(jnp.zeros((8,)))                 # new shape: second compile
    assert fn.cache_size() == 2
    fn.fn = lambda *a: None             # a jax without _cache_size()
    with pytest.raises(AttributeError):
        fn.cache_size()


def test_sweep_cross_product_order():
    cfg = _small_cluster("order", followers=(1, 1), max_log=256)
    fleet = FleetSim.from_sweep(cfg, {"phi": [0.0, 0.1],
                                      "write_rate": [2.0, 4.0]},
                                read_rate=8.0)
    assert fleet.shapes.B == 4
    got = [(m.spec.phi, m.spec.write_rate) for m in fleet.members]
    assert got == [(0.0, 2.0), (0.0, 4.0), (0.1, 2.0), (0.1, 4.0)]
    with pytest.raises(AssertionError):
        FleetSim.from_sweep(cfg, {"not_a_knob": [1]})
