"""The serving-surface invariants (DESIGN.md §11):

1. *closed-loop bit-identity* — the open-loop plumbing is strictly
   additive: pre-PR closed-loop configs replay the golden trajectories
   captured before the serving surface landed, report-for-report and
   state-leaf-for-state-leaf (sha256).
2. *goodput math pin* — the device-resident digest histograms (read AND
   write) equal a numpy recomputation over the raw per-request
   latencies collected tick by tick on the host path, and
   `goodput_under_deadline` equals the naive `(latency <= D).sum()`.
"""
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.step as step_mod
from repro.configs.bwraft_kv import CONFIG
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim, goodput_under_deadline
from repro.core.state import hist_bins
from repro.workload import ConstantRate, DiurnalRate, FlashCrowd, OpenLoop

GOLDEN = pathlib.Path(__file__).parent / "data" / "closed_loop_golden.json"


def _check_golden(name, sim_state, reports, g, note=""):
    """Reports: ints compare exactly, floats by repr round-trip; state
    leaves by sha256 over the raw bytes.  Only keys recorded in the
    golden are compared — fields/leaves ADDED by this PR (read
    percentiles, `read_lat_hist`) are allowed to exist, but nothing the
    pre-PR code produced may change."""
    for i, grep in enumerate(g["reports"]):
        rep = reports[i]
        for k, v in grep.items():
            got = getattr(rep, k)
            if isinstance(v, str):
                assert repr(float(got)) == v, \
                    f"{name} epoch {i}: {k} = {float(got)!r}, golden {v}; " \
                    f"{note}"
            else:
                assert int(got) == v, \
                    f"{name} epoch {i}: {k} = {int(got)}, golden {v}; " \
                    f"{note}"
    for k, leaf in g["state"].items():
        arr = np.asarray(sim_state[k])
        assert list(arr.shape) == leaf["shape"], (name, k)
        assert str(arr.dtype) == leaf["dtype"], (name, k)
        got = hashlib.sha256(arr.tobytes()).hexdigest()
        assert got == leaf["sha256"], \
            f"{name}: state leaf {k!r} diverged from pre-PR trajectory; " \
            f"{note}"


def test_closed_loop_solo_bit_identical_to_golden(golden_note):
    """A managed solo run (control plane + synthetic market on) replays
    the pre-PR trajectory exactly: the open-loop path is compiled in but
    `open_loop=False` selects the scalar knob, same lam -> same draws."""
    golden = json.loads(GOLDEN.read_text())
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=32.0, phi=0.02,
                    seed=0)
    reps = sim.run(2)
    _check_golden("solo_managed", sim.state, reps, golden["solo_managed"],
                  golden_note(golden))


def test_closed_loop_fleet_bit_identical_to_golden(golden_note):
    """The fixed-role fleet scan (batched members, one of them plain
    Raft) replays its pre-PR trajectory through the widened cfg_c."""
    golden = json.loads(GOLDEN.read_text())
    specs = [MemberSpec(cfg=CONFIG, write_rate=6.0, read_rate=24.0, seed=1,
                        manage_resources=False, prelease=(2, 6)),
             MemberSpec(cfg=CONFIG, mode="raft", write_rate=12.0,
                        read_rate=12.0, seed=2, manage_resources=False)]
    fleet = FleetSim(specs)
    fleet.run(3)
    g = golden["fleet_fixed"]
    for m, (member_reports, gm) in enumerate(
            zip(fleet.reports, g["reports"])):
        _check_golden(f"fleet_fixed[{m}]", {}, member_reports,
                      {"reports": gm, "state": {}}, golden_note(golden))
    _check_golden("fleet_fixed", fleet.state, [],
                  {"reports": [], "state": g["state"]}, golden_note(golden))


# ------------------------------------------------------------------ #
# goodput math pin: device digest == numpy over raw latencies
# ------------------------------------------------------------------ #
P95_DEADLINE = 30


@pytest.fixture(scope="module")
def digest_and_raw():
    """Run ONE epoch twice from the same (state, rng): once on the
    device digest path, once tick-by-tick on the host collecting the
    raw per-request latency samples the digest histograms summarize."""
    plan = OpenLoop(write=DiurnalRate(6.0, amplitude=0.6),
                    read=FlashCrowd(ConstantRate(30.0), mult=5.0,
                                    every_ticks=25, burst_ticks=4),
                    ticks=CONFIG.period_ticks)
    sim = BWRaftSim(CONFIG, write_rate=0.0, read_rate=0.0, seed=4,
                    manage_resources=False, arrivals=plan)
    sim._lease(1, 5)
    # snapshot before run_epoch: the jitted epoch donates its buffers
    state0 = jax.tree.map(jnp.array, sim.state)
    _, sub = jax.random.split(sim.rng)
    sim.run_epoch()
    dg = sim.last_digest

    T = CONFIG.period_ticks
    H = hist_bins(CONFIG)
    static, cfg_c = sim.static, sim.cfg_c
    tickfn = jax.jit(lambda s, r: step_mod.tick(s, static, cfg_c, r))
    st = state0
    read_raw = []
    # device_epoch splits the epoch key into T per-tick keys; mirroring
    # the split reproduces the scan trajectory tick for tick
    for r in jax.random.split(sub, T):
        st, m = tickfn(st, r)
        served = np.asarray(m["read_served_tick"])
        lat = np.asarray(m["read_lat_tick"])
        for n in np.where(served > 0)[0]:
            read_raw.extend([int(lat[n])] * int(served[n]))
    sub_t = np.asarray(st["entry_submit_t"])
    com_t = np.asarray(st["entry_commit_t"])
    done = (sub_t >= 0) & (com_t >= 0)
    write_raw = (com_t[done] - sub_t[done]).astype(np.int64)
    return dg, np.asarray(read_raw, np.int64), write_raw, H


def test_read_histogram_equals_numpy_recomputation(digest_and_raw):
    dg, read_raw, _, H = digest_and_raw
    assert read_raw.size > 0, "epoch served no reads — workload too thin"
    want = np.bincount(np.clip(read_raw, 0, H - 1), minlength=H)
    np.testing.assert_array_equal(np.asarray(dg["read_lat_hist"]), want)
    assert int(dg["reads_served"]) == read_raw.size


def test_write_histogram_equals_numpy_recomputation(digest_and_raw):
    dg, _, write_raw, H = digest_and_raw
    assert write_raw.size > 0, "epoch committed no writes"
    want = np.bincount(np.clip(write_raw, 0, H - 1), minlength=H)
    np.testing.assert_array_equal(np.asarray(dg["write_lat_hist"]), want)


def test_goodput_equals_raw_latency_count(digest_and_raw):
    """`goodput_under_deadline` off the device histograms == the naive
    numpy count over the raw latencies, for BOTH read and write — the
    arithmetic `benchmarks/perf_serving.py` builds its SLO rows on."""
    dg, read_raw, write_raw, H = digest_and_raw
    assert P95_DEADLINE < H - 1          # deadline clear of the clip bin
    got_r = goodput_under_deadline(dg["read_lat_hist"], P95_DEADLINE)
    got_w = goodput_under_deadline(dg["write_lat_hist"], P95_DEADLINE)
    assert got_r == int((read_raw <= P95_DEADLINE).sum())
    assert got_w == int((write_raw <= P95_DEADLINE).sum())
    # edge cases: negative deadline is empty; a deadline past the last
    # bin is total throughput
    assert goodput_under_deadline(dg["read_lat_hist"], -1) == 0
    assert goodput_under_deadline(dg["read_lat_hist"], 10 * H) == \
        read_raw.size
