"""Flight-recorder tests (DESIGN.md §14, ISSUE 10).

The §14 contract, pinned:

- **Golden bit-identity** — with `trace_on=0` the instrumented code
  must replay the committed pre-instrumentation fixture
  (`tests/data/trace_golden.json`) bit for bit: reports AND state-leaf
  hashes, solo managed and fixed-role fleet.  The gated scatter writes
  nothing when off; toggling never recompiles (CountingJit-asserted).
- **Host-replay equivalence** — events decoded from the ring must
  match what a host loop recomputes from the raw state transitions
  (alive drops, leader presence, commit advances, warn/reprieve).
- **Exact drop accounting** — a capacity sweep with forced overflow:
  decoded + dropped == emitted per class at every capacity, and the
  small-ring event stream is a per-drain suffix of the big-ring one.
- **First-tick leader_changes** — a leader elected on the FIRST tick
  of an epoch counts, in the in-scan digest AND the host `build_report`
  form, pinned against the trace-derived elect count (the pre-§14
  blindness this PR fixes).
"""
import json
import pathlib
from collections import Counter

import numpy as np
import jax
import pytest

from repro.configs.bwraft_kv import CONFIG
from repro.core import state as SM
from repro.core import step as step_mod
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import (BWRaftSim, build_report, device_epoch,
                                make_cfg_arrays)
from repro.trace import (CLASS_NAMES, EV_COMMIT, EV_ELECT, EV_KILL,
                         EV_REPRIEVE, EV_SEC_STOP, EV_WARN, NCLASS,
                         DrainCursor, default_mask, leader_timeline,
                         timeline, to_perfetto)
from repro.trace import metrics as trace_metrics

GOLDEN = pathlib.Path(__file__).parent / "data" / "trace_golden.json"


def _hash(arr) -> str:
    import hashlib
    return hashlib.sha256(np.ascontiguousarray(
        np.asarray(arr)).tobytes()).hexdigest()


def _reports_match(greports, reports):
    for grep, rep in zip(greports, reports):
        for k, v in grep.items():
            got = getattr(rep, k)
            ok = (repr(float(got)) == v if isinstance(v, str)
                  else int(got) == v)
            if not ok:
                return False, (k, v, got)
    return True, None


def _state_match(gstate, state):
    for k, leaf in gstate.items():
        arr = np.asarray(state[k])
        if list(arr.shape) != leaf["shape"] \
                or str(arr.dtype) != leaf["dtype"] \
                or _hash(arr) != leaf["sha256"]:
            return False, k
    return True, None


# --------------------------------------------------------------------- #
# satellite 1: golden bit-identity + zero-recompile toggles
# --------------------------------------------------------------------- #
def test_trace_off_is_bit_identical_solo(golden_note):
    """The pre-instrumentation solo trajectory, replayed through the
    instrumented code with tracing off: reports and every state leaf
    hash must match exactly — emit's scatter is provably inert at
    trace_on=0."""
    fixture = json.loads(GOLDEN.read_text())
    g = fixture["solo_managed"]
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=32.0, phi=0.02,
                    seed=0)
    reps = sim.run(len(g["reports"]))
    ok, why = _reports_match(g["reports"], reps)
    assert ok, f"report field diverged: {why}; {golden_note(fixture)}"
    ok, why = _state_match(g["state"], sim.state)
    assert ok, f"state leaf diverged: {why}; {golden_note(fixture)}"


def test_trace_off_is_bit_identical_fleet(golden_note):
    """Same gate for the fixed-role fleet recipe — the vmapped rings
    and the grouped-reduction plumbing must be equally inert."""
    fixture = json.loads(GOLDEN.read_text())
    g = fixture["fleet_fixed"]
    fleet = FleetSim([
        MemberSpec(cfg=CONFIG, write_rate=6.0, read_rate=24.0, seed=1,
                   manage_resources=False, prelease=(2, 6)),
        MemberSpec(cfg=CONFIG, mode="raft", write_rate=12.0,
                   read_rate=12.0, seed=2, manage_resources=False)])
    fleet.run(len(g["reports"][0]))
    for greports, member in zip(g["reports"], fleet.reports):
        ok, why = _reports_match(greports, member)
        assert ok, \
            f"fleet report field diverged: {why}; {golden_note(fixture)}"
    ok, why = _state_match(g["state"], fleet.state)
    assert ok, f"fleet state leaf diverged: {why}; {golden_note(fixture)}"


def test_trace_toggle_never_recompiles_solo():
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, seed=4,
                    manage_resources=False)
    sim.run(1)
    n0 = sim._epoch_fn.cache_size()
    sim.set_trace(on=True)
    sim.run(1)
    sim.set_trace(mask=default_mask(commit=False, ae=False))
    sim.run(1)
    sim.set_trace(on=False)
    sim.run(1)
    assert sim._epoch_fn.cache_size() == n0, \
        "trace_on/trace_mask flips must be cfg_c data, not compile keys"


def test_trace_toggle_never_recompiles_fleet():
    fleet = FleetSim([MemberSpec(cfg=CONFIG, write_rate=8.0,
                                 read_rate=16.0, seed=i,
                                 manage_resources=False)
                      for i in range(2)])
    fleet.run_epoch()
    n0 = fleet._epoch_fn.cache_size()
    fleet.set_trace(on=True)
    fleet.run_epoch()
    fleet.set_trace(on=False, members=[1])
    fleet.run_epoch()
    assert fleet._epoch_fn.cache_size() == n0
    assert any(e.member == 0 for e in fleet.trace_events)


# --------------------------------------------------------------------- #
# satellite 2: host-replay equivalence + capacity sweep
# --------------------------------------------------------------------- #
def _host_loop(ticks, *, seed=11, phi=0.03, warning_ticks=0,
               capacity=2048, lease=(3, 5), spot_bid=None):
    """Drive step.tick directly, drain every tick, and snapshot the raw
    transitions the events claim to describe."""
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, phi=phi,
                    seed=seed, warning_ticks=warning_ticks,
                    spot_bid=spot_bid,
                    trace_on=True, trace_capacity=capacity)
    if lease is not None:
        sim._lease(*lease)
    static, cfg_c = sim.static, sim.cfg_c
    tickfn = jax.jit(lambda s, r, c: step_mod.tick(s, static, c, r))
    state = sim.state
    rng = jax.random.PRNGKey(seed)
    cur = DrainCursor()
    per_tick = []
    prev = {k: np.asarray(state[k]).copy()
            for k in ("alive", "role", "warn_timer", "commit_len")}
    for t in range(ticks):
        rng, sub = jax.random.split(rng)
        state, _ = tickfn(state, sub, cfg_c)
        now = {k: np.asarray(state[k]) for k in prev}
        per_tick.append({"events": cur.drain(state), "prev": prev,
                         "now": now})
        prev = {k: v.copy() for k, v in now.items()}
    return per_tick, cur


def test_host_replay_alive_drops_and_leader_presence():
    """Every alive->dead transition must be explained by exactly one
    EV_KILL or EV_SEC_STOP event on that node at that tick, and the
    replayed leader timeline must match the per-tick probe."""
    ticks = 3 * CONFIG.period_ticks // 2
    per_tick, cur = _host_loop(ticks)
    assert not any(cur.dropped), cur.dropped_by_class()
    all_events = []
    leader_probe = []
    for t, row in enumerate(per_tick):
        dropped_alive = set(
            np.where(row["prev"]["alive"] & ~row["now"]["alive"])[0])
        explained = {e.node for e in row["events"]
                     if e.code in (EV_KILL, EV_SEC_STOP)}
        assert explained == dropped_alive, \
            (t, sorted(explained), sorted(dropped_alive))
        for e in row["events"]:
            assert e.tick == t, (e, t)
        all_events.extend(row["events"])
        leader_probe.append(bool(((row["now"]["role"] == SM.LEADER) &
                                  row["now"]["alive"]).any()))
    assert len(all_events) > 0
    up = leader_timeline(all_events, ticks)
    assert (up == np.asarray(leader_probe, bool)).all()


def test_host_replay_commit_advances():
    """EV_COMMIT events must be exactly the leader's commit-index
    advances: one event per advancing tick, aux = the new index."""
    ticks = CONFIG.period_ticks
    per_tick, _ = _host_loop(ticks, phi=0.0, seed=2)
    prev_commit = -1
    for t, row in enumerate(per_tick):
        role, alive = row["now"]["role"], row["now"]["alive"]
        lids = np.where((role == SM.LEADER) & alive)[0]
        commits = [e for e in row["events"] if e.code == EV_COMMIT]
        if lids.size:
            c = int(row["now"]["commit_len"][int(lids.max())])
            if prev_commit >= 0 and c > prev_commit:
                assert len(commits) == 1, (t, commits)
                assert commits[0].aux == c, (t, commits[0], c)
            prev_commit = c
        else:
            assert not commits


def test_host_replay_warn_and_reprieve():
    """Under an advance-warning window, every warn_timer arming is an
    EV_WARN and every early signal drop an EV_REPRIEVE.  Warnings come
    from the MARKET signal only (a phi kill is unwarned by design,
    DESIGN.md §12), so the bid is pinned at the price mean to make the
    synthetic walk cross it."""
    ticks = 2 * CONFIG.period_ticks
    per_tick, _ = _host_loop(ticks, phi=0.0, warning_ticks=6, seed=9,
                             spot_bid=0.0125)
    warns = reprieves = 0
    for t, row in enumerate(per_tick):
        armed = set(np.where((row["prev"]["warn_timer"] < 0) &
                             (row["now"]["warn_timer"] >= 0))[0])
        ev_warn = {e.node for e in row["events"] if e.code == EV_WARN}
        assert ev_warn == armed, (t, sorted(ev_warn), sorted(armed))
        # reprieve: the timer was running and reset without a death
        calm = set(np.where((row["prev"]["warn_timer"] >= 0) &
                            (row["now"]["warn_timer"] < 0) &
                            row["now"]["alive"] &
                            row["prev"]["alive"])[0])
        ev_rep = {e.node for e in row["events"] if e.code == EV_REPRIEVE}
        assert ev_rep == calm, (t, sorted(ev_rep), sorted(calm))
        warns += len(ev_warn)
        reprieves += len(ev_rep)
    assert warns > 0, "drill never armed a warning — raise phi/ticks"


@pytest.mark.parametrize("cap", [4, 16, 64])
def test_capacity_sweep_exact_drop_accounting(cap):
    """Forced overflow: per class, decoded + dropped == emitted exactly,
    drops are positive at tiny rings, and every drain's decoded slice is
    a suffix of the full-ring stream (the ring keeps the newest)."""
    epochs = 2

    def run(capacity):
        sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, phi=0.02,
                        seed=6, manage_resources=False, prelease=(2, 4),
                        trace_on=True, trace_capacity=capacity)
        drains, seen = [], 0
        for _ in range(epochs):
            sim.run(1)
            drains.append(list(sim.trace_events[seen:]))
            seen = len(sim.trace_events)
        emitted = np.asarray(sim.state["trace_emit"]).astype(np.int64)
        return sim, drains, emitted

    big_sim, big_drains, big_emit = run(4096)
    sim, drains, emitted = run(cap)
    assert (big_emit == emitted).all(), "emission is capacity-independent"
    assert not any(big_sim._trace_cursor.dropped)

    decoded = np.zeros(NCLASS, np.int64)
    for d in drains:
        for e in d:
            decoded[e.cls] += 1
    dropped = sim._trace_cursor.dropped
    assert (decoded + dropped == emitted).all(), \
        (decoded.tolist(), dropped.tolist(), emitted.tolist())
    if int(emitted.sum()) > epochs * cap:
        assert int(dropped.sum()) > 0, "overflow must report drops"
    key = lambda e: (e.code, e.tick, e.node, e.term, e.aux)
    for small, big in zip(drains, big_drains):
        if small:
            assert [key(e) for e in small] == \
                [key(e) for e in big][-len(small):], \
                "small ring must keep the newest events"


# --------------------------------------------------------------------- #
# satellite 3: first-tick-of-epoch leader_changes
# --------------------------------------------------------------------- #
def _staged_first_tick_state():
    """A cluster one tick away from electing node 0: pre-staged
    candidate with majority-1 banked votes, so the win lands on the
    FIRST tick of the next epoch."""
    static = SM.build_static(CONFIG)
    state = SM.init_state(CONFIG, static)
    maj = int(static["majority"])
    N = state["role"].shape[0]
    state = dict(
        state,
        role=state["role"].at[0].set(SM.CANDIDATE),
        term=state["term"].at[0].set(1),
        voted_for=state["voted_for"].at[0].set(0),
        votes_received=state["votes_received"].at[0].set(maj - 1),
        election_timer=jax.numpy.full((N,), 50, state["election_timer"].dtype),
    )
    return state, static


def test_first_tick_leader_change_counts_in_digest():
    state, static = _staged_first_tick_state()
    cfg_c = make_cfg_arrays(CONFIG, write_rate=0.0, read_rate=0.0,
                            phi=0.0, trace_on=True)
    out, digest = device_epoch(state, static, cfg_c,
                               jax.random.PRNGKey(0), 1)
    assert int(digest["no_leader_ticks"]) == 0, "the win must land tick 0"
    assert int(digest["leader_changes"]) == 1, \
        "a first-tick election is a leader change (pre-§14 blindness)"
    events = DrainCursor().drain(out)
    elects = [e for e in events if e.code == EV_ELECT]
    assert len(elects) == 1 and elects[0].node == 0 and elects[0].tick == 0
    assert int(digest["leader_changes"]) == len(elects), \
        "digest count must agree with the trace-derived count"


def test_first_tick_leader_change_counts_in_host_report():
    state, static = _staged_first_tick_state()
    cfg_c = make_cfg_arrays(CONFIG, write_rate=0.0, read_rate=0.0, phi=0.0)
    st, m = step_mod.tick(state, static, cfg_c, jax.random.PRNGKey(0),
                          reference=True)
    ms = jax.tree.map(lambda x: np.asarray(x)[None], m)
    rep = build_report(0, jax.tree.map(np.asarray, st), ms, 0.0,
                       leader_term0=-1)
    assert rep.leader_changes == 1, \
        "host np.diff form must count the first tick given leader_term0"


# --------------------------------------------------------------------- #
# metrics registry + export surfaces
# --------------------------------------------------------------------- #
def test_metrics_registry_always_on_and_per_epoch():
    """Named counters flow through the digest with tracing OFF, and
    compaction resets them so each report is per-epoch."""
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, seed=3,
                    manage_resources=False, prelease=(2, 4))
    r1, r2 = sim.run(2)
    for rep in (r1, r2):
        assert rep.metrics is not None
        assert set(rep.metrics) == set(trace_metrics.COUNTERS)
    assert r1.metrics["leader_elected"] >= 1
    assert r2.metrics["elections_started"] <= r1.metrics["elections_started"], \
        "counters must reset at compaction (steady state re-elects less)"
    assert r2.metrics["commit_advances"] > 0
    assert len(sim.trace_events) == 0, "no ring writes while off"


def test_metrics_match_trace_counts():
    """The in-digest counters and the decoded ring agree where a class
    is 1 event : 1 count (elections, kills, commits)."""
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, phi=0.02,
                    seed=5, manage_resources=False, prelease=(2, 4),
                    trace_on=True, trace_capacity=4096)
    reps = sim.run(2)
    assert not any(sim._trace_cursor.dropped)
    by = Counter(e.code for e in sim.trace_events)
    tot = {k: sum(r.metrics[k] for r in reps) for k in reps[0].metrics}
    assert by[EV_ELECT] == tot["leader_elected"]
    assert by[EV_KILL] == tot["kills"]
    assert by[EV_COMMIT] == tot["commit_advances"]


def test_perfetto_export_shape():
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, phi=0.02,
                    seed=5, manage_resources=False, prelease=(2, 4),
                    trace_on=True, trace_capacity=4096)
    sim.run(2)
    doc = to_perfetto(sim.trace_events,
                      ticks=2 * CONFIG.period_ticks,
                      annotations=[{"name": "read k", "start_tick": 3,
                                    "end_tick": 9, "fence": 2}])
    evs = doc["traceEvents"]
    assert evs and all({"ph", "pid", "name"} <= set(e) for e in evs)
    assert any(e["ph"] == "X" and e["tid"] == 9_999 for e in evs), \
        "leader tenure spans must be on the leader track"
    assert any(e.get("name") == "read k" for e in evs), \
        "client annotations must land in the export"
    assert json.loads(json.dumps(doc)) == doc
    art = timeline.render(sim.trace_events, ticks=2 * CONFIG.period_ticks)
    assert "leader" in art and "\n" in art


def test_trace_mask_filters_classes():
    """Masking a class suppresses its ring events AND its drop
    accounting, while the unmasked classes still record."""
    sim = BWRaftSim(CONFIG, write_rate=8.0, read_rate=16.0, phi=0.02,
                    seed=5, manage_resources=False, prelease=(2, 4),
                    trace_on=True, trace_capacity=4096,
                    trace_mask=default_mask(commit=False))
    reps = sim.run(2)
    codes = Counter(e.cls for e in sim.trace_events)
    assert codes[CLASS_NAMES.index("commit")] == 0
    assert sum(codes.values()) > 0
    assert sum(r.metrics["commit_advances"] for r in reps) > 0, \
        "metrics registry must stay on under a mask"


# --------------------------------------------------------------------- #
# satellite 6: BENCH schema over every committed artifact
# --------------------------------------------------------------------- #
def test_bench_schema_validates_all_committed_files():
    import sys
    repo = pathlib.Path(__file__).parent.parent
    sys.path.insert(0, str(repo))
    from benchmarks.common import validate_bench_file
    files = sorted(repo.glob("BENCH_*.json"))
    expected = {"BENCH_fleet.json", "BENCH_tick.json", "BENCH_market.json",
                "BENCH_serving.json", "BENCH_faults.json",
                "BENCH_observers.json", "BENCH_trace.json"}
    assert expected <= {f.name for f in files}, \
        f"missing committed BENCH files: {expected - {f.name for f in files}}"
    problems = [p for f in files for p in validate_bench_file(f)]
    assert not problems, problems
