"""Digest-tier observer suite (DESIGN.md §13).

Covers the §13 contract end to end:

  * golden gate — with the digest tier OFF (O = 0) the dense voter core
    and the legacy full-log observers are bit-identical to the frozen
    pre-tier fixture (`tests/data/observer_golden.json`), managed and
    fixed-role runs both;
  * equivalence — attaching a tier (O > 0) leaves every dense core leaf
    bit-identical at the same seed (the tier only adds digest-shaped
    state and redistributes reads);
  * Property 3.2 prefix mirrors — legacy observers' mirrored state
    equals a prefix of their follower's applied log at every tick, the
    rolling `applied_digest` equals the recompute-from-scratch
    `prefix_digest` on every alive node, and every digest observer's
    `dobs_digest` certifies a committed voter prefix;
  * anti-entropy convergence — under random gossip schedules,
    revocation kills, and warned drains every live digest observer
    converges within `ae_interval + max hop` of the fleet tick
    (hypothesis when installed, fixed-seed fallback otherwise);
  * staleness histogram pin — the device `obs_stale_hist` equals a
    numpy recomputation from the raw per-tick samples;
  * fleet equivalence — a solo digest-tier run and the same spec as a
    one-member fleet produce identical reports.
"""
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from repro.configs.bwraft_kv import CONFIG
from repro.core import state as SM
from repro.core import step as step_mod
from repro.core.cluster_config import ClusterConfig, SiteConfig
from repro.core.fleet import FleetSim, MemberSpec
from repro.core.runtime import BWRaftSim

try:
    from hypothesis import assume, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # fixed-seed fallback
    HAVE_HYPOTHESIS = False

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "observer_golden.json")

# report fields frozen in the fixture: ints exact, floats by repr
INT_FIELDS = ("killed", "leader_changes", "n_observers", "n_secretaries",
              "no_leader_ticks", "reads_arrived", "reads_served",
              "writes_arrived", "writes_committed")
FLOAT_FIELDS = ("cost", "read_lat_max", "read_lat_mean", "write_lat_mean",
                "write_lat_p95", "write_lat_p99")

# the two frozen scenarios (digest tier off): the managed headline run
# and a fixed-role run with legacy full-log observers serving reads
SCENARIOS = {
    "solo_managed": dict(write_rate=8.0, read_rate=32.0, phi=0.05, seed=7),
    "solo_fixed_obs": dict(write_rate=6.0, read_rate=48.0, phi=0.02,
                           seed=11, manage_resources=False,
                           prelease=(2, 8)),
}

# leaves the digest tier is ALLOWED to move: its own state, read
# serving, and cost (digest observers lease spot capacity); everything
# else is dense voter core and must stay bit-identical (DESIGN.md §13)
_NON_CORE = ("read_queue", "reads_served", "read_lat_hist",
             "read_lat_sum", "read_lat_max", "cost_accrued")


def _is_core_leaf(name: str) -> bool:
    return (not name.startswith("dobs_") and not name.startswith("obs_")
            and name not in _NON_CORE)


def _sha(arr) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(arr)).tobytes()).hexdigest()


def _small_cluster(name="obs-small", followers=(2, 2, 1), max_log=1024):
    sites = tuple(
        SiteConfig(f"{name}-s{i}", followers=f, rtt_intra=1,
                   rtt_inter=6 + 2 * i, on_demand_price=0.0416,
                   spot_price_mean=0.0125)
        for i, f in enumerate(followers))
    return ClusterConfig(name=name, sites=sites, max_log=max_log,
                         key_space=256, max_secretaries=4,
                         max_observers=8, period_ticks=60)


# ------------------------------------------------------------------ golden


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_golden_bit_identity_digest_off(scenario, golden_note):
    """With the digest tier off, the run is bit-identical to the frozen
    pre-tier fixture: every report field and every recorded state leaf."""
    with open(GOLDEN) as f:
        fixture = json.load(f)
    golden, note = fixture[scenario], golden_note(fixture)
    sim = BWRaftSim(CONFIG, **SCENARIOS[scenario])
    reports = sim.run(len(golden["reports"]))
    for i, (rep, want) in enumerate(zip(reports, golden["reports"])):
        for fld in INT_FIELDS:
            assert getattr(rep, fld) == want[fld], \
                f"{scenario} epoch {i}: {fld}; {note}"
        for fld in FLOAT_FIELDS:
            assert repr(float(getattr(rep, fld))) == want[fld], \
                f"{scenario} epoch {i}: {fld}; {note}"
    for leaf, meta in golden["state"].items():
        arr = np.asarray(sim.state[leaf])
        assert list(arr.shape) == meta["shape"], f"{scenario}: {leaf} shape"
        assert str(arr.dtype) == meta["dtype"], f"{scenario}: {leaf} dtype"
        assert _sha(arr) == meta["sha256"], \
            f"{scenario}: {leaf} bytes; {note}"


# ------------------------------------------------------- core equivalence


def test_digest_tier_leaves_voter_core_bit_identical():
    """O = 0 vs O > 0 at the same seed: every dense core leaf equal, and
    the tier actually served reads (the comparison is not vacuous)."""
    cfg = _small_cluster()
    kw = dict(write_rate=6.0, read_rate=24.0, phi=0.05, seed=3,
              manage_resources=False, prelease=(2, 4))
    base = BWRaftSim(cfg, **kw)
    base.run(2)
    tier = BWRaftSim(cfg, **kw, n_observers=12, staleness_bound=10,
                     ae_interval=3)
    reports = tier.run(2)
    for leaf in base.state:
        if _is_core_leaf(leaf):
            assert np.array_equal(np.asarray(base.state[leaf]),
                                  np.asarray(tier.state[leaf])), leaf
    assert reports[-1].obs_reads_served > 0


# -------------------------------------------- Property 3.2 prefix mirror


def _tick_trace(cfg, *, ticks, seed, n_observers=0, prelease=(1, 4),
                phi=0.02, staleness_bound=12, ae_interval=3,
                snapshot_every=3, ae_phase=None, warning_ticks=0):
    """Host tick loop (no epoch machinery): snapshots of the raw state
    every few ticks, for the per-tick Property 3.2 pins."""
    sim = BWRaftSim(cfg, write_rate=6.0, read_rate=24.0, phi=phi,
                    seed=seed, manage_resources=False, prelease=prelease,
                    n_observers=n_observers,
                    staleness_bound=staleness_bound,
                    ae_interval=ae_interval, ae_phase=ae_phase,
                    warning_ticks=warning_ticks)
    static, cfg_c = sim.static, sim.cfg_c
    tickfn = jax.jit(lambda s, r: step_mod.tick(s, static, cfg_c, r))
    rng = sim.rng
    state, snaps, mets = sim.state, [], []
    for t in range(ticks):
        rng, sub = jax.random.split(rng)
        state, m = tickfn(state, sub)
        if t % snapshot_every == 0:
            snaps.append({k: np.asarray(v) for k, v in state.items()})
        mets.append({k: np.asarray(v) for k, v in m.items()
                     if k.startswith("obs_")})
    return sim, snaps, mets, {k: np.asarray(v) for k, v in state.items()}


def test_property_32_legacy_observer_prefix_mirror():
    """Property 3.2 pin on `observer_sync_step`: at every snapshot, an
    alive legacy observer with an alive follower holds a prefix of that
    follower's applied log — applied index behind or equal, identical
    keys/values over the observer's applied prefix, identical KV image
    over it, and the mirrored digest certifying exactly that prefix."""
    _, snaps, _, _ = _tick_trace(_small_cluster(), ticks=90, seed=5,
                                 prelease=(1, 6))
    checked = 0
    for s in snaps:
        is_obs = (s["role"] == SM.OBSERVER) & s["alive"]
        for o in np.where(is_obs)[0]:
            f = int(s["obs_of"][o])
            if f < 0 or not s["alive"][f]:
                continue
            a = int(s["applied_len"][o])
            assert a <= int(s["applied_len"][f])
            assert np.array_equal(s["log_key"][o][:a], s["log_key"][f][:a])
            assert np.array_equal(s["log_val"][o][:a], s["log_val"][f][:a])
            checked += 1
    assert checked > 0, "no live observer/follower pair ever checked"


def test_rolling_digest_equals_prefix_recompute():
    """The incremental `applied_digest` chain equals the
    recompute-from-scratch `prefix_digest` on every alive node at every
    snapshot — voters, secretaries, and legacy observers alike."""
    _, snaps, _, _ = _tick_trace(_small_cluster(), ticks=90, seed=9,
                                 prelease=(2, 4))
    for s in snaps:
        for n in np.where(s["alive"])[0]:
            want = SM.prefix_digest(s["log_key"][n], s["log_val"][n],
                                    int(s["applied_len"][n]), xp=np)
            assert s["applied_digest"][n] == want, f"node {n}"


def test_digest_observer_certifies_committed_prefix():
    """Every alive digest observer's (applied, digest) pair names a
    committed prefix: recomputing the digest over the most-applied live
    voter's log at `dobs_applied` reproduces `dobs_digest` exactly."""
    sim, snaps, _, _ = _tick_trace(_small_cluster(), ticks=90, seed=13,
                                   n_observers=10)
    is_voter = np.asarray(sim.static["is_voter"])
    checked = 0
    for s in snaps:
        live_v = np.where(is_voter & s["alive"])[0]
        v = live_v[np.argmax(s["applied_len"][live_v])]
        for o in np.where(s["dobs_alive"])[0]:
            a = int(s["dobs_applied"][o])
            if a == 0:
                continue                      # nothing adopted yet
            assert a <= int(s["applied_len"][v])
            want = SM.prefix_digest(s["log_key"][v], s["log_val"][v],
                                    a, xp=np)
            assert s["dobs_digest"][o] == want, f"slot {o}"
            checked += 1
    assert checked > 0, "no synced digest observer ever checked"


# --------------------------------------------- anti-entropy convergence


def _check_convergence(seed, phi, ae_interval, warning_ticks):
    """Under a random gossip phase schedule, revocation kills, and
    warned drains, every live digest observer's last sync is within
    `ae_interval + max hop` of the fleet tick at every snapshot, and its
    digest certifies a committed prefix (monotone adoption never
    regresses).  Checked on a raw tick trace: the epoch boundary
    deliberately revives slots stale (`compact_state`), so convergence
    is a steady-state property, not a post-`run()` one.  Returns how
    many (live, synced) observer snapshots were certified: a run whose
    kills revoke every slot before any sync checks nothing."""
    cfg = _small_cluster()
    O = 16
    rng = np.random.default_rng(seed)
    sim, snaps, _, _ = _tick_trace(
        cfg, ticks=90, seed=seed, n_observers=O, prelease=(1, 2),
        phi=phi, staleness_bound=24, ae_interval=ae_interval,
        ae_phase=rng.integers(0, max(ae_interval, 1), size=O),
        warning_ticks=warning_ticks)
    is_voter = np.asarray(sim.static["is_voter"])
    hop_max = int(np.asarray(sim.static["site_rtt"]).max())
    checked = 0
    for s in snaps:
        tick = int(s["tick"])
        live = np.where(s["dobs_alive"])[0]
        stale = tick - s["dobs_synced_t"][live]
        assert (stale <= ae_interval + hop_max).all(), \
            f"tick {tick}: stale={stale.max()} > interval " \
            f"{ae_interval} + hop {hop_max}"
        live_v = np.where(is_voter & s["alive"])[0]
        v = live_v[np.argmax(s["applied_len"][live_v])]
        for o in live:
            a = int(s["dobs_applied"][o])
            assert a <= int(s["applied_len"][v])
            if a:
                assert s["dobs_digest"][o] == SM.prefix_digest(
                    s["log_key"][v], s["log_val"][v], a, xp=np)
                checked += 1
    return checked


_CONVERGENCE_CASES = [(0, 0.0, 1, 0), (3, 0.05, 4, 0), (11, 0.02, 7, 3),
                      (21, 0.02, 2, 2), (42, 0.05, 3, 0)]

if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000),
           phi=st.sampled_from([0.0, 0.02, 0.05]),
           ae_interval=st.integers(1, 7),
           warning_ticks=st.sampled_from([0, 3]))
    def test_anti_entropy_convergence(seed, phi, ae_interval,
                                      warning_ticks):
        # precondition: only runs with a live synced observer judge the
        # property (phi kills can revoke all 16 slots before the first
        # commit, and nothing re-leases them inside a raw tick trace)
        assume(_check_convergence(seed, phi, ae_interval,
                                  warning_ticks) > 0)
else:
    @pytest.mark.parametrize("seed,phi,ae_interval,warning_ticks",
                             _CONVERGENCE_CASES)
    def test_anti_entropy_convergence(seed, phi, ae_interval,
                                      warning_ticks):
        assert _check_convergence(seed, phi, ae_interval,
                                  warning_ticks) > 0, \
            "no live synced digest observer ever checked"


# ------------------------------------------------- staleness histogram


def test_staleness_histogram_numpy_pin():
    """The device `obs_stale_hist` equals a numpy recomputation from the
    raw per-tick (served, staleness) samples, and the serve counter
    equals the histogram mass — so the staleness percentiles the reports
    quote are exact, and every sample is <= the configured bound."""
    bound = 12
    _, _, mets, final = _tick_trace(_small_cluster(), ticks=90, seed=17,
                                    n_observers=10, staleness_bound=bound)
    H = final["obs_stale_hist"].shape[0]
    hist = np.zeros(H, np.int64)
    for m in mets:
        served, stale = m["obs_served_tick"], m["obs_stale_tick"]
        for o in np.where(served > 0)[0]:
            hist[min(int(stale[o]), H - 1)] += int(served[o])
    assert hist.sum() > 0, "digest tier never served"
    assert np.array_equal(hist, final["obs_stale_hist"])
    assert int(final["obs_reads_served"]) == hist.sum()
    assert hist[bound + 1:].sum() == 0, "served a read beyond the bound"


# ------------------------------------------------------ fleet equivalence


def test_fleet_member_matches_solo_with_observers():
    """The same digest-tier spec run solo and as a one-member fleet
    produces identical reports, observer columns included."""
    cfg = _small_cluster()
    kw = dict(write_rate=6.0, read_rate=24.0, phi=0.02, seed=19,
              manage_resources=False, prelease=(1, 3))
    tier = dict(n_observers=12, staleness_bound=10, ae_interval=3)
    solo = BWRaftSim(cfg, **kw, **tier).run(2)
    fleet = FleetSim([MemberSpec(cfg=cfg, mode="bwraft", **kw, **tier)])
    batched = fleet.run(2)[0]
    fields = INT_FIELDS + ("obs_reads_served", "obs_rerouted",
                           "n_obs_digest")
    for a, b in zip(solo, batched):
        for fld in fields:
            assert getattr(a, fld) == getattr(b, fld), fld
        for fld in FLOAT_FIELDS + ("obs_stale_p95", "obs_stale_p99"):
            fa, fb = getattr(a, fld), getattr(b, fld)
            assert (np.isnan(fa) and np.isnan(fb)) or fa == fb, fld
