"""BW-Raft runtime: jitted tick-scan epochs + host-side control plane.

One *epoch* = `cfg.period_ticks` protocol ticks (jitted `lax.scan`), after
which the control plane runs: collect stats ("peek", Algorithm 1), score
the spot-offer pool and select instances (MCSA, "peak"), lease them into
dead spot slots, wire secretaries/observers, compact the log window.
`mode="raft"` disables spot roles entirely (the Original baseline).

Compilation contract (DESIGN.md §7): the epoch function is compiled **once
per static shape** — the cache key is (cluster config, padding), and every
workload knob in `cfg_c` (rates, phi, prices, volatility, timeouts, the
(S, Tt) market-trace arrays of DESIGN.md §10) is a jit *argument*, so
rate/volatility/kill-rate/trace sweeps over one topology reuse the
compiled program.  For sweeps over many clusters in a single compiled
program, use `core/fleet.py`, which vmaps the same tick over a leading
batch axis; the host-side control plane below (`ClusterController`,
`lease_and_wire`, `build_report`, `compact_state`) is shared by both.

Epoch digest contract (DESIGN.md §7.1): the jitted epoch reduces its
per-tick metrics *inside* the scan and returns `(compacted_state, digest)`
where the digest is a few-KB pytree — counters, a write-latency histogram,
the final (N,) role/alive vectors and (S,) spot prices — independent of
the log window L and key space K.  Only the digest crosses the device→host
boundary per epoch (`report_from_digest`); the state pytree stays on
device, is compacted in-graph, and its input buffers are donated back to
XLA (`donate_argnums`), so epochs neither copy state in device memory nor
materialize it to host.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import manager as mgr
from repro.kernels import resolve_backend
from repro.core import mcsa
from repro.core import step as step_mod
from repro.core import state as state_mod
from repro.core.cluster_config import ClusterConfig
from repro.core.state import (DEAD, FOLLOWER, LEADER, OBSERVER, SECRETARY,
                              HIST_TAIL)
from repro.trace import export as trace_export
from repro.trace import metrics as trace_metrics
from repro.trace import ring as trace_ring
from repro.trace import spans as trace_spans
from repro.workload import arrivals as workload_arrivals


class CountingJit:
    """`jax.jit` wrapper that reports how many programs it compiled,
    read from the jit cache (`_cache_size()`, present in the pinned
    jax).  Used by every cached epoch function, so
    `FleetSim.compile_count` / `fleet.total_compile_count` count real
    compiles."""

    def __init__(self, fun, **jit_kwargs):
        self.fn = jax.jit(fun, **jit_kwargs)

    def __call__(self, *args):
        return self.fn(*args)

    def cache_size(self) -> int:
        return int(self.fn._cache_size())


# HIST_TAIL moved to `state.py` with the read-histogram state (§11); it
# is re-exported here so `runtime.HIST_TAIL` keeps resolving — both the
# write and read latency histograms share the T + 1 + HIST_TAIL layout
# (`state.hist_bins`, DESIGN.md §7.1/§11).


def make_cfg_arrays(cfg: ClusterConfig, *, write_rate: float,
                    read_rate: float, phi: float = 0.0,
                    pad_nodes: int = 0,
                    pad_sites: int = 0, pad_keys: int = 0,
                    spot_price_vol: Optional[float] = None,
                    cross_shard_frac: float = 0.0,
                    two_pc_ticks: int = 0,
                    market: str = "process",
                    trace=None, trace_ticks: Optional[int] = None,
                    arrivals=None, arrival_ticks: Optional[int] = None,
                    keypop=None,
                    warning_ticks: int = 0, spot_bid=None,
                    bid_on_trace: bool = False,
                    faults=None, fault_ticks: Optional[int] = None,
                    n_observers: int = 0, pad_observers: int = 0,
                    staleness_bound: int = 16, ae_interval: int = 4,
                    ae_phase=None, trace_on: bool = False,
                    trace_mask=None) -> Dict:
    """Per-epoch dynamic knobs — all jit arguments, never baked into the
    compiled program.  `pad_sites` repeats the last site's prices so padded
    clusters share one (S,) shape (DESIGN.md §7).  `cross_shard_frac` /
    `two_pc_ticks` are the Multi-Raft 2PC coupling knobs (DESIGN.md §9):
    zero for ungrouped members, which keeps the tick bit-identical to the
    pre-group program.

    `market` selects the spot-market source (DESIGN.md §10):
    `"process"` runs the synthetic walk, `"trace"` replays the given
    `market.MarketTrace` — its (S, Tt) price/revocation arrays enter
    here as jit arguments (`price_trace` / `revoke_trace`, fitted to the
    padded site count), so swapping traces at one shape never recompiles.
    `trace_ticks` widens the trace arrays to a fleet-shared Tt (time
    wrap, `MarketTrace.fit_to`); process-only members carry an inert
    (S, max(trace_ticks, 1)) placeholder so mixed fleets still stack.

    `arrivals` selects the workload source (DESIGN.md §11): None keeps
    the closed-loop scalar knob (bit-identical to the pre-§11 tick); a
    `workload.OpenLoop` plan enters as the `write_curve`/`read_curve`
    jit-argument arrays, wrapped at the plan's own length, optionally
    widened to a fleet-shared `arrival_ticks` (replay-neutral, like
    market traces).  `keypop` is the write-key popularity: None keeps
    the uniform draw, a `workload.ZipfianKeys` rides in as the (K,)
    `key_cdf` the leader inverse-transform samples; `pad_keys` widens
    the CDF with a saturated (never-sampled) tail so padded fleets
    stack.

    Revocation-robustness knobs (DESIGN.md §12), all cfg_c data:
    `warning_ticks` is the advance-warning window W (0 = today's
    immediate kill, bit-identical); `spot_bid` overrides the per-site
    bid (default: `state.site_price_init`'s 1.5x-mean rule) — carried
    here instead of in state so per-epoch bid-policy updates never
    recompile; `bid_on_trace` re-derives trace-path revocations from
    the replayed prices vs the CURRENT bid (default False = verbatim
    replay of the trace's revocation columns); a trace with per-node
    `revoked_node` columns enters as `revoke_node_trace` (node rows
    round-robin, time wrap shared with the site arrays); `faults` is a
    deterministic `market.chaos.FaultSchedule` riding in as the (N, Tf)
    `fault_trace` jit-argument array (widened to a fleet-shared
    `fault_ticks` with inert False padding; the in-step lookup wraps at
    the array width, so build schedules covering the full run for
    one-shot semantics).

    Digest-tier observer knobs (DESIGN.md §13), all cfg_c data so
    staleness/cadence sweeps at one O never recompile:
    `staleness_bound` is the read-freshness contract in ticks (a digest
    observer serves iff `tick - last_sync <= bound`); `ae_interval` is
    the anti-entropy round period; `ae_phase` is the per-observer `(O,)`
    phase schedule (default `arange(O)` — maximally staggered cohorts;
    `O = n_observers + pad_observers` must match the shapes from
    `state.build_static`).  The bound must fit the unit-bin staleness
    histogram (`period_ticks + HIST_TAIL`).

    Flight-recorder knobs (DESIGN.md §14), both cfg_c data so toggling
    capture or remasking event classes never recompiles: `trace_on`
    gates ring capture (the metrics registry stays on either way);
    `trace_mask` is the per-event-class capture mask (default: all
    `trace.NCLASS` classes on — see `trace.ring.default_mask`)."""
    assert 0.0 <= cross_shard_frac <= 1.0, cross_shard_frac
    assert 0 <= two_pc_ticks <= HIST_TAIL, \
        f"two_pc_ticks={two_pc_ticks} exceeds the histogram tail " \
        f"(HIST_TAIL={HIST_TAIL}) — widen runtime.HIST_TAIL"
    assert market in ("process", "trace"), market
    assert market == "process" or trace is not None, \
        "market='trace' needs a market.MarketTrace (see market.load / " \
        "market/synthetic.py providers)"
    S = cfg.num_sites + pad_sites
    N = cfg.max_nodes + pad_nodes
    per_node = (trace is not None
                and getattr(trace, "revoked_node", None) is not None)
    if trace is not None:
        width = trace_ticks or trace.ticks
        fitted = trace.fit_to(S, width)
        price_trace = jnp.asarray(fitted.price, jnp.float32)
        revoke_trace = jnp.asarray(fitted.revoked, bool)
        # the member's OWN period: the in-step lookup wraps at this (a
        # jit argument), not at the fleet-shared array width, so a short
        # trace widened next to a longer one still replays its own
        # columns exactly (DESIGN.md §10 replay-neutral widening)
        trace_len = min(trace.ticks, width)
    else:
        price_trace = jnp.zeros((S, trace_ticks or 1), jnp.float32)
        revoke_trace = jnp.zeros((S, trace_ticks or 1), bool)
        trace_len = 1
    if per_node:
        revoke_node = jnp.asarray(
            trace.node_columns(N, int(price_trace.shape[1])), bool)
    else:
        revoke_node = jnp.zeros((N, int(price_trace.shape[1])), bool)
    if faults is not None:
        fault_len = fault_ticks or faults.ticks
        fault_trace = jnp.asarray(faults.fit_to(N, fault_len), bool)
    else:
        fault_len = 1
        fault_trace = jnp.zeros((N, fault_ticks or 1), bool)
    if spot_bid is None:
        bid = state_mod.site_price_init(cfg, S)[1]
    else:
        bid = np.asarray(spot_bid, np.float32).reshape(-1)
        if bid.size == 1:
            bid = np.full((S,), bid[0], np.float32)
        elif bid.size < S:           # padded sites repeat the last bid
            bid = np.concatenate(
                [bid, np.full((S - bid.size,), bid[-1], np.float32)])
        bid = bid[:S]
    if arrivals is not None:
        width = arrival_ticks or arrivals.ticks
        write_curve, read_curve, arrival_len = arrivals.fit_to(width)
    else:
        width = arrival_ticks or 1
        write_curve = np.zeros((width,), np.float32)
        read_curve = np.zeros((width,), np.float32)
        arrival_len = 1
    if keypop is not None:
        key_cdf = keypop.materialize(cfg.key_space, pad_keys)
    else:
        key_cdf = workload_arrivals.uniform_key_cdf(cfg.key_space, pad_keys)
    O = n_observers + pad_observers
    assert 0 <= staleness_bound <= cfg.period_ticks + HIST_TAIL, \
        f"staleness_bound={staleness_bound} exceeds the unit-bin " \
        f"staleness histogram ({cfg.period_ticks + HIST_TAIL})"
    assert ae_interval >= 1, ae_interval
    if ae_phase is None:
        phase = np.arange(O, dtype=np.int32)
    else:
        phase = np.asarray(ae_phase, np.int32).reshape(-1)
        assert phase.size == O, (phase.size, O)
    if trace_mask is None:
        mask = np.ones((trace_ring.NCLASS,), bool)
    else:
        mask = np.asarray(trace_mask, bool).reshape(-1)
        assert mask.size == trace_ring.NCLASS, \
            (mask.size, trace_ring.NCLASS)
    od = [s.on_demand_price for s in cfg.sites]
    sp = [s.spot_price_mean for s in cfg.sites]
    od = od + [od[-1]] * pad_sites
    sp = sp + [sp[-1]] * pad_sites
    vol = (cfg.sites[0].spot_price_vol if spot_price_vol is None
           else spot_price_vol)
    return {
        "open_loop": jnp.asarray(arrivals is not None),
        "write_curve": jnp.asarray(write_curve, jnp.float32),
        "read_curve": jnp.asarray(read_curve, jnp.float32),
        "arrival_len": jnp.int32(arrival_len),
        "key_zipf": jnp.asarray(keypop is not None),
        "key_cdf": jnp.asarray(key_cdf, jnp.float32),
        "market_trace": jnp.asarray(market == "trace"),
        "price_trace": price_trace,
        "revoke_trace": revoke_trace,
        "trace_len": jnp.int32(trace_len),
        # revocation-robustness data (DESIGN.md §12)
        "spot_bid": jnp.asarray(bid, jnp.float32),
        "warn_ticks": jnp.int32(warning_ticks),
        "bid_on_trace": jnp.asarray(bool(bid_on_trace)),
        "node_trace": jnp.asarray(per_node),
        "revoke_node_trace": revoke_node,
        "fault_on": jnp.asarray(faults is not None),
        "fault_trace": fault_trace,
        "fault_len": jnp.int32(fault_len),
        "write_rate": jnp.float32(write_rate),
        "read_rate": jnp.float32(read_rate),
        "phi": jnp.float32(phi),
        "heartbeat_interval": jnp.int32(cfg.heartbeat_interval),
        "election_timeout_min": jnp.int32(cfg.election_timeout_min),
        "election_timeout_max": jnp.int32(cfg.election_timeout_max),
        "on_demand_price": jnp.asarray(od, jnp.float32),
        "spot_price_mean": jnp.asarray(sp, jnp.float32),
        "spot_price_vol": jnp.float32(vol),
        "ticks_per_hour": jnp.float32(3600.0 / 0.01 / 100),  # 1 tick = 10ms
        "network_cost_coef": jnp.float32(0.0005),
        "cross_frac": jnp.float32(cross_shard_frac),
        "two_pc_ticks": jnp.int32(two_pc_ticks),
        # digest-tier observer contract (DESIGN.md §13)
        "staleness_bound": jnp.int32(staleness_bound),
        "ae_interval": jnp.int32(ae_interval),
        "ae_phase": jnp.asarray(phase, jnp.int32),
        # flight-recorder gate + per-class capture mask (DESIGN.md §14)
        "trace_on": jnp.asarray(bool(trace_on)),
        "trace_mask": jnp.asarray(mask),
    }


@dataclasses.dataclass
class EpochReport:
    epoch: int
    reads_arrived: int
    writes_arrived: int
    reads_served: int
    writes_committed: int
    read_lat_mean: float
    read_lat_max: float
    write_lat_mean: float
    write_lat_p95: float
    write_lat_p99: float
    cost: float
    n_secretaries: int
    n_observers: int
    leader_changes: int
    no_leader_ticks: int
    killed: int
    # read-path tail stats, recovered exactly from the per-request
    # read-latency histogram (DESIGN.md §11) — NaN when no read served
    read_lat_p95: float = float("nan")
    read_lat_p99: float = float("nan")
    # end-of-epoch warning census: nodes alive with a raised advance-
    # warning bit (DESIGN.md §12) — 0 whenever warning_ticks == 0
    n_warned: int = 0
    # digest-tier observer census (DESIGN.md §13) — all zero/NaN when
    # the tier is off (O == 0)
    obs_reads_served: int = 0
    obs_rerouted: int = 0
    obs_stale_p95: float = float("nan")
    obs_stale_p99: float = float("nan")
    n_obs_digest: int = 0
    # unified control-plane metrics registry (DESIGN.md §14): the named
    # counters of `trace.metrics`, reduced in-digest — new per-epoch
    # counters land here instead of growing this dataclass field by
    # field.  None only on reports predating the registry.
    metrics: Optional[Dict[str, int]] = None
    decision: Optional[mgr.PeekDecision] = None

    @property
    def goodput(self) -> float:
        return (self.reads_served + self.writes_committed) / 1.0


def build_report(epoch: int, st: Dict, ms: Dict,
                 cost_before: float,
                 leader_term0: Optional[int] = None) -> EpochReport:
    """Distill one cluster's post-epoch state + per-tick metrics (numpy,
    leaves shaped (T,)) into an EpochReport.

    This is the host-marshalling reference path: it needs the FULL state
    pytree (O(N·(L+K)) device→host bytes per cluster).  The hot path is
    `report_from_digest`, which consumes only the few-KB on-device digest
    (DESIGN.md §7.1); this function is kept for the `pipeline="host"`
    A/B fallback and the digest-equivalence tests.

    `leader_term0` is the PRE-epoch leader term (-1 = no leader): the
    `np.diff(leader_term)` change count is taken over the prepended
    series so a change landing on the epoch's first tick is counted,
    matching the fixed in-scan accumulator (`_digest_acc_init`).  None
    preserves the legacy within-epoch-only diff."""
    lt = np.asarray(ms["leader_term"])
    if leader_term0 is not None:
        lt = np.concatenate([[np.int64(leader_term0)],
                             lt.astype(np.int64)])
    sub_t = np.asarray(st["entry_submit_t"])
    com_t = np.asarray(st["entry_commit_t"])
    done = (sub_t >= 0) & (com_t >= 0)
    lat = (com_t[done] - sub_t[done]).astype(float)
    reads_served = int(st["reads_served"])
    _, _, read_p95, read_p99 = hist_stats(st["read_lat_hist"])
    _, _, stale_p95, stale_p99 = hist_stats(st["obs_stale_hist"])
    return EpochReport(
        read_lat_p95=read_p95,
        read_lat_p99=read_p99,
        n_warned=int((np.asarray(st["alive"]) &
                      (np.asarray(st["warn_timer"]) >= 0)).sum()),
        obs_reads_served=int(st["obs_reads_served"]),
        obs_rerouted=int(st["obs_rerouted"]),
        obs_stale_p95=stale_p95,
        obs_stale_p99=stale_p99,
        n_obs_digest=int(np.asarray(st["dobs_alive"]).sum()),
        epoch=epoch,
        reads_arrived=int(st["reads_arrived"]),
        writes_arrived=int(st["writes_arrived"]),
        reads_served=reads_served,
        writes_committed=int(done.sum()),
        read_lat_mean=float(st["read_lat_sum"] / max(reads_served, 1)),
        read_lat_max=float(st["read_lat_max"]),
        write_lat_mean=float(lat.mean()) if lat.size else float("nan"),
        write_lat_p95=float(np.percentile(lat, 95)) if lat.size
        else float("nan"),
        write_lat_p99=float(np.percentile(lat, 99)) if lat.size
        else float("nan"),
        cost=float(st["cost_accrued"]) - cost_before,
        n_secretaries=int(ms["n_secretaries"][-1]),
        n_observers=int(ms["n_observers"][-1]),
        leader_changes=int((np.diff(lt) > 0).sum()),
        no_leader_ticks=int((ms["has_leader"] == 0).sum()),
        killed=int(ms["killed"].sum()),
        metrics=(trace_metrics.as_dict(st["metrics_ctr"])
                 if "metrics_ctr" in st else None),
    )


def _digest_acc_init(leader_term0) -> Dict:
    """In-scan accumulators for the per-tick metric reductions, seeded
    with the PRE-epoch leader term (same `-1 = no leader` sentinel as
    the tick metric).  Seeding — instead of skipping the first tick —
    is the fix for the boundary blindness pinned by
    `tests/test_trace.py::test_leader_changes_first_tick_regression`: a
    leader change landing on the first tick after compaction used to be
    invisible to both this counter and the host `np.diff` form."""
    return {
        "killed": jnp.int32(0),
        "no_leader_ticks": jnp.int32(0),
        "leader_changes": jnp.int32(0),
        "prev_leader_term": jnp.asarray(leader_term0, jnp.int32),
    }


def _digest_acc_update(acc: Dict, m: Dict) -> Dict:
    """Fold one tick's metrics into the accumulators (replaces the
    T-stacked metric arrays of the host path: `leader_changes` is the
    in-scan equivalent of `(np.diff(leader_term) > 0).sum()` over the
    epoch-start-prepended term series)."""
    changed = m["leader_term"] > acc["prev_leader_term"]
    return {
        "killed": acc["killed"] + m["killed"].astype(jnp.int32),
        "no_leader_ticks": acc["no_leader_ticks"] +
        (m["has_leader"] == 0).astype(jnp.int32),
        "leader_changes": acc["leader_changes"] +
        changed.astype(jnp.int32),
        "prev_leader_term": m["leader_term"],
    }


def _finalize_digest(state: Dict, acc: Dict, cost_before, T: int,
                     cfg_c: Dict) -> Dict:
    """Build the epoch digest from the final (pre-compaction) state.

    The write-latency distribution becomes an exact per-tick histogram:
    latencies are integer ticks in [0, T + HIST_TAIL] (the tail holds the
    in-graph 2PC rounds of cross-shard commits, DESIGN.md §9), so
    `hist[b]` = number of committed entries with latency b fully
    determines the sorted latency sample — `report_from_digest` recovers
    mean/p95/p99 exactly.  The 2PC prepare/abort census counts entries
    marked as cross-shard coordinators: prepares = marked entries that
    reached the log, aborts = prepares whose commit never landed inside
    the epoch (the partner shard's held capacity is released uncommitted).
    """
    sub, com = state["entry_submit_t"], state["entry_commit_t"]
    done = (sub >= 0) & (com >= 0)
    H = T + 1 + HIST_TAIL
    lat = jnp.clip(com - sub, 0, H - 1)
    hist = jnp.zeros((H,), jnp.int32).at[
        jnp.where(done, lat, H)].add(1, mode="drop")
    marked = step_mod.cross_shard_mark(
        jnp.arange(sub.shape[0]), cfg_c["cross_frac"])
    prepared = marked & (sub >= 0)
    alive = state["alive"]
    return {
        "cross_arrived": state["cross_arrived"],
        "two_pc_prepares": jnp.sum(prepared).astype(jnp.int32),
        "two_pc_aborts": jnp.sum(prepared & (com < 0)).astype(jnp.int32),
        "reads_arrived": state["reads_arrived"],
        "writes_arrived": state["writes_arrived"],
        "reads_served": state["reads_served"],
        "read_lat_sum": state["read_lat_sum"],
        "read_lat_max": state["read_lat_max"],
        # per-request read latencies, accumulated tick by tick on device
        # (`step.read_step`) — same unit-bin layout as the write
        # histogram below (DESIGN.md §11)
        "read_lat_hist": state["read_lat_hist"],
        "write_lat_hist": hist,
        "cost_delta": state["cost_accrued"] - cost_before,
        "n_secretaries": jnp.sum((state["role"] == SECRETARY) &
                                 alive).astype(jnp.int32),
        "n_observers": jnp.sum((state["role"] == OBSERVER) &
                               alive).astype(jnp.int32),
        "killed": acc["killed"],
        "no_leader_ticks": acc["no_leader_ticks"],
        "leader_changes": acc["leader_changes"],
        # control-plane inputs: O(N) role/alive for lease_and_wire, O(S)
        # prices for Algorithm 1 — the only per-node data leaving device
        "role": state["role"],
        "alive": alive,
        "spot_price": state["spot_price"],
        # advance-warning census (DESIGN.md §12): which nodes carry a
        # raised warning bit at epoch end, so the control plane can
        # re-lease replacements BEFORE the kill lands
        "warned": alive & (state["warn_timer"] >= 0),
        "n_warned": jnp.sum(alive &
                            (state["warn_timer"] >= 0)).astype(jnp.int32),
        # digest-tier observer census (DESIGN.md §13): the staleness
        # histogram + three scalars — present (zeros) at O == 0 so the
        # digest pytree structure is uniform across fleet members.  The
        # (O,) leaves themselves never cross the boundary.
        "obs_stale_hist": state["obs_stale_hist"],
        "obs_reads_served": state["obs_reads_served"],
        "obs_rerouted": state["obs_rerouted"],
        "n_obs_digest": jnp.sum(state["dobs_alive"]).astype(jnp.int32),
        # flight-recorder registry + ring cursors (DESIGN.md §14): the
        # named counters become `EpochReport.metrics`; pos/emit ride
        # along so scan-mode runs keep per-epoch drop accounting even
        # though the ring itself is only fetched at drain time
        "trace_metrics": state["metrics_ctr"],
        "trace_pos": state["trace_pos"],
        "trace_emit": state["trace_emit"],
    }


def device_epoch(state: Dict, static, cfg_c: Dict, rng, T: int, *,
                 backend: str = "xla") -> Tuple[Dict, Dict]:
    """One fully device-resident epoch: T-tick scan with in-scan metric
    reduction, digest extraction, then in-graph log compaction.  Returns
    `(compacted_state, digest)`; meant to be jitted with the state buffers
    donated (DESIGN.md §7.1).  `backend` picks the tick hot-op
    implementation — `"xla"`, `"pallas"`, or `"auto"` (pallas on TPU,
    xla elsewhere — DESIGN.md §8).  The spot
    market (synthetic process or trace replay) is selected by `cfg_c` —
    the trace arrays are jit arguments, so a trace sweep reuses this
    compiled program (DESIGN.md §10)."""
    cost_before = state["cost_accrued"]
    # pre-epoch leader term, mirroring the tick metric's sentinel — the
    # seed that makes a first-tick leader change countable (see
    # `_digest_acc_init`)
    lid0 = state_mod.leader_id(state, static)
    lt0 = jnp.where(lid0 >= 0, state["term"][jnp.maximum(lid0, 0)], -1)

    def body(carry, r):
        st, acc = carry
        st, m = step_mod.tick(st, static, cfg_c, r, backend=backend)
        with jax.named_scope(trace_spans.EPOCH_DIGEST):
            acc = _digest_acc_update(acc, m)
        return (st, acc), None

    rngs = jax.random.split(rng, T)
    (state, acc), _ = jax.lax.scan(body, (state, _digest_acc_init(lt0)),
                                   rngs)
    with jax.named_scope(trace_spans.EPOCH_DIGEST):
        digest = _finalize_digest(state, acc, cost_before, T, cfg_c)
    with jax.named_scope(trace_spans.EPOCH_COMPACT):
        state = compact_state(state)
    return state, digest


def hist_percentile(counts: np.ndarray, q: float) -> float:
    """Exact `np.percentile(sample, q)` (linear interpolation) for an
    integer-valued sample given as a unit-width histogram: `counts[v]` =
    multiplicity of value v.  NaN on an empty histogram."""
    counts = np.asarray(counts)
    n = int(counts.sum())
    if n == 0:
        return float("nan")
    cum = np.cumsum(counts)
    rank = (n - 1) * q / 100.0
    lo, hi = int(np.floor(rank)), int(np.ceil(rank))
    vlo = int(np.searchsorted(cum, lo + 1))
    vhi = vlo if hi == lo else int(np.searchsorted(cum, hi + 1))
    return float(vlo + (rank - lo) * (vhi - vlo))


def hist_stats(hist) -> Tuple[int, float, float, float]:
    """(count, mean, p95, p99) of the integer sample encoded by a
    unit-bin histogram — the one place the digest's histogram layout
    (`_finalize_digest`, T + 1 + HIST_TAIL bins) is distilled; shared by
    `report_from_digest` and `multiraft.report_from_group_digest`.
    Mean/percentiles are NaN on an empty histogram."""
    hist = np.asarray(hist)
    n = int(hist.sum())
    lat_sum = float(hist @ np.arange(hist.shape[0], dtype=np.int64))
    mean = lat_sum / n if n else float("nan")
    return n, mean, hist_percentile(hist, 95), hist_percentile(hist, 99)


def goodput_under_deadline(hist, deadline: int) -> int:
    """Requests that finished within `deadline` ticks, read straight off a
    unit-bin latency histogram: ``sum(hist[:deadline+1])``.  The SLO-
    goodput metric of `benchmarks/perf_serving.py` (DESIGN.md §11);
    `tests/test_serving.py` pins it against a numpy recomputation over
    the raw per-request latencies."""
    hist = np.asarray(hist)
    d = min(int(deadline), hist.shape[0] - 1)
    if d < 0:
        return 0
    return int(hist[:d + 1].sum())


def report_from_digest(epoch: int, dg: Dict) -> EpochReport:
    """Distill one cluster's epoch digest (numpy leaves, O(T + N + S)
    bytes) into an EpochReport — the digest-path twin of `build_report`.
    Counters are exact; write-latency stats are recovered exactly from the
    unit-bin histogram (integer-tick latencies, see `_finalize_digest`)."""
    n_done, lat_mean, lat_p95, lat_p99 = hist_stats(dg["write_lat_hist"])
    reads_served = int(dg["reads_served"])
    _, _, read_p95, read_p99 = hist_stats(dg["read_lat_hist"])
    _, _, stale_p95, stale_p99 = hist_stats(dg["obs_stale_hist"])
    return EpochReport(
        read_lat_p95=read_p95,
        read_lat_p99=read_p99,
        n_warned=int(dg["n_warned"]),
        obs_reads_served=int(dg["obs_reads_served"]),
        obs_rerouted=int(dg["obs_rerouted"]),
        obs_stale_p95=stale_p95,
        obs_stale_p99=stale_p99,
        n_obs_digest=int(dg["n_obs_digest"]),
        epoch=epoch,
        reads_arrived=int(dg["reads_arrived"]),
        writes_arrived=int(dg["writes_arrived"]),
        reads_served=reads_served,
        writes_committed=n_done,
        read_lat_mean=float(dg["read_lat_sum"] / max(reads_served, 1)),
        read_lat_max=float(dg["read_lat_max"]),
        write_lat_mean=lat_mean,
        write_lat_p95=lat_p95,
        write_lat_p99=lat_p99,
        cost=float(dg["cost_delta"]),
        n_secretaries=int(dg["n_secretaries"]),
        n_observers=int(dg["n_observers"]),
        leader_changes=int(dg["leader_changes"]),
        no_leader_ticks=int(dg["no_leader_ticks"]),
        killed=int(dg["killed"]),
        metrics=(trace_metrics.as_dict(dg["trace_metrics"])
                 if "trace_metrics" in dg else None),
    )


def compact_state(state: Dict) -> Dict:
    """Epoch-boundary log compaction (state machines keep the data).

    Shape-generic — written with zeros_like/full_like only, so it works on
    a single cluster ((N, L) leaves) and on a batched fleet ((B, N, L)).

    Digest tier (DESIGN.md §13): the log window the digests fingerprint
    resets here, so `dobs_applied`/`dobs_digest` reset with it; and the
    epoch boundary is the in-graph re-lease point for the tier — digest
    observers are stateless and cheap, so every enabled slot comes back
    alive (`dobs_alive = dobs_enabled`) with its warning cleared, the
    sparse twin of the host-side `lease_and_wire`.  The last sync tick is
    kept: a revived slot stays stale (reroutes reads) until its first
    anti-entropy round lands."""
    return dict(
        state,
        dobs_applied=jnp.zeros_like(state["dobs_applied"]),
        dobs_term=jnp.zeros_like(state["dobs_term"]),
        dobs_digest=jnp.zeros_like(state["dobs_digest"]),
        dobs_alive=state["dobs_enabled"],
        dobs_warn=jnp.full_like(state["dobs_warn"], -1),
        obs_reads_served=jnp.zeros_like(state["obs_reads_served"]),
        obs_rerouted=jnp.zeros_like(state["obs_rerouted"]),
        obs_stale_hist=jnp.zeros_like(state["obs_stale_hist"]),
        log_term=jnp.zeros_like(state["log_term"]),
        log_key=jnp.zeros_like(state["log_key"]),
        log_val=jnp.zeros_like(state["log_val"]),
        log_len=jnp.zeros_like(state["log_len"]),
        commit_len=jnp.zeros_like(state["commit_len"]),
        applied_len=jnp.zeros_like(state["applied_len"]),
        applied_digest=jnp.zeros_like(state["applied_digest"]),
        match_len=jnp.zeros_like(state["match_len"]),
        app_arrive_t=jnp.full_like(state["app_arrive_t"], -1),
        ack_arrive_t=jnp.full_like(state["ack_arrive_t"], -1),
        entry_submit_t=jnp.full_like(state["entry_submit_t"], -1),
        entry_commit_t=jnp.full_like(state["entry_commit_t"], -1),
        reads_arrived=jnp.zeros_like(state["reads_arrived"]),
        writes_arrived=jnp.zeros_like(state["writes_arrived"]),
        cross_arrived=jnp.zeros_like(state["cross_arrived"]),
        reads_served=jnp.zeros_like(state["reads_served"]),
        writes_committed=jnp.zeros_like(state["writes_committed"]),
        read_lat_sum=jnp.zeros_like(state["read_lat_sum"]),
        read_lat_max=jnp.zeros_like(state["read_lat_max"]),
        read_lat_hist=jnp.zeros_like(state["read_lat_hist"]),
        # the metrics registry is per-epoch (its digest row was just
        # taken); the trace ring + cursor are NOT reset — the cursor is
        # monotone so host drains stay exact (DESIGN.md §14)
        metrics_ctr=jnp.zeros_like(state["metrics_ctr"]),
    )


def lease_and_wire(cfg: ClusterConfig, static, role: np.ndarray,
                   alive: np.ndarray, np_rng, predictor, leased: np.ndarray,
                   want_sec: int, want_obs: int,
                   warned: Optional[np.ndarray] = None
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                              np.ndarray]:
    """Peak: score a spot-offer pool (eq. 2), MCSA-select, wire roles.

    Pure numpy control-plane step shared by BWRaftSim and FleetSim.
    Returns updated (role, alive, sec_of, obs_of); `leased` is a per-site
    lease census updated in place.  `warned` (optional (N,) bool, the
    digest's advance-warning census, DESIGN.md §12) excludes warned
    secretaries from the follower fan-out wiring so replacements leased
    this epoch take over BEFORE the kill lands; None or all-False is
    bit-identical to the pre-warning wiring.
    """
    site = static["site"]
    V = static["V"]
    n_sites = cfg.num_sites
    role = np.asarray(role).copy()
    alive = np.asarray(alive).copy()
    warned = (np.zeros(role.shape, bool) if warned is None
              else np.asarray(warned).astype(bool))

    def lease_slots(slot_mask, want):
        free = np.where(slot_mask & (role == DEAD))[0]
        if want <= 0 or len(free) == 0:
            return []
        pool = min(len(free) * 4, 256)
        offer_site = np_rng.integers(0, n_sites, pool)
        cpu = np_rng.uniform(1, 4, pool)
        mem = np_rng.uniform(1, 8, pool)
        price = np.array([cfg.sites[s].spot_price_mean for s in
                          offer_site]) * np_rng.uniform(0.6, 1.6, pool)
        revoke = predictor.predict()[offer_site]
        scores = mgr.spot_scores(cpu, mem, price, revoke)
        picked = mcsa.mcsa_topk(scores, min(want, len(free)), np_rng)
        chosen_sites = [int(offer_site[i]) for i in picked]
        slots = []
        for s_id in chosen_sites:
            cands = [f for f in free
                     if site[f] == s_id and f not in slots]
            if not cands:
                cands = [f for f in free if f not in slots]
            if cands:
                slots.append(int(cands[0]))
                leased[site[slots[-1]]] += 1
        return slots

    for s in lease_slots(static["is_secretary_slot"], want_sec):
        role[s] = SECRETARY
        alive[s] = True
    for s in lease_slots(static["is_observer_slot"], want_obs):
        role[s] = OBSERVER
        alive[s] = True

    # wire followers -> site secretary (round robin), observers -> a
    # follower at their site
    sec_of = np.full(role.shape, -1, np.int32)
    obs_of = np.full(role.shape, -1, np.int32)
    for s_id in range(n_sites):
        secs = [i for i in range(len(role))
                if role[i] == SECRETARY and alive[i] and not warned[i]
                and site[i] == s_id]
        fols = [i for i in range(V)
                if role[i] in (FOLLOWER, LEADER) and alive[i]
                and site[i] == s_id]
        if secs:
            for j, f in enumerate(fols):
                sec_of[f] = secs[j % len(secs)]
        obss = [i for i in range(len(role))
                if role[i] == OBSERVER and alive[i] and site[i] == s_id]
        if fols:
            for j, o in enumerate(obss):
                obs_of[o] = fols[j % len(fols)]
    # cross-site fallback wiring for observers at secretary-less sites
    all_fols = [i for i in range(V) if role[i] in (FOLLOWER, LEADER)
                and alive[i]]
    for o in range(len(role)):
        if role[o] == OBSERVER and alive[o] and obs_of[o] < 0 and all_fols:
            obs_of[o] = all_fols[o % len(all_fols)]
    return role, alive, sec_of, obs_of


class ClusterController:
    """Host-side per-cluster control plane ("peek" + "peak" bookkeeping).

    Owns the numpy RNG, the revocation predictor, the per-site lease
    census, and the read-growth history — everything Algorithm 1 needs
    between epochs.  One instance per simulated cluster, shared by the
    sequential `BWRaftSim` and every member of a batched `FleetSim`.
    """

    def __init__(self, cfg: ClusterConfig, static, *, seed: int,
                 predictor: Optional[mgr.RevocationPredictor] = None):
        self.cfg = cfg
        self.static = static
        self.np_rng = np.random.default_rng(seed + 1)
        # default: flat-prior EWMA; pass a trace-calibrated predictor
        # (`market.calibrate.calibrate_predictor`) to score spot offers
        # with per-site rates fitted offline (DESIGN.md §10)
        self.predictor = predictor if predictor is not None \
            else mgr.RevocationPredictor(cfg.num_sites)
        self.reads_prev = 0
        self.leased = np.zeros(cfg.num_sites, np.int64)

    def decide(self, rep: EpochReport, spot_price: float
               ) -> mgr.PeekDecision:
        """Algorithm 1 on this epoch's stats (call only when managing)."""
        self.predictor.update(
            np.full(self.cfg.num_sites,
                    rep.killed / max(self.cfg.num_sites, 1)),
            np.maximum(self.leased, 1))
        stats = mgr.PeekStats(
            reads_prev=self.reads_prev,
            reads_now=rep.reads_arrived,
            writes_now=rep.writes_arrived,
            followers_per_site=[s.followers for s in self.cfg.sites],
            k_s=rep.n_secretaries, k_o=rep.n_observers,
            budget=self.cfg.budget_per_period,
            spot_price=spot_price,
            on_demand_price=float(
                np.mean([s.on_demand_price for s in self.cfg.sites])),
        )
        return mgr.algorithm1(self.cfg, stats)

    def lease(self, role, alive, want_sec: int, want_obs: int,
              warned=None):
        return lease_and_wire(self.cfg, self.static, role, alive,
                              self.np_rng, self.predictor, self.leased,
                              want_sec, want_obs, warned=warned)

    def end_epoch(self, rep: EpochReport) -> None:
        self.reads_prev = rep.reads_arrived


_EPOCH_CACHE: Dict = {}


def _epoch_fn_for(cfg: ClusterConfig, static,
                  pads=(0, 0, 0, 0, 0, 0, trace_ring.DEFAULT_CAPACITY),
                  backend: str = "xla"):
    """One jitted epoch function per (cluster config, padding, backend) —
    cfg_c values are jit *arguments* (rate sweeps re-use the compiled
    program).  The returned function is the device-resident digest path:
    it compacts in-graph and donates the state buffers (DESIGN.md §7.1).
    `backend` is resolved first (DESIGN.md §8), so `"auto"` and its
    per-platform resolution share one compiled program."""
    backend = resolve_backend(backend)
    key = (cfg, pads, backend)
    if key not in _EPOCH_CACHE:
        def epoch_fn(state, rng, cfg_c):
            return device_epoch(state, static, cfg_c, rng, cfg.period_ticks,
                                backend=backend)
        _EPOCH_CACHE[key] = CountingJit(epoch_fn, donate_argnums=(0,))
    return _EPOCH_CACHE[key]


class BWRaftSim:
    """In-process BW-Raft cluster simulation (the paper's prototype).

    `pad_*` widen the state shapes with inert slots/sites/log tail so a
    solo run can reproduce exactly the shapes a `FleetSim` member gets when
    batched next to bigger clusters (DESIGN.md §7).  `backend` selects the
    tick hot-op implementation — `"xla"` (default), `"pallas"` (the
    fused kernel families, DESIGN.md §8), or `"auto"` (pallas on TPU,
    xla elsewhere — resolved at construction, `self.backend` holds the
    resolution); trajectories are bit-identical either way (test
    invariant).

    `market="trace"` replays a `market.MarketTrace` instead of the
    synthetic walk (DESIGN.md §10) — the trace rides in `cfg_c` as jit
    arguments, and a walk exported via
    `market/synthetic.export_walk_trace` at this seed replays
    bit-identically.  `predictor` optionally seeds the control plane
    with a trace-calibrated `RevocationPredictor`
    (`market.calibrate.calibrate_predictor`).
    """

    def __init__(self, cfg: ClusterConfig, *, mode: str = "bwraft",
                 write_rate: float = 8.0, read_rate: float = 32.0,
                 phi: float = 0.0, seed: int = 0,
                 manage_resources: bool = True,
                 pad_nodes: int = 0, pad_sites: int = 0,
                 pad_log: int = 0, pad_keys: int = 0,
                 spot_price_vol: Optional[float] = None,
                 prelease: Optional[Tuple[int, int]] = None,
                 backend: str = "xla",
                 cross_shard_frac: float = 0.0, two_pc_ticks: int = 0,
                 market: str = "process", trace=None, predictor=None,
                 arrivals=None, keypop=None,
                 warning_ticks: int = 0, spot_bid=None,
                 bid_on_trace: bool = False, faults=None,
                 fault_ticks: Optional[int] = None, bid_policy=None,
                 n_observers: int = 0, pad_observers: int = 0,
                 staleness_bound: int = 16, ae_interval: int = 4,
                 ae_phase=None, trace_on: bool = False, trace_mask=None,
                 trace_capacity: int = trace_ring.DEFAULT_CAPACITY):
        assert mode in ("bwraft", "raft")
        backend = resolve_backend(backend)
        self.cfg = cfg
        self.mode = mode
        self.backend = backend
        self.static = state_mod.build_static(cfg, pad_nodes=pad_nodes,
                                             pad_sites=pad_sites,
                                             n_obs_digest=n_observers,
                                             pad_obs=pad_observers,
                                             trace_capacity=trace_capacity)
        self.state = state_mod.init_state(cfg, self.static, pad_log=pad_log,
                                          pad_keys=pad_keys)
        self.cfg_c = make_cfg_arrays(cfg, write_rate=write_rate,
                                     read_rate=read_rate, phi=phi,
                                     pad_nodes=pad_nodes,
                                     pad_sites=pad_sites, pad_keys=pad_keys,
                                     spot_price_vol=spot_price_vol,
                                     cross_shard_frac=cross_shard_frac,
                                     two_pc_ticks=two_pc_ticks,
                                     market=market, trace=trace,
                                     arrivals=arrivals, keypop=keypop,
                                     warning_ticks=warning_ticks,
                                     spot_bid=spot_bid,
                                     bid_on_trace=bid_on_trace,
                                     faults=faults, fault_ticks=fault_ticks,
                                     n_observers=n_observers,
                                     pad_observers=pad_observers,
                                     staleness_bound=staleness_bound,
                                     ae_interval=ae_interval,
                                     ae_phase=ae_phase,
                                     trace_on=trace_on,
                                     trace_mask=trace_mask)
        # hazard-aware bid policy (DESIGN.md §12): an object with
        # `.update(predictor=, trace=, end_tick=, sites=)` returning the
        # next (S,) bids — applied per epoch through `set_bid`, which is
        # a cfg_c data swap (never recompiles)
        self.bid_policy = bid_policy
        self._trace = trace
        self.rng = jax.random.PRNGKey(seed)
        self.manage = manage_resources and mode == "bwraft"
        self.controller = ClusterController(cfg, self.static, seed=seed,
                                            predictor=predictor)
        self.epoch = 0
        self._reports: List[EpochReport] = []
        # most recent epoch digest (numpy leaves) — kept so benchmarks
        # and tests can reach the raw unit-bin latency histograms
        # (goodput-under-deadline, DESIGN.md §11) without re-marshalling
        self.last_digest: Optional[Dict] = None

        # flight-recorder drain state (DESIGN.md §14): events appended
        # here once per traced epoch by `run_epoch`'s single D2H fetch
        self._trace_cursor = trace_export.DrainCursor()
        self.trace_events: List[trace_export.TraceEvent] = []

        self._epoch_fn = _epoch_fn_for(
            cfg, self.static, (pad_nodes, pad_sites, pad_log, pad_keys,
                               n_observers, pad_observers, trace_capacity),
            backend=backend)
        if prelease is not None:
            # fixed-role mode: wire a static secretary/observer complement
            # once, before the run (no per-epoch management)
            self._lease(max(prelease[0], 0), max(prelease[1], 0))

    # ------------------------------------------------------------------ #
    def set_rates(self, write_rate=None, read_rate=None, phi=None):
        if write_rate is not None:
            self.cfg_c["write_rate"] = jnp.float32(write_rate)
        if read_rate is not None:
            self.cfg_c["read_rate"] = jnp.float32(read_rate)
        if phi is not None:
            self.cfg_c["phi"] = jnp.float32(phi)

    def set_arrivals(self, arrivals) -> None:
        """Swap the open-loop arrival plan in place.  Curves are jit
        arguments at a fixed width (the width the sim was built with),
        so the swap never recompiles (DESIGN.md §11) — the serving-side
        twin of swapping market traces at one shape."""
        width = int(self.cfg_c["write_curve"].shape[0])
        w, r, alen = arrivals.fit_to(width)
        self.cfg_c["open_loop"] = jnp.asarray(True)
        self.cfg_c["write_curve"] = jnp.asarray(w)
        self.cfg_c["read_curve"] = jnp.asarray(r)
        self.cfg_c["arrival_len"] = jnp.int32(alen)

    def set_bid(self, bids) -> None:
        """Swap the per-site spot bids in place — cfg_c data at a fixed
        (S,) shape, so bid-policy updates never recompile (DESIGN.md
        §12); the market-side twin of `set_arrivals`.  A scalar
        broadcasts; a short vector repeats its last site (the
        `site_price_init` padding rule)."""
        S = int(self.cfg_c["spot_bid"].shape[0])
        b = np.asarray(bids, np.float32).reshape(-1)
        if b.size == 1:
            b = np.full((S,), b[0], np.float32)
        elif b.size < S:
            b = np.concatenate(
                [b, np.full((S - b.size,), b[-1], np.float32)])
        self.cfg_c["spot_bid"] = jnp.asarray(b[:S], jnp.float32)

    def set_trace(self, on=None, mask=None) -> None:
        """Toggle flight-recorder capture / remask event classes in
        place — cfg_c data at fixed shapes, so flips never recompile
        (DESIGN.md §14); the observability twin of `set_rates` /
        `set_bid`.  `mask` accepts anything `trace.ring.default_mask`
        produces (an (NCLASS,) bool sequence)."""
        if on is not None:
            self.cfg_c["trace_on"] = jnp.asarray(bool(on))
        if mask is not None:
            m = np.asarray(mask, bool).reshape(-1)
            assert m.size == trace_ring.NCLASS, m.size
            self.cfg_c["trace_mask"] = jnp.asarray(m)

    def drain_trace(self) -> List[trace_export.TraceEvent]:
        """Decode the ring slots appended since the last drain (one D2H
        fetch of the three trace leaves); `run_epoch` calls this
        automatically while `trace_on` is set.  Exact per-class
        overwrite counts accumulate on `self.events_dropped`."""
        events = self._trace_cursor.drain(self.state)
        self.trace_events.extend(events)
        return events

    @property
    def events_dropped(self) -> Dict[str, int]:
        return self._trace_cursor.dropped_by_class()

    def _lease(self, want_sec: int, want_obs: int, warned=None) -> None:
        """Peak: score a spot-offer pool (eq. 2), MCSA-select, wire roles."""
        role, alive, sec_of, obs_of = self.controller.lease(
            np.asarray(self.state["role"]), np.asarray(self.state["alive"]),
            want_sec, want_obs, warned=warned)
        self.state = dict(self.state,
                          role=jnp.asarray(role),
                          alive=jnp.asarray(alive),
                          sec_of=jnp.asarray(sec_of),
                          obs_of=jnp.asarray(obs_of))

    def lease_fixed(self, want_sec: int, want_obs: int) -> None:
        """One-shot fixed-role wiring (the solo twin of
        `FleetSim.lease_fixed`): lease and wire a static complement now,
        typically after a stabilization epoch, with per-epoch management
        off — the fixed-role sweep recipe (fig12/fig13)."""
        self._lease(max(want_sec, 0), max(want_obs, 0))

    # ------------------------------------------------------------------ #
    def run_epoch(self) -> EpochReport:
        """One epoch on the digest path: the jitted scan compacts in-graph
        and donates the state buffers; only the few-KB digest is pulled to
        host (DESIGN.md §7.1 — no full log/kv/entry transfer)."""
        self.rng, sub = jax.random.split(self.rng)
        self.state, digest = self._epoch_fn(self.state, sub, self.cfg_c)
        dg = jax.tree.map(np.asarray, digest)
        self.last_digest = dg
        if bool(np.asarray(self.cfg_c["trace_on"])):
            # drain the ring from the RETURNED state (the donated input
            # buffers are gone) before the next epoch overwrites it —
            # the one extra D2H fetch tracing costs (DESIGN.md §14)
            self.drain_trace()

        rep = report_from_digest(self.epoch, dg)

        # ---- control plane: peek (Algorithm 1) + peak (MCSA lease) ------
        if self.manage:
            dec = self.controller.decide(
                rep, float(np.mean(dg["spot_price"][:self.cfg.num_sites])))
            rep.decision = dec
            # re-lease BEFORE the kill lands (DESIGN.md §12): warned
            # secretaries/observers get replacements on top of Algorithm
            # 1's delta, and warned secretaries drop out of the wiring;
            # with no warnings raised this is exactly the pre-§12 lease
            warned = np.asarray(dg["warned"])
            roles = np.asarray(dg["role"])
            self._lease(
                max(dec.dk_s, 0) + int(((roles == SECRETARY) &
                                        warned).sum()),
                max(dec.dk_o, 0) + int(((roles == OBSERVER) &
                                        warned).sum()),
                warned=warned)
        if self.bid_policy is not None:
            self.set_bid(self.bid_policy.update(
                predictor=self.controller.predictor, trace=self._trace,
                end_tick=(self.epoch + 1) * self.cfg.period_ticks,
                sites=int(self.cfg_c["spot_bid"].shape[0])))
        self.controller.end_epoch(rep)

        self.epoch += 1
        self._reports.append(rep)
        return rep

    def run(self, epochs: int) -> List[EpochReport]:
        return [self.run_epoch() for _ in range(epochs)]

    @property
    def reports(self) -> List[EpochReport]:
        return self._reports
