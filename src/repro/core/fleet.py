"""Batched fleet simulator: B independent BW-Raft clusters in ONE program.

The paper's headline results are sweep-shaped — goodput/cost versus node
count, write ratio, spot volatility, and kill rate — yet a sequential
`BWRaftSim` pays one Python-driven jitted epoch per point.  `FleetSim`
vmaps the same `core/step.tick` over a leading batch axis of B clusters so
an entire sweep grid advances in a single `lax.scan` epoch.

Compilation contract (DESIGN.md §7): the batched epoch function is
compiled **once per static shape**.  The cache key is

    (B, N, S, L, K, period_ticks, shared capacity scalars)

where N/S/L/K are the node/site/log/key-space sizes **padded to the max
across the batch**.  Everything else — per-cluster rates, phi, prices,
volatility, timeouts, voter majorities, RTT matrices, the (S, Tt)
market-trace arrays (DESIGN.md §10) — enters as jit *arguments*, so
changing the sweep grid, the seeds, the traces, or even the member
topologies (at equal padded shapes) never recompiles.  Check
`FleetSim.compile_count` (the example `examples/sweep_fleet.py` asserts
it is exactly 1 for a 32-cluster sweep).

Epoch pipeline (DESIGN.md §7.1): the default `pipeline="device"` keeps the
whole epoch loop device-resident — per-tick metrics reduce inside the
scan, log compaction is fused into the jitted epoch, and the state pytree
is donated back to XLA, so the only per-epoch device→host traffic is a
few-KB digest per member (`runtime.report_from_digest`).  When no member
manages resources (plain-Raft baselines, fixed-role `prelease` sweeps) a
whole `run(E)` collapses into ONE dispatch: a scan over E epochs with
in-graph compaction between them.  `pipeline="host"` retains the PR-1
host-marshalling path (full state + T-stacked metrics pulled to host each
epoch) for A/B benchmarking (`benchmarks/perf_fleet.py`) and the
digest-equivalence tests.

Padding/masking rules (DESIGN.md §7): smaller clusters are padded with
inert node slots (non-voter, non-leasable, forever DEAD — every step rule
masks on `alive`), price-only padded sites, and dead log/key tail space.
Batched results are element-wise equal to sequential `BWRaftSim` runs of
the same padded shapes and seeds (`tests/test_fleet.py` proves it): the
per-member RNG streams are split identically, and member dynamics never
couple across the batch axis.

The host-side control plane (Algorithm 1 "peek", MCSA "peak" leasing)
still runs per member between epochs, reusing `runtime.ClusterController`
— it reads the (N,) role/alive vectors from the digest and writes back
only the four (B, N) role/wiring arrays for the members that manage.
Each part of a digest-path epoch on the host runs under its profiler
span (`trace.spans.FLEET_SPANS`): dispatch, digest fetch, control
plane, write-back, flight-recorder drain.

Shard groups (DESIGN.md §9): members with `group_id >= 0` are the shards
of ONE Multi-Raft system.  The epoch function reduces their digests to
per-group digests in-graph (segment ops over the batch axis, same
compiled dispatch) and `group_reports` serves them as `MultiRaftReport`s
— a whole S-shard x B-system baseline sweep is one program, its 2PC
rounds measured per request by the tick itself.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import state as state_mod
from repro.core import step as step_mod
from repro.core.cluster_config import ClusterConfig
from repro.core.runtime import (ClusterController, CountingJit, EpochReport,
                                build_report, compact_state, device_epoch,
                                make_cfg_arrays, report_from_digest)
from repro.core.state import pytree_nbytes
from repro.kernels import resolve_backend
from repro.kernels.group_digest import ops as gd_ops
from repro.trace import export as trace_export
from repro.trace import ring as trace_ring
from repro.trace import spans as trace_spans

# static scalars every member must agree on (baked into the compiled
# program; per-node capacities from state.build_static)
_SHARED_STATIC_KEYS = ("work_capacity", "msg_budget", "entries_per_msg",
                       "max_ship", "max_apply")
# per-member static arrays that become jit arguments (batch axis 0);
# site_rtt/dobs_site are the digest-tier addressing tables (DESIGN.md §13)
_BATCHED_STATIC_KEYS = ("site", "is_voter", "rtt", "majority",
                        "site_rtt", "dobs_site")

# spec fields sweepable via FleetSim.from_sweep axes
_SWEEP_AXES = ("mode", "write_rate", "read_rate", "phi", "seed",
               "manage_resources", "spot_price_vol", "budget_per_period",
               "market", "trace", "arrivals", "keypop",
               "warning_ticks", "bid_policy", "faults", "bid_on_trace",
               "n_observers", "staleness_bound", "ae_interval",
               "trace_on")


@dataclasses.dataclass(frozen=True)
class MemberSpec:
    """One cluster in the fleet: topology + workload knobs + seed."""
    cfg: ClusterConfig
    mode: str = "bwraft"
    write_rate: float = 8.0
    read_rate: float = 32.0
    phi: float = 0.0
    seed: int = 0
    manage_resources: bool = True
    spot_price_vol: Optional[float] = None      # None -> cfg.sites[0]
    budget_per_period: Optional[float] = None   # None -> cfg value
    # fixed-role mode: wire (n_secretaries, n_observers) once at t=0 and
    # never manage again — eligible for the single-dispatch multi-epoch
    # scan when combined with manage_resources=False (DESIGN.md §7.1)
    prelease: Optional[Tuple[int, int]] = None
    # shard-group identity (DESIGN.md §9): members sharing a group_id >= 0
    # are the shards of ONE Multi-Raft system — the fleet reduces their
    # digests to a per-group digest in-graph and reports them as a single
    # `MultiRaftReport`.  `shards_per_group` is the declared group size
    # (validated against the actual member count — the ragged-group
    # guard); `cross_shard_frac` is the 2PC coupling fraction χ;
    # `two_pc_ticks` overrides the 2PC round trip (None -> derived from
    # the topology via `multiraft.two_pc_penalty`).
    group_id: int = -1
    shards_per_group: int = 1
    cross_shard_frac: float = 0.0
    two_pc_ticks: Optional[int] = None
    # spot-market source (DESIGN.md §10): "process" runs the synthetic
    # walk, "trace" replays this member's `market.MarketTrace` — the
    # (S, Tt) price/revocation arrays ride in cfg_c as jit arguments
    # (every member's arrays are fitted to the fleet-wide max trace
    # length, time-wrapped, so one batched program serves any mix of
    # traced and process members and a B-trace sweep is one dispatch)
    market: str = "process"
    trace: Optional[object] = None          # market.MarketTrace
    # open-loop workload source (DESIGN.md §11): None keeps the closed-
    # loop scalar rates above; a `workload.OpenLoop` plan rides in cfg_c
    # as per-tick rate curves, every member's curves fitted to the
    # fleet-wide max plan length the way market traces are — one batched
    # program serves any mix of open- and closed-loop members.  `keypop`
    # (a `workload.ZipfianKeys`) skews the leader's write-key draws; None
    # keeps the uniform draw.
    arrivals: Optional[object] = None       # workload.OpenLoop
    keypop: Optional[object] = None         # workload.ZipfianKeys
    # revocation robustness (DESIGN.md §12): `warning_ticks` is the
    # advance-warning window W (cfg_c data — a W sweep is one program);
    # `bid_on_trace` re-derives trace revocations from replayed prices
    # vs the member's CURRENT bid; `bid_policy` (e.g.
    # `market.calibrate.HazardAwareBid`, eq=False so the frozen spec
    # stays hashable) recomputes the (S,) bids per epoch — a cfg_c row
    # write, never a recompile, but it does exclude the fleet from the
    # multi-epoch single-dispatch scan; `faults` is a deterministic
    # `market.chaos.FaultSchedule` riding in cfg_c like market traces
    warning_ticks: int = 0
    bid_on_trace: bool = False
    bid_policy: Optional[object] = None     # market.calibrate.HazardAwareBid
    faults: Optional[object] = None         # market.chaos.FaultSchedule
    # digest-tier observer count (DESIGN.md §13): sparse (O,)-shaped
    # slots that sync via anti-entropy under a staleness bound — a sweep
    # axis; members pad to the fleet-wide max O, so mixed observer
    # counts stay one compiled program.  `staleness_bound`/`ae_interval`
    # are cfg_c data (swaps never recompile).
    n_observers: int = 0
    staleness_bound: int = 16
    ae_interval: int = 4
    # flight recorder (DESIGN.md §14): `trace_on`/`trace_mask` are cfg_c
    # data (flips never recompile; a traced/untraced mix is one batched
    # program); `trace_capacity` is the per-member ring depth — members
    # pad to the fleet-wide max, the one compile-key trace knob.  The
    # mask is a length-NCLASS bool tuple (tuple, not array, so the
    # frozen spec stays hashable); None = all classes.
    trace_on: bool = False
    trace_mask: Optional[Tuple[bool, ...]] = None
    trace_capacity: int = trace_ring.DEFAULT_CAPACITY

    @property
    def manage(self) -> bool:
        return self.manage_resources and self.mode == "bwraft"


@dataclasses.dataclass(frozen=True)
class FleetShapes:
    B: int
    N: int   # nodes, padded to max over members
    S: int   # sites, padded
    L: int   # log window, padded
    K: int   # KV key space, padded
    T: int   # period_ticks (must be equal across members)
    O: int = 0   # digest-tier observer slots, padded (DESIGN.md §13)
    C: int = trace_ring.DEFAULT_CAPACITY  # trace ring depth (§14), padded


# (kind, shapes, shared scalars[, E]) -> CountingJit
_FLEET_EPOCH_CACHE: Dict = {}


def total_compile_count() -> int:
    """Compiled batched-epoch programs across every fleet shape and
    pipeline this process has run (`runtime.CountingJit`)."""
    return sum(fn.cache_size() for fn in _FLEET_EPOCH_CACHE.values())


# per-member digest fields reduced to a per-group digest in-graph
# (DESIGN.md §9): everything a MultiRaftReport needs, pooled over the
# shards of each group by a segment sum (read_lat_max by a segment max)
_GROUP_SUM_KEYS = ("write_lat_hist", "read_lat_hist", "reads_arrived",
                   "writes_arrived", "reads_served", "read_lat_sum",
                   "cost_delta", "killed", "no_leader_ticks",
                   "leader_changes", "cross_arrived", "two_pc_prepares",
                   "two_pc_aborts", "trace_metrics")


# float digest leaves: summed (order-sensitive — the kernel accumulates
# in ascending member order, which is scatter-add order) + the one max
_GROUP_FLOAT_KEYS = ("read_lat_sum", "cost_delta")
_GROUP_INT_KEYS = tuple(k for k in _GROUP_SUM_KEYS
                        if k not in _GROUP_FLOAT_KEYS)


def _group_digest(digest: Dict, gids, n_groups: int,
                  backend: str = "xla") -> Dict:
    """Reduce per-member digest leaves (B, ...) to per-group leaves
    (G, ...).  Ungrouped members carry segment id G and are dropped by
    the segment ops — the masking rule that makes ragged group sizes and
    mixed grouped/ungrouped fleets shape-free (DESIGN.md §9).

    `backend="pallas"` packs the leaves into one (B, F) int32 matrix
    plus a (B, 3) float32 matrix and runs the single blockwise masked
    reduction of `kernels/group_digest` instead of the per-leaf
    `segment_sum`/`segment_max` pair — bit-identical, floats included
    (test invariant, DESIGN.md §8)."""
    if backend == "pallas":
        parts, widths = [], []
        for k in _GROUP_INT_KEYS:
            v = jnp.asarray(digest[k], jnp.int32)
            v = v[:, None] if v.ndim == 1 else v
            parts.append(v)
            widths.append(v.shape[1])
        int_mat = jnp.concatenate(parts, axis=1)
        flt_mat = jnp.stack([digest[k] for k in _GROUP_FLOAT_KEYS] +
                            [digest["read_lat_max"]], axis=1)
        g_int, g_sum, g_max = gd_ops.group_reduce(gids, int_mat, flt_mat,
                                                  n_groups=n_groups)
        out, off = {}, 0
        for k, w in zip(_GROUP_INT_KEYS, widths):
            leaf = g_int[:, off:off + w]
            out[k] = leaf[:, 0] if jnp.asarray(digest[k]).ndim == 1 \
                else leaf
            off += w
        for i, k in enumerate(_GROUP_FLOAT_KEYS):
            out[k] = g_sum[:, i]
        out["read_lat_max"] = g_max[:, len(_GROUP_FLOAT_KEYS)]
        return out
    out = {k: jax.ops.segment_sum(digest[k], gids, num_segments=n_groups)
           for k in _GROUP_SUM_KEYS}
    out["read_lat_max"] = jax.ops.segment_max(
        digest["read_lat_max"], gids, num_segments=n_groups)
    return out


def _vmapped_epoch(shapes: FleetShapes, shared: Dict, backend: str = "xla",
                   n_groups: int = 0):
    """One device epoch vmapped over the batch axis — the single body
    shared by the per-epoch and multi-epoch pipelines, so their dynamics
    can never diverge.  `backend` picks the tick hot-op implementation
    (DESIGN.md §8); the Pallas kernels batch under vmap like any op.
    With `n_groups > 0` the epoch takes a trailing (B,) segment-id
    argument and the digest gains a `"group"` subtree — the in-graph
    grouped reduction (DESIGN.md §9), fused into the same program so a
    sharded sweep stays one dispatch per epoch."""
    backend = resolve_backend(backend)

    def epoch(state, rngs, bstatic, cfg_c):
        def one_epoch(st, rng, bstat, cc):
            static = {**shared, **bstat}
            return device_epoch(st, static, cc, rng, shapes.T,
                                backend=backend)
        return jax.vmap(one_epoch)(state, rngs, bstatic, cfg_c)
    if n_groups == 0:
        return epoch

    def grouped_epoch(state, rngs, bstatic, cfg_c, gids):
        state, digest = epoch(state, rngs, bstatic, cfg_c)
        return state, dict(digest,
                           group=_group_digest(digest, gids, n_groups,
                                               backend=backend))
    return grouped_epoch


def _fleet_epoch_fn(shapes: FleetShapes, shared: Dict,
                    backend: str = "xla", n_groups: int = 0,
                    widths: Tuple[int, ...] = ()):
    """Digest pipeline: a jitted, vmapped, fully device-resident epoch —
    in-scan metric reduction, in-graph compaction, donated state buffers.
    Returns `(compacted_state, digest)` with digest leaves batched over B.
    One compile per (static shape, backend, group count, cfg_c array
    widths); `shared` (python ints) is closed over, batched statics,
    cfg_c, and the group segment ids are runtime arguments.  `widths`
    (the fleet's trace/arrival/fault-schedule tick widths, §10–§12) are
    jit-static shapes of the cfg_c arguments, so they belong in the
    cache key — two same-shape fleets at different widths are different
    programs and must not share one compile counter.  `backend` is
    resolved first (DESIGN.md §8), so `"auto"` and its per-platform
    resolution share one compiled program."""
    backend = resolve_backend(backend)
    key = ("device", shapes, tuple(sorted(shared.items())), backend,
           n_groups, widths)
    if key not in _FLEET_EPOCH_CACHE:
        _FLEET_EPOCH_CACHE[key] = CountingJit(
            _vmapped_epoch(shapes, shared, backend, n_groups),
            donate_argnums=(0,))
    return _FLEET_EPOCH_CACHE[key]


def _fleet_multi_epoch_fn(shapes: FleetShapes, shared: Dict, epochs: int,
                          backend: str = "xla", n_groups: int = 0,
                          widths: Tuple[int, ...] = ()):
    """Single-dispatch fast path: scan-of-scans over `epochs` device
    epochs (compaction in-graph between them) for fleets with no managing
    member.  Digest leaves come back stacked (E, B, ...) — group leaves,
    when present, (E, G, ...)."""
    backend = resolve_backend(backend)
    key = ("multi", shapes, tuple(sorted(shared.items())), epochs, backend,
           n_groups, widths)
    if key not in _FLEET_EPOCH_CACHE:
        epoch = _vmapped_epoch(shapes, shared, backend, n_groups)

        if n_groups == 0:
            def multi_fn(state, rngs, bstatic, cfg_c):
                def epoch_body(st, rngs_b):
                    return epoch(st, rngs_b, bstatic, cfg_c)
                return jax.lax.scan(epoch_body, state, rngs)
        else:
            def multi_fn(state, rngs, bstatic, cfg_c, gids):
                def epoch_body(st, rngs_b):
                    return epoch(st, rngs_b, bstatic, cfg_c, gids)
                return jax.lax.scan(epoch_body, state, rngs)
        _FLEET_EPOCH_CACHE[key] = CountingJit(multi_fn, donate_argnums=(0,))
    return _FLEET_EPOCH_CACHE[key]


def _fleet_epoch_fn_host(shapes: FleetShapes, shared: Dict,
                         widths: Tuple[int, ...] = ()):
    """The PR-1 reference path, op for op: the original tick formulations
    (`step.tick(reference=True)`), per-tick metrics stacked over T,
    compaction as a separate dispatch, no donation.  Kept for A/B
    benchmarking and the digest-equivalence tests (DESIGN.md §7.1)."""
    key = ("host", shapes, tuple(sorted(shared.items())), widths)
    if key not in _FLEET_EPOCH_CACHE:
        def epoch_fn(state, rngs, bstatic, cfg_c):
            def one_epoch(st, rng, bstat, cc):
                static = {**shared, **bstat}

                def body(carry, r):
                    s, m = step_mod.tick(carry, static, cc, r,
                                         reference=True)
                    return s, m
                ticks = jax.random.split(rng, shapes.T)
                return jax.lax.scan(body, st, ticks)
            return jax.vmap(one_epoch)(state, rngs, bstatic, cfg_c)
        _FLEET_EPOCH_CACHE[key] = CountingJit(epoch_fn)
    return _FLEET_EPOCH_CACHE[key]


class _Member:
    """Host-side bookkeeping for one fleet slot.  `trace_ticks` is the
    fleet-wide market-trace width every member's cfg_c arrays share
    (DESIGN.md §10); `arrival_ticks` the fleet-wide arrival-curve width
    (DESIGN.md §11)."""

    def __init__(self, spec: MemberSpec, shapes: FleetShapes,
                 trace_ticks: int = 1, arrival_ticks: int = 1,
                 fault_ticks: int = 1):
        assert spec.mode in ("bwraft", "raft")
        cfg = spec.cfg
        if spec.budget_per_period is not None:
            cfg = dataclasses.replace(
                cfg, budget_per_period=spec.budget_per_period)
        self.spec = spec
        self.cfg = cfg
        self.pads = {
            "pad_nodes": shapes.N - cfg.max_nodes,
            "pad_sites": shapes.S - cfg.num_sites,
            "pad_log": shapes.L - cfg.max_log,
            "pad_keys": shapes.K - cfg.key_space,
            "pad_observers": shapes.O - spec.n_observers,
        }
        assert all(p >= 0 for p in self.pads.values()), \
            f"member {cfg.name} exceeds fleet shapes {shapes}"
        self.static = state_mod.build_static(
            cfg, pad_nodes=self.pads["pad_nodes"],
            pad_sites=self.pads["pad_sites"],
            n_obs_digest=spec.n_observers,
            pad_obs=self.pads["pad_observers"],
            trace_capacity=shapes.C)
        self.state0 = state_mod.init_state(
            cfg, self.static, pad_log=self.pads["pad_log"],
            pad_keys=self.pads["pad_keys"])
        if spec.two_pc_ticks is not None:
            two_pc = spec.two_pc_ticks
        elif spec.group_id >= 0:
            from repro.core.multiraft import two_pc_penalty
            two_pc = two_pc_penalty(cfg)
        else:
            two_pc = 0
        self.cfg_c = make_cfg_arrays(
            cfg, write_rate=spec.write_rate, read_rate=spec.read_rate,
            phi=spec.phi, pad_nodes=self.pads["pad_nodes"],
            pad_sites=self.pads["pad_sites"],
            pad_keys=self.pads["pad_keys"],
            spot_price_vol=spec.spot_price_vol,
            cross_shard_frac=spec.cross_shard_frac, two_pc_ticks=two_pc,
            market=spec.market, trace=spec.trace, trace_ticks=trace_ticks,
            arrivals=spec.arrivals, arrival_ticks=arrival_ticks,
            keypop=spec.keypop,
            warning_ticks=spec.warning_ticks,
            bid_on_trace=spec.bid_on_trace,
            faults=spec.faults, fault_ticks=fault_ticks,
            n_observers=spec.n_observers,
            pad_observers=self.pads["pad_observers"],
            staleness_bound=spec.staleness_bound,
            ae_interval=spec.ae_interval,
            trace_on=spec.trace_on, trace_mask=spec.trace_mask)
        self.rng = jax.random.PRNGKey(spec.seed)
        self.controller = ClusterController(cfg, self.static,
                                            seed=spec.seed)
        if spec.prelease is not None:
            role, alive, sec_of, obs_of = self.controller.lease(
                np.asarray(self.state0["role"]),
                np.asarray(self.state0["alive"]),
                max(spec.prelease[0], 0), max(spec.prelease[1], 0))
            self.state0 = dict(self.state0,
                               role=jnp.asarray(role),
                               alive=jnp.asarray(alive),
                               sec_of=jnp.asarray(sec_of),
                               obs_of=jnp.asarray(obs_of))
        self.manage = spec.manage
        self.epoch = 0
        self.reports: List[EpochReport] = []


class FleetSim:
    """B independent clusters stepped in one jitted, vmapped program.

    Per-member dynamics are identical to a sequential `BWRaftSim` with the
    same padded shapes and seed; the control plane runs per member on the
    host between epochs.  `pipeline` selects the epoch implementation:
    `"device"` (default) is the digest path — donated state, in-graph
    compaction, O(digest) device→host traffic — `"host"` the PR-1
    full-marshalling reference (DESIGN.md §7.1).  `backend` selects the
    tick hot-op implementation on the device pipeline: `"xla"`
    (default), `"pallas"` (the fused kernel families, DESIGN.md §8), or
    `"auto"` (pallas on TPU, xla elsewhere — resolved at construction,
    `self.backend` holds the resolution) — trajectories are
    bit-identical either way (test invariant).
    """

    def __init__(self, specs: Sequence[MemberSpec], *,
                 pipeline: str = "device", backend: str = "xla"):
        assert pipeline in ("device", "host"), pipeline
        backend = resolve_backend(backend)
        assert backend == "xla" or pipeline == "device", \
            "the pallas backend applies to the device pipeline only " \
            "(the host pipeline is the frozen PR-1 reference)"
        self.backend = backend
        specs = list(specs)
        assert specs, "fleet needs at least one member"
        periods = {s.cfg.period_ticks for s in specs}
        assert len(periods) == 1, \
            f"all members must share period_ticks, got {periods}"
        self.pipeline = pipeline
        self.shapes = FleetShapes(
            B=len(specs),
            N=max(s.cfg.max_nodes for s in specs),
            S=max(s.cfg.num_sites for s in specs),
            L=max(s.cfg.max_log for s in specs),
            K=max(s.cfg.key_space for s in specs),
            T=periods.pop(),
            O=max(s.n_observers for s in specs),
            C=max(s.trace_capacity for s in specs),
        )
        # fleet-shared market-trace width (DESIGN.md §10): every member's
        # cfg_c trace arrays stack to (B, S, Tt); shorter traces time-wrap
        # (`MarketTrace.fit_to`, matching the in-step modulo lookup) and
        # process members carry inert placeholders of the same width
        self.trace_ticks = max(
            [s.trace.ticks for s in specs if s.trace is not None],
            default=1)
        # fleet-shared arrival-curve width (DESIGN.md §11): every member's
        # cfg_c rate curves stack to (B, Ta); shorter plans time-wrap
        # (`OpenLoop.fit_to`, matching the in-step modulo lookup) and
        # closed-loop members carry inert zero curves of the same width
        self.arrival_ticks = max(
            [s.arrivals.ticks for s in specs if s.arrivals is not None],
            default=1)
        # fleet-shared fault-schedule width (DESIGN.md §12): members'
        # (N, Tf) kill schedules stack like market traces; schedule-free
        # members carry inert all-False placeholders of the same width
        self.fault_ticks = max(
            [s.faults.ticks for s in specs if s.faults is not None],
            default=1)
        self.members = [_Member(s, self.shapes, self.trace_ticks,
                                self.arrival_ticks, self.fault_ticks)
                        for s in specs]

        # ---- shard groups (DESIGN.md §9) -----------------------------
        # members with group_id >= 0 are Multi-Raft shards; groups may be
        # ragged (different sizes) and interleave with ungrouped members.
        order = sorted({s.group_id for s in specs if s.group_id >= 0})
        self.groups: Dict[int, List[int]] = {
            g: [i for i, s in enumerate(specs) if s.group_id == g]
            for g in order}
        self.n_groups = len(order)
        self._group_chi: Dict[int, float] = {}
        for g, idxs in self.groups.items():
            gspecs = [specs[i] for i in idxs]
            assert all(s.mode == "raft" for s in gspecs), \
                f"group {g}: Multi-Raft shards must be mode='raft'"
            assert all(not s.manage for s in gspecs), \
                f"group {g}: shard members must not manage resources"
            sizes = {s.shards_per_group for s in gspecs}
            assert sizes == {len(idxs)}, \
                f"group {g}: declared shards_per_group {sizes} != actual " \
                f"member count {len(idxs)} (ragged-group guard)"
            chis = {s.cross_shard_frac for s in gspecs}
            assert len(chis) == 1, \
                f"group {g}: shards disagree on cross_shard_frac {chis}"
            self._group_chi[g] = chis.pop()
            taxes = {int(self.members[i].cfg_c["two_pc_ticks"])
                     for i in idxs}
            assert len(taxes) == 1, \
                f"group {g}: shards disagree on two_pc_ticks {taxes} — " \
                f"one 2PC charge per system (DESIGN.md §9)"
        # segment ids: group slot in `order`, or n_groups for ungrouped
        # members (dropped by the in-graph segment reduction)
        self._gids = jnp.asarray(
            [order.index(s.group_id) if s.group_id >= 0 else self.n_groups
             for s in specs], jnp.int32)
        self._group_reports: Dict[int, List] = {g: [] for g in order}

        self._shared = {k: self.members[0].static[k]
                        for k in _SHARED_STATIC_KEYS}
        for m in self.members[1:]:
            for k in _SHARED_STATIC_KEYS:
                assert m.static[k] == self._shared[k], \
                    f"member {m.cfg.name} disagrees on static {k}"

        self._bstatic = {
            k: (jnp.asarray([m.static[k] for m in self.members], jnp.int32)
                if k == "majority" else                      # scalar per member
                jnp.stack([jnp.asarray(m.static[k]) for m in self.members]))
            for k in _BATCHED_STATIC_KEYS
        }
        self._state = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[m.state0 for m in self.members])
        self._cfg_c = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[m.cfg_c for m in self.members])
        assert pipeline == "device" or self.n_groups == 0, \
            "shard groups need the digest pipeline (the host pipeline " \
            "is the frozen PR-1 reference and has no group reduction)"
        widths = (self.trace_ticks, self.arrival_ticks, self.fault_ticks)
        self._epoch_fn = (_fleet_epoch_fn(self.shapes, self._shared,
                                          backend, self.n_groups, widths)
                          if pipeline == "device" else
                          _fleet_epoch_fn_host(self.shapes, self._shared,
                                               widths))
        # cumulative device->host bytes fetched for report building
        # (digest leaves on the device path, full state + T-stacked
        # metrics on the host path) — perf_fleet.py reads the deltas
        self.d2h_bytes = 0
        # most recent epoch's per-member digest (numpy, leading axis =
        # member; group subtree popped off separately) — raw-histogram
        # access for goodput-under-deadline (DESIGN.md §11).  Digest
        # pipeline only; stays None on the host path.
        self.last_digest: Optional[Dict] = None
        self.last_group_digest: Optional[Dict] = None
        # flight recorder (DESIGN.md §14): one incremental ring reader
        # per member; `run_epoch` auto-drains whenever any member's
        # trace_on is set, appending typed events to `trace_events`
        self._trace_cursors = [trace_export.DrainCursor(member=i)
                               for i in range(len(self.members))]
        self.trace_events: List[trace_export.TraceEvent] = []

    # ------------------------------------------------------------------ #
    @classmethod
    def from_sweep(cls, configs, axes: Optional[Dict] = None,
                   pipeline: str = "device", backend: str = "xla",
                   **defaults) -> "FleetSim":
        """Cross-product sweep constructor.

        `configs`: one ClusterConfig or a sequence of them.  `axes`: dict
        mapping a MemberSpec field name (write_rate / read_rate / phi /
        seed / mode / spot_price_vol / budget_per_period / ...) to the
        values to sweep; the member list is configs x product(axes).
        `defaults` fill the remaining MemberSpec fields.  `backend`
        accepts `"auto"` (pallas on TPU, xla elsewhere — DESIGN.md §8);
        the constructed fleet's `.backend` is the resolution.
        """
        if isinstance(configs, ClusterConfig):
            configs = [configs]
        axes = dict(axes or {})
        for name in axes:
            assert name in _SWEEP_AXES, \
                f"unknown sweep axis {name!r}; valid: {_SWEEP_AXES}"
        names = list(axes.keys())
        specs = []
        for cfg in configs:
            for combo in itertools.product(*axes.values()):
                specs.append(MemberSpec(cfg=cfg, **defaults,
                                        **dict(zip(names, combo))))
        return cls(specs, pipeline=pipeline, backend=backend)

    @classmethod
    def sweep(cls, configs, axes: Optional[Dict] = None, *,
              epochs: int = 5, **defaults) -> List[List[EpochReport]]:
        """One-call sweep: build the fleet and run it.  Returns reports
        indexed [member][epoch]; member order is configs-major, then the
        cross product of `axes` in insertion order."""
        return cls.from_sweep(configs, axes, **defaults).run(epochs)

    # ------------------------------------------------------------------ #
    @property
    def compile_count(self) -> int:
        """How many programs the underlying per-epoch function has
        compiled (1 after any number of epochs/sweeps at this static
        shape); the multi-epoch fast path caches separately — see
        `total_compile_count`."""
        return self._epoch_fn.cache_size()

    def pads_for(self, i: int) -> Dict[str, int]:
        """Padding a solo BWRaftSim needs to reproduce member i exactly."""
        return dict(self.members[i].pads)

    @property
    def state(self) -> Dict:
        """Batched state pytree (leading axis = member)."""
        return self._state

    def _split_epoch_rngs(self) -> jnp.ndarray:
        subs = []
        for m in self.members:
            m.rng, sub = jax.random.split(m.rng)
            subs.append(sub)
        return jnp.stack(subs)

    def epoch_hlo(self) -> str:
        """HLO text of the compiled digest-path epoch: the instruction
        names a device trace gives its op events, each with this code's
        `op_name` metadata, for mapping a trace to the tick's phases
        (`trace.spans.hlo_op_scopes`, DESIGN.md §14).  Runs nothing and
        changes no state.  The program that ran may carry stale
        metadata: the in-process caches reuse an earlier trace, and the
        persistent cache's key leaves metadata out.  So this traces the
        epoch afresh and compiles it with the metadata in the key (an
        entry of its own); the instructions are the same either way."""
        fn = jax.jit(_vmapped_epoch(self.shapes, self._shared, self.backend,
                                    self.n_groups), donate_argnums=(0,))
        rngs = jnp.stack([jnp.zeros_like(m.rng) for m in self.members])
        lowered = fn.lower(self._state, rngs, self._bstatic, self._cfg_c,
                           *self._epoch_args())
        key = "jax_compilation_cache_include_metadata_in_key"
        was = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            return lowered.compile().as_text()
        finally:
            jax.config.update(key, was)

    # ------------------------------------------------------------------ #
    def _epoch_args(self) -> Tuple:
        return ((self._gids,) if self.n_groups else ())

    def _append_group_reports(self, gdg: Dict) -> None:
        """Distill one epoch's per-group digest rows (numpy leaves,
        leading axis = group slot) into MultiRaftReports."""
        from repro.core.multiraft import report_from_group_digest
        for slot, g in enumerate(sorted(self.groups)):
            rows = {k: v[slot] for k, v in gdg.items()}
            self._group_reports[g].append(report_from_group_digest(
                len(self._group_reports[g]), rows, self._group_chi[g]))

    @property
    def group_reports(self) -> Dict[int, List]:
        """Per-group `MultiRaftReport` history, keyed by the members'
        `group_id` (DESIGN.md §9).  Digest pipeline only."""
        return {g: list(reps) for g, reps in self._group_reports.items()}

    def run_epoch(self) -> List[EpochReport]:
        if self.pipeline == "host":
            return self._run_epoch_host()
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_DISPATCH):
            rngs = self._split_epoch_rngs()
            self._state, digest = self._epoch_fn(self._state, rngs,
                                                 self._bstatic, self._cfg_c,
                                                 *self._epoch_args())
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_FETCH):
            dg = jax.tree.map(np.asarray, digest)
        self.d2h_bytes += pytree_nbytes(dg)
        if self.n_groups:
            self.last_group_digest = dg.pop("group")
            with jax.profiler.TraceAnnotation(trace_spans.FLEET_CONTROL):
                self._append_group_reports(self.last_group_digest)
        self.last_digest = dg
        if bool(np.asarray(self._cfg_c["trace_on"]).any()):
            self.drain_trace()
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_CONTROL):
            out, managed_rows, managed_vals = self._control(dg)
        if managed_rows:
            # write back ONLY the managed members' role/wiring rows — the
            # rest of the state never leaves (or re-enters) the device
            with jax.profiler.TraceAnnotation(trace_spans.FLEET_WRITEBACK):
                idx = jnp.asarray(managed_rows, jnp.int32)
                upd = {name: jnp.asarray(np.stack([v[j]
                                                   for v in managed_vals]))
                       for j, name in enumerate(("role", "alive", "sec_of",
                                                 "obs_of"))}
                self._state = dict(
                    self._state,
                    **{name: self._state[name].at[idx].set(arr)
                       for name, arr in upd.items()})
        return out

    def _control(self, dg: Dict) -> Tuple[List[EpochReport], List[int],
                                          List[Tuple]]:
        """The host control plane of one digest-path epoch: each member's
        report, Algorithm 1 and the warned-aware MCSA lease for the
        members that manage, then the bid policies.  Returns the reports
        and the managed members' rows and leased (role, alive, sec_of,
        obs_of) values."""
        managed_rows: List[int] = []
        managed_vals: List[Tuple] = []
        out = []
        for i, m in enumerate(self.members):
            dgi = {k: v[i] for k, v in dg.items()}
            rep = report_from_digest(m.epoch, dgi)
            if m.manage:
                dec = m.controller.decide(
                    rep,
                    float(np.mean(dgi["spot_price"][:m.cfg.num_sites])))
                rep.decision = dec
                managed_rows.append(i)
                # warned census (DESIGN.md §12): replace warned
                # secretaries/observers on top of Algorithm 1's delta
                # and drop warned secretaries from the wiring — inert
                # (exact pre-§12 lease) when no warnings are raised
                warned = np.asarray(dgi["warned"])
                roles = np.asarray(dgi["role"])
                managed_vals.append(m.controller.lease(
                    dgi["role"], dgi["alive"],
                    max(dec.dk_s, 0) + int(((roles == state_mod.SECRETARY)
                                            & warned).sum()),
                    max(dec.dk_o, 0) + int(((roles == state_mod.OBSERVER)
                                            & warned).sum()),
                    warned=warned))
            m.controller.end_epoch(rep)
            m.epoch += 1
            m.reports.append(rep)
            out.append(rep)
        self._apply_bid_policies()
        return out, managed_rows, managed_vals

    def _run_epoch_host(self) -> List[EpochReport]:
        """PR-1 reference epoch: full state + per-tick metric stacks are
        materialized to host, the report is built from raw entry
        timelines, and compaction is a separate post-hoc dispatch."""
        rngs = self._split_epoch_rngs()
        cost_before = np.asarray(self._state["cost_accrued"])
        # pre-epoch leader terms, so build_report's np.diff counts a
        # leader change on the FIRST tick of the epoch too — the host
        # twin of the digest accumulator's seeded prev_leader_term
        # (DESIGN.md §14, first-tick blindness fix)
        role0 = np.asarray(self._state["role"])
        alive0 = np.asarray(self._state["alive"])
        term0 = np.asarray(self._state["term"])
        self.d2h_bytes += role0.nbytes + alive0.nbytes + term0.nbytes
        ids = np.arange(role0.shape[1])
        lid0 = np.where((role0 == state_mod.LEADER) & alive0,
                        ids[None, :], -1).max(axis=1)
        lt0 = np.where(lid0 >= 0,
                       term0[np.arange(role0.shape[0]),
                             np.maximum(lid0, 0)], -1)

        self._state, ms = self._epoch_fn(self._state, rngs, self._bstatic,
                                         self._cfg_c)
        st_np = jax.tree.map(np.asarray, self._state)
        ms_np = jax.tree.map(np.asarray, ms)
        self.d2h_bytes += (pytree_nbytes(st_np) + pytree_nbytes(ms_np) +
                           cost_before.nbytes)

        role = st_np["role"].copy()
        alive = st_np["alive"].copy()
        sec_of = st_np["sec_of"].copy()
        obs_of = st_np["obs_of"].copy()

        out = []
        for i, m in enumerate(self.members):
            sti = {k: v[i] for k, v in st_np.items()}
            msi = {k: v[i] for k, v in ms_np.items()}
            rep = build_report(m.epoch, sti, msi, float(cost_before[i]),
                               leader_term0=int(lt0[i]))
            if m.manage:
                dec = m.controller.decide(
                    rep,
                    float(np.mean(sti["spot_price"][:m.cfg.num_sites])))
                rep.decision = dec
                # same warned-aware lease as the digest path (§12), so
                # the two pipelines stay decision-equal under warnings
                warned = sti["alive"] & (sti["warn_timer"] >= 0)
                role[i], alive[i], sec_of[i], obs_of[i] = m.controller.lease(
                    role[i], alive[i],
                    max(dec.dk_s, 0) + int(((role[i] == state_mod.SECRETARY)
                                            & warned).sum()),
                    max(dec.dk_o, 0) + int(((role[i] == state_mod.OBSERVER)
                                            & warned).sum()),
                    warned=warned)
            m.controller.end_epoch(rep)
            m.epoch += 1
            m.reports.append(rep)
            out.append(rep)
        self._apply_bid_policies()

        self._state = compact_state(dict(
            self._state,
            role=jnp.asarray(role), alive=jnp.asarray(alive),
            sec_of=jnp.asarray(sec_of), obs_of=jnp.asarray(obs_of)))
        if bool(np.asarray(self._cfg_c["trace_on"]).any()):
            self.drain_trace()
        return out

    # ------------------------------------------------------------------ #
    def set_trace(self, on: Optional[bool] = None,
                  mask: Optional[Sequence[bool]] = None,
                  members: Optional[Sequence[int]] = None) -> None:
        """Flip the flight recorder for `members` (default: all) — a
        cfg_c row write at a fixed shape, so toggling mid-run NEVER
        recompiles the batched program (DESIGN.md §14)."""
        idx = jnp.asarray(
            list(range(len(self.members))) if members is None
            else list(members), jnp.int32)
        if on is not None:
            self._cfg_c["trace_on"] = \
                self._cfg_c["trace_on"].at[idx].set(bool(on))
        if mask is not None:
            m = jnp.asarray(mask, bool)
            assert m.shape == (trace_ring.NCLASS,), \
                f"trace mask must be ({trace_ring.NCLASS},), got {m.shape}"
            self._cfg_c["trace_mask"] = \
                self._cfg_c["trace_mask"].at[idx].set(m)

    def drain_trace(self) -> List[trace_export.TraceEvent]:
        """One D2H fetch of every member's ring + cursors; returns (and
        appends to `trace_events`) the events since the last drain, in
        per-member emission order (DESIGN.md §14)."""
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_DRAIN):
            ev = np.asarray(self._state["trace_ev"])
            pos = np.asarray(self._state["trace_pos"])
            emit = np.asarray(self._state["trace_emit"])
            self.d2h_bytes += ev.nbytes + pos.nbytes + emit.nbytes
            new: List[trace_export.TraceEvent] = []
            for i, cur in enumerate(self._trace_cursors):
                new.extend(cur.drain({"trace_ev": ev[i],
                                      "trace_pos": pos[i],
                                      "trace_emit": emit[i]}))
            self.trace_events.extend(new)
        return new

    @property
    def events_dropped(self) -> List[Dict[str, int]]:
        """Exact per-member, per-class ring-overwrite counts."""
        return [c.dropped_by_class() for c in self._trace_cursors]

    def _apply_bid_policies(self) -> None:
        """Per-epoch hazard-aware bid updates (DESIGN.md §12): recompute
        each policy member's (S,) bids on the host and write ONLY those
        members' `spot_bid` cfg_c rows back.  cfg_c is jit-argument data
        at a fixed shape, so the swap never recompiles (the market-side
        twin of the manage write-back above)."""
        rows, vals = [], []
        for i, m in enumerate(self.members):
            if m.spec.bid_policy is None:
                continue
            rows.append(i)
            vals.append(np.asarray(m.spec.bid_policy.update(
                predictor=m.controller.predictor, trace=m.spec.trace,
                end_tick=m.epoch * m.cfg.period_ticks,
                sites=self.shapes.S), np.float32))
        if rows:
            idx = jnp.asarray(rows, jnp.int32)
            self._cfg_c["spot_bid"] = self._cfg_c["spot_bid"].at[idx].set(
                jnp.asarray(np.stack(vals), jnp.float32))

    def lease_fixed(self, want_sec: int, want_obs: int) -> None:
        """One-shot fixed-role wiring for every member: lease/wire
        `want_sec` secretaries and `want_obs` observers on the host and
        write the four (B, N) role/wiring arrays back.  The fixed-role
        recipe for sweep grids (fig12/fig13): run one epoch so leadership
        stabilizes (the FIRST election stops preleased secretaries —
        paper Step 1), wire the complement once, then run the rest of the
        sweep as a single dispatch.  O(B·N) transfer, once per run."""
        role = np.asarray(self._state["role"]).copy()
        alive = np.asarray(self._state["alive"]).copy()
        sec_of = np.asarray(self._state["sec_of"]).copy()
        obs_of = np.asarray(self._state["obs_of"]).copy()
        for i, m in enumerate(self.members):
            role[i], alive[i], sec_of[i], obs_of[i] = m.controller.lease(
                role[i], alive[i], max(want_sec, 0), max(want_obs, 0))
        self._state = dict(self._state,
                           role=jnp.asarray(role), alive=jnp.asarray(alive),
                           sec_of=jnp.asarray(sec_of),
                           obs_of=jnp.asarray(obs_of))

    # ------------------------------------------------------------------ #
    @property
    def single_dispatch_eligible(self) -> bool:
        """True when `run(E)` can collapse into one device dispatch: the
        digest pipeline with no member running the per-epoch control
        plane (plain-Raft baselines, fixed-role `prelease` sweeps) and
        no per-epoch bid policy (bid updates are host writes between
        epochs, DESIGN.md §12)."""
        return (self.pipeline == "device" and
                not any(m.manage for m in self.members) and
                not any(m.spec.bid_policy is not None
                        for m in self.members))

    def _run_scan(self, epochs: int) -> None:
        """The multi-epoch fast path: ONE dispatch scans over `epochs`
        device epochs (in-graph compaction between them) and returns the
        digests stacked (E, B, ...)."""
        fn = _fleet_multi_epoch_fn(self.shapes, self._shared, epochs,
                                   self.backend, self.n_groups,
                                   (self.trace_ticks, self.arrival_ticks,
                                    self.fault_ticks))
        # identical split order to the epoch-by-epoch path, so the two are
        # trajectory-equal at the same seeds (tests/test_fleet.py)
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_DISPATCH):
            rngs = jnp.stack([self._split_epoch_rngs()
                              for _ in range(epochs)])
            self._state, digests = fn(self._state, rngs, self._bstatic,
                                      self._cfg_c, *self._epoch_args())
        with jax.profiler.TraceAnnotation(trace_spans.FLEET_FETCH):
            dg = jax.tree.map(np.asarray, digests)
        self.d2h_bytes += pytree_nbytes(dg)
        gdg = dg.pop("group") if self.n_groups else None
        self.last_digest = {k: v[-1] for k, v in dg.items()}
        if gdg is not None:
            self.last_group_digest = {k: v[-1] for k, v in gdg.items()}
        for e in range(epochs):
            if gdg is not None:
                self._append_group_reports({k: v[e] for k, v in
                                            gdg.items()})
            for i, m in enumerate(self.members):
                rep = report_from_digest(
                    m.epoch, {k: v[e, i] for k, v in dg.items()})
                m.controller.end_epoch(rep)
                m.epoch += 1
                m.reports.append(rep)
        if bool(np.asarray(self._cfg_c["trace_on"]).any()):
            self.drain_trace()

    def run(self, epochs: int, *,
            single_dispatch: Optional[bool] = None
            ) -> List[List[EpochReport]]:
        """Run `epochs` epochs; returns the reports of *this call* indexed
        [member][epoch] (matching BWRaftSim.run; the full history stays on
        `self.reports`).  `single_dispatch=None` auto-selects the
        multi-epoch scan whenever it is eligible; pass False to force the
        epoch-by-epoch loop (A/B testing), True to assert eligibility."""
        if single_dispatch is None:
            single_dispatch = epochs > 1 and self.single_dispatch_eligible
        if single_dispatch:
            assert self.single_dispatch_eligible, \
                "single-dispatch run needs pipeline='device' and no " \
                "managing member"
        start = len(self.members[0].reports)
        if single_dispatch:
            self._run_scan(epochs)
        else:
            for _ in range(epochs):
                self.run_epoch()
        return [list(m.reports[start:]) for m in self.members]

    @property
    def reports(self) -> List[List[EpochReport]]:
        return [list(m.reports) for m in self.members]
