"""One BW-Raft protocol tick — pure, branch-free, jit/vmap/scan-able.

Implements the paper's §3 mechanics with an explicit latency model
(per-link RTT classes) and per-node work-capacity accounting:

  1. spot-market dynamics: price step (synthetic walk or trace replay,
     DESIGN.md §10), revocations kill secretaries/observers
  2. client arrivals: Poisson reads (to observers/followers) + writes (to
     the leader's queue)
  3. leader: accept writes into the log (capacity-bounded), ship
     AppendEntries batches — to its secretaries (BW-Raft) or directly to
     every follower (plain Raft) — heartbeats included
  4. secretary relay: forward leader batches to assigned followers,
     aggregate acks, report counts to the leader
  5. followers: log-matching check on (prev_idx, prev_term), truncate
     conflicts, append, ack; forward uncommitted appends to observers
  6. leader commit: majority of *voters* (secretaries/observers never
     count — Property 3.4 state irrelevancy), entry commit times recorded
  7. all nodes: apply committed entries to the KV state machine
  8. reads: served by observers that applied >= readindex, else rerouted
     to their follower (queueing latency tracked)
  9. elections: randomized timeouts, RequestVote with log-up-to-date
     restriction, majority-of-voters win (Property 3.1)

Every rule is masked array math, so thousands of clusters step in parallel
under vmap and 1e5+ ticks run under lax.scan.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.state import (CANDIDATE, DEAD, FOLLOWER, LEADER, OBSERVER,
                              SECRETARY, entry_mix, leader_id)
from repro.kernels import resolve_backend
from repro.kernels.ae_sync import ops as ae_ops
from repro.kernels.leader_fanout import ops as lf_ops
from repro.kernels.raft_tick import ops as rt_ops
from repro.market import synthetic as market_synth
from repro.trace import metrics as trace_metrics
from repro.trace import ring as trace_ring
from repro.trace import spans as trace_spans


def _rand(rng, n):
    return jax.random.split(rng, n)


def cross_shard_mark(idx, frac):
    """Deterministic cross-shard marking (DESIGN.md §9): entry `idx` is a
    cross-shard 2PC coordinator iff `floor((idx+1)*frac) > floor(idx*frac)`
    — exactly `floor(n*frac)` of the first n entries are marked, and no RNG
    is consumed, so `frac == 0` leaves the trajectory bit-identical to an
    unsharded run.  Used both for the commit-time 2PC latency charge
    (`commit_step`) and the prepare/abort census (`runtime` digest)."""
    i = idx.astype(jnp.float32)
    return jnp.floor((i + 1) * frac) > jnp.floor(i * frac)


def spot_step(state, static, cfg_c, rng):
    """Site price dynamics + revocation of spot nodes (DESIGN.md §10).

    Two market sources, selected per member by the `cfg_c["market_trace"]`
    flag — a jit *argument*, so process and trace members mix freely in
    one compiled fleet program:

      process  the synthetic mean-reverting walk
               (`market/synthetic.walk_price_update` — the §10 provider
               refactor keeps the expression bit-identical); revocation
               is price-driven (price > the site's standing bid)
      trace    per-tick lookup into the (S, Tt) `cfg_c["price_trace"]` /
               `cfg_c["revoke_trace"]` arrays at column
               `tick % cfg_c["trace_len"]` — the member's OWN trace
               period, a jit argument, so short traces wrap correctly
               even when widened to a fleet-shared Tt (the §10
               time-wrap rule); both price and revocation replay the
               trace verbatim, no RNG drawn from the market

    The i.i.d. failure knob `phi` applies on top of either source (set
    phi=0 for pure trace replay).  The tick's RNG is split identically on
    both sources and the process branch is computed-then-discarded under
    a trace, so a synthetic walk exported as a trace
    (`market/synthetic.export_walk_trace`) replays **bit-identically**
    through this function — the §10 replay invariant
    (`tests/test_market.py`, gated by `benchmarks/perf_market.py`).

    Revocation robustness (DESIGN.md §12) rides on top, all cfg_c data
    and RNG-free so `warn_ticks == 0` with no faults is bit-identical to
    the frozen site-level rule (`spot_step_reference`):

      * the standing bid is `cfg_c["spot_bid"]` (per-epoch policy
        updates without recompiles); `bid_on_trace` re-derives trace
        revocations from replayed prices vs the CURRENT bid
      * per-node revocation columns (`node_trace` /
        `revoke_node_trace`) replace the site broadcast when the trace
        carries them
      * deterministic chaos schedules (`fault_on` / `fault_trace`,
        column `tick % fault_len`) raise the same signal on ANY node —
        voters included (leader-kill drills)
      * the advance-warning window: a raised revocation signal arms
        `warn_timer` at W = `warn_ticks` and counts down while the
        signal holds; the kill lands only when it hits 0, and a signal
        that drops early (price dips back under the bid) is a
        *reprieve* — the timer resets to -1 and the node resumes.  The
        `phi` i.i.d. knob stays an unwarned immediate kill.
    """
    S = state["spot_price"].shape[0]
    r_price, r_revoke, r_fail = _rand(rng, 3)
    synth_price = market_synth.walk_price_update(
        state["spot_price"], cfg_c["spot_price_mean"],
        cfg_c["spot_price_vol"], r_price)
    use_trace = cfg_c["market_trace"]
    t = jnp.mod(state["tick"], cfg_c["trace_len"])
    price = jnp.where(use_trace, cfg_c["price_trace"][:, t], synth_price)

    over_bid = price > cfg_c["spot_bid"]                      # (S,)
    revoked_site = jnp.where(use_trace & ~cfg_c["bid_on_trace"],
                             cfg_c["revoke_trace"][:, t],
                             over_bid)                        # (S,)
    site = jnp.asarray(static["site"])
    is_spot = ~jnp.asarray(static["is_voter"])
    # per-node revocation columns, else the site signal broadcast (N,)
    market_sig = jnp.where(cfg_c["node_trace"] & use_trace,
                           cfg_c["revoke_node_trace"][:, t],
                           revoked_site[site])
    # deterministic chaos schedule: hits any node, voters included
    tf = jnp.mod(state["tick"], cfg_c["fault_len"])
    fault_sig = cfg_c["fault_on"] & cfg_c["fault_trace"][:, tf]
    sig = state["alive"] & ((is_spot & market_sig) | fault_sig)

    # advance-warning countdown (RNG-free; W=0 kills the tick the
    # signal rises, exactly the pre-§12 rule)
    timer = state["warn_timer"]
    newly = sig & (timer < 0)
    timer = jnp.where(sig,
                      jnp.where(newly, cfg_c["warn_ticks"],
                                jnp.maximum(timer - 1, 0)),
                      -1)
    due = sig & (timer <= 0)

    # i.i.d. failure knob phi on top: immediate, no warning
    iid_fail = jax.random.uniform(r_fail, site.shape) < cfg_c["phi"]
    killed = state["alive"] & (due | (is_spot & iid_fail))
    timer = jnp.where(killed, -1, timer)

    # flight-recorder inputs (DESIGN.md §14), captured before the state
    # rewrite: a reprieve is a held warning whose signal dropped this
    # tick; the warned-secretary/observer handoff edges mirror the
    # `warn_timer >= 0` rules in `leader_step`/`commit_step`/`read_step`
    prev_role = state["role"]
    reprieve = (state["warn_timer"] >= 0) & ~sig & state["alive"]
    warn_live = cfg_c["warn_ticks"] > 0

    alive = state["alive"] & ~killed
    role = jnp.where(killed, DEAD, state["role"])
    state = dict(state, spot_price=price, alive=alive, role=role,
                 warn_timer=timer)

    # §12 revocation seam -> ring + registry (all RNG-free, gated
    # capture — trace_on=0 stays bit-identical, DESIGN.md §14)
    nid = jnp.arange(killed.shape[0])
    # minimal unit-test states omit consensus leaves (tests/test_market
    # drives spot_step alone); record no-ops without the ring leaves,
    # so the term lane just falls back to 0 there
    term = state["term"] if "term" in state else 0
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_WARN, valid=newly & warn_live,
        node=nid, term=term, aux=cfg_c["warn_ticks"],
        counter="warns_armed")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_KILL, valid=killed, node=nid,
        term=term, aux=prev_role, counter="kills")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_REPRIEVE, valid=reprieve, node=nid,
        term=term, counter="reprieves")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_SEC_HANDOFF,
        valid=newly & warn_live & (prev_role == SECRETARY), node=nid,
        term=term, counter="sec_handoffs")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_OBS_DRAIN,
        valid=newly & warn_live & (prev_role == OBSERVER), node=nid,
        term=term, counter="obs_drains")

    # digest-tier observers (DESIGN.md §13) are spot instances too: the
    # site revocation signal, the §12 warning window, and the phi knob
    # all apply, addressed by `static["dobs_site"]`.  Per-node trace
    # columns and chaos fault schedules stay dense-only (they are
    # node-indexed).  The phi draw uses a FRESH fold of r_fail so the
    # dense streams above are untouched; the whole block vanishes at
    # O == 0 (python guard — epoch programs compile per static shape),
    # which is what keeps digest-off trajectories bit-identical to the
    # pre-§13 golden fixtures.  Minimal unit-test states omit the
    # digest leaves entirely — treat absence as O == 0.
    O = state["dobs_alive"].shape[0] if "dobs_alive" in state else 0
    if O:
        dsite = jnp.asarray(static["dobs_site"])
        sig_d = state["dobs_alive"] & revoked_site[dsite]
        timer_d = state["dobs_warn"]
        newly_d = sig_d & (timer_d < 0)
        timer_d = jnp.where(sig_d,
                            jnp.where(newly_d, cfg_c["warn_ticks"],
                                      jnp.maximum(timer_d - 1, 0)),
                            -1)
        due_d = sig_d & (timer_d <= 0)
        iid_d = jax.random.uniform(jax.random.fold_in(r_fail, 1),
                                   (O,)) < cfg_c["phi"]
        killed_d = state["dobs_alive"] & (due_d | iid_d)
        timer_d = jnp.where(killed_d, -1, timer_d)
        state = dict(state, dobs_alive=state["dobs_alive"] & ~killed_d,
                     dobs_warn=timer_d)
    return state, killed


def spot_step_reference(state, static, cfg_c, rng):
    """The frozen pre-§12 site-level market step: immediate kills, no
    warning window, no per-node columns, no chaos schedules.  Kept
    verbatim as the reference twin — `tests/test_faults.py` pins
    `spot_step` at `warn_ticks=0` (and no faults) bit-identical to this
    on both market paths (DESIGN.md §12); the only delta from the
    historical body is that the standing bid now reads from
    `cfg_c["spot_bid"]` (same values at init, see `state.init_state`)."""
    r_price, r_revoke, r_fail = _rand(rng, 3)
    synth_price = market_synth.walk_price_update(
        state["spot_price"], cfg_c["spot_price_mean"],
        cfg_c["spot_price_vol"], r_price)
    use_trace = cfg_c["market_trace"]
    t = jnp.mod(state["tick"], cfg_c["trace_len"])
    price = jnp.where(use_trace, cfg_c["price_trace"][:, t], synth_price)

    revoked_site = jnp.where(use_trace, cfg_c["revoke_trace"][:, t],
                             price > cfg_c["spot_bid"])       # (S,)
    site = jnp.asarray(static["site"])
    is_spot = ~jnp.asarray(static["is_voter"])
    iid_fail = jax.random.uniform(r_fail, site.shape) < cfg_c["phi"]
    killed = is_spot & state["alive"] & (revoked_site[site] | iid_fail)

    alive = state["alive"] & ~killed
    role = jnp.where(killed, DEAD, state["role"])
    return dict(state, spot_price=price, alive=alive, role=role), killed


def workload_step(state, static, cfg_c, rng):
    """Client arrivals this tick: writes -> leader queue, reads -> per-node
    read queues (observers first, at their site, else followers).

    Cross-shard split (DESIGN.md §9): when this member is one shard of a
    Multi-Raft group, a `cross_frac` fraction of the arriving writes are
    cross-shard 2PC coordinators.  The split is deterministic — cumulative
    cross arrivals = floor(cumulative writes * cross_frac) — so it costs
    no RNG draw and is inert at `cross_frac == 0`."""
    r_w, r_r, r_key = _rand(rng, 3)
    # open-loop arrival schedule (DESIGN.md §11): per-tick rate curves
    # ride in cfg_c as jit-argument arrays the way market traces do
    # (DESIGN.md §10) — the lookup wraps at the plan's OWN length, so
    # fleet-widened curves replay identically and swapping schedules at
    # one shape never recompiles.  Closed loop (`open_loop` off) keeps
    # the scalar-rate knob: the `where` selects the identical rate
    # value, so pre-§11 trajectories are bit-identical
    # (`tests/test_serving.py` golden regression).
    ta = jnp.mod(state["tick"], cfg_c["arrival_len"])
    lam_w = jnp.where(cfg_c["open_loop"], cfg_c["write_curve"][ta],
                      cfg_c["write_rate"])
    lam_r = jnp.where(cfg_c["open_loop"], cfg_c["read_curve"][ta],
                      cfg_c["read_rate"])
    n_writes = jax.random.poisson(r_w, lam_w).astype(jnp.int32)
    n_reads = jax.random.poisson(r_r, lam_r).astype(jnp.int32)

    chi = cfg_c["cross_frac"]
    w_before = state["writes_arrived"].astype(jnp.float32)
    w_after = (state["writes_arrived"] + n_writes).astype(jnp.float32)
    n_cross = (jnp.floor(w_after * chi) -
               jnp.floor(w_before * chi)).astype(jnp.int32)

    N = state["role"].shape[0]
    # read routing: spread over alive observers; overflow to followers.
    # Warned observers drain: they take no NEW reads (routing skips
    # them, DESIGN.md §12) but `read_step` still serves their queue
    # until the kill lands
    is_obs = (state["role"] == OBSERVER) & state["alive"] & \
        (state["warn_timer"] < 0)
    is_fol = ((state["role"] == FOLLOWER) | (state["role"] == LEADER)) & \
        state["alive"]
    n_obs = jnp.maximum(jnp.sum(is_obs), 0)
    n_fol = jnp.maximum(jnp.sum(is_fol), 1)
    cap = jnp.int32(static["work_capacity"])
    # digest-tier observers (DESIGN.md §13) join the observer pool:
    # routing treats a digest slot exactly like a dense observer slot
    # (same 90% offload ceiling, same per-slot split), and the same §12
    # drain rule skips warned slots.  At O == 0 `pool` is literally
    # `n_obs` (python guard), so pre-§13 routing is bit-identical.
    O = state["dobs_alive"].shape[0] if "dobs_alive" in state else 0
    if O:
        is_dobs = state["dobs_alive"] & (state["dobs_warn"] < 0)
        pool = n_obs + jnp.sum(is_dobs)
    else:
        pool = n_obs
    # offload up to 90% of reads, but never beyond observer service capacity
    # (headroom x2 absorbs bursts; the rest goes to followers)
    obs_share = jnp.where(pool > 0,
                          jnp.minimum((n_reads * 9) // 10, pool * cap),
                          0)
    fol_share = n_reads - obs_share
    extra = {}
    per_obs = jnp.where(is_obs, obs_share // jnp.maximum(pool, 1), 0)
    if O:
        # dense observers keep the exact O == 0 floor rule above (so a
        # member padded with never-enabled digest slots routes
        # bit-identically to its unpadded twin — the fleet/sequential
        # A/B invariant); the floored remainder, which the O == 0 rule
        # drops, is spread by rank over the digest slots instead — the
        # tier absorbs it
        base = obs_share // jnp.maximum(pool, 1)
        rem = obs_share - base * jnp.maximum(pool, 1)
        r_dobs = jnp.cumsum(is_dobs.astype(jnp.int32)) - 1
        extra["dobs_read_queue"] = state["dobs_read_queue"] + \
            jnp.where(is_dobs, base + (r_dobs < rem), 0)
    per_fol = jnp.where(is_fol, fol_share // n_fol, 0)
    read_queue = state["read_queue"] + per_obs + per_fol

    return dict(state, **extra,
                read_queue=read_queue,
                write_pending=state["write_pending"] + n_writes,
                reads_arrived=state["reads_arrived"] + n_reads,
                writes_arrived=state["writes_arrived"] + n_writes,
                cross_arrived=state["cross_arrived"] + n_cross), \
        (n_writes, n_reads, r_key)


def leader_step(state, static, cfg_c, rng_key, *, backend="xla"):
    """Leader accepts queued writes into its log and ships append batches.

    `backend="pallas"` fuses the budgeted ship — the relay/direct
    split, the secretary/warned handoff mask, the rank-based message
    budget, and the five app_* writes — into one in-register pass
    (`kernels/leader_fanout`, DESIGN.md §8); bit-identical to the XLA
    cumsum/gather formulation below (test invariant)."""
    N = state["role"].shape[0]
    L = state["log_term"].shape[1]
    lid = leader_id(state, static)
    has_leader = lid >= 0
    lid_c = jnp.maximum(lid, 0)
    tick = state["tick"]

    # --- accept writes into the leader log (bounded by capacity & space) --
    cap = jnp.int32(static["work_capacity"])
    space = L - state["log_len"][lid_c]
    n_accept = jnp.where(has_leader,
                         jnp.minimum(jnp.minimum(state["write_pending"],
                                                 cap), space), 0)
    start = state["log_len"][lid_c]
    idxs = start + jnp.arange(64)                             # static window
    take = jnp.arange(64) < n_accept
    # key popularity (DESIGN.md §11): uniform draw (the pre-§11 stream,
    # untouched) or inverse-transform sampling of the (K,) cfg_c CDF —
    # Zipfian hot keys under `workload.ZipfianKeys`.  The Zipfian draw
    # uses a FRESH fold of the tick key, so closed-loop runs
    # (`key_zipf` off) consume exactly the pre-§11 RNG stream.
    keys_uniform = jax.random.randint(rng_key, (64,), 0,
                                      state["kv"].shape[1])
    u = jax.random.uniform(jax.random.fold_in(rng_key, 2), (64,))
    keys_zipf = jnp.clip(
        jnp.searchsorted(cfg_c["key_cdf"], u, side="left"),
        0, state["kv"].shape[1] - 1).astype(jnp.int32)
    keys = jnp.where(cfg_c["key_zipf"], keys_zipf, keys_uniform)
    vals = jax.random.randint(jax.random.fold_in(rng_key, 1), (64,),
                              0, 2**20)
    safe_idx = jnp.where(take, idxs, L - 1)
    log_term = state["log_term"].at[lid_c, safe_idx].set(
        jnp.where(take, state["term"][lid_c], state["log_term"][lid_c,
                                                                safe_idx]),
        mode="drop")
    log_key = state["log_key"].at[lid_c, safe_idx].set(
        jnp.where(take, keys, state["log_key"][lid_c, safe_idx]),
        mode="drop")
    log_val = state["log_val"].at[lid_c, safe_idx].set(
        jnp.where(take, vals, state["log_val"][lid_c, safe_idx]),
        mode="drop")
    entry_submit = state["entry_submit_t"].at[safe_idx].set(
        jnp.where(take & has_leader, tick, state["entry_submit_t"][safe_idx]),
        mode="drop")
    new_len = jnp.where(has_leader, start + n_accept, start)
    log_len = state["log_len"].at[lid_c].set(new_len)

    state = dict(state, log_term=log_term, log_key=log_key, log_val=log_val,
                 log_len=log_len,
                 write_pending=state["write_pending"] - n_accept,
                 entry_submit_t=entry_submit)

    # Multi-Raft 2PC prepare seam -> ring + registry (DESIGN.md §9/§14):
    # entries accepted this tick carrying the cross-shard coordinator
    # mark.  Shared by both backends (emitted before the pallas split);
    # `cross_frac == 0` keeps the count at zero — no event, no bump.
    n_prep = jnp.sum(take & cross_shard_mark(idxs, cfg_c["cross_frac"])
                     ).astype(jnp.int32)
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_2PC_PREPARE, valid=n_prep > 0,
        node=lid_c, term=state["term"][lid_c], aux=n_prep,
        counter="twopc_prepared", count=n_prep)

    # --- ship AppendEntries (budgeted fan-out: THE leader bottleneck) ----
    rtt = jnp.asarray(static["rtt"])

    if backend == "pallas":
        # fused kernel: handoff mask, relay/direct split, budget rank,
        # and the app_* writes in one pass (`kernels/leader_fanout`)
        (app_arrive_t, app_from_len, app_upto, app_term, app_commit,
         work) = lf_ops.leader_fanout(
            state["role"], state["alive"], state["warn_timer"],
            state["sec_of"], state["match_len"], state["app_arrive_t"],
            state["app_from_len"], state["app_upto"], state["app_term"],
            state["app_commit"], rtt, lid_c, has_leader, tick,
            state["log_len"][lid_c], state["term"][lid_c],
            state["commit_len"][lid_c],
            msg_budget=static["msg_budget"], max_ship=static["max_ship"],
            entries_per_msg=static["entries_per_msg"])
        leader_work = state["leader_work"].at[lid_c].add(work)
        return dict(state, app_arrive_t=app_arrive_t,
                    app_from_len=app_from_len, app_upto=app_upto,
                    app_term=app_term, app_commit=app_commit,
                    leader_work=leader_work)

    # secretary relay wiring: follower f's batch goes via sec_of[f] if that
    # secretary is alive, else directly from the leader.
    sec = state["sec_of"]                                     # (N,)
    # a warned secretary hands its fan-out back to the leader NOW, so
    # no in-flight batch is stranded when the kill lands (DESIGN.md §12;
    # `warn_timer < 0` is all-True whenever warnings are off)
    sec_alive = (sec >= 0) & state["alive"][jnp.maximum(sec, 0)] & \
        (state["role"][jnp.maximum(sec, 0)] == SECRETARY) & \
        (state["warn_timer"][jnp.maximum(sec, 0)] < 0)
    relay = jnp.where(sec_alive, sec, lid_c)                  # hop node
    is_target = ((state["role"] == FOLLOWER) | (state["role"] == CANDIDATE)) \
        & state["alive"] & (jnp.arange(N) != lid_c)
    # delivery latency: leader->relay + relay->target (direct: leader->target)
    lat = rtt[lid_c, relay] * (relay != lid_c) + \
        rtt[relay, jnp.arange(N)]
    arrive = tick + lat
    # Shipping is continuous (slot-free gating paces it to one batch per
    # RTT), but the LEADER can emit at most `msg_budget` direct messages
    # per tick: plain Raft pays one per follower, BW-Raft pays one per
    # secretary (the offload, paper §3/Fig 4).  Relayed batches spend the
    # secretary's capacity instead, which is bounded by fanout f by
    # construction.
    want = has_leader & is_target & (state["app_arrive_t"] < 0)
    direct = want & (relay == lid_c)
    relayed = want & (relay != lid_c)
    n_sec_msgs = jnp.sum(jnp.any(relayed) &
                         ((state["role"] == SECRETARY) & state["alive"] &
                          (state["warn_timer"] < 0)))
    msg_budget = jnp.maximum(
        jnp.int32(static["msg_budget"]) - n_sec_msgs, 0)
    # cost of a batch scales with its payload (network/CPU bytes): this is
    # what makes the single leader the bottleneck at scale (paper §1)
    pending = jnp.maximum(state["log_len"][lid_c] - state["match_len"], 0)
    batch_cost = 1 + jnp.minimum(pending, static["max_ship"]) //         static["entries_per_msg"]
    rank = jnp.cumsum(jnp.where(direct, batch_cost, 0))
    ship = relayed | (direct & (rank <= msg_budget))
    app_arrive_t = jnp.where(ship, arrive, state["app_arrive_t"])
    app_from_len = jnp.where(ship, state["match_len"], state["app_from_len"])
    app_upto = jnp.where(
        ship, jnp.minimum(state["log_len"][lid_c],
                          state["match_len"] + static["max_ship"]),
        state["app_upto"])
    app_term = jnp.where(ship, state["term"][lid_c], state["app_term"])
    app_commit = jnp.where(ship, state["commit_len"][lid_c],
                           state["app_commit"])
    # leader work accounting: direct messages + one per active secretary
    leader_work = state["leader_work"].at[lid_c].add(
        jnp.sum(ship & direct) + n_sec_msgs)

    return dict(state, app_arrive_t=app_arrive_t, app_from_len=app_from_len,
                app_upto=app_upto, app_term=app_term, app_commit=app_commit,
                leader_work=leader_work)


def follower_step(state, static, cfg_c, *, reference=False, backend="xla"):
    """Deliver due append batches: log-matching check, truncate-adopt,
    schedule acks; followers forward to observers eagerly (Step 6, Fig. 5).

    The window adopt is position-aligned (a follower copies the LEADER'S
    row at the same log indices), so the fast path expresses it as one
    elementwise select over (N, L) with the broadcast leader row — XLA CPU
    vectorizes it, unlike the (N, W) gather + scatter of the PR-1
    formulation, which `reference=True` preserves bit-for-bit as the
    benchmark baseline (`benchmarks/perf_fleet.py`, DESIGN.md §7.1).
    `backend="pallas"` fuses the prev-term check, conflict truncation,
    and append into one VMEM pass (`kernels/raft_tick`, DESIGN.md §8) —
    bit-identical to both XLA formulations (test invariant)."""
    N = state["role"].shape[0]
    L = state["log_term"].shape[1]
    tick = state["tick"]
    lid = leader_id(state, static)
    lid_c = jnp.maximum(lid, 0)
    rtt = jnp.asarray(static["rtt"])

    delivered = (state["app_arrive_t"] >= 0) & \
        (state["app_arrive_t"] <= tick) & state["alive"]
    # term check: reject stale-term appends (Property 3.1/3.3); the slot
    # clears on ANY delivery, else stale batches deadlock the link
    ok_term = state["app_term"] >= state["term"]
    due = delivered & ok_term & (lid >= 0)

    W = static["max_ship"]
    if backend == "pallas" and not reference:
        # fused kernel: log-matching check + truncate + append in one
        # pass through VMEM; accept comes back out for the ack schedule
        log_term, log_key, log_val, new_len, accept = \
            rt_ops.log_match_append(
                state["log_term"], state["log_key"], state["log_val"],
                state["log_term"][lid_c], state["log_key"][lid_c],
                state["log_val"][lid_c],
                state["log_len"], state["app_from_len"],
                state["app_upto"], due, w=W)
        nack = due & ~accept
    else:
        # log-matching at prev = app_from_len-1: follower's term at that
        # index must equal the leader's (content is the leader's log row).
        prev = state["app_from_len"] - 1
        prev_c = jnp.clip(prev, 0, L - 1)
        my_prev_term = jnp.take_along_axis(
            state["log_term"], prev_c[:, None], axis=1)[:, 0]
        ldr_prev_term = state["log_term"][lid_c, prev_c]
        match = (prev < 0) | (my_prev_term == ldr_prev_term)
        accept = due & match
        # mismatch: nack -> leader will retry from an earlier match
        # point; we model the optimized backtrack by halving match_len
        nack = due & ~match

        # adopt leader entries [from_len, upto) — window-bounded copy
        if reference:
            # PR-1 formulation: (N, W) gather of the leader window, then
            # a masked scatter back — kept only as the perf baseline
            base = jnp.where(accept, state["app_from_len"], 0)
            widx = base[:, None] + jnp.arange(W)[None, :]     # (N,W)
            valid = accept[:, None] & \
                (widx < state["app_upto"][:, None]) & (widx < L)
            widx_c = jnp.clip(widx, 0, L - 1)
            ldr_terms = state["log_term"][lid_c][widx_c]
            ldr_keys = state["log_key"][lid_c][widx_c]
            ldr_vals = state["log_val"][lid_c][widx_c]
            rows = jnp.broadcast_to(jnp.arange(N)[:, None], widx.shape)
            put = lambda dst, src: dst.at[
                jnp.where(valid, rows, N),
                jnp.where(valid, widx_c, L)].set(src, mode="drop")
            log_term = put(state["log_term"], ldr_terms)
            log_key = put(state["log_key"], ldr_keys)
            log_val = put(state["log_val"], ldr_vals)
        else:
            # fast path: position p adopts leader_row[p] iff p lies in
            # the accepted window [from_len, min(upto, from_len + W))
            pos = jnp.arange(L)[None, :]                      # (1,L)
            lo = state["app_from_len"][:, None]
            hi = jnp.minimum(state["app_upto"],
                             state["app_from_len"] + W)[:, None]
            sel = accept[:, None] & (pos >= lo) & (pos < hi)
            adopt = lambda dst, ldr_row: jnp.where(sel, ldr_row[None, :],
                                                   dst)
            log_term = adopt(state["log_term"], state["log_term"][lid_c])
            log_key = adopt(state["log_key"], state["log_key"][lid_c])
            log_val = adopt(state["log_val"], state["log_val"][lid_c])
        new_len = jnp.where(accept,
                            jnp.minimum(state["app_upto"],
                                        state["app_from_len"] + W),
                            state["log_len"])
        new_len = jnp.where(accept & (state["log_len"] > new_len) &
                            (my_prev_term == ldr_prev_term),
                            jnp.maximum(state["log_len"], new_len), new_len)
    # followers adopt term & learn commit (piggybacked)
    term = jnp.where(due, jnp.maximum(state["term"], state["app_term"]),
                     state["term"])
    role = jnp.where(due & (state["role"] == CANDIDATE), FOLLOWER,
                     state["role"])
    commit_len = jnp.where(accept,
                           jnp.maximum(state["commit_len"],
                                       jnp.minimum(state["app_commit"],
                                                   new_len)),
                           state["commit_len"])
    # heartbeat resets election timer (deterministic jitter from tick+id)
    span = cfg_c["election_timeout_max"] - cfg_c["election_timeout_min"] + 1
    jitter = (tick + jnp.arange(N) * 7) % span
    election_timer = jnp.where(
        due, cfg_c["election_timeout_min"] + jitter,
        state["election_timer"])

    # ack back via the same relay path
    sec = state["sec_of"]
    # a warned secretary hands its fan-out back to the leader NOW, so
    # no in-flight batch is stranded when the kill lands (DESIGN.md §12;
    # `warn_timer < 0` is all-True whenever warnings are off)
    sec_alive = (sec >= 0) & state["alive"][jnp.maximum(sec, 0)] & \
        (state["role"][jnp.maximum(sec, 0)] == SECRETARY) & \
        (state["warn_timer"][jnp.maximum(sec, 0)] < 0)
    relay = jnp.where(sec_alive, sec, lid_c)
    lat = rtt[jnp.arange(N), relay] + rtt[relay, lid_c] * (relay != lid_c)
    ack_arrive_t = jnp.where(accept | nack, tick + lat,
                             state["ack_arrive_t"])
    ack_upto = jnp.where(accept, new_len,
                         jnp.where(nack, state["app_from_len"] // 2,
                                   state["ack_upto"]))

    app_arrive_t = jnp.where(delivered, -1, state["app_arrive_t"])
    return dict(state, log_term=log_term, log_key=log_key, log_val=log_val,
                log_len=new_len, term=term, role=role, commit_len=commit_len,
                election_timer=election_timer, ack_arrive_t=ack_arrive_t,
                ack_upto=ack_upto, app_arrive_t=app_arrive_t)


def commit_step(state, static, cfg_c, *, reference=False, backend="xla"):
    """Leader ingests due acks -> match_len; commits majority-replicated
    prefix (voters only); records entry commit times.

    The majority test is computed from the majority-th largest voter
    match_len (one (N,) sort) on the fast path — `counts(l) >= majority`
    iff `l <= that order statistic` since counts is non-increasing in l —
    instead of the PR-1 O(L·N) comparison matrix (`reference=True`).
    `backend="pallas"` computes the same order statistic blockwise with
    the voter mask applied in-register (`kernels/raft_tick`, DESIGN.md
    §8) — bit-identical (test invariant).

    2PC coupling (DESIGN.md §9): entries marked as cross-shard
    coordinators (`cross_shard_mark`) record their commit time shifted by
    `two_pc_ticks` — the prepare + commit round with the partner shard's
    leader — so the 2PC tax flows into the measured write-latency
    histogram per request instead of being added post hoc.  The charge is
    applied identically on the reference/xla/pallas paths (it is model
    semantics, not a formulation) and never feeds back into dynamics."""
    N = state["role"].shape[0]
    L = state["log_term"].shape[1]
    tick = state["tick"]
    lid = leader_id(state, static)
    lid_c = jnp.maximum(lid, 0)
    has_leader = lid >= 0

    ack_due = (state["ack_arrive_t"] >= 0) & (state["ack_arrive_t"] <= tick)
    # ack ingestion is budgeted the same way: direct acks consume leader
    # capacity, secretary-aggregated reports are O(#secretaries)
    sec = state["sec_of"]
    # a warned secretary hands its fan-out back to the leader NOW, so
    # no in-flight batch is stranded when the kill lands (DESIGN.md §12;
    # `warn_timer < 0` is all-True whenever warnings are off)
    sec_alive = (sec >= 0) & state["alive"][jnp.maximum(sec, 0)] & \
        (state["role"][jnp.maximum(sec, 0)] == SECRETARY) & \
        (state["warn_timer"][jnp.maximum(sec, 0)] < 0)
    direct_ack = ack_due & ~sec_alive
    rank = jnp.cumsum(direct_ack.astype(jnp.int32))
    ingest = (ack_due & sec_alive) | \
        (direct_ack & (rank <= static["msg_budget"]))
    match_len = jnp.where(ingest, jnp.maximum(state["match_len"],
                                              state["ack_upto"]),
                          state["match_len"])
    # nacks shrink match (ack_upto < match): allow decrease for retry
    match_len = jnp.where(ingest & (state["ack_upto"] <
                                    state["match_len"]),
                          state["ack_upto"], match_len)
    ack_arrive_t = jnp.where(ingest, -1, state["ack_arrive_t"])
    match_len = match_len.at[lid_c].set(
        jnp.where(has_leader, state["log_len"][lid_c], match_len[lid_c]))

    # commit = largest l such that #voters with match>=l is a majority,
    # restricted to entries of the current term (Raft §5.4.2)
    is_voter = jnp.asarray(static["is_voter"])
    lens = jnp.arange(L) + 1
    if backend == "pallas" and not reference:
        commit = rt_ops.commit_majority(
            match_len, is_voter & state["alive"],
            state["log_term"][lid_c], state["term"][lid_c],
            jnp.asarray(static["majority"], jnp.int32))
    else:
        if reference:
            counts = jnp.sum((match_len[None, :] >=
                              (jnp.arange(L) + 1)[:, None]) &
                             is_voter[None, :] & state["alive"][None, :],
                             axis=1)
            can = counts >= static["majority"]
        else:
            vmatch = jnp.where(is_voter & state["alive"], match_len, -1)
            kth = jnp.sort(vmatch)[::-1][
                jnp.maximum(static["majority"] - 1, 0)]
            can = lens <= kth
        term_ok = state["log_term"][lid_c, jnp.arange(L)] == \
            state["term"][lid_c]
        commit = jnp.max(jnp.where(can & term_ok, lens, 0))
    new_commit = jnp.where(has_leader,
                           jnp.maximum(state["commit_len"][lid_c], commit),
                           0)
    newly = (jnp.arange(L) >= state["commit_len"][lid_c]) & \
        (jnp.arange(L) < new_commit) & has_leader
    # cross-shard coordinators pay the two inter-site 2PC rounds before
    # the client sees the commit (DESIGN.md §9); intra-shard entries and
    # ungrouped members (cross_frac == 0) record plain `tick`
    cross = cross_shard_mark(jnp.arange(L), cfg_c["cross_frac"])
    commit_seen_t = tick + jnp.where(cross, cfg_c["two_pc_ticks"], 0)
    entry_commit_t = jnp.where(newly & (state["entry_commit_t"] < 0),
                               commit_seen_t, state["entry_commit_t"])
    commit_len = state["commit_len"].at[lid_c].set(
        jnp.where(has_leader, new_commit, state["commit_len"][lid_c]))
    n_new = jnp.where(has_leader,
                      new_commit - state["commit_len"][lid_c], 0)
    state = dict(state, match_len=match_len, ack_arrive_t=ack_arrive_t,
                 commit_len=commit_len, entry_commit_t=entry_commit_t,
                 writes_committed=state["writes_committed"] + n_new)
    # commit-advance + 2PC-commit seams -> ring + registry (§9/§14):
    # one event per tick the commit index moves (aux = new length) and
    # one per tick any cross-shard coordinators land in the advance
    n_cross = jnp.sum(newly & cross).astype(jnp.int32)
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_COMMIT, valid=n_new > 0, node=lid_c,
        term=state["term"][lid_c], aux=new_commit,
        counter="commit_advances")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_2PC_COMMIT, valid=n_cross > 0,
        node=lid_c, term=state["term"][lid_c], aux=n_cross,
        counter="twopc_committed", count=n_cross)
    state = trace_metrics.bump(state, "entries_committed", n_new)
    return state


def apply_step(state, static, cfg_c, *, reference=False, backend="xla"):
    """All nodes apply committed entries to their KV state machine
    (bounded per tick; Property 3.2 order = log order).  `reference=True`
    keeps the PR-1 Python-unrolled loop of A sequential scatters as the
    perf baseline; the fast path dedupes and scatters once.
    `backend="pallas"` replaces the scatter with an in-register
    last-wins select over (N, K) blocks (`kernels/raft_tick`, DESIGN.md
    §8) — bit-identical (test invariant)."""
    N, L = state["log_term"].shape
    A = static["max_apply"]
    base = state["applied_len"]                               # (N,)
    todo = jnp.minimum(state["commit_len"] - base, A)
    offs = jnp.arange(A)[None, :]
    idx = base[:, None] + offs
    valid = (offs < todo[:, None]) & (idx < L) & state["alive"][:, None]
    idx_c = jnp.clip(idx, 0, L - 1)
    keys = jnp.take_along_axis(state["log_key"], idx_c, axis=1)
    vals = jnp.take_along_axis(state["log_val"], idx_c, axis=1)
    rows = jnp.broadcast_to(jnp.arange(N)[:, None], keys.shape)
    K = state["kv"].shape[1]
    if backend == "pallas" and not reference:
        kv = rt_ops.apply_last_wins(state["kv"], keys, vals, valid)
    elif reference:
        # PR-1: apply sequentially over the A offsets to preserve order
        kv = state["kv"]
        for a in range(A):
            kv = kv.at[jnp.where(valid[:, a], jnp.arange(N), N),
                       jnp.where(valid[:, a], keys[:, a], K)].set(
                vals[:, a], mode="drop")
    else:
        # later entries win.  A single scatter with duplicate (row, key)
        # pairs has unspecified order, so dedupe first: drop any entry
        # that a LATER valid entry in the same row overwrites (O(A^2)
        # mask, A small), then scatter every surviving entry at once —
        # one HLO scatter instead of A sequential ones (compile time and
        # HLO size stay flat in max_apply).
        offs_a = jnp.arange(A)
        later = offs_a[:, None] < offs_a[None, :]             # (A, A): b > a
        overwritten = jnp.any(later[None, :, :] &
                              (keys[:, :, None] == keys[:, None, :]) &
                              valid[:, None, :], axis=2)      # (N, A)
        keep = valid & ~overwritten
        kv = state["kv"].at[jnp.where(keep, rows, N),
                            jnp.where(keep, keys, K)].set(vals, mode="drop")
    applied = base + jnp.maximum(todo, 0)
    # rolling applied-prefix digest (DESIGN.md §13): XOR in the mix of
    # every entry applied this tick.  Shared by all three formulations
    # (it is model semantics, not a formulation), RNG-free, and
    # independent of the digest-tier width O.
    out = dict(state, kv=kv, applied_len=applied)
    if "applied_digest" in state:      # minimal unit-test states omit it
        contrib = jnp.where(valid, entry_mix(idx_c, keys, vals),
                            jnp.uint32(0))                    # (N, A)
        digest = state["applied_digest"]
        for a in range(A):
            digest = digest ^ contrib[:, a]
        out["applied_digest"] = digest
    return out


def observer_sync_step(state, static, cfg_c):
    """Followers eagerly forward appended entries to their observers
    (paper Fig. 5 / §3.1 Step 6): observers mirror their follower's applied
    state machine with intra-site lag (rtt_intra=1 tick)."""
    is_obs = (state["role"] == OBSERVER) & state["alive"]
    fol = jnp.maximum(state["obs_of"], 0)
    fol_ok = (state["obs_of"] >= 0) & state["alive"][fol]
    sync = is_obs & fol_ok
    applied = jnp.where(sync, state["applied_len"][fol],
                        state["applied_len"])
    commit = jnp.where(sync, state["commit_len"][fol], state["commit_len"])
    log_len = jnp.where(sync, state["log_len"][fol], state["log_len"])
    kv = jnp.where(sync[:, None], state["kv"][fol], state["kv"])
    # observers mirror the log too (they apply the same commands in the
    # same order — Property 3.2 holds across observer replicas)
    lt = jnp.where(sync[:, None], state["log_term"][fol], state["log_term"])
    lk = jnp.where(sync[:, None], state["log_key"][fol], state["log_key"])
    lv = jnp.where(sync[:, None], state["log_val"][fol], state["log_val"])
    # the applied-prefix digest travels with the applied state it
    # fingerprints (DESIGN.md §13), so the prefix-mirror claim above is
    # checkable: observer digest == follower digest at the same applied
    dg = jnp.where(sync, state["applied_digest"][fol],
                   state["applied_digest"])
    return dict(state, applied_len=applied, commit_len=commit,
                log_len=log_len, kv=kv, log_term=lt, log_key=lk, log_val=lv,
                applied_digest=dg)


def anti_entropy_step(state, static, cfg_c, *, backend="xla"):
    """Batched anti-entropy rounds for the digest-tier observers
    (DESIGN.md §13; the sparse scale-out twin of `observer_sync_step`).

    A digest observer `o` syncs on ticks where
    `(tick + ae_phase[o]) % ae_interval == 0` — `ae_interval` and the
    `(O,)` phase schedule ride in cfg_c as jit-argument data, so gossip
    cadences sweep without recompiling (the §10 trace rule).  On a due
    round the observer adopts its source's `(applied_len, term,
    applied_digest)` triple — a few scalars per observer, never a log
    row, which is what lets O run 50X past the dense node count.  The
    adopt is monotone (an observer never regresses its applied index,
    e.g. when failing over to a less-caught-up voter), but the sync
    *timestamp* still advances on any completed round: freshness bounds
    time-since-contact, and the observer's own state is at least as new
    as the source's.  Source = the wired follower (`dobs_fol`), falling
    back in-graph to the first alive voter when the follower is down.
    No RNG is drawn; at O == 0 this is a python no-op.

    `backend="pallas"` fuses the due rule, the any-live-voter fallback,
    the monotone adoption, and the sync-hop RTT aging into one pass
    over the observer lanes (`kernels/ae_sync`, DESIGN.md §8) —
    bit-identical to the XLA gather formulation below (test
    invariant)."""
    O = state["dobs_alive"].shape[0] if "dobs_alive" in state else 0
    if O == 0:
        return state
    # the due rule / source selection, hoisted above the backend split
    # (RNG-free, a few O-wide gathers): the XLA path consumes it
    # directly, the pallas kernel recomputes it internally — and the
    # flight-recorder events below (DESIGN.md §14) read THESE values so
    # the decoded event stream is backend-uniform
    N = state["role"].shape[0]
    tick = state["tick"]
    is_voter = jnp.asarray(static["is_voter"])
    fol = state["dobs_fol"]
    fol_c = jnp.clip(fol, 0, N - 1)
    fol_ok = (fol >= 0) & state["alive"][fol_c] & is_voter[fol_c]
    alive_voter = is_voter & state["alive"]
    any_voter = jnp.any(alive_voter)
    fallback = jnp.argmax(alive_voter)
    eff = jnp.where(fol_ok, fol_c, fallback)
    interval = jnp.maximum(cfg_c["ae_interval"], 1)
    due = state["dobs_alive"] & (fol_ok | any_voter) & \
        (jnp.mod(tick + cfg_c["ae_phase"], interval) == 0)
    src_applied = state["applied_len"][eff]

    if backend == "pallas":
        applied, term, digest, synced = ae_ops.ae_sync(
            state["dobs_alive"], state["dobs_fol"], state["dobs_applied"],
            state["dobs_term"], state["dobs_digest"],
            state["dobs_synced_t"], cfg_c["ae_phase"],
            jnp.asarray(static["dobs_site"]), state["alive"],
            jnp.asarray(static["is_voter"]), state["applied_len"],
            state["term"], state["applied_digest"],
            jnp.asarray(static["site"]), jnp.asarray(static["site_rtt"]),
            state["tick"], cfg_c["ae_interval"])
        state = dict(state, dobs_applied=applied, dobs_term=term,
                     dobs_digest=digest, dobs_synced_t=synced)
        return _ae_trace(state, cfg_c, due, fol_ok, eff, src_applied)
    adopt = due & (src_applied >= state["dobs_applied"])
    applied = jnp.where(adopt, src_applied, state["dobs_applied"])
    term = jnp.where(adopt, state["term"][eff], state["dobs_term"])
    digest = jnp.where(adopt, state["applied_digest"][eff],
                       state["dobs_digest"])
    # the adopted state ages by the transfer hop (site-pair RTT): a sync
    # from the observer's own site costs rtt_intra, a cross-site
    # fallback costs the inter-site trip — so a remote fallback is
    # honestly staler and reroutes sooner under a tight bound
    hop = jnp.asarray(static["site_rtt"])[
        jnp.asarray(static["dobs_site"]),
        jnp.asarray(static["site"])[eff]]
    synced = jnp.where(due, tick - hop, state["dobs_synced_t"])
    state = dict(state, dobs_applied=applied, dobs_term=term,
                 dobs_digest=digest, dobs_synced_t=synced)
    return _ae_trace(state, cfg_c, due, fol_ok, eff, src_applied)


def _ae_trace(state, cfg_c, due, fol_ok, eff, src_applied):
    """Anti-entropy seam -> ring + registry (§13/§14): one `ae_sync`
    event per due observer slot (node lane = the SLOT index — the
    Perfetto exporter maps it to a site track via `static["dobs_site"]`;
    term lane = source node id; aux = source applied length), plus an
    `ae_fallback` event when the round used the any-voter fallback."""
    o_ids = jnp.arange(due.shape[0])
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_AE_SYNC, valid=due, node=o_ids,
        term=eff, aux=src_applied, counter="ae_rounds")
    return trace_ring.record(
        state, cfg_c, trace_ring.EV_AE_FALLBACK, valid=due & ~fol_ok,
        node=o_ids, term=eff, aux=src_applied, counter="ae_fallbacks")


def read_step(state, static, cfg_c):
    """Serve queued reads through the read-index round (DESIGN.md §11).

    Observers serve only if applied >= readindex (= leader commit at
    request time; approximated by current leader commit) — the observer
    apply-index wait; otherwise the read reroutes to the observer's
    follower (+rtt).  Latency = service wait (queue/capacity) + the
    readindex confirmation fence (via global secretary when present —
    §4.3).  Every served request's integer-tick latency lands in the
    unit-bin `read_lat_hist` — the read-side twin of the write
    histogram, same `period_ticks + 1 + HIST_TAIL` layout (DESIGN.md
    §7.1/§11), so `runtime.hist_stats` recovers read p95/p99 exactly.
    Digest-tier observers (DESIGN.md §13) serve under a *bounded
    staleness* contract instead: a digest slot serves its queue iff
    `tick - dobs_synced_t <= cfg_c["staleness_bound"]` — the anti-entropy
    round amortizes the readindex fence across the whole cohort, so a
    served digest read pays queue wait + unit service only, no per-read
    fence trip.  Each served request's staleness lands in the unit-bin
    `obs_stale_hist` (so staleness p99 is exact, and <= the bound by
    construction); a slot that is behind the bound (or dead/warned with
    a residual queue) reroutes to its follower's queue, counted in
    `obs_rerouted`.

    Returns `(state, (served, lat, obs_served, obs_stale))` — per-node
    and per-digest-slot raw samples this tick, consumed by the tick
    metrics for the numpy-recomputation pin tests
    (`tests/test_serving.py`, `tests/test_observers.py`)."""
    N = state["role"].shape[0]
    tick = state["tick"]
    lid = leader_id(state, static)
    lid_c = jnp.maximum(lid, 0)
    rtt = jnp.asarray(static["rtt"])
    cap = jnp.int32(static["work_capacity"])

    is_obs = (state["role"] == OBSERVER) & state["alive"]
    is_srv = ((state["role"] == FOLLOWER) | (state["role"] == LEADER)) & \
        state["alive"]
    readindex = state["commit_len"][lid_c]
    fresh = state["applied_len"] >= readindex
    can_serve = (is_obs & fresh) | is_srv

    served = jnp.where(can_serve, jnp.minimum(state["read_queue"], cap), 0)
    # stale observers reroute to their follower (1 extra hop)
    fol = jnp.maximum(state["obs_of"], 0)
    reroute = jnp.where(is_obs & ~fresh, state["read_queue"], 0)
    read_queue = state["read_queue"] - served - reroute
    read_queue = read_queue.at[fol].add(
        jnp.where(is_obs & ~fresh, reroute, 0), mode="drop")

    # latency model: queue wait + readindex confirmation.  With a global
    # secretary alive the leader needs no self-confirmation round (§4.3),
    # halving the observer readindex trip.
    any_sec = jnp.any((state["role"] == SECRETARY) & state["alive"])
    ri_rtt = rtt[jnp.arange(N), lid_c] * jnp.where(any_sec, 1, 2)
    wait = state["read_queue"] // jnp.maximum(cap, 1)
    lat = (wait + 1 + jnp.where(is_obs, ri_rtt, rtt[jnp.arange(N), lid_c]))
    lat_sum = jnp.sum(jnp.where(served > 0,
                                lat.astype(jnp.float32) * served, 0.0))
    lat_max = jnp.max(jnp.where(served > 0, lat.astype(jnp.float32), 0.0))
    # per-request histogram: `served` requests at integer latency `lat`
    # per node, overload tails clipped into the last bin
    H = state["read_lat_hist"].shape[0]
    bins = jnp.clip(lat, 0, H - 1)
    read_hist = state["read_lat_hist"].at[
        jnp.where(served > 0, bins, H)].add(served, mode="drop")

    # --- digest-tier serving (DESIGN.md §13; python no-op at O == 0) ----
    O = state["dobs_alive"].shape[0] if "dobs_alive" in state else 0
    extra = {}
    obs_served = jnp.zeros((O,), jnp.int32)
    obs_stale = jnp.zeros((O,), jnp.int32)
    if O:
        q = state["dobs_read_queue"]
        stale = tick - state["dobs_synced_t"]
        can_d = state["dobs_alive"] & \
            (stale <= cfg_c["staleness_bound"])
        obs_served = jnp.where(can_d, jnp.minimum(q, cap), 0)
        reroute_d = jnp.where(~can_d, q, 0)
        # failover target = same source rule as `anti_entropy_step`
        is_voter = jnp.asarray(static["is_voter"])
        fold = state["dobs_fol"]
        fold_c = jnp.clip(fold, 0, N - 1)
        fol_ok = (fold >= 0) & state["alive"][fold_c] & is_voter[fold_c]
        eff = jnp.where(fol_ok, fold_c,
                        jnp.argmax(is_voter & state["alive"]))
        read_queue = read_queue.at[
            jnp.where(reroute_d > 0, eff, N)].add(reroute_d, mode="drop")
        # latency: queue wait + unit service, served at the observer's
        # own site — the fence is amortized by the anti-entropy round
        wait_d = q // jnp.maximum(cap, 1)
        lat_d = wait_d + 1
        lat_sum = lat_sum + jnp.sum(jnp.where(
            obs_served > 0, lat_d.astype(jnp.float32) * obs_served, 0.0))
        lat_max = jnp.maximum(lat_max, jnp.max(jnp.where(
            obs_served > 0, lat_d.astype(jnp.float32), 0.0)))
        read_hist = read_hist.at[
            jnp.where(obs_served > 0, jnp.clip(lat_d, 0, H - 1), H)
        ].add(obs_served, mode="drop")
        obs_stale = jnp.where(obs_served > 0, stale, 0)
        extra = dict(
            dobs_read_queue=q - obs_served - reroute_d,
            obs_stale_hist=state["obs_stale_hist"].at[
                jnp.where(obs_served > 0, jnp.clip(stale, 0, H - 1), H)
            ].add(obs_served, mode="drop"),
            obs_reads_served=state["obs_reads_served"] +
            jnp.sum(obs_served),
            obs_rerouted=state["obs_rerouted"] + jnp.sum(reroute_d))

    total_served = jnp.sum(served)
    if O:
        total_served = total_served + jnp.sum(obs_served)
    state = dict(state, **extra, read_queue=read_queue,
                 reads_served=state["reads_served"] + total_served,
                 read_lat_sum=state["read_lat_sum"] + lat_sum,
                 read_lat_max=jnp.maximum(state["read_lat_max"], lat_max),
                 read_lat_hist=read_hist)
    return state, (served, lat, obs_served, obs_stale)


def election_step(state, static, cfg_c, rng):
    """Timeouts -> candidacy; RequestVote/grants with log restriction;
    majority of voters -> leader (Property 3.1)."""
    N = state["role"].shape[0]
    L = state["log_term"].shape[1]
    tick = state["tick"]
    rtt = jnp.asarray(static["rtt"])
    is_voter = jnp.asarray(static["is_voter"])
    r_timeout, = _rand(rng, 1)

    # --- timers ----------------------------------------------------------
    lid = leader_id(state, static)
    et = state["election_timer"] - 1
    timed_out = (et <= 0) & is_voter & state["alive"] & \
        ((state["role"] == FOLLOWER) | (state["role"] == CANDIDATE))
    # become candidate
    term = jnp.where(timed_out, state["term"] + 1, state["term"])
    role = jnp.where(timed_out, CANDIDATE, state["role"])
    voted_for = jnp.where(timed_out, jnp.arange(N), state["voted_for"])
    new_timeout = jax.random.randint(
        r_timeout, (N,), cfg_c["election_timeout_min"],
        cfg_c["election_timeout_max"] + 1)
    et = jnp.where(timed_out | (et <= 0), new_timeout, et)

    # candidates broadcast vote requests (one in-flight slot per voter;
    # higher term wins the slot)
    is_cand = (role == CANDIDATE) & state["alive"]
    cand_term = jnp.where(is_cand, term, -1)
    best_cand = jnp.argmax(cand_term)                         # highest term
    have_cand = jnp.max(cand_term) >= 0
    last_len = state["log_len"][best_cand]
    last_term = state["log_term"][best_cand,
                                  jnp.clip(last_len - 1, 0, L - 1)]
    newer = term[best_cand] > state["vreq_term"]
    place = have_cand & is_voter & newer & state["alive"]
    vreq_t = jnp.where(place, tick + rtt[best_cand], state["vreq_t"])
    vreq_from = jnp.where(place, best_cand, state["vreq_from"])
    vreq_term = jnp.where(place, term[best_cand], state["vreq_term"])
    vreq_lastterm = jnp.where(place, last_term, state["vreq_lastterm"])
    vreq_lastlen = jnp.where(place, last_len, state["vreq_lastlen"])

    # --- process due vote requests --------------------------------------
    due = (vreq_t >= 0) & (vreq_t <= tick) & state["alive"] & is_voter
    req_term = vreq_term
    higher = req_term > term
    # flight-recorder mask (§14): leaders demoted by a higher-term
    # request — captured before the role rewrite
    dem_higher = due & higher & (role == LEADER)
    term = jnp.where(due & higher, req_term, term)
    role = jnp.where(due & higher & (role == LEADER), FOLLOWER, role)
    role = jnp.where(due & higher & (role == CANDIDATE), FOLLOWER, role)
    voted_for = jnp.where(due & higher, -1, voted_for)
    my_last_len = state["log_len"]
    my_last_term = jnp.take_along_axis(
        state["log_term"], jnp.clip(my_last_len - 1, 0, L - 1)[:, None],
        axis=1)[:, 0]
    log_ok = (vreq_lastterm > my_last_term) | \
        ((vreq_lastterm == my_last_term) & (vreq_lastlen >= my_last_len))
    can_grant = due & (req_term >= term) & log_ok & \
        ((voted_for == -1) | (voted_for == vreq_from))
    voted_for = jnp.where(can_grant, vreq_from, voted_for)
    et = jnp.where(can_grant, new_timeout, et)      # granting defers timeout
    # schedule grant arrival at candidate
    grant_t = jnp.where(can_grant,
                        tick + rtt[jnp.arange(N),
                                   jnp.maximum(vreq_from, 0)],
                        state["grant_t"])
    grant_to = jnp.where(can_grant, vreq_from, state["grant_to"])
    grant_term = jnp.where(can_grant, req_term, state["grant_term"])
    vreq_t = jnp.where(due, -1, vreq_t)

    # --- candidates tally grants (accumulated across ticks) --------------
    g_due = (grant_t >= 0) & (grant_t <= tick)
    tgt = jnp.maximum(grant_to, 0)
    term_match = grant_term == term[tgt]
    arrivals = jnp.zeros((N,), jnp.int32).at[
        jnp.where(g_due & term_match, tgt, N)].add(1, mode="drop")
    vr = jnp.where(timed_out, 0, state["votes_received"])   # new candidacy
    vr = jnp.where(role == CANDIDATE, vr + arrivals, 0)
    votes = vr + 1                                           # self-vote
    win = (role == CANDIDATE) & state["alive"] & \
        (votes >= static["majority"])
    role = jnp.where(win, LEADER, role)
    grant_t = jnp.where(g_due, -1, grant_t)
    # demote any older-term leader the moment a newer one exists
    max_leader_term = jnp.max(jnp.where((role == LEADER) & state["alive"],
                                        term, -1))
    dem_older = (role == LEADER) & (term < max_leader_term)
    role = jnp.where((role == LEADER) & (term < max_leader_term),
                     FOLLOWER, role)
    # new leader: reset bookkeeping, stop secretaries (paper Step 1); the
    # manager re-provisions them next period (Step 2)
    any_new = jnp.any(win)
    match_len = jnp.where(any_new, jnp.zeros_like(state["match_len"]),
                          state["match_len"])
    sec_stop = any_new & (role == SECRETARY) & state["alive"]
    role = jnp.where(any_new & (role == SECRETARY), DEAD, role)
    alive = state["alive"] & ~(any_new & (state["role"] == SECRETARY))
    heartbeat_timer = jnp.where(win, 0, state["heartbeat_timer"])

    state = dict(state, alive=alive, term=term, role=role,
                 voted_for=voted_for, votes_received=vr,
                 election_timer=et, vreq_t=vreq_t, vreq_from=vreq_from,
                 vreq_term=vreq_term, vreq_lastterm=vreq_lastterm,
                 vreq_lastlen=vreq_lastlen, grant_t=grant_t,
                 grant_to=grant_to, grant_term=grant_term,
                 match_len=match_len, heartbeat_timer=heartbeat_timer)

    # election seam -> ring + registry (DESIGN.md §14): candidacies,
    # grants (aux = candidate), wins (aux = tallied votes), the two
    # leader-demotion rules, and the new-leader secretary stop — every
    # mask captured above at the point its rule fired
    nid = jnp.arange(N)
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_CANDIDACY, valid=timed_out, node=nid,
        term=term, counter="elections_started")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_GRANT, valid=can_grant, node=nid,
        term=req_term, aux=vreq_from, counter="votes_granted")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_ELECT, valid=win, node=nid,
        term=term, aux=votes, counter="leader_elected")
    state = trace_ring.record(
        state, cfg_c, trace_ring.EV_STEPDOWN,
        valid=dem_higher | dem_older, node=nid, term=term,
        counter="leader_stepdowns")
    return trace_ring.record(
        state, cfg_c, trace_ring.EV_SEC_STOP, valid=sec_stop, node=nid,
        term=term, counter="sec_stops")


def cost_step(state, static, cfg_c):
    """Accrue $ cost: on-demand voters + alive spot nodes (eq. 1).
    Digest-tier observers (DESIGN.md §13) bill as spot instances at their
    site's spot price and count toward the linear network term — they
    are cheap because they are spot and stateless, not free."""
    site = jnp.asarray(static["site"])
    is_voter = jnp.asarray(static["is_voter"])
    od_price = cfg_c["on_demand_price"][site]
    sp_price = state["spot_price"][site]
    spot_sum = jnp.sum(jnp.where(~is_voter & state["alive"], sp_price, 0.0))
    n_alive = jnp.sum(state["alive"])
    O = state["dobs_alive"].shape[0] if "dobs_alive" in state else 0
    if O:
        d_price = state["spot_price"][jnp.asarray(static["dobs_site"])]
        spot_sum = spot_sum + jnp.sum(jnp.where(state["dobs_alive"],
                                                d_price, 0.0))
        n_alive = n_alive + jnp.sum(state["dobs_alive"])
    per_tick = jnp.sum(jnp.where(is_voter & state["alive"], od_price, 0.0)) \
        + spot_sum
    per_tick = per_tick / cfg_c["ticks_per_hour"]
    # + C: linear network cost in total instances
    per_tick = per_tick * (1.0 + cfg_c["network_cost_coef"] * n_alive)
    return dict(state, cost_accrued=state["cost_accrued"] + per_tick)


def _phase(name: str):
    """The named scope of one tick phase."""
    assert name in trace_spans.TICK_PHASES, name
    return jax.named_scope(f"tick.{name}")


def tick(state, static, cfg_c, rng, *, reference=False,
         backend="xla") -> Tuple[Dict, Dict]:
    """One full protocol tick. Returns (state, per-tick metrics).

    `reference=True` selects the PR-1 formulations of the follower adopt,
    the commit majority test, and the apply scatter — bit-identical
    results, kept as the epoch-loop perf baseline (DESIGN.md §7.1,
    `benchmarks/perf_fleet.py`); the equivalence is a test invariant
    (`tests/test_fleet.py`).  `backend` selects the implementation of
    the tick hot ops on the non-reference path: `"xla"` (the PR-2 fast
    formulations, default), `"pallas"` (the fused kernel families —
    `raft_tick`, `leader_fanout`, `ae_sync` — interpret-mode on CPU,
    DESIGN.md §8), or `"auto"` (pallas on TPU, xla elsewhere — the
    per-platform resolution rule); results are bit-identical across
    all of them (`tests/test_raft_tick_kernels.py`,
    `tests/test_wide_kernels.py`, `benchmarks/perf_tick.py`)."""
    backend = resolve_backend(backend)
    # reference runs pin the PR-1 ops AND the XLA forms of the paths
    # that predate the reference split (fan-out, anti-entropy)
    hot = "xla" if reference else backend
    r_spot, r_work, r_lead, r_elec = jax.random.split(rng, 4)
    # each phase under its named scope (`trace.spans.TICK_SCOPES`): op
    # metadata only, so a device trace's ops map back to their phase
    with _phase("spot"):
        state, killed = spot_step(state, static, cfg_c, r_spot)
    with _phase("workload"):
        state, (n_w, n_r, r_key) = workload_step(state, static, cfg_c,
                                                 r_work)
    with _phase("election"):
        state = election_step(state, static, cfg_c, r_elec)
    with _phase("leader"):
        state = leader_step(state, static, cfg_c, r_lead, backend=hot)
    with _phase("follower"):
        state = follower_step(state, static, cfg_c, reference=reference,
                              backend=backend)
    with _phase("commit"):
        state = commit_step(state, static, cfg_c, reference=reference,
                            backend=backend)
    with _phase("apply"):
        state = apply_step(state, static, cfg_c, reference=reference,
                           backend=backend)
    with _phase("observer_sync"):
        state = observer_sync_step(state, static, cfg_c)
    with _phase("anti_entropy"):
        state = anti_entropy_step(state, static, cfg_c, backend=hot)
    with _phase("read"):
        state, (read_served, read_lat, obs_served, obs_stale) = \
            read_step(state, static, cfg_c)
    with _phase("cost"):
        state = cost_step(state, static, cfg_c)
    state = dict(state, tick=state["tick"] + 1)

    lid = leader_id(state, static)
    metrics = {
        "has_leader": (lid >= 0).astype(jnp.int32),
        "leader_term": jnp.where(lid >= 0, state["term"][jnp.maximum(lid, 0)],
                                 -1),
        "n_leaders": jnp.sum((state["role"] == LEADER) & state["alive"]),
        "n_secretaries": jnp.sum((state["role"] == SECRETARY) &
                                 state["alive"]),
        "n_observers": jnp.sum((state["role"] == OBSERVER) & state["alive"]),
        "commit_len": jnp.max(state["commit_len"]),
        "write_queue": state["write_pending"],
        "read_queue": jnp.sum(state["read_queue"]),
        "killed": jnp.sum(killed),
        "cost": state["cost_accrued"],
        # raw per-node read service sample this tick (DESIGN.md §11):
        # the host-path reference for the read histogram pin test —
        # ignored by the in-scan digest reduction
        "read_served_tick": read_served,
        "read_lat_tick": read_lat,
        # digest-tier twins (DESIGN.md §13): per-slot serves and the
        # staleness of each served batch, for the numpy pin of
        # `obs_stale_hist` in `tests/test_observers.py`
        "obs_served_tick": obs_served,
        "obs_stale_tick": obs_stale,
        "n_obs_digest": jnp.sum(state["dobs_alive"]),
    }
    return state, metrics
