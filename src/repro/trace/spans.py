"""Names of the program's profiler spans and scopes (DESIGN.md §14), in
one place, so a reader of a `jax.profiler` trace imports them instead of
copying them.

Two kinds, both on the profiler's one clock:

  device scopes  `jax.named_scope` around each phase of `step.tick` and
                 around the epoch's digest and compaction
                 (`runtime.device_epoch`).  They cost nothing at run
                 time: XLA carries them as path components of each op's
                 `op_name` metadata, wrapped by transforms
                 (`jit(epoch)/vmap(epoch.compact)/rev`,
                 `.../while/body/closed_call/tick.leader/mul`).
  host spans     `jax.profiler.TraceAnnotation` around the host work of
                 the two drivers of the hot path: `BWKVService` and
                 `FleetSim.run_epoch`.  Disabled (no trace running) a
                 span costs about a microsecond.

This is not the flight recorder (`trace.ring`), whose events are the
simulated cluster's, in simulated ticks.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

# ---------------------------------------------------------- device scopes
# the phases of one protocol tick, in the order `step.tick` runs them
TICK_PHASES = ("spot", "workload", "election", "leader", "follower",
               "commit", "apply", "observer_sync", "anti_entropy", "read",
               "cost")
TICK_SCOPES = tuple(f"tick.{p}" for p in TICK_PHASES)
# the epoch boundary: the in-scan digest accumulation and the digest
# itself, then the in-graph log compaction
EPOCH_DIGEST = "epoch.digest"
EPOCH_COMPACT = "epoch.compact"
SCOPES = TICK_SCOPES + (EPOCH_DIGEST, EPOCH_COMPACT)
# device time outside every scope above
UNSCOPED = "unscoped"

# ------------------------------------------------------------- host spans
# BWKVService: the dispatch of each device program (one per request,
# its ticks and their PRNG splits inside it, or one per `_step`), each
# blocking device-to-host read (one per request: its answer), and each
# host-issued device write (left only in `get_stale`'s read record).
# Counters beside them: `host_reads`, `dispatches`, and `device_ticks`,
# the ticks stepped inside the programs (ticks per dispatch is how much
# of a request's wait the device runs without the host)
KV_TICK = "kv.tick"
KV_SYNC = "kv.sync"
KV_WRITE = "kv.write"
KV_SPANS = (KV_TICK, KV_SYNC, KV_WRITE)
# FleetSim: the RNG splits and the epoch call; the digest fetch, which
# blocks until the epoch ends; reports, Algorithm 1, MCSA leasing and
# the bid policies; the role and wiring rows written back; the
# flight-recorder drain
FLEET_DISPATCH = "fleet.dispatch"
FLEET_FETCH = "fleet.fetch"
FLEET_CONTROL = "fleet.control"
FLEET_WRITEBACK = "fleet.writeback"
FLEET_DRAIN = "fleet.drain"
FLEET_SPANS = (FLEET_DISPATCH, FLEET_FETCH, FLEET_CONTROL, FLEET_WRITEBACK,
               FLEET_DRAIN)
HOST_SPANS = KV_SPANS + FLEET_SPANS

# a scope as one component of an op_name path, bare or wrapped by
# transforms: `/tick.leader/`, `/vmap(epoch.compact)/`
_SCOPE_RE = re.compile(r"(?:^|[/(])(" + "|".join(
    re.escape(s) for s in SCOPES) + r")(?=[)/]|$)")
# an instruction of an HLO module's text, and its op_name metadata
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME_RE = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost scope of `SCOPES` on an op's `op_name` path, or None
    for an op outside all of them."""
    found = _SCOPE_RE.findall(op_name or "")
    return found[-1] if found else None


def hlo_op_scopes(hlo_text: str) -> Dict[str, str]:
    """Scope of every instruction of a compiled HLO module's text
    (`jax.stages.Compiled.as_text()`), by instruction name: the names a
    device trace gives its op events (`fusion.1165`).  A fusion carries
    its root's `op_name`, so it counts under its root's scope; an
    instruction without metadata, or outside every scope, is
    `UNSCOPED`."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        meta = _OP_NAME_RE.search(line)
        out[m.group(1)] = (scope_of(meta.group(1)) if meta else None) \
            or UNSCOPED
    return out
