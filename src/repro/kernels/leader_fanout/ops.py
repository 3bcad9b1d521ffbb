"""Public op for the leader fan-out kernel: padding, dispatch.

`core/step.py:leader_step` calls `leader_fanout` when
`backend="pallas"` is resolved (DESIGN.md §8).  The wrapper

  * normalizes per-node operands to (1, Np) lane-tiled int32 rows and
    the RTT matrix to (Np, Np), Np a lane multiple — padded lanes carry
    `alive == 0`, which zeroes every ship/budget/rank contribution
    (masking contract; see kernel.py),
  * compiles the Pallas kernel on TPU and interprets it on CPU (the
    `raft_tick` interpret rule; any other platform raises),
  * slices the app_* rows back to (N,) and the work delta to a scalar.

Bit-identical to `ref.py` and to the XLA formulation in
`core/step.py` (test invariant, `tests/test_wide_kernels.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import state as _state
from repro.kernels.leader_fanout import kernel as _k
from repro.kernels.leader_fanout.kernel import leader_fanout_kernel
from repro.kernels.raft_tick.ops import use_interpret

_BLOCK_LANE = 128   # node lane multiple: the (1, Np) row tile width

# the kernel mirrors the role constants to stay import-light; pin them
assert (_k.FOLLOWER, _k.CANDIDATE, _k.SECRETARY) == \
    (_state.FOLLOWER, _state.CANDIDATE, _state.SECRETARY)


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _row(v, n_pad: int):
    """(N,) vector -> zero-padded (1, n_pad) int32 lane row."""
    v = jnp.asarray(v, jnp.int32)
    return jnp.pad(v, (0, n_pad - v.shape[0]))[None, :]


@functools.partial(jax.jit, static_argnames=("msg_budget", "max_ship",
                                             "entries_per_msg"))
def leader_fanout(role, alive, warn_timer, sec_of, match_len,
                  app_arrive_t, app_from_len, app_upto, app_term,
                  app_commit, rtt, lid_c, has_leader, tick,
                  ldr_len, ldr_term, ldr_commit, *,
                  msg_budget: int, max_ship: int, entries_per_msg: int):
    """Fused budgeted fan-out (DESIGN.md §8).

    Per-node vectors (N,); rtt (N, N) int32; scalars lid_c /
    has_leader / tick and the leader's log length, term, and commit
    length; the three message-budget knobs are static python ints (the
    §7 static-shape rule).  Returns (app_arrive_t, app_from_len,
    app_upto, app_term, app_commit, work) with `work` the scalar
    leader-work delta."""
    N = role.shape[0]
    Np = _pad_to(N, _BLOCK_LANE)
    rtt = jnp.asarray(rtt, jnp.int32)
    rtt_p = jnp.pad(rtt, ((0, Np - N), (0, Np - N)))
    scalar = lambda s: jnp.asarray(s, jnp.int32).reshape(1, 1)
    out = leader_fanout_kernel(
        scalar(lid_c), scalar(has_leader), scalar(tick),
        scalar(ldr_len), scalar(ldr_term), scalar(ldr_commit),
        _row(role, Np), _row(alive, Np), _row(warn_timer, Np),
        _row(sec_of, Np), _row(match_len, Np),
        _row(app_arrive_t, Np), _row(app_from_len, Np),
        _row(app_upto, Np), _row(app_term, Np), _row(app_commit, Np),
        rtt_p,
        msg_budget=msg_budget, max_ship=max_ship,
        entries_per_msg=entries_per_msg, interpret=use_interpret())
    arrive, frm, upto, term, commit, work = out
    return (arrive[0, :N], frm[0, :N], upto[0, :N], term[0, :N],
            commit[0, :N], work[0, 0])
