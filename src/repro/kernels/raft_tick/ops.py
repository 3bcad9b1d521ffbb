"""Public ops for the raft_tick kernels: padding, dispatch.

The jitted wrappers below are what `core/step.py` calls when
`backend="pallas"` is selected (DESIGN.md §8).  They

  * normalize operands to the kernels' 2D int32 layout,
  * pad N to a sublane multiple and L/K to a lane multiple (padded rows
    arrive fully masked — `due`/`valid`/`voter_alive` pad with 0 — and
    padded columns are unreachable because window/commit bounds use the
    REAL sizes, passed statically),
  * compile the Pallas kernel on TPU and interpret it on CPU (the
    interpret rule, `use_interpret`), so the same tick runs — and the
    tier-1 suite passes — on CPU-only hosts; any other platform raises,
  * slice the result back to the caller's shapes.

Each op is bit-identical to its `ref.py` twin and to the XLA
formulations in `core/step.py` (test invariant,
`tests/test_raft_tick_kernels.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.raft_tick.kernel import (apply_last_wins_kernel,
                                            commit_majority_kernel,
                                            log_match_append_kernel)

_BLOCK_N = 8        # int32 sublane multiple
_BLOCK_LANE = 128   # lane width: L and K blocks


def use_interpret() -> bool:
    """The interpret rule shared by all four kernel families: compile
    the Pallas kernels on TPU, run them through the Pallas interpreter
    on CPU (inside jit, so they still trace into one XLA program —
    DESIGN.md §8), and refuse any other platform rather than hide it
    behind the interpreter."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and run interpreted on CPU; "
        f"JAX's default backend is {platform!r}")


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad2(x, rows: int, cols: int):
    """Zero-pad a 2D int32 array up to (rows, cols)."""
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _col(v, n_pad: int):
    """(N,) vector -> zero-padded (n_pad, 1) int32 column."""
    v = jnp.asarray(v, jnp.int32)
    return jnp.pad(v, (0, n_pad - v.shape[0]))[:, None]


@functools.partial(jax.jit, static_argnames=("w",))
def log_match_append(log_term, log_key, log_val, ldr_term, ldr_key, ldr_val,
                     log_len, app_from_len, app_upto, due, *, w: int):
    """Fused follower log-match + window append (kernel 1, DESIGN.md §8).

    log_* (N, L) int32; ldr_* (L,) — the leader's log row; log_len /
    app_from_len / app_upto (N,) int32; due (N,) bool; w = max_ship.
    Returns (log_term, log_key, log_val, new_len, accept) with accept
    bool — the tuple `step.follower_step` consumes."""
    N, L = log_term.shape
    Np, Lp = _pad_to(N, _BLOCK_N), _pad_to(L, _BLOCK_LANE)
    row = lambda r: _pad2(jnp.asarray(r, jnp.int32)[None, :], 1, Lp)
    out = log_match_append_kernel(
        _pad2(log_term, Np, Lp), _pad2(log_key, Np, Lp),
        _pad2(log_val, Np, Lp),
        row(ldr_term), row(ldr_key), row(ldr_val),
        _col(log_len, Np), _col(app_from_len, Np), _col(app_upto, Np),
        _col(due, Np),
        w=w, true_l=L, block_n=_BLOCK_N, block_l=_BLOCK_LANE,
        interpret=use_interpret())
    out_term, out_key, out_val, new_len, accept = out
    return (out_term[:N, :L], out_key[:N, :L], out_val[:N, :L],
            new_len[:N, 0], accept[:N, 0] != 0)


@jax.jit
def commit_majority(match_len, voter_alive, ldr_term, ldr_cur_term,
                    majority):
    """Majority-replicated commit length (kernel 2, DESIGN.md §8).

    match_len (N,) int32; voter_alive (N,) bool (is_voter & alive — the
    in-register mask; secretaries/observers never count, Property 3.4);
    ldr_term (L,) the leader's per-entry terms; scalars ldr_cur_term and
    majority.  Returns the scalar int32 commit length."""
    N, L = match_len.shape[0], ldr_term.shape[0]
    Np, Lp = _pad_to(N, _BLOCK_N), _pad_to(L, _BLOCK_LANE)
    scalar = lambda s: jnp.asarray(s, jnp.int32).reshape(1, 1)
    commit = commit_majority_kernel(
        _col(match_len, Np), _col(voter_alive, Np),
        _pad2(jnp.asarray(ldr_term, jnp.int32)[None, :], 1, Lp),
        scalar(ldr_cur_term), scalar(majority),
        true_l=L, block_l=_BLOCK_LANE, interpret=use_interpret())
    return commit[0, 0]


@jax.jit
def apply_last_wins(kv, keys, vals, valid):
    """Last-wins state-machine apply (kernel 3, DESIGN.md §8).

    kv (N, K) int32; keys/vals (N, A) int32; valid (N, A) bool.  Entry a
    of row i writes kv[i, keys[i, a]] = vals[i, a] iff valid — ascending
    a, so the LAST committed entry per key wins (log order, Property
    3.2); keys outside [0, K) drop.  Returns the updated (N, K) kv."""
    N, K = kv.shape
    A = keys.shape[1]
    Np, Kp = _pad_to(N, _BLOCK_N), _pad_to(K, _BLOCK_LANE)
    pad_win = lambda x: _pad2(jnp.asarray(x, jnp.int32), Np, A)
    # XLA scatter wraps negative indices once (numpy semantics); the
    # kernel's column match would silently drop them — normalize here so
    # the op stays bit-identical to the scatter formulations
    keys = jnp.asarray(keys, jnp.int32)
    keys = jnp.where(keys < 0, keys + K, keys)
    out = apply_last_wins_kernel(
        _pad2(kv, Np, Kp), pad_win(keys), pad_win(vals), pad_win(valid),
        block_n=_BLOCK_N, block_k=_BLOCK_LANE, interpret=use_interpret())
    return out[:N, :K]
