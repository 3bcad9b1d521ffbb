"""Public op for the anti-entropy sync kernel: padding, bitcast,
dispatch.

`core/step.py:anti_entropy_step` calls `ae_sync` when
`backend="pallas"` is resolved (DESIGN.md §8/§13).  The wrapper

  * normalizes observer operands to (1, Op) and node operands to
    (1, Np) lane-tiled int32 rows — padded observer lanes carry
    `dobs_alive == 0` (never due), padded node lanes `alive == 0`
    (never a voter or source); the REAL N and S ride as static bounds,
  * bitcasts the uint32 applied digests to int32 for the kernel and
    back on the way out (one-hot sums preserve the bit pattern),
  * flattens the (S, S) site-pair RTT matrix to a (1, S*S) row so the
    sync-hop gather is a single fused one-hot,
  * compiles the Pallas kernel on TPU and interprets it on CPU (the
    `raft_tick` interpret rule; any other platform raises),
  * slices the four dobs_* rows back to (O,).

Bit-identical to `ref.py` and to the XLA formulation in
`core/step.py` (test invariant, `tests/test_wide_kernels.py`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ae_sync.kernel import ae_sync_kernel
from repro.kernels.raft_tick.ops import use_interpret

_BLOCK_LANE = 128   # lane multiple for observer/node/site-pair rows


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _row(v, n_pad: int):
    """(X,) vector -> zero-padded (1, n_pad) int32 lane row."""
    v = jnp.asarray(v, jnp.int32)
    return jnp.pad(v, (0, n_pad - v.shape[0]))[None, :]


@jax.jit
def ae_sync(dobs_alive, dobs_fol, dobs_applied, dobs_term, dobs_digest,
            dobs_synced_t, ae_phase, dobs_site, alive, is_voter,
            applied_len, term, applied_digest, site, site_rtt,
            tick, ae_interval):
    """Fused anti-entropy round (DESIGN.md §8/§13).

    Observer vectors (O,); node vectors (N,); site_rtt (S, S) int32;
    scalars tick / ae_interval (cfg_c data — a traced argument, so
    cadence sweeps never recompile).  The digests are uint32.  Returns
    (dobs_applied, dobs_term, dobs_digest, dobs_synced_t)."""
    O = dobs_fol.shape[0]
    N = alive.shape[0]
    S = site_rtt.shape[0]
    Op, Np = _pad_to(O, _BLOCK_LANE), _pad_to(N, _BLOCK_LANE)
    Fp = _pad_to(S * S, _BLOCK_LANE)
    as_i32 = lambda v: jax.lax.bitcast_convert_type(
        jnp.asarray(v, jnp.uint32), jnp.int32)
    srtt_flat = jnp.asarray(site_rtt, jnp.int32).reshape(-1)
    scalar = lambda s: jnp.asarray(s, jnp.int32).reshape(1, 1)
    out = ae_sync_kernel(
        scalar(tick), scalar(ae_interval),
        _row(dobs_alive, Op), _row(dobs_fol, Op), _row(dobs_applied, Op),
        _row(dobs_term, Op), _row(as_i32(dobs_digest), Op),
        _row(dobs_synced_t, Op), _row(ae_phase, Op), _row(dobs_site, Op),
        _row(alive, Np), _row(is_voter, Np), _row(applied_len, Np),
        _row(term, Np), _row(as_i32(applied_digest), Np), _row(site, Np),
        _row(srtt_flat, Fp),
        true_n=N, true_s=S, interpret=use_interpret())
    applied, oterm, odigest, synced = (v[0, :O] for v in out)
    return applied, oterm, jax.lax.bitcast_convert_type(
        odigest, jnp.uint32), synced
