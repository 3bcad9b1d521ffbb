"""Public op for the grouped digest reduction: packing, padding,
dispatch.

`core/fleet.py:_group_digest` calls `group_reduce` when
`backend="pallas"` is resolved (DESIGN.md §8/§9).  The wrapper

  * packs the int digest leaves (counters + unit-bin histograms) into
    one (B, Fi) int32 matrix and the float leaves into a (B, Ff)
    float32 matrix — sums and maxes share the float matrix, the kernel
    reduces both ways and callers slice what they packed,
  * pads B to a sublane multiple with dropped rows (segment id == G,
    the masking rule that also drops ungrouped members), F to lane
    multiples, and G to a sublane multiple,
  * compiles the Pallas kernel on TPU and interprets it on CPU (the
    `raft_tick` interpret rule; any other platform raises),
  * slices back to (G, ...) leaves.

Bit-identical to `ref.py` (the segment-op formulation kept in
`core/fleet.py` as the XLA path) — test invariant,
`tests/test_wide_kernels.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.group_digest.kernel import group_reduce_kernel
from repro.kernels.raft_tick.ops import use_interpret

_BLOCK_B = 8        # member-row sublane multiple (the grid axis)
_BLOCK_LANE = 128   # packed-leaf lane multiple


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("n_groups",))
def group_reduce(gids, int_mat, flt_mat, *, n_groups: int):
    """Blockwise masked group reduction (DESIGN.md §8/§9).

    gids (B,) int32 — ungrouped members carry `n_groups` and drop;
    int_mat (B, Fi) int32; flt_mat (B, Ff) float32.  Returns
    (g_int (G, Fi) sums, g_sum (G, Ff) sums, g_max (G, Ff) maxes),
    bit-identical to the segment-op twins including float order."""
    B, Fi = int_mat.shape
    Ff = flt_mat.shape[1]
    Bp = _pad_to(B, _BLOCK_B)
    Fip, Ffp = _pad_to(Fi, _BLOCK_LANE), _pad_to(Ff, _BLOCK_LANE)
    Gp = _pad_to(max(n_groups, 1), _BLOCK_B)
    # padded member rows drop like ungrouped ones: segment id == G
    gids_p = jnp.pad(jnp.asarray(gids, jnp.int32), (0, Bp - B),
                     constant_values=n_groups)[:, None]
    int_p = jnp.pad(jnp.asarray(int_mat, jnp.int32),
                    ((0, Bp - B), (0, Fip - Fi)))
    flt_p = jnp.pad(jnp.asarray(flt_mat, jnp.float32),
                    ((0, Bp - B), (0, Ffp - Ff)))
    g_int, g_sum, g_max = group_reduce_kernel(
        gids_p, int_p, flt_p, Gp, block_b=_BLOCK_B,
        interpret=use_interpret())
    return g_int[:n_groups, :Fi], g_sum[:n_groups, :Ff], \
        g_max[:n_groups, :Ff]
