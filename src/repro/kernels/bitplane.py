"""MXU bit-plane Hamming kernel — the beyond-paper TPU reformulation.

On GPUs the fast path for the bitmap filter is ``XOR`` + ``__popc``.  On TPUs
the fast path is the 128x128 systolic array, so we re-express Hamming distance
as a matmul:

    popcount(x XOR y) = popcount(x) + popcount(y) - 2 * <bits(x), bits(y)>

After a one-time ``O(N*b)`` unpack of each bitmap into a {0,1} ``int8`` plane,
the all-pairs inner-product term becomes an ``int8 x int8 -> int32``
``dot_general`` that runs on the MXU.  Arithmetic intensity per output tile is
``2*b`` MACs vs ``~6*b/32`` VPU bit-ops for the SWAR kernel; whether the
MXU's higher throughput wins at large b is unmeasured on the chip, so
``impl='auto'`` resolves to SWAR and this kernel runs only when asked for
(``impl='mxu'``).

Per-row popcounts are precomputed (cheap, O(N*W)) and streamed in as a
``(tile, 1)`` column and a ``(1, tile)`` row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitmap_filter import _row_col

DEFAULT_TILE = 256


def _bitplane_kernel(pr_ref, ps_ref, pcr_ref, pcs_ref, out_ref):
    # pr: (TR, b) int8 bit planes; pcr: (TR, 1) / pcs: (1, TS) popcounts.
    dot = jax.lax.dot_general(
        pr_ref[...],
        ps_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (TR, TS) int32 — MXU
    out_ref[...] = pcr_ref[...] + pcs_ref[...] - 2 * dot


def bitplane_hamming_pallas(
    planes_r: jnp.ndarray,
    planes_s: jnp.ndarray,
    pc_r: jnp.ndarray,
    pc_s: jnp.ndarray,
    *,
    tile_r: int = DEFAULT_TILE,
    tile_s: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """int8[NR, b] x int8[NS, b] (+popcounts) -> int32[NR, NS] Hamming."""
    nr, b = planes_r.shape
    ns, _ = planes_s.shape
    grid = (nr // tile_r, ns // tile_s)
    return pl.pallas_call(
        _bitplane_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, b), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_s, b), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile_s), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, ns), jnp.int32),
        interpret=interpret,
    )(planes_r, planes_s, *_row_col(pc_r, pc_s))
