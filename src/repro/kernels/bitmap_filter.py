"""Pallas TPU kernels for the Bitmap Filter hot spot.

The paper's inner loop — ``popcount(b_r XOR b_s)`` for every candidate pair —
is re-tiled for the TPU memory hierarchy:

* grid ``(NR/TR, NS/TS)``; each program owns one ``(TR, TS)`` output tile;
* BlockSpecs stage a ``(TR, W)`` packed ``uint32`` R block and a word-major
  ``(W, TS)`` S block (plus the lengths as a ``(TR, 1)`` column and a
  ``(1, TS)`` row) from HBM into VMEM;
* the Hamming accumulation unrolls the ``W = b/32`` words with a SWAR
  popcount on the 8x128 VPU (TPUs have no scalar POPCNT — the bit-slice
  reduction is the vector-unit equivalent);
* the *fused* candidate kernel additionally evaluates the Eq. 2 overlap upper
  bound, the equivalent-overlap threshold (Table 1), and the self-join
  upper-triangle mask, emitting a compact ``bool`` tile. This fusion is the
  TPU analogue of the paper's GPU kernel (Algorithm 8): filter evaluation
  never leaves the core's registers/VMEM, and only a 1-bit verdict per pair
  is written back to HBM.

Default tiles: ``TR = TS = 256`` — the ``(256, 256)`` int32 accumulator is
256 KiB, both bitmap blocks at b=4096 are 128 KiB each, everything fits VMEM
(~16 MiB) with headroom; the 256-lane minor dim is a multiple of the 128-wide
vector lanes and MXU tiles.

Correctness of every kernel is asserted against ``repro.kernels.ref`` oracles
in ``tests/test_kernels.py`` (interpret mode on CPU; shape/dtype sweeps).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import bounds

DEFAULT_TILE = 256


def _popcount32(v: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount on uint32 lanes (VPU-friendly, branch-free)."""
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def _tile_hamming(r_words: jnp.ndarray, s_words_t: jnp.ndarray) -> jnp.ndarray:
    """(TR, W) x (W, TS) uint32 -> (TR, TS) int32 Hamming distances.

    S arrives word-major, so word ``k`` of every S row is one lane-dense
    ``(1, TS)`` row, and word ``k`` of every R row a ``(TR, 1)`` column that
    broadcasts across lanes.  ``W = b/32`` is static and small, so the word
    loop is unrolled at trace time (Mosaic has no dynamic lane slice); the
    (TR, TS, W) cross-product is never materialised.
    """
    tr, w = r_words.shape
    ts = s_words_t.shape[1]
    acc = jnp.zeros((tr, ts), dtype=jnp.int32)
    for k in range(w):
        x = r_words[:, k:k + 1] ^ s_words_t[k:k + 1, :]
        acc = acc + _popcount32(x).astype(jnp.int32)
    return acc


def _row_col(len_r: jnp.ndarray, len_s: jnp.ndarray):
    """Per-row vectors as ``(NR, 1)`` columns and ``(1, NS)`` rows: the
    2-D blocks Mosaic accepts (1-D blocks shorter than XLA's 1024-element
    tiling are refused), already shaped to broadcast over a tile."""
    return len_r.reshape(-1, 1), len_s.reshape(1, -1)


# ---------------------------------------------------------------------------
# Kernel 1: raw Hamming-distance tile kernel
# ---------------------------------------------------------------------------

def _hamming_kernel(r_ref, st_ref, out_ref):
    out_ref[...] = _tile_hamming(r_ref[...], st_ref[...])


def hamming_matrix_pallas(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    *,
    tile_r: int = DEFAULT_TILE,
    tile_s: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """All-pairs Hamming distance. uint32[NR, W] x uint32[NS, W] -> int32[NR, NS].

    NR/NS must be multiples of the tile sizes (ops.py pads).
    """
    nr, w = words_r.shape
    ns, _ = words_s.shape
    grid = (nr // tile_r, ns // tile_s)
    return pl.pallas_call(
        _hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, tile_s), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, ns), jnp.int32),
        interpret=interpret,
    )(words_r, words_s.T)


# ---------------------------------------------------------------------------
# Kernel 2: fused candidate kernel (bound + threshold + triangle mask)
# ---------------------------------------------------------------------------

def _tile_verdict(r_words: jnp.ndarray, s_words_t: jnp.ndarray,
                  lr: jnp.ndarray, ls: jnp.ndarray,
                  *, sim: str, tau: float, cutoff: int) -> jnp.ndarray:
    """Fused bitmap-filter verdict for one tile -> bool[TR, TS].

    ``lr`` is a ``(TR, 1)`` column, ``ls`` a ``(1, TS)`` row (see
    :func:`_row_col`).  Shared by the candidate kernel below and the count
    prepass kernel in :mod:`repro.kernels.compaction` so both apply exactly
    the same test.
    """
    ham = _tile_hamming(r_words, s_words_t)
    ub = (lr + ls - ham) // 2
    # Tighten: overlap can never exceed min(|r|, |s|).
    ub = jnp.minimum(ub, jnp.minimum(lr, ls))
    # Prune against the epsilon-relaxed threshold: float32 rounding may sit
    # a few ulps above the f64 oracle value, and a prune is irreversible.
    need = bounds.required_overlap_safe(sim, tau, lr, ls)
    passed = ub.astype(jnp.float32) >= need
    # Cutoff (Alg. 7): past the precision cliff the bitmap test is void —
    # such pairs must be *kept* (conservative), not pruned.
    over_cut = (lr > cutoff) | (ls > cutoff)
    cand = passed | over_cut
    # Padding rows have length 0 -> never candidates.
    cand &= (lr > 0) & (ls > 0)
    return cand


def _tile_triangle(tile_r: int, tile_s: int) -> jnp.ndarray:
    """Strict upper triangle ``gi < gj`` in global row ids for the current
    (i, j) grid tile -> bool[TR, TS]."""
    shape = (tile_r, tile_s)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return pl.program_id(0) * tile_r + rows < pl.program_id(1) * tile_s + cols


def _make_candidate_kernel(sim: str, tau: float, self_join: bool, tile_r: int, tile_s: int,
                           cutoff: int):
    def kernel(r_ref, st_ref, lr_ref, ls_ref, out_ref):
        lr = lr_ref[...].astype(jnp.int32)  # (TR, 1)
        ls = ls_ref[...].astype(jnp.int32)  # (1, TS)
        cand = _tile_verdict(r_ref[...], st_ref[...], lr, ls,
                             sim=sim, tau=tau, cutoff=cutoff)
        if self_join:
            cand &= _tile_triangle(tile_r, tile_s)
        out_ref[...] = cand

    return kernel


def candidate_matrix_pallas(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    len_r: jnp.ndarray,
    len_s: jnp.ndarray,
    *,
    sim: str,
    tau: float,
    self_join: bool,
    cutoff: int = 1 << 30,
    tile_r: int = DEFAULT_TILE,
    tile_s: int = DEFAULT_TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused bitmap-filter verdict tile: bool[NR, NS] candidate mask."""
    nr, w = words_r.shape
    ns, _ = words_s.shape
    grid = (nr // tile_r, ns // tile_s)
    kernel = _make_candidate_kernel(sim, float(tau), self_join, tile_r, tile_s, int(cutoff))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_r, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, tile_s), lambda i, j: (0, j)),
            pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tile_s), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tile_r, tile_s), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nr, ns), jnp.bool_),
        interpret=interpret,
    )(words_r, words_s.T, *_row_col(len_r, len_s))
