"""Pallas tile-count prepass for device-resident candidate compaction.

The resident blocked join (``core/join.py``, ``compaction="device"``) keeps
filtering *and* compaction on device: candidates are packed into a
fixed-capacity buffer with ``jnp.nonzero(size=cap)`` inside one jit'd step,
so only compacted pairs and a few counters ever cross to the host.  The
capacity has to be a static (compile-time) size, and guessing it wrong means
either wasted VMEM/transfer or an overflow escalation — so this kernel
measures the *real* per-tile counts first.

Each grid program evaluates the same fused verdict as the candidate kernel
(:func:`repro.kernels.bitmap_filter._tile_verdict` — Eq. 2 bound, Table 1
threshold, cutoff, padding rows) plus the integer length-window and the
self-join triangle, then writes back two int32 counts per tile: the number
of window-surviving pairs and the number of bitmap candidates.  O(NR*NS)
compute like the filter itself, but only ``O(grid)`` bytes cross to the
host — the dense bool verdict tile the host-compaction path ships never
leaves the device.

:func:`compact_indices` is the step's compaction itself: what
``jnp.nonzero(mask, size=cap, fill_value=0)`` returns, bit for bit, in the
form that does less work for the mask's shape and ``cap``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitmap_filter import (
    DEFAULT_TILE, _row_col, _tile_triangle, _tile_verdict)

# Each tile's count is broadcast over one (8, 128) int32 output block — the
# smallest block Mosaic's (8, 128) rule admits; the wrapper keeps element
# [0, 0] of every block, so only the (GR, GS) counts leave the jit.
_COUNT_BLOCK = (8, 128)


def _make_count_kernel(sim: str, tau: float, self_join: bool, cutoff: int,
                       window: bool, tile_r: int, tile_s: int):
    def kernel(r_ref, st_ref, lr_ref, ls_ref, lo_ref, hi_ref, win_ref, cand_ref):
        lr = lr_ref[...].astype(jnp.int32)  # (TR, 1)
        ls = ls_ref[...].astype(jnp.int32)  # (1, TS)
        win = (lr > 0) & (ls > 0)
        if window:
            lo = lo_ref[...].astype(jnp.int32)
            hi = hi_ref[...].astype(jnp.int32)
            win &= (ls >= lo) & (ls <= hi)
        if self_join:
            win &= _tile_triangle(tile_r, tile_s)
        cand = _tile_verdict(r_ref[...], st_ref[...], lr, ls,
                             sim=sim, tau=tau, cutoff=cutoff) & win
        win_ref[...] = jnp.full(_COUNT_BLOCK, jnp.sum(win.astype(jnp.int32)))
        cand_ref[...] = jnp.full(_COUNT_BLOCK, jnp.sum(cand.astype(jnp.int32)))

    return kernel


def count_candidates_pallas(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    len_r: jnp.ndarray,
    len_s: jnp.ndarray,
    lo_s: jnp.ndarray,
    hi_s: jnp.ndarray,
    *,
    sim: str,
    tau: float,
    self_join: bool,
    cutoff: int = 1 << 30,
    window: bool = True,
    tile_r: int = DEFAULT_TILE,
    tile_s: int = DEFAULT_TILE,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tile (window-pair count, candidate count) -> two int32[GR, GS].

    NR/NS must be multiples of the tile sizes (ops.py pads; padded rows have
    length 0 and count in neither output).  ``lo_s``/``hi_s`` are int32[NR]
    admissible |s| windows per R row (``bounds.length_window_int``).
    """
    nr, w = words_r.shape
    ns, _ = words_s.shape
    gr, gs = nr // tile_r, ns // tile_s
    kernel = _make_count_kernel(sim, float(tau), self_join, int(cutoff),
                                window, tile_r, tile_s)
    bh, bw = _COUNT_BLOCK
    count_spec = pl.BlockSpec(_COUNT_BLOCK, lambda i, j: (i, j))
    col_spec = pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0))
    lr, ls = _row_col(len_r, len_s)
    nwin, ncand = pl.pallas_call(
        kernel,
        grid=(gr, gs),
        in_specs=[
            pl.BlockSpec((tile_r, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, tile_s), lambda i, j: (0, j)),
            col_spec,
            pl.BlockSpec((1, tile_s), lambda i, j: (0, j)),
            col_spec,
            col_spec,
        ],
        out_specs=(count_spec, count_spec),
        out_shape=(jax.ShapeDtypeStruct((gr * bh, gs * bw), jnp.int32),) * 2,
        interpret=interpret,
    )(words_r, words_s.T, lr, ls, lo_s.reshape(-1, 1), hi_s.reshape(-1, 1))
    return nwin[::bh, ::bw], ncand[::bh, ::bw]


# Binary-search loop of the lookup form (``jnp.searchsorted``'s ``method``).
_SEARCH_METHOD = "scan"


def lookup_applies(size: int, cap: int) -> bool:
    """Whether :func:`compact_indices` takes the lookup form for a mask of
    ``size`` cells and ``cap`` output slots: its ``cap`` binary searches of
    ``ceil(log2(size))`` probes each do no more work than the scatter
    form's one update per mask cell."""
    return size > 0 and cap * math.ceil(math.log2(size)) <= size


def _lookup_form(mask, cap: int, method: str = _SEARCH_METHOD):
    # Slot k holds the first flat position whose running count reaches
    # k + 1; slots at or past the count hold 0, as nonzero's fill does.
    cs = jnp.cumsum(mask.ravel(), dtype=jnp.int32)
    slot = jnp.arange(cap, dtype=jnp.int32)
    flat = jnp.searchsorted(cs, slot + 1, side="left", method=method)
    flat = jnp.where(slot < cs[-1], flat, 0).astype(jnp.int32)
    out = []
    for dim in reversed(mask.shape[1:]):
        out.append(flat % dim)
        flat = flat // dim
    return (flat, *reversed(out))


def compact_indices(mask: jnp.ndarray, cap: int, *, _form: str | None = None):
    """``jnp.nonzero(mask, size=cap, fill_value=0)`` of a bool ``mask``,
    bit for bit: a tuple of int32[cap], one per axis, holding the first
    ``cap`` true cells in row-major order and 0 past the true count.

    Two forms, chosen from the static shapes alone
    (:func:`lookup_applies`).  The scatter form is ``jnp.nonzero``, whose
    ``bincount`` adds one update per mask cell; the lookup form takes the
    mask's running count and binary-searches it once per slot, so its work
    grows with ``cap`` rather than with the mask's area.  ``_form``
    ("lookup" or "scatter") forces one, for tests.
    """
    if _form is None:
        _form = "lookup" if lookup_applies(mask.size, cap) else "scatter"
    if _form == "lookup":
        return _lookup_form(mask, cap)
    if _form == "scatter":
        return jnp.nonzero(mask, size=cap, fill_value=0)
    raise ValueError(f"_form must be 'lookup' or 'scatter', got {_form!r}")
