"""Jit'd public wrappers around the Pallas kernels.

Handles tile padding, implementation dispatch, and the CPU story:

* on TPU the compiled Pallas kernels run natively;
* on CPU ``interpret=True`` executes the kernel bodies in Python — correct but
  slow, used by the test suite;
* ``impl='ref'`` (the pure-jnp oracle) is the default *performance* path on
  CPU so that benchmarks and the data pipeline stay fast in this container.

``impl='auto'`` resolves to ``ref`` on CPU.  On TPU it resolves only to
kernels that compile for the chip at every bitmap width (b = 64..1024 is
pinned by ``tests/test_chip_compile.py``):

* the dense ops (``hamming_matrix``, ``candidate_matrix``,
  ``count_candidates``) -> ``swar``, the word-unrolled VPU tile kernel;
* ``pair_verdict`` (1-D candidate stream, :func:`_resolve_pairwise_impl`)
  -> ``swar_tiled``, the candidate-major streaming kernel;
* ``entry_filter`` (pure integer filtering, no bitmap words) -> ``swar``.

The other formulations (``mxu`` bit-plane, the word-loop pairwise ``swar``)
stay reachable by explicit ``impl=`` only; which one wins where is a
measurement, not a default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import bounds
from repro.core.bitmap import popcount_rows, unpack_bits
from repro.kernels import bitplane, bitmap_filter, compaction, postings, ref

_TILE = bitmap_filter.DEFAULT_TILE
_TILE_1D = postings.DEFAULT_TILE_1D


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(a: jnp.ndarray, multiple: int, fill=0) -> jnp.ndarray:
    n = a.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return a
    pad_widths = [(0, rem)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad_widths, constant_values=fill)


def resolve_impl(impl: str, b: int) -> str:
    if impl != "auto":
        return impl
    if not _on_tpu():
        return "ref"
    return "swar"


@functools.partial(jax.jit, static_argnames=("impl", "interpret", "tile"))
def hamming_matrix(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    impl: str = "auto",
    interpret: bool | None = None,
    tile: int = _TILE,
) -> jnp.ndarray:
    """All-pairs Hamming distance between packed bitmaps -> int32[NR, NS]."""
    nr, w = words_r.shape
    ns, _ = words_s.shape
    b = 32 * w
    impl = resolve_impl(impl, b)
    if interpret is None:
        interpret = not _on_tpu()
    if impl == "ref":
        return ref.hamming_matrix_ref(words_r, words_s)
    if impl == "ref_mxu":
        return ref.bitplane_hamming_ref(
            unpack_bits(words_r).astype(jnp.int8),
            unpack_bits(words_s).astype(jnp.int8),
            popcount_rows(words_r), popcount_rows(words_s))
    pr = _pad_rows(words_r, tile)
    ps = _pad_rows(words_s, tile)
    if impl == "swar":
        out = bitmap_filter.hamming_matrix_pallas(pr, ps, tile_r=tile, tile_s=tile,
                                                  interpret=interpret)
    elif impl == "mxu":
        planes_r = unpack_bits(pr).astype(jnp.int8)
        planes_s = unpack_bits(ps).astype(jnp.int8)
        out = bitplane.bitplane_hamming_pallas(
            planes_r, planes_s, popcount_rows(pr), popcount_rows(ps),
            tile_r=tile, tile_s=tile, interpret=interpret)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return out[:nr, :ns]


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "self_join", "cutoff", "impl", "interpret", "tile"),
)
def candidate_matrix(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    len_r: jnp.ndarray,
    len_s: jnp.ndarray,
    sim: str,
    tau: float,
    self_join: bool,
    cutoff: int = 1 << 30,
    impl: str = "auto",
    interpret: bool | None = None,
    tile: int = _TILE,
) -> jnp.ndarray:
    """Fused bitmap-filter verdicts -> bool[NR, NS] candidate mask."""
    nr, w = words_r.shape
    ns, _ = words_s.shape
    b = 32 * w
    impl = resolve_impl(impl, b)
    if interpret is None:
        interpret = not _on_tpu()
    if impl == "ref":
        return ref.candidate_matrix_ref(
            words_r, words_s, len_r, len_s, sim=sim, tau=tau,
            self_join=self_join, cutoff=cutoff)
    if impl == "ref_mxu":
        ham = hamming_matrix(words_r, words_s, impl="ref_mxu")
        lr = len_r.astype(jnp.int32)[:, None]
        ls = len_s.astype(jnp.int32)[None, :]
        ub = jnp.minimum((lr + ls - ham) // 2, jnp.minimum(lr, ls))
        need = bounds.required_overlap_safe(sim, tau, lr, ls)
        cand = (ub.astype(jnp.float32) >= need) | (lr > cutoff) | (ls > cutoff)
        cand &= (lr > 0) & (ls > 0)
        if self_join:
            cand &= jnp.arange(words_r.shape[0])[:, None] < jnp.arange(words_s.shape[0])[None, :]
        return cand
    if impl == "mxu":
        # MXU path computes Hamming on the systolic array, then applies the
        # (cheap, elementwise) verdict outside the kernel.
        ham = hamming_matrix(words_r, words_s, impl="mxu", interpret=interpret, tile=tile)
        lr = len_r.astype(jnp.int32)[:, None]
        ls = len_s.astype(jnp.int32)[None, :]
        ub = jnp.minimum((lr + ls - ham) // 2, jnp.minimum(lr, ls))
        need = bounds.required_overlap_safe(sim, tau, lr, ls)
        cand = (ub.astype(jnp.float32) >= need) | (lr > cutoff) | (ls > cutoff)
        cand &= (lr > 0) & (ls > 0)
        if self_join:
            cand &= jnp.arange(nr)[:, None] < jnp.arange(ns)[None, :]
        return cand
    pr = _pad_rows(words_r, tile)
    ps = _pad_rows(words_s, tile)
    plr = _pad_rows(len_r.astype(jnp.int32), tile)
    pls = _pad_rows(len_s.astype(jnp.int32), tile)
    out = bitmap_filter.candidate_matrix_pallas(
        pr, ps, plr, pls, sim=sim, tau=tau, self_join=self_join,
        cutoff=cutoff, tile_r=tile, tile_s=tile, interpret=interpret)
    return out[:nr, :ns]


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "self_join", "cutoff", "window", "impl",
                     "interpret", "tile"),
)
def count_candidates(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    len_r: jnp.ndarray,
    len_s: jnp.ndarray,
    lo_s: jnp.ndarray,
    hi_s: jnp.ndarray,
    sim: str,
    tau: float,
    self_join: bool = False,
    cutoff: int = 1 << 30,
    window: bool = True,
    impl: str = "auto",
    interpret: bool | None = None,
    tile: int = _TILE,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Tile-count prepass -> (window counts, candidate counts), int32[GR, GS].

    Counts exactly what :func:`candidate_matrix` intersected with the integer
    length window (``lo_s``/``hi_s`` per R row, from
    ``bounds.length_window_int``) would mark true — but without materialising
    the dense mask.  The resident join sizes its compaction capacity from
    these counts.  Non-Pallas impls (``ref``/``ref_mxu``/``mxu``) share the
    pure-jnp oracle; the dense intermediate then lives only on device inside
    this jit.  One fused pass counts the verdict and the window together, so
    its device time is the ``verdict`` stage's.
    """
    # A Pallas call takes the innermost scope's name as its HLO name: the
    # inner scope keeps it ``%count_candidates``, as the benchmark's layer
    # table of the verdict kernels expects.
    with jax.named_scope(tracing.VERDICT), jax.named_scope("count_candidates"):
        nr, w = words_r.shape
        ns, _ = words_s.shape
        b = 32 * w
        impl = resolve_impl(impl, b)
        if interpret is None:
            interpret = not _on_tpu()
        if impl in ("ref", "ref_mxu", "mxu"):
            return ref.count_candidates_ref(
                words_r, words_s, len_r, len_s, lo_s, hi_s, sim=sim, tau=tau,
                self_join=self_join, cutoff=cutoff, window=window,
                tile_r=tile, tile_s=tile)
        if impl != "swar":
            raise ValueError(f"unknown impl {impl!r}")
        pr = _pad_rows(words_r, tile)
        ps = _pad_rows(words_s, tile)
        plr = _pad_rows(len_r.astype(jnp.int32), tile)
        pls = _pad_rows(len_s.astype(jnp.int32), tile)
        plo = _pad_rows(lo_s.astype(jnp.int32), tile)
        phi = _pad_rows(hi_s.astype(jnp.int32), tile)
        return compaction.count_candidates_pallas(
            pr, ps, plr, pls, plo, phi, sim=sim, tau=tau, self_join=self_join,
            cutoff=cutoff, window=window, tile_r=tile, tile_s=tile,
            interpret=interpret)


def _resolve_pairwise_impl(impl: str, b: int) -> str:
    """Pairwise (1-D candidate stream) dispatch.

    ``auto`` resolves to ref on CPU and, on TPU, to the candidate-major
    tiled SWAR kernel at every b — the one pairwise formulation that
    compiles for the chip (the word-loop ``swar`` needs a dynamic lane
    slice, the bit-plane ``mxu`` a batched ``dot_general``, and Mosaic
    lowers neither).  Explicit impls pass through unchanged.
    """
    if impl != "auto":
        return impl
    if not _on_tpu():
        return "ref"
    return "swar_tiled"


def _resolve_entry_impl(impl: str) -> str:
    """``entry_filter`` is pure integer filtering — there are no bitmap
    words, hence no bit-plane formulation; the mxu impls resolve to their
    elementwise equivalents (and ``swar_tiled`` to ``swar``: the kernel is
    already a single vectorized pass per tile)."""
    impl = resolve_impl(impl, 32)
    return {"mxu": "swar", "ref_mxu": "ref", "swar_tiled": "swar"}.get(impl, impl)


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "self_join", "impl", "interpret", "tile"),
)
def entry_filter(
    len_r: jnp.ndarray,
    pos_r: jnp.ndarray,
    len_s: jnp.ndarray,
    pos_s: jnp.ndarray,
    lo: jnp.ndarray,
    hi: jnp.ndarray,
    idx_r: jnp.ndarray,
    idx_s: jnp.ndarray,
    valid: jnp.ndarray,
    sim: str,
    tau: float,
    self_join: bool = False,
    impl: str = "auto",
    interpret: bool | None = None,
    tile: int = _TILE_1D,
) -> jnp.ndarray:
    """Postings-entry admission mask -> bool[G] (index candidate generation).

    Applies the classic filters of :mod:`repro.core.filters` per expanded
    posting entry: the probe's integer length window on |r|, the positional
    upper bound at this matching prefix position, non-empty rows, and (for
    self-joins) the strict ``idx_r < idx_s`` triangle.  ``valid`` masks
    padding/overrun slots.
    """
    (g,) = len_r.shape
    impl = _resolve_entry_impl(impl)
    if interpret is None:
        interpret = not _on_tpu()
    args = (len_r, pos_r, len_s, pos_s, lo, hi, idx_r, idx_s)
    if impl == "ref":
        return ref.entry_filter_ref(*args, valid, sim=sim, tau=tau,
                                    self_join=self_join)
    if impl != "swar":
        raise ValueError(f"unknown impl {impl!r}")
    padded = [_pad_rows(a.astype(jnp.int32), tile) for a in args]
    pvalid = _pad_rows(valid, tile, fill=False)
    out = postings.entry_filter_pallas(
        *padded, pvalid, sim=sim, tau=tau, self_join=self_join, tile=tile,
        interpret=interpret)
    return out[:g]


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "cutoff", "impl", "interpret", "tile"),
)
def pair_verdict(
    words_r: jnp.ndarray,
    words_s: jnp.ndarray,
    len_r: jnp.ndarray,
    len_s: jnp.ndarray,
    sim: str,
    tau: float,
    cutoff: int = 1 << 30,
    impl: str = "auto",
    interpret: bool | None = None,
    tile: int = _TILE_1D,
) -> jnp.ndarray:
    """Pairwise fused bitmap-filter verdict -> bool[G].

    The same Eq. 2 + Table 1 + cutoff test as :func:`candidate_matrix`, but
    over *gathered* candidate rows (``words_r[g]`` vs ``words_s[g]``) instead
    of the dense cross product — the indexed driver's bitmap cost is
    proportional to G, not |R|·|S|.

    Impls (all bit-identical, conformance-gated against ``ref``):
    ``swar`` word-loop kernel, ``swar_tiled`` candidate-major streaming
    kernel, ``mxu`` batched bit-plane kernel, plus the ``ref``/``ref_mxu``
    pure-jnp oracles; ``auto`` picks per backend and b
    (:func:`_resolve_pairwise_impl`).
    """
    g, w = words_r.shape
    impl = _resolve_pairwise_impl(impl, 32 * w)
    if interpret is None:
        interpret = not _on_tpu()
    if impl == "ref":
        return ref.pair_verdict_ref(words_r, words_s, len_r, len_s,
                                    sim=sim, tau=tau, cutoff=cutoff)
    if impl == "ref_mxu":
        ham = ref.bitplane_pair_hamming_ref(
            unpack_bits(words_r).astype(jnp.int8),
            unpack_bits(words_s).astype(jnp.int8),
            popcount_rows(words_r), popcount_rows(words_s))
        return postings._verdict_from_hamming(
            ham, len_r.astype(jnp.int32), len_s.astype(jnp.int32),
            sim=sim, tau=tau, cutoff=cutoff)
    plr = _pad_rows(len_r.astype(jnp.int32), tile)
    pls = _pad_rows(len_s.astype(jnp.int32), tile)
    if impl == "mxu":
        pr = _pad_rows(words_r, tile)
        ps = _pad_rows(words_s, tile)
        out = postings.pair_verdict_bitplane_pallas(
            unpack_bits(pr).astype(jnp.int8), unpack_bits(ps).astype(jnp.int8),
            popcount_rows(pr), popcount_rows(ps), plr, pls,
            sim=sim, tau=tau, cutoff=cutoff, tile=tile, interpret=interpret)
        return out[:g]
    if impl not in ("swar", "swar_tiled"):
        raise ValueError(f"unknown impl {impl!r}")
    pr = _pad_rows(words_r, tile)
    ps = _pad_rows(words_s, tile)
    kernel = (postings.pair_verdict_tiled_pallas if impl == "swar_tiled"
              else postings.pair_verdict_pallas)
    out = kernel(
        pr, ps, plr, pls, sim=sim, tau=tau, cutoff=cutoff, tile=tile,
        interpret=interpret)
    return out[:g]
