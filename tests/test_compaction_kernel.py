"""Interpret-mode sweeps for the compaction tile-count prepass kernel.

The Pallas kernel must agree exactly (integer counts) with the pure-jnp
oracle across word widths W ∈ {1, 4, 128}, non-multiple-of-tile NR/NS,
all-pass and all-prune tiles, and empty (length-0 padding) rows.
"""

import itertools

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitmap as bm, bounds
from repro.core.constants import PAD_TOKEN
from repro.kernels import compaction
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _padded_tokens(lengths, seed, width=24, universe=600):
    rng = np.random.default_rng(seed)
    toks = np.full((len(lengths), width), PAD_TOKEN, dtype=np.int32)
    for i, l in enumerate(lengths):
        if l:
            toks[i, :l] = np.sort(rng.choice(universe, size=l, replace=False))
    return jnp.asarray(toks)


def _words(lengths, b, seed):
    toks = _padded_tokens(lengths, seed)
    return bm.generate_bitmaps(toks, jnp.asarray(lengths), b, method="xor")


def _counts_both(lens_r, lens_s, b, *, sim="jaccard", tau=0.6, self_join=False,
                 cutoff=1 << 30, window=True, tile=32, seed=0):
    wr = _words(lens_r, b, seed)
    ws = wr if self_join else _words(lens_s, b, seed + 1)
    lo, hi = bounds.length_window_int(sim, tau, np.asarray(lens_r))
    args = (wr, ws, jnp.asarray(lens_r), jnp.asarray(lens_s),
            jnp.asarray(lo), jnp.asarray(hi))
    kw = dict(sim=sim, tau=tau, self_join=self_join, cutoff=cutoff,
              window=window, tile=tile)
    ref_out = kops.count_candidates(*args, impl="ref", **kw)
    pal_out = kops.count_candidates(*args, impl="swar", interpret=True, **kw)
    return [np.asarray(x) for x in ref_out], [np.asarray(x) for x in pal_out]


def _rand_lens(n, seed, lo=0, hi=21):
    return np.random.default_rng(seed).integers(lo, hi, size=n).astype(np.int32)


@pytest.mark.parametrize("b", [32, 128, 4096])  # W = 1, 4, 128 words
def test_count_kernel_word_widths(b):
    lens = _rand_lens(64, 3, lo=1)
    wr = _words(lens, b, 3)
    lo, hi = bounds.length_window_int("jaccard", 0.6, lens)
    args = (wr, wr, jnp.asarray(lens), jnp.asarray(lens),
            jnp.asarray(lo), jnp.asarray(hi))
    kw = dict(sim="jaccard", tau=0.6, self_join=False, window=True, tile=32)
    ref_w, ref_c = kops.count_candidates(*args, impl="ref", **kw)
    pal_w, pal_c = kops.count_candidates(*args, impl="swar", interpret=True, **kw)
    assert np.array_equal(np.asarray(ref_w), np.asarray(pal_w)), b
    assert np.array_equal(np.asarray(ref_c), np.asarray(pal_c)), b
    # identical R and S rows -> at least the 64 self-pairs are candidates
    assert np.asarray(ref_c).sum() >= 64


@pytest.mark.parametrize("nr,ns,tile", [(32, 32, 32), (33, 70, 32), (96, 64, 32),
                                        (40, 56, 8), (31, 17, 16)])
def test_count_kernel_nonmultiple_shapes(nr, ns, tile):
    """Last tiles are padded with empty rows; counts must be unaffected."""
    (wr, cr), (wp, cp) = _counts_both(_rand_lens(nr, nr, lo=1),
                                      _rand_lens(ns, ns + 1, lo=1), 64, tile=tile)
    assert wr.shape == (-(-nr // tile), -(-ns // tile))
    assert np.array_equal(wr, wp) and np.array_equal(cr, cp), (nr, ns, tile)


@pytest.mark.parametrize("self_join", [False, True])
@pytest.mark.parametrize("window", [False, True])
def test_count_kernel_masks(self_join, window):
    (wr, cr), (wp, cp) = _counts_both(
        _rand_lens(48, 9, lo=1), _rand_lens(48, 9, lo=1), 64,
        self_join=self_join, window=window, seed=9)
    assert np.array_equal(wr, wp) and np.array_equal(cr, cp)
    if self_join:  # strict upper triangle: fewer than half of all pairs
        assert wr.sum() <= 48 * 47 // 2


def test_count_kernel_all_pass_tile():
    """Identical sets at a permissive threshold: every (ordered) pair is both
    in-window and a bitmap candidate."""
    n = 64
    lens = np.full(n, 5, dtype=np.int32)
    rng = np.random.default_rng(0)
    row = np.sort(rng.choice(600, size=5, replace=False))
    toks = jnp.asarray(np.tile(np.concatenate(
        [row, np.full(19, PAD_TOKEN)]).astype(np.int32)[None], (n, 1)))
    words = bm.generate_bitmaps(toks, jnp.asarray(lens), 64, method="xor")
    lo, hi = bounds.length_window_int("jaccard", 0.5, lens)
    win_c, cand_c = kops.count_candidates(
        words, words, jnp.asarray(lens), jnp.asarray(lens),
        jnp.asarray(lo), jnp.asarray(hi),
        sim="jaccard", tau=0.5, self_join=False, impl="swar", interpret=True,
        tile=32)
    assert int(np.asarray(win_c).sum()) == n * n
    assert int(np.asarray(cand_c).sum()) == n * n


def test_count_kernel_all_prune_tile():
    """Length-incompatible sets (1 vs 20 at jaccard 0.9): the window prunes
    every pair, so both counts collapse to the diagonal-free zero."""
    lens_r = np.full(32, 1, dtype=np.int32)
    lens_s = np.full(32, 20, dtype=np.int32)
    (win_c, cand_c), (wp, cp) = _counts_both(lens_r, lens_s, 64, tau=0.9, seed=2)
    assert win_c.sum() == 0 and cand_c.sum() == 0
    assert wp.sum() == 0 and cp.sum() == 0


def test_count_kernel_empty_rows_never_count():
    """Length-0 rows (padding) contribute to neither output, wherever they
    sit in the tile grid."""
    lens_r = _rand_lens(48, 5, lo=0, hi=15)
    lens_r[::3] = 0
    lens_s = _rand_lens(48, 6, lo=0, hi=15)
    lens_s[1::4] = 0
    (wr, cr), (wp, cp) = _counts_both(lens_r, lens_s, 64, tau=0.5, seed=5)
    assert np.array_equal(wr, wp) and np.array_equal(cr, cp)
    # upper bound: only rows/cols with nonzero lengths can ever pair
    assert wr.sum() <= int((lens_r > 0).sum()) * int((lens_s > 0).sum())
    # all-empty collection: exactly zero
    zero = np.zeros(32, dtype=np.int32)
    (wz, cz), (wzp, czp) = _counts_both(zero, zero, 64, seed=7)
    assert wz.sum() == 0 and cz.sum() == 0 and wzp.sum() == 0 and czp.sum() == 0


def test_count_kernel_matches_dense_candidate_matrix():
    """The prepass totals equal the dense mask the host path would ship —
    the capacity it sizes is exact, not an estimate."""
    lens_r = _rand_lens(33, 11, lo=1)
    lens_s = _rand_lens(70, 12, lo=1)
    b = 64
    wr = _words(lens_r, b, 20)
    ws = _words(lens_s, b, 21)
    lo, hi = bounds.length_window_int("cosine", 0.7, lens_r)
    win_c, cand_c = kops.count_candidates(
        wr, ws, jnp.asarray(lens_r), jnp.asarray(lens_s),
        jnp.asarray(lo), jnp.asarray(hi),
        sim="cosine", tau=0.7, self_join=False, cutoff=18, impl="ref")
    dense = np.asarray(kref.candidate_matrix_ref(
        wr, ws, jnp.asarray(lens_r), jnp.asarray(lens_s), sim="cosine",
        tau=0.7, self_join=False, cutoff=18))
    win = ((lens_s[None, :] >= lo[:, None]) & (lens_s[None, :] <= hi[:, None])
           & (lens_r[:, None] > 0) & (lens_s[None, :] > 0))
    assert int(np.asarray(cand_c).sum()) == int((dense & win).sum())
    assert int(np.asarray(win_c).sum()) == int(win.sum())


# compact_indices: both forms against jnp.nonzero(size=cap, fill_value=0),
# over 1-D and 2-D masks of odd shapes, densities 0 / 0.02% / 30% / 100%,
# and cap below, equal to and above the true count (below: the first cap
# true cells in row-major order).
@pytest.mark.parametrize("form,shape,density,cap_vs_count", list(
    itertools.product(("lookup", "scatter"), ((37,), (33, 65)),
                      (0.0, 0.0002, 0.3, 1.0), ("below", "equal", "above"))))
def test_compact_indices_is_nonzero(form, shape, density, cap_vs_count):
    rng = np.random.default_rng(7)
    size = int(np.prod(shape))
    count = 0 if density == 0 else max(1, round(density * size))
    flat = np.zeros(size, dtype=bool)
    flat[rng.choice(size, size=count, replace=False)] = True
    mask = jnp.asarray(flat.reshape(shape))
    cap = {"below": max(count // 2, 1), "equal": max(count, 1),
           "above": count + 7}[cap_vs_count]
    want = jnp.nonzero(mask, size=cap, fill_value=0)
    got = compaction.compact_indices(mask, cap, _form=form)
    assert len(got) == len(shape)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == (cap,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("size,cap,form", [
    (4096 * 4096, 4096, "lookup"),      # a sparse blocked-join tile
    (4096 * 4096, 1 << 19, "lookup"),
    (16 * 16, 256, "scatter"),          # the CPU tests' block=16 tiles
    (4096, 4096, "scatter"),            # the cap-sized second compaction
])
def test_compaction_form_follows_the_shapes(size, cap, form):
    assert compaction.lookup_applies(size, cap) == (form == "lookup")
