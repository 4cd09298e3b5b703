"""The ``"indexed"`` join driver: sub-quadratic, index-driven candidate
generation feeding the bitmap filter + fused verification.

Every other device driver (``naive``/``blocked``/``ring``) evaluates the
bitmap filter over the (windowed) O(|R|·|S|) grid; at paper scale that grid
— not the per-pair cost — is the wall.  The CPU algorithms avoid it with
prefix-filter inverted indexes; this driver brings the same asymptotics to
the accelerator stack:

1. **Expand** — for each probe batch, look up the probe prefix tokens in the
   CSR postings index (:mod:`repro.index.postings`) and expand the matching
   lists into a flat entry stream, sized by a count prepass (the capacity
   pattern of ``kernels/compaction.py``).
2. **Filter** — admit entries through the classic filters
   (:func:`repro.kernels.ops.entry_filter`: integer length window,
   positional bound, self-join triangle) on device.
3. **Deduplicate** — sort the surviving ``(probe, set)`` keys and keep
   unique ones, compacted to a fixed ``(cap, 2)`` candidate buffer.
4. **Verify** — the PR 2 fused step, but over the candidate *list*: pairwise
   bitmap verdict (:func:`repro.kernels.ops.pair_verdict`) → exact
   ``searchsorted`` verification → compaction down to verified pairs.

Steps 1-4 run inside one jit per probe chunk; only the compacted pair
buffer and four counters cross to the host.  A chunk whose expansion
exceeds an explicitly forced ``capacity`` escalates to a dense
grid-over-chunk fallback (flagged in ``JoinStats.overflow_blocks``), so the
result is exact for *any* capacity — same contract as the blocked driver.

``JoinStats`` for this driver reports the candidate funnel:
``postings_expanded`` (pre-dedup entries) → ``candidates_generated`` (==
``total_pairs``: deduped pairs the bitmap is evaluated on) → ``candidates``
(after the bitmap) → ``verified_true``.  ``filter_ratio`` therefore measures
the bitmap's pruning over *generated* candidates, and comparing
``candidates_generated`` against the blocked driver's quantifies the
sub-quadratic win (asserted in ``tests/test_indexed_join.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import bitmap as bm
from repro.core import bounds, expected, verify
from repro.core.collection import Collection, split_join_args
from repro.core.constants import BITMAP_COMBINED, JACCARD, PAD_TOKEN
from repro.core.engine import PreparedCollection, as_prepared
from repro.core.join import JoinStats, _bucket_capacity
from repro.kernels import ops as kops

_INT32_MAX = np.int32(np.iinfo(np.int32).max)
# Auto-sized chunk buffers are capped here; a chunk whose (exact, host
# int64) expansion count exceeds it escalates to the dense fallback instead
# of allocating buffers the device cannot hold (or wrapping int32 on
# device).  The fused chunk step needs about 1 KiB of device memory per
# slot (v5e compile, 224-token rows: 1.1 GB of temporaries at 2^20 slots),
# so 2^22 slots keep it near 4 GiB of a 16 GB chip.
_MAX_AUTO_CAPACITY = 1 << 22


def _windowed_ranges(vocab, vocab_tid, post_key, probe_tokens, probe_prefix,
                     lo_r, hi_r, lp: int, scale: int):
    """Vocab lookup + window-narrowed CSR ranges per (probe, prefix pos).

    One vectorized ``searchsorted`` against the composite non-decreasing
    ``post_key`` = ``tid * scale + length`` bounds each lookup to postings
    whose set length falls inside the probe's admissible window — the
    device analogue of the CPU algorithms' sorted-list early-outs, and what
    keeps expansion volume near the candidate count on skewed data.

    Returns ``(range_start, count)``, both int32[C, lp] (count 0 where the
    prefix position is invalid or the token is unknown).
    """
    ptoks = probe_tokens[:, :lp]
    j = jnp.clip(jnp.searchsorted(vocab, ptoks), 0, vocab.shape[0] - 1)
    found = vocab[j] == ptoks
    tid = jnp.where(found, vocab_tid[j], 0)
    evalid = found & (jnp.arange(lp)[None, :] < probe_prefix[:, None])
    base = tid * scale
    lo_c = jnp.clip(lo_r, 0, scale - 1)[:, None]
    hi_c = jnp.clip(hi_r, 0, scale - 1)[:, None]
    a = jnp.searchsorted(post_key, base + lo_c, side="left")
    b = jnp.searchsorted(post_key, base + hi_c, side="right")
    cnt = jnp.where(evalid, jnp.maximum(b - a, 0), 0)
    return a.astype(jnp.int32), cnt.astype(jnp.int32)


def _expansion_count_host(post, tokens_np, ps_np, lo_np, hi_np,
                          lp: int, scale: int) -> int:
    """Count prepass on host numpy (int64-exact): total window-surviving
    postings entries this probe chunk expands to.

    Runs the same vocab lookup + composite-key narrowing as the device
    step, but in host int64 — the count both sizes the fused step's
    capacity and guards it: a pathological chunk (hot token × huge window)
    whose expansion would wrap int32 or exhaust device memory is detected
    *before* anything is allocated and escalated to the dense fallback.
    (``scale`` is implied by ``post``; kept for call-site symmetry with the
    device step.)
    """
    from repro.index.postings import lookup_counts_host

    cnt, _tid, valid = lookup_counts_host(
        post, tokens_np, ps_np, lo_np, hi_np, lp)
    return int(cnt[valid].sum())


def expand_and_filter(
    post_set, post_pos, post_len, post_key, vocab, vocab_tid,
    probe_tokens, probe_lengths, probe_prefix, lo_r, hi_r, s0,
    *, sim: str, tau: float, cap: int, lp: int, scale: int, self_join: bool,
    impl: str,
):
    """Traced stage 1: CSR expansion + per-entry admission filters over one
    postings *view* — the full index, or one token slab of a
    :class:`~repro.index.postings.ShardedPostings` (the arrays are
    interchangeable: slab tails carry sentinel keys, so the same windowed
    ``searchsorted`` sees count 0 for tokens the view does not own).

    Returns ``(rr, ss, n_expanded)``: sentinel-keyed entry streams (pruned
    slots hold ``_INT32_MAX``) ready for :func:`dedup_pairs`, plus the exact
    expansion count of this view.
    """
    with jax.named_scope(tracing.EXPAND):
        c = probe_tokens.shape[0]

        # -- expand: window-narrowed CSR lookups per (probe, prefix pos) --
        rng_start, cnt2d = _windowed_ranges(
            vocab, vocab_tid, post_key, probe_tokens, probe_prefix, lo_r,
            hi_r, lp, scale)
        rng_flat = rng_start.reshape(-1)
        cnt = cnt2d.reshape(-1)
        seg_end = jnp.cumsum(cnt)
        n_expanded = seg_end[-1]

        g = jnp.arange(cap, dtype=jnp.int32)
        k = jnp.clip(jnp.searchsorted(seg_end, g, side="right"), 0,
                     c * lp - 1)
        in_range = g < n_expanded
        within = g - (seg_end[k] - cnt[k])
        pidx = jnp.clip(rng_flat[k] + within, 0, post_set.shape[0] - 1)
        r_idx = post_set[pidx]
        s_loc = (k // lp).astype(jnp.int32)

        # -- filter: length window + positional bound + triangle --------
        keep = kops.entry_filter(
            post_len[pidx], post_pos[pidx],
            probe_lengths[s_loc], (k % lp).astype(jnp.int32),
            lo_r[s_loc], hi_r[s_loc],
            r_idx, s0 + s_loc, in_range,
            sim=sim, tau=tau, self_join=self_join, impl=impl)

        rr = jnp.where(keep, r_idx, _INT32_MAX)
        ss = jnp.where(keep, s_loc, _INT32_MAX)
    return rr, ss, n_expanded


def dedup_pairs(rr, ss, cap: int):
    """Traced stage 2: lexsort sentinel-keyed ``(probe, set)`` entries, keep
    uniques, compact into a ``cap``-slot buffer.

    ``rr`` / ``ss`` may be any length (a chunk's entry stream, or shard
    buffers gathered across the mesh); pruned/padding slots must hold
    ``_INT32_MAX``.  Returns ``(cand_r, cand_s, n_generated)`` with slots
    ``>= n_generated`` holding ``_INT32_MAX`` again — the output composes
    with itself, which is exactly how the sharded driver re-deduplicates
    the allgathered per-shard buffers.
    """
    with jax.named_scope(tracing.DEDUP):
        # (two int32 sort keys rather than one fused int64 key: x64 stays off)
        # s major, r minor; pruned slots sort last
        order = jnp.lexsort((rr, ss))
        sr = rr[order]
        s2 = ss[order]
        uniq = (s2 != _INT32_MAX) & jnp.concatenate(
            [jnp.ones((1,), dtype=bool),
             (s2[1:] != s2[:-1]) | (sr[1:] != sr[:-1])])
        n_generated = jnp.sum(uniq, dtype=jnp.int32)
        ui = jnp.nonzero(uniq, size=cap, fill_value=0)[0]
        slot_ok = jnp.arange(cap) < n_generated
        cand_r = jnp.where(slot_ok, sr[ui], _INT32_MAX)
        cand_s = jnp.where(slot_ok, s2[ui], _INT32_MAX)
    return cand_r, cand_s, n_generated


def verdict_and_verify(
    tokens_r, lengths_r, words_r, probe_tokens, probe_lengths, probe_words,
    cand_r, cand_s, slot_ok, need_tab, s0,
    *, sim: str, tau: float, cutoff: int, impl: str,
    return_masks: bool = False,
):
    """Traced stage 3: pairwise bitmap verdict → exact overlap verification
    → verified-only compaction, over a compacted candidate buffer (a whole
    chunk's, or one device's slice of the globally deduped list).

    Returns ``(pairs, n_bitmap, n_verified)``; pair slots ``>= n_verified``
    are garbage.  ``return_masks=True`` additionally returns the per-slot
    bitmap-survivor and verified masks (``bool[cap]`` each) — the serving
    layer (:mod:`repro.serve`) segment-sums them per probe row to recover
    per-request funnel counters from a coalesced batch.
    """
    cap = cand_r.shape[0]
    with jax.named_scope(tracing.VERDICT):
        safe_r = jnp.where(slot_ok, cand_r, 0)
        safe_s = jnp.where(slot_ok, cand_s, 0)
        bm_pass = kops.pair_verdict(
            words_r[safe_r], probe_words[safe_s],
            lengths_r[safe_r], probe_lengths[safe_s],
            sim=sim, tau=tau, cutoff=cutoff, impl=impl)
        cand_mask = slot_ok & bm_pass
        n_bitmap = jnp.sum(cand_mask, dtype=jnp.int32)
    with jax.named_scope(tracing.VERIFY):
        o = verify.gathered_overlap(tokens_r, safe_r, probe_tokens, safe_s,
                                    cand_mask)
        # Integer-exact acceptance (min_overlap_table) — identical to the
        # f64 oracle; f32 thresholds are prune-only in this driver too.
        need = bounds.min_overlap_gather(
            sim, need_tab, lengths_r[safe_r], probe_lengths[safe_s])
        ok = cand_mask & (o >= need)
        n_verified = jnp.sum(ok, dtype=jnp.int32)
    with jax.named_scope(tracing.COMPACT):
        vi = jnp.nonzero(ok, size=cap, fill_value=0)[0]
        pairs = jnp.stack([safe_r[vi], safe_s[vi] + s0], axis=1)
    if return_masks:
        return pairs, n_bitmap, n_verified, cand_mask, ok
    return pairs, n_bitmap, n_verified


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "cap", "lp", "scale", "self_join",
                     "cutoff", "impl"),
)
def _indexed_chunk_step(
    tokens_r, lengths_r, words_r,
    vocab, vocab_tid, post_set, post_pos, post_len, post_key,
    probe_tokens, probe_lengths, probe_words, probe_prefix, lo_r, hi_r,
    need_tab, s0,
    *, sim: str, tau: float, cap: int, lp: int, scale: int, self_join: bool,
    cutoff: int, impl: str,
):
    """One fused candidate-generation + verification step for a probe chunk:
    the three traced stages (:func:`expand_and_filter` → :func:`dedup_pairs`
    → :func:`verdict_and_verify`) composed under a single jit.  The sharded
    driver (:mod:`repro.distributed.sharded_index`) composes the *same*
    stages per shard inside ``shard_map`` — one code path, two meshes.

    Expansion, entry filters, sort-dedup, pairwise bitmap verdict and exact
    verification all stay on device; the host receives the compacted
    ``(cap, 2)`` verified-pair buffer plus four scalars.

    Returns ``(pairs, n_expanded, n_generated, n_bitmap, n_verified)``:
    pairs are ``(r_sorted, s_sorted)`` ids (slots ``>= n_verified`` are
    garbage); ``n_expanded > cap`` means the entry stream was truncated and
    the caller must escalate this chunk (it pre-checks via the count
    prepass, so this only happens under an explicitly forced capacity).
    """
    rr, ss, n_expanded = expand_and_filter(
        post_set, post_pos, post_len, post_key, vocab, vocab_tid,
        probe_tokens, probe_lengths, probe_prefix, lo_r, hi_r, s0,
        sim=sim, tau=tau, cap=cap, lp=lp, scale=scale, self_join=self_join,
        impl=impl)
    cand_r, cand_s, n_generated = dedup_pairs(rr, ss, cap)
    with jax.named_scope(tracing.DEDUP):
        slot_ok = jnp.arange(cap) < n_generated
    pairs, n_bitmap, n_verified = verdict_and_verify(
        tokens_r, lengths_r, words_r, probe_tokens, probe_lengths,
        probe_words, cand_r, cand_s, slot_ok, need_tab, s0,
        sim=sim, tau=tau, cutoff=cutoff, impl=impl)
    return pairs, n_expanded, n_generated, n_bitmap, n_verified


def chunk_step_spec(
    prep_r: "PreparedCollection",
    prep_s: "PreparedCollection | None" = None,
    *,
    sim: str = JACCARD,
    tau: float = 0.8,
    b: int = 128,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    ell: int = 1,
    probe_block: int = 4096,
    impl: str = "auto",
    use_cutoff: bool = True,
):
    """Concrete ``(args, statics)`` for one fused chunk step over the first
    probe chunk — exactly what :func:`indexed_join_prepared` dispatches, but
    reified so callers can ``_indexed_chunk_step.lower(*args, **statics)``
    (roofline/HLO analysis in ``benchmarks/bench_kernels.py``) or time the
    compiled step in isolation.

    Raises ``ValueError`` for a degenerate spec (empty index or zero prefix
    lengths) where the driver would never dispatch the step at all.
    """
    self_join = prep_s is None
    if self_join:
        prep_s = prep_r
    chosen = bm.choose_method(tau, b) if method == BITMAP_COMBINED else method
    cutoff = (expected.cutoff_point(chosen, b, float(tau)) if use_cutoff
              else 1 << 30)
    post = prep_r.postings(sim, tau, ell)
    ps_np, lp = probe_prefix_lengths(prep_s, sim, tau)
    if post.num_postings == 0 or lp == 0:
        raise ValueError("degenerate chunk spec: empty index or prefixes")
    tokens_r, lengths_r = prep_r.device_arrays()
    words_r = prep_r.bitmap_words(b, chosen, mix=mix)
    tokens_s, lengths_s = prep_s.device_arrays()
    words_s = prep_s.bitmap_words(b, chosen, mix=mix)
    lo_np, hi_np, lo_d, hi_d = prep_s.length_window_int(sim, tau)
    csr = post.device_arrays()
    scale = post.max_len + 1
    need_tab = verify.min_overlap_table_dev(
        sim, float(tau), prep_r.max_len, prep_s.max_len)
    cb = min(int(probe_block), prep_s.num_sets)
    n_exp = _expansion_count_host(
        post, prep_s.tokens[:cb], ps_np[:cb], lo_np[:cb], hi_np[:cb],
        lp, scale)
    cap = min(_bucket_capacity(max(n_exp, 1)), prep_r.num_sets * cb * lp)
    ps_d = jnp.asarray(ps_np)
    args = (
        tokens_r, lengths_r, words_r, *csr,
        _pad_chunk(tokens_s[:cb], cb, PAD_TOKEN),
        _pad_chunk(lengths_s[:cb], cb, 0),
        _pad_chunk(words_s[:cb], cb, 0),
        _pad_chunk(ps_d[:cb], cb, 0),
        _pad_chunk(lo_d[:cb], cb, 0), _pad_chunk(hi_d[:cb], cb, 0),
        need_tab, jnp.int32(0),
    )
    statics = dict(sim=sim, tau=float(tau), cap=cap, lp=lp, scale=scale,
                   self_join=self_join, cutoff=int(cutoff), impl=impl)
    return args, statics


@functools.partial(
    jax.jit, static_argnames=("sim", "tau", "cutoff", "impl", "self_join"))
def _fallback_mask(words_r, lengths_r, words_c, lengths_c, lo_c, hi_c, s0,
                   *, sim, tau, cutoff, impl, self_join):
    """Grid verdict ∩ length window over R × chunk, on device ->
    ``(mask bool[NR, C], window cells per R row, R rows with a candidate)``."""
    with jax.named_scope(tracing.VERDICT):
        cand = kops.candidate_matrix(
            words_r, words_c, lengths_r, lengths_c, sim=sim, tau=tau,
            self_join=False, cutoff=cutoff, impl=impl)
    with jax.named_scope(tracing.WINDOW):
        lr = lengths_r[:, None]
        win = ((lr >= lo_c[None, :]) & (lr <= hi_c[None, :])
               & (lr > 0) & (lengths_c[None, :] > 0))
        if self_join:
            win &= (jnp.arange(lr.shape[0])[:, None]
                    < (s0 + jnp.arange(lengths_c.shape[0]))[None, :])
        mask = cand & win
        return (mask, jnp.sum(win, axis=1, dtype=jnp.int32),
                jnp.any(mask, axis=1))


def _dense_chunk_fallback(tokens_r, lengths_r, words_r, tokens_c, lengths_c,
                          words_c, lo_c, hi_c, s0, *, sim, tau, cutoff, impl,
                          self_join):
    """Dense escalation for a probe chunk whose expansion overflowed a
    forced capacity: grid verdict over R × chunk on device, then only the
    R rows holding a candidate cross to the host for compaction, and
    batched exact verification (the blocked driver's classic route).

    Returns ``(n_window_cells, n_bitmap, verified sorted-space pairs)``.
    """
    mask, win_rows, any_rows = _fallback_mask(
        words_r, lengths_r, words_c, lengths_c, jnp.asarray(lo_c),
        jnp.asarray(hi_c), jnp.int32(s0), sim=sim, tau=float(tau),
        cutoff=int(cutoff), impl=impl, self_join=self_join)
    n_win = int(np.asarray(win_rows).sum(dtype=np.int64))
    rows = np.flatnonzero(np.asarray(any_rows))
    if len(rows) == 0:
        return n_win, 0, np.zeros((0, 2), dtype=np.int64)
    # Row and pair counts are padded to power-of-two buckets so the gather
    # and the verification compile once per bucket, not once per count.
    sub = np.asarray(jnp.take(mask, jnp.asarray(_pad_pow2(rows)), axis=0))
    ii, jj = np.nonzero(sub[:len(rows)])
    ii = rows[ii]
    ok = np.asarray(verify.verify_pairs_rs(
        tokens_r, lengths_r, tokens_c, lengths_c,
        jnp.asarray(_pad_pow2(ii)), jnp.asarray(_pad_pow2(jj)),
        sim, float(tau)))[:len(ii)]
    pairs = np.stack([ii[ok], jj[ok] + s0], axis=1).astype(np.int64)
    return n_win, len(ii), pairs


def _pad_pow2(a: np.ndarray) -> np.ndarray:
    """``a`` padded with its first element to a power-of-two length."""
    return np.pad(a, (0, _bucket_capacity(len(a), floor=1) - len(a)),
                  mode="edge")


def _pad_chunk(a, rows: int, fill):
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def probe_prefix_lengths(prep_s, sim: str, tau: float):
    """1-prefix schema lengths per probe row -> ``(ps_np int32[N], lp)``.

    Probe prefixes use the 1-prefix schema regardless of the index's ℓ (an
    ℓ-prefix index is a superset of the 1-prefix one, so matches are only
    ever added, never lost).  Shared by the single-device and sharded
    drivers so both expand the identical lookup set.
    """
    ns = prep_s.num_sets
    ps_np = np.zeros(ns, dtype=np.int32)
    nz = prep_s.lengths > 0
    if nz.any():
        ps_np[nz] = bounds.prefix_length(
            sim, tau, prep_s.lengths[nz].astype(np.int64)).astype(np.int32)
    return ps_np, int(ps_np.max(initial=0))


def finish_pairs(prep_r, prep_s, self_join: bool, pairs_list) -> np.ndarray:
    """Concatenate sorted-space chunk pair buffers, remap through the
    prepared orders to *original* indices, canonicalize (i < j for a
    self-join) and lexsort — every index-driven driver's epilogue."""
    if pairs_list:
        pairs = np.concatenate(pairs_list, axis=0)
        gi = prep_r.order[pairs[:, 0]]
        gj = prep_s.order[pairs[:, 1]]
        if self_join:
            pairs = np.stack([np.minimum(gi, gj), np.maximum(gi, gj)],
                             axis=1)
        else:
            pairs = np.stack([gi, gj], axis=1)
        pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
        return pairs.astype(np.int64)
    return np.zeros((0, 2), dtype=np.int64)


def indexed_join_prepared(
    prep_r: PreparedCollection,
    prep_s: PreparedCollection | None = None,
    *,
    sim: str = JACCARD,
    tau: float = 0.8,
    b: int = 128,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    ell: int = 1,
    probe_block: int = 4096,
    impl: str = "auto",
    use_cutoff: bool = True,
    capacity: int | None = None,
    return_stats: bool = False,
):
    """Index-driven exact join over prepared inputs.

    The ℓ-prefix CSR postings index is built over R (cached on ``prep_r``
    per ``(sim, tau, ell)``); S streams through in ``probe_block``-sized
    chunks.  Self-join ONLY when ``prep_s`` is omitted (same contract as
    the other prepared drivers: explicitly passing the same object as both
    operands is a full R×S cross product including the diagonal).

    ``capacity=None`` (default) sizes each chunk's buffer from the count
    prepass, so nothing ever overflows; an explicit capacity bounds device
    memory and escalates overflowing chunks to a dense fallback
    (``JoinStats.overflow_blocks``), preserving exactness.

    Returns lexicographically sorted ``int64[K, 2]`` pairs in *original*
    indices — ``(i, j)`` with ``i < j`` for a self-join, ``(r_index,
    s_index)`` otherwise — exactly :func:`repro.core.join.naive_join`'s
    pair set (property-tested).  With ``return_stats=True`` also returns
    the candidate-funnel :class:`~repro.core.join.JoinStats`.
    """
    self_join = prep_s is None
    if self_join:
        prep_s = prep_r
    chosen = bm.choose_method(tau, b) if method == BITMAP_COMBINED else method
    cutoff = (expected.cutoff_point(chosen, b, float(tau)) if use_cutoff
              else 1 << 30)
    nr, ns = prep_r.num_sets, prep_s.num_sets
    stats = JoinStats()

    def _finish(pairs_list):
        with tracing.span("join.finish"):
            pairs = finish_pairs(prep_r, prep_s, self_join, pairs_list)
        return (pairs, stats) if return_stats else pairs

    post = prep_r.postings(sim, tau, ell)
    ps_np, lp = probe_prefix_lengths(prep_s, sim, tau)
    if nr == 0 or ns == 0 or post.num_postings == 0 or lp == 0:
        return _finish([])

    tokens_r, lengths_r = prep_r.device_arrays()
    words_r = prep_r.bitmap_words(b, chosen, mix=mix)
    if self_join:
        tokens_s, lengths_s, words_s = tokens_r, lengths_r, words_r
    else:
        tokens_s, lengths_s = prep_s.device_arrays()
        words_s = prep_s.bitmap_words(b, chosen, mix=mix)
    # Admissible |r| window per probe row (cached per (sim, tau) on S).
    lo_np, hi_np, lo_d, hi_d = prep_s.length_window_int(sim, tau)
    ps_d = jnp.asarray(ps_np)
    csr = post.device_arrays()
    scale = post.max_len + 1
    need_tab = verify.min_overlap_table_dev(
        sim, float(tau), prep_r.max_len, prep_s.max_len)

    cb = int(probe_block)
    pairs_out: list[np.ndarray] = []
    for c0 in range(0, ns, cb):
        c1 = min(c0 + cb, ns)
        stats.blocks_total += 1
        with tracing.span("join.count"):
            n_exp = _expansion_count_host(
                post, prep_s.tokens[c0:c1], ps_np[c0:c1],
                lo_np[c0:c1], hi_np[c0:c1], lp, scale)
        stats.postings_expanded += n_exp
        if n_exp == 0:
            stats.blocks_skipped += 1
            continue
        if capacity is None:
            cap = min(_bucket_capacity(n_exp), nr * (c1 - c0) * lp)
        else:
            cap = int(capacity)
        if n_exp > cap or n_exp > _MAX_AUTO_CAPACITY:
            # The entry stream would truncate (forced capacity) or the
            # auto-sized buffer would be unreasonably large (pathological
            # hot-token chunk) — escalate the whole chunk to the dense
            # grid fallback.
            stats.overflow_blocks += 1
            with tracing.span("join.fallback"):
                n_win, n_bm, vpairs = _dense_chunk_fallback(
                    tokens_r, lengths_r, words_r,
                    tokens_s[c0:c1], lengths_s[c0:c1], words_s[c0:c1],
                    np.asarray(lo_d[c0:c1]), np.asarray(hi_d[c0:c1]), c0,
                    sim=sim, tau=tau, cutoff=cutoff, impl=impl,
                    self_join=self_join)
            stats.total_pairs += n_win
            stats.candidates_generated += n_win
            stats.candidates += n_bm
            stats.verified_true += len(vpairs)
            if len(vpairs):
                pairs_out.append(vpairs)
            continue
        with tracing.span("join.dispatch"):
            pairs_d, _, n_gen, n_bm, n_ok = _indexed_chunk_step(
                tokens_r, lengths_r, words_r, *csr,
                _pad_chunk(tokens_s[c0:c1], cb, PAD_TOKEN),
                _pad_chunk(lengths_s[c0:c1], cb, 0),
                _pad_chunk(words_s[c0:c1], cb, 0),
                _pad_chunk(ps_d[c0:c1], cb, 0),
                _pad_chunk(lo_d[c0:c1], cb, 0),
                _pad_chunk(hi_d[c0:c1], cb, 0),
                need_tab, jnp.int32(c0),
                sim=sim, tau=float(tau), cap=cap, lp=lp, scale=scale,
                self_join=self_join, cutoff=int(cutoff), impl=impl)
        stats.slots += cap
        with tracing.span("join.sync"):
            n_gen, n_bm, k = int(n_gen), int(n_bm), int(n_ok)
        stats.total_pairs += n_gen
        stats.candidates_generated += n_gen
        stats.candidates += n_bm
        stats.verified_true += k
        if k:
            with tracing.span("join.fetch"):
                pairs_out.append(np.asarray(pairs_d)[:k].astype(np.int64))

    return _finish(pairs_out)


def indexed_bitmap_join(
    col_r: Collection | PreparedCollection,
    col_s: Collection | PreparedCollection | str | None = None,
    sim: str = JACCARD,
    tau: float = 0.8,
    **kwargs,
):
    """Collection-level wrapper of :func:`indexed_join_prepared` (the
    ``blocked_bitmap_join`` calling convention: ``(col, sim, tau)`` for a
    self-join, ``(col_r, col_s, sim, tau)`` for R×S; plain collections are
    prepared on the spot, prepared ones reuse their caches)."""
    col_s, sim, tau = split_join_args(col_s, sim, tau)
    return indexed_join_prepared(
        as_prepared(col_r), None if col_s is None else as_prepared(col_s),
        sim=sim, tau=tau, **kwargs)
