"""Exact set-similarity join drivers.

Three tiers, mirroring the paper's structure:

* :func:`naive_join` — Algorithm 1, the O(|R|·|S|) oracle (tests/small inputs).
* :func:`blocked_bitmap_join` — the TPU adaptation of the paper's GPU
  Algorithm 8: length-sorted collection, block-level length-filter early-out,
  fused bitmap-filter tiles (Pallas), candidate compaction (on host, or fully
  device-resident with ``compaction="device"``), batched exact verification
  on device. Host drives the block loop (like the GPU host code drives
  kernel launches).
* :func:`ring_join_sharded` / :func:`ring_join` — multi-device version: R is
  sharded over the mesh's batch axes, S blocks circulate via
  ``collective_permute``; each ring step runs the same fused filter +
  fixed-capacity compaction + verification locally.  ``ring_join`` is the
  exactness-preserving driver: it densely re-runs any (device, step) tile
  whose candidate list overflowed.  Used by the dedup pipeline and the
  dry-run.

A fourth driver family lives in :mod:`repro.index`:
``indexed_bitmap_join`` / ``indexed_join_prepared`` generate candidates
from a CSR ℓ-prefix inverted index instead of walking the grid — the only
driver whose work scales with candidate count rather than |R|·|S|.

Every driver accepts plain :class:`~repro.core.collection.Collection` inputs
(prepared internally — the historical one-shot shape) or build-once
:class:`~repro.core.engine.PreparedCollection` artifacts whose cached length
sort, bitmap words and length windows are reused across calls (the serving
shape; see :mod:`repro.core.engine`).

Every driver supports both the paper's general two-collection R×S join and
the optimized self-join special case.  Self-join is selected by omitting the
second collection: ``naive_join(col, sim, tau)`` (the seed calling convention
still works positionally); R×S by passing it: ``naive_join(col_r, col_s, sim,
tau)``.  Self-joins return pairs ``(i, j)`` with ``i < j``; R×S joins return
``(r_index, s_index)`` pairs over the two collections' original indices.

All joins return *exactly* the same pair set as the oracle (property-tested);
the bitmap filter only ever removes pairs that verification would reject.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import bitmap as bm
from repro.core import bounds, expected, verify
from repro.core.collection import Collection, split_join_args
from repro.core.constants import BITMAP_COMBINED, JACCARD, PAD_TOKEN
from repro.core.engine import PreparedCollection, as_prepared
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Oracle
# ---------------------------------------------------------------------------

_normalize_rs_args = split_join_args


def naive_join(col_r: Collection, col_s: Collection | str | None = None,
               sim: str = JACCARD, tau: float = 0.8) -> np.ndarray:
    """Algorithm 1: all verified pairs as int64[K, 2].

    Self-join (``col_s`` omitted) returns pairs with i < j; R×S returns
    (r_index, s_index) over the full cross product.
    """
    col_s, sim, tau = _normalize_rs_args(col_s, sim, tau)
    if isinstance(col_r, PreparedCollection):
        col_r = col_r.source
    if isinstance(col_s, PreparedCollection):
        col_s = col_s.source
    self_join = col_s is None
    if self_join:
        col_s = col_r
    o = _overlap_matrix(jnp.asarray(col_r.tokens), jnp.asarray(col_s.tokens))
    len_r = np.asarray(col_r.lengths)
    len_s = np.asarray(col_s.lengths)
    need = bounds.equivalent_overlap(sim, tau, len_r[:, None], len_s[None, :])
    simmat = np.asarray(o) >= need
    # Empty sets (padding) are never similar to anything — the vacuous
    # 0 >= 0 case for normalised similarities is excluded, matching the
    # paper's definition over non-empty sets.
    simmat &= (len_r > 0)[:, None] & (len_s > 0)[None, :]
    if self_join:
        iu = np.triu_indices(col_r.num_sets, k=1)
        mask = simmat[iu]
        return np.stack([iu[0][mask], iu[1][mask]], axis=1).astype(np.int64)
    ii, jj = np.nonzero(simmat)
    return np.stack([ii, jj], axis=1).astype(np.int64)


@jax.jit
def _overlap_matrix(tokens_r: jnp.ndarray, tokens_s: jnp.ndarray) -> jnp.ndarray:
    """int32[NR, NS] overlaps of every row pair — the oracle's own merge by
    ``searchsorted``, independent of the drivers' gathered verification."""
    def overlap(tok_r, tok_s):
        idx = jnp.clip(jnp.searchsorted(tok_s, tok_r), 0, tok_s.shape[0] - 1)
        hit = (tok_s[idx] == tok_r) & (tok_r != PAD_TOKEN)
        return jnp.sum(hit.astype(jnp.int32))

    def row_vs_all(row):
        return jax.vmap(lambda s: overlap(row, s))(tokens_s)

    return jax.vmap(row_vs_all)(tokens_r)


# ---------------------------------------------------------------------------
# Blocked device join (Algorithm 8, TPU-native)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JoinStats:
    """Observability counters (paper Tables 9-10 are derived from these).

    ``total_pairs`` is always the number of cells the bitmap filter's
    verdict was actually *consumed* on: window-surviving grid cells for the
    grid drivers (``naive``/``blocked``/``ring``), index-generated deduped
    candidates for the ``indexed`` driver — so ``filter_ratio`` measures the
    bitmap's pruning over its real input for every driver.  The candidate
    funnel is reported explicitly by ``candidates_generated`` (==
    ``total_pairs``) → ``candidates`` (after the bitmap) →
    ``verified_true``; ``postings_expanded`` additionally records the
    indexed driver's pre-dedup postings volume (0 for grid drivers).
    """

    total_pairs: int = 0          # pairs the bitmap verdict is consumed on
    blocks_total: int = 0         # block pairs / probe chunks walked
    blocks_skipped: int = 0       # pruned by the length filter / empty chunks
    candidates: int = 0           # pairs surviving the bitmap filter
    verified_true: int = 0        # final result size
    overflow_blocks: int = 0      # tiles/chunks escalated to the dense path
    candidates_generated: int = 0  # pre-bitmap candidate pairs (the funnel top)
    postings_expanded: int = 0    # indexed driver: pre-dedup postings entries
    # Execution cost, not part of the funnel: the summed static capacity
    # ``cap`` of the device steps dispatched (the indexed driver's entry
    # stream, the blocked driver's compaction buffer).  It differs between
    # drivers and modes that share a funnel, so it is left out of equality
    # and of ``to_dict``.
    slots: int = dataclasses.field(default=0, compare=False)

    @property
    def filter_ratio(self) -> float:
        """Fraction of length-surviving pairs pruned by the bitmap filter."""
        if self.total_pairs == 0:
            return 0.0
        return 1.0 - self.candidates / self.total_pairs

    @property
    def precision(self) -> float:
        """true positives / unfiltered (Section 5.1.3)."""
        if self.candidates == 0:
            return 1.0
        return self.verified_true / self.candidates

    def to_dict(self) -> dict:
        """Counters + derived ratios as plain JSON-able types (benchmarks
        emit these so filter-ratio/perf trajectories can be diffed)."""
        d = dataclasses.asdict(self)
        del d["slots"]
        d["filter_ratio"] = self.filter_ratio
        d["precision"] = self.precision
        return d


def _bucket_capacity(n: int, floor: int = 128) -> int:
    """Round a measured candidate count up to a power of two (>= floor).

    The compaction capacity is a static (compile-time) size; bucketing keeps
    the number of distinct jit variants logarithmic in the observed counts.
    """
    return max(floor, 1 << max(int(n) - 1, 0).bit_length())


@functools.partial(
    jax.jit,
    static_argnames=("sim", "tau", "cap", "diag", "cutoff", "impl", "use_bitmap"),
)
def _resident_block_step(
    tokens_r, lengths_r, words_r, tokens_s, lengths_s, words_s,
    lo_s, hi_s, need_tab, r0, s0,
    *, sim: str, tau: float, cap: int, diag: bool, cutoff: int, impl: str,
    use_bitmap: bool = True,
):
    """One fused, fully device-resident block-pair step (Algorithm 8's local
    candidate list, TPU-shaped).

    Bitmap verdict -> integer length-window mask -> fixed-capacity compaction
    (``jnp.nonzero(size=cap)``) -> exact searchsorted verification -> second
    compaction down to verified pairs, all inside one jit.  Only the
    ``(cap, 2)`` compacted pair buffer and five scalars are ever transferred
    to the host; the dense ``(TR, TS)`` verdict tile never leaves the device.

    Returns ``(pairs, n_win, n_cand, n_ok, overflow)``: global sorted-index
    pairs (slots ``>= n_ok`` are garbage), window-pair / candidate / verified
    counts, and whether the candidate count exceeded ``cap`` (the caller then
    escalates this block pair to the dense host-compaction path).
    """
    with jax.named_scope(tracing.WINDOW):
        win = ((lengths_s[None, :] >= lo_s[:, None])
               & (lengths_s[None, :] <= hi_s[:, None])
               & (lengths_r[:, None] > 0) & (lengths_s[None, :] > 0))
        if diag:
            win &= (jnp.arange(win.shape[0])[:, None]
                    < jnp.arange(win.shape[1])[None, :])
        n_win = jnp.sum(win, dtype=jnp.int32)
    with jax.named_scope(tracing.VERDICT):
        if use_bitmap:
            cand = kops.candidate_matrix(
                words_r, words_s, lengths_r, lengths_s, sim=sim, tau=tau,
                self_join=False, cutoff=cutoff, impl=impl) & win
        else:
            cand = win
        n_cand = jnp.sum(cand, dtype=jnp.int32)
    with jax.named_scope(tracing.COMPACT):
        ii, jj = jnp.nonzero(cand, size=cap, fill_value=0)
        slot_ok = jnp.arange(cap) < n_cand
    with jax.named_scope(tracing.VERIFY):
        o = verify.gathered_overlap(tokens_r, ii, tokens_s, jj, slot_ok)
        # Integer-exact acceptance (min_overlap_table): bit-identical to the
        # f64 oracle — f32 thresholds may only ever *prune*, never accept.
        need = bounds.min_overlap_gather(sim, need_tab, lengths_r[ii],
                                         lengths_s[jj])
        ok = slot_ok & (o >= need)
        n_ok = jnp.sum(ok, dtype=jnp.int32)
    with jax.named_scope(tracing.COMPACT):
        vi = jnp.nonzero(ok, size=cap, fill_value=0)[0]
        pairs = jnp.stack([ii[vi].astype(jnp.int32) + r0,
                           jj[vi].astype(jnp.int32) + s0], axis=1)
        return pairs, n_win, n_cand, n_ok, n_cand > cap


def _dense_block_verify(
    tokens_r, lengths_r, words_r, tokens_s, lengths_s, words_s,
    np_len_r, np_len_s, r0, r1, s0, s1,
    *, sim, tau, cutoff, impl, diag, self_join, use_bitmap=True,
):
    """Host-compaction path for one block pair: dense mask -> ``np.nonzero``
    on host -> batched exact verification.  The classic route, and the dense
    escalation target when a device-resident tile overflows its capacity.

    Returns ``(n_win, n_cand, verified sorted-index pairs int64[K, 2])``.
    """
    win = _window_pair_mask(np_len_r[r0:r1], np_len_s[s0:s1], sim, tau)
    if diag:
        win = np.triu(win, k=1)
    if use_bitmap:
        cand = kops.candidate_matrix(
            words_r[r0:r1], words_s[s0:s1],
            lengths_r[r0:r1], lengths_s[s0:s1],
            sim=sim, tau=float(tau), self_join=False,
            cutoff=int(cutoff), impl=impl)
        # The fused kernel does not apply the length filter; without this
        # intersection `candidates` could exceed `total_pairs` and
        # filter_ratio could go negative.
        cand = np.asarray(cand) & win
    else:
        cand = win
    n_win = int(win.sum())
    ii, jj = np.nonzero(cand)
    if len(ii) == 0:
        return n_win, 0, np.zeros((0, 2), dtype=np.int64)
    gi = jnp.asarray(ii + r0)
    gj = jnp.asarray(jj + s0)
    if self_join:
        ok = np.asarray(verify.verify_pairs(
            tokens_r, lengths_r, gi, gj, sim, float(tau)))
    else:
        ok = np.asarray(verify.verify_pairs_rs(
            tokens_r, lengths_r, tokens_s, lengths_s, gi, gj,
            sim, float(tau)))
    pairs = np.stack([np.asarray(gi)[ok], np.asarray(gj)[ok]], axis=1)
    return n_win, len(ii), pairs.astype(np.int64)


def blocked_bitmap_join(
    col_r: Collection | PreparedCollection,
    col_s: Collection | PreparedCollection | str | None = None,
    sim: str = JACCARD,
    tau: float = 0.8,
    *,
    b: int = 128,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    block: int = 4096,
    impl: str = "auto",
    use_cutoff: bool = True,
    use_bitmap: bool = True,
    compaction: str = "host",
    capacity: int | None = None,
    return_stats: bool = False,
):
    """Exact join; returns int64[K, 2] pairs in original indices.

    Thin wrapper over :func:`blocked_bitmap_join_prepared`: plain
    ``Collection`` inputs are prepared on the spot (one-shot call, today's
    behaviour bit-for-bit), :class:`~repro.core.engine.PreparedCollection`
    inputs reuse their cached length sort / bitmap words / length windows
    across calls (the serving shape — see ``repro.core.engine.JoinEngine``).

    The driver walks block pairs of the length-sorted collections — the full
    R×S grid for two collections, the upper triangle for a self-join. Because
    blocks are length-contiguous, the Table 2 length window prunes whole block
    pairs in both directions (the TPU analogue of the paper's sorted
    inverted-list early termination).

    Surviving block pairs run one of two compaction modes:

    * ``compaction="host"`` — the original path: the fused bitmap kernel's
      dense bool tile is shipped to the host, ``np.nonzero`` compacts it
      there, and the candidate indices round-trip back for verification.
    * ``compaction="device"`` — the resident path (the paper's Algorithm 8
      local candidate lists): a tile-count prepass (`kops.count_candidates`)
      measures the real candidate count, a power-of-two capacity is sized
      from it, and one jit'd step fuses verdict -> length-window mask ->
      fixed-capacity compaction -> exact verification, so only compacted
      ``(i, j)`` pairs and counters ever cross to the host.  Passing an
      explicit ``capacity`` skips the prepass; a block pair whose candidate
      count exceeds it is flagged and escalated to the dense host path
      (``JoinStats.overflow_blocks`` counts these), preserving exactness.

    Both modes return identical pairs and bit-identical ``JoinStats``
    counters (property-tested against the ``naive_join`` oracle).
    """
    col_s, sim, tau = _normalize_rs_args(col_s, sim, tau)
    return blocked_bitmap_join_prepared(
        as_prepared(col_r), None if col_s is None else as_prepared(col_s),
        sim=sim, tau=tau, b=b, method=method, mix=mix, block=block,
        impl=impl, use_cutoff=use_cutoff, use_bitmap=use_bitmap,
        compaction=compaction, capacity=capacity, return_stats=return_stats)


def blocked_bitmap_join_prepared(
    prep_r: PreparedCollection,
    prep_s: PreparedCollection | None = None,
    *,
    sim: str = JACCARD,
    tau: float = 0.8,
    b: int = 128,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    block: int = 4096,
    impl: str = "auto",
    use_cutoff: bool = True,
    use_bitmap: bool = True,
    compaction: str = "host",
    capacity: int | None = None,
    return_stats: bool = False,
):
    """The blocked join over prepared inputs (see :func:`blocked_bitmap_join`
    for the full driver contract).

    Everything derivable from the collection alone comes from the
    :class:`~repro.core.engine.PreparedCollection` caches: the length-sorted
    arrays and inverse permutation, the packed bitmap words keyed by
    ``(b, method, mix)``, and the integer length windows keyed by
    ``(sim, tau)``.  Repeated probes against the same prepared collection
    skip the length sort and bitmap generation entirely (assertable via
    ``prep.builds``).
    """
    if compaction not in ("host", "device"):
        raise ValueError(f"compaction must be 'host' or 'device', got {compaction!r}")
    # Self-join ONLY when S is omitted: passing the same prepared object as
    # both operands is an R×S join over the full cross product (including
    # the diagonal), matching the plain-Collection wrapper's semantics.
    self_join = prep_s is None
    if self_join:
        prep_s = prep_r
    order_r, order_s = prep_r.order, prep_s.order
    nr, ns = prep_r.num_sets, prep_s.num_sets
    tokens_r, lengths_r = prep_r.device_arrays()
    tokens_s, lengths_s = prep_s.device_arrays()

    if method == BITMAP_COMBINED:
        chosen = bm.choose_method(tau, b)
    else:
        chosen = method
    cutoff = expected.cutoff_point(chosen, b, float(tau)) if use_cutoff else 1 << 30
    words_r = prep_r.bitmap_words(b, chosen, mix=mix)
    words_s = words_r if self_join else prep_s.bitmap_words(b, chosen, mix=mix)

    np_len_r = prep_r.lengths
    np_len_s = prep_s.lengths
    stats = JoinStats()
    pairs_out: list[np.ndarray] = []
    nb_r = math.ceil(nr / block)
    nb_s = math.ceil(ns / block)
    if compaction == "device":
        # Cached integer windows for every sorted row (built at most once per
        # (sim, tau) over this prepared collection; block rows slice it).
        _, _, full_lo, full_hi = prep_r.length_window_int(sim, tau)
        need_tab = verify.min_overlap_table_dev(
            sim, float(tau), prep_r.max_len, prep_s.max_len)

    for bi in range(nb_r):
        r0, r1 = bi * block, min((bi + 1) * block, nr)
        min_lr = int(np_len_r[r0])
        max_lr = int(np_len_r[r1 - 1])
        # Admissible |s| window for the whole R block: the length bounds are
        # nondecreasing in |r|, so the block-wide window is
        # [lo(min |r|), hi(max |r|)] — integer-exact via length_window_int,
        # the same single source of truth as the per-pair window (the raw
        # float bounds can exclude boundary partners the verifier accepts).
        blk_lo, blk_hi = bounds.length_window_int(
            sim, tau, np.array([max(min_lr, 1), max(max_lr, 1)]))
        lo_r0, hi_r1 = int(blk_lo[0]), int(blk_hi[1])
        for bj in range(bi if self_join else 0, nb_s):
            s0, s1 = bj * block, min((bj + 1) * block, ns)
            stats.blocks_total += 1
            min_ls = int(np_len_s[s0])
            max_ls = int(np_len_s[s1 - 1])
            # Blocks are length-sorted: if the smallest |s| already exceeds
            # the window every later bj fails too (terminate the row) ...
            if min_ls > hi_r1:
                stats.blocks_total += nb_s - bj - 1
                stats.blocks_skipped += nb_s - bj
                break
            # ... and if the largest |s| is still below it, only this bj
            # fails (later blocks hold longer sets).
            if max_ls < lo_r0:
                stats.blocks_skipped += 1
                continue
            diag = self_join and bi == bj

            if compaction == "host":
                n_win, n_cand, vpairs = _dense_block_verify(
                    tokens_r, lengths_r, words_r, tokens_s, lengths_s, words_s,
                    np_len_r, np_len_s, r0, r1, s0, s1,
                    sim=sim, tau=tau, cutoff=cutoff, impl=impl, diag=diag,
                    self_join=self_join, use_bitmap=use_bitmap)
                stats.total_pairs += n_win
                stats.candidates += n_cand
                stats.verified_true += len(vpairs)
                if len(vpairs):
                    pairs_out.append(np.stack(
                        [order_r[vpairs[:, 0]], order_s[vpairs[:, 1]]], axis=1))
                continue

            # --- device-resident compaction ---
            win_lo, win_hi = full_lo[r0:r1], full_hi[r0:r1]
            if capacity is None:
                # Tile-count prepass: size the capacity from the real counts
                # (only two int32 grids cross to the host).
                with tracing.span("join.count"):
                    nwin_t, ncand_t = kops.count_candidates(
                        words_r[r0:r1], words_s[s0:s1],
                        lengths_r[r0:r1], lengths_s[s0:s1], win_lo, win_hi,
                        sim=sim, tau=float(tau), self_join=diag,
                        cutoff=int(cutoff), impl=impl)
                    n_win = int(np.asarray(nwin_t).sum())
                    n_cand_pre = int(np.asarray(ncand_t).sum())
                stats.total_pairs += n_win
                if not use_bitmap:
                    n_cand_pre = n_win
                if n_cand_pre == 0:
                    continue
                cap = min(_bucket_capacity(n_cand_pre), (r1 - r0) * (s1 - s0))
            else:
                cap = int(capacity)
            with tracing.span("join.dispatch"):
                pairs_d, n_win_d, n_cand_d, n_ok_d, ovf = _resident_block_step(
                    tokens_r[r0:r1], lengths_r[r0:r1], words_r[r0:r1],
                    tokens_s[s0:s1], lengths_s[s0:s1], words_s[s0:s1],
                    win_lo, win_hi, need_tab, jnp.int32(r0), jnp.int32(s0),
                    sim=sim, tau=float(tau), cap=cap, diag=diag,
                    cutoff=int(cutoff), impl=impl, use_bitmap=use_bitmap)
            stats.slots += cap
            with tracing.span("join.sync"):
                if capacity is not None:
                    stats.total_pairs += int(n_win_d)
                stats.candidates += int(n_cand_d)
                overflow = bool(ovf)
                k = int(n_ok_d)
            if overflow:
                # Escalation: the fixed-capacity list truncated this tile —
                # re-run it densely (host compaction) for exactness.  The
                # counters above are exact (counted before truncation).
                stats.overflow_blocks += 1
                with tracing.span("join.fallback"):
                    _, _, vpairs = _dense_block_verify(
                        tokens_r, lengths_r, words_r, tokens_s, lengths_s,
                        words_s, np_len_r, np_len_s, r0, r1, s0, s1,
                        sim=sim, tau=tau, cutoff=cutoff, impl=impl,
                        diag=diag, self_join=self_join, use_bitmap=use_bitmap)
                stats.verified_true += len(vpairs)
                if len(vpairs):
                    pairs_out.append(np.stack(
                        [order_r[vpairs[:, 0]], order_s[vpairs[:, 1]]], axis=1))
                continue
            stats.verified_true += k
            if k:
                with tracing.span("join.fetch"):
                    vp = np.asarray(pairs_d)[:k].astype(np.int64)
                pairs_out.append(np.stack(
                    [order_r[vp[:, 0]], order_s[vp[:, 1]]], axis=1))

    with tracing.span("join.finish"):
        if pairs_out:
            pairs = np.concatenate(pairs_out, axis=0)
            if self_join:
                lo = np.minimum(pairs[:, 0], pairs[:, 1])
                hi_ = np.maximum(pairs[:, 0], pairs[:, 1])
                pairs = np.stack([lo, hi_], axis=1)
            pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
        else:
            pairs = np.zeros((0, 2), dtype=np.int64)
    # Grid driver: the bitmap verdict is consumed on every window-surviving
    # cell, so the funnel top equals the windowed grid (set identically on
    # both compaction paths — the stats stay bit-for-bit comparable).
    stats.candidates_generated = stats.total_pairs
    if return_stats:
        return pairs, stats
    return pairs


def _window_pair_mask(len_r: np.ndarray, len_s: np.ndarray, sim: str, tau: float) -> np.ndarray:
    # Integer-exact form of the Table 2 window: identical to comparing the
    # real-valued bounds (lengths are integers), and the same int32 test the
    # device-resident step applies — so host and device paths agree on
    # `total_pairs` bit-for-bit.
    lo_i, hi_i = bounds.length_window_int(sim, tau, len_r)
    ls = len_s[None, :]
    return ((ls >= lo_i[:, None]) & (ls <= hi_i[:, None])
            & (len_r[:, None] > 0) & (len_s[None, :] > 0))


# ---------------------------------------------------------------------------
# Distributed ring join (shard_map + collective_permute)
# ---------------------------------------------------------------------------

_RING_ENTRYPOINTS = None


def _ring_entrypoint_cache():
    """The ring driver's traced-factory cache — a
    :class:`repro.serve.entrypoints.EntrypointCache` (lazy: ``repro.serve``
    imports the engine, so the import happens at first probe, not at module
    load)."""
    global _RING_ENTRYPOINTS
    if _RING_ENTRYPOINTS is None:
        from repro.serve.entrypoints import EntrypointCache
        _RING_ENTRYPOINTS = EntrypointCache(maxsize=256)
    return _RING_ENTRYPOINTS


def _ring_sweep_fn(mesh, axes, *, shard_r: int, shard_s: int, cap: int,
                   sim: str, tau: float, cutoff: int, impl: str,
                   rs_join: bool):
    """Memoized traced factory for the ring sweep: repeated ring joins with
    the same mesh/shape/knobs — the engine's probe loop, the conformance
    sweep — reuse the compiled executable instead of re-tracing a fresh
    closure per call (the jit cache then keys on operand shapes as usual).
    """
    key = ("ring_sweep", mesh, axes, shard_r, shard_s, cap, sim, tau,
           cutoff, impl, rs_join)
    return _ring_entrypoint_cache().get(
        key, lambda: _build_ring_sweep_fn(
            mesh, axes, shard_r=shard_r, shard_s=shard_s, cap=cap, sim=sim,
            tau=tau, cutoff=cutoff, impl=impl, rs_join=rs_join))


def _build_ring_sweep_fn(mesh, axes, *, shard_r: int, shard_s: int, cap: int,
                         sim: str, tau: float, cutoff: int, impl: str,
                         rs_join: bool):
    """Compile (once per static ring config) the jitted shard_map sweep."""
    from jax.sharding import PartitionSpec as P

    axis_name = axes if len(axes) > 1 else axes[0]
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    spec = P(axes)

    def local(tok, length, word, s_tok0, s_len0, s_word0, ntab):
        my = jax.lax.axis_index(axis_name)
        gi = my * shard_r + jnp.arange(shard_r, dtype=jnp.int32)

        def step(carry, t):
            (s_tok, s_len, s_word), (cand_acc, ver_acc, ovf_acc) = carry
            s_dev = (my - t) % n_dev  # origin device of the S shard we hold
            gj = s_dev * shard_s + jnp.arange(shard_s, dtype=jnp.int32)
            cand = kops.candidate_matrix(
                word, s_word, length, s_len,
                sim=sim, tau=float(tau), self_join=False,
                cutoff=int(cutoff), impl=impl)
            if not rs_join:
                cand &= gi[:, None] < gj[None, :]
            n_cand = jnp.sum(cand, dtype=jnp.int32)
            # Fixed-capacity compaction (Algorithm 8's local candidate list).
            ii, jj = jnp.nonzero(cand, size=cap, fill_value=0)
            slot_valid = jnp.arange(cap) < n_cand
            ok = verify.gathered_overlap(tok, ii, s_tok, jj, slot_valid)
            need = bounds.min_overlap_gather(sim, ntab, length[ii], s_len[jj])
            ok_mask = slot_valid & (ok >= need)
            out_pairs = jnp.stack([ii + my * shard_r,
                                   jj + s_dev * shard_s], axis=1).astype(jnp.int32)
            perm = [(d, (d + 1) % n_dev) for d in range(n_dev)]
            nxt = tuple(jax.lax.ppermute(x, axis_name, perm)
                        for x in (s_tok, s_len, s_word))
            overflowed = n_cand > cap
            accs = (cand_acc + n_cand.astype(jnp.int64),
                    ver_acc + jnp.sum(ok_mask, dtype=jnp.int64),
                    ovf_acc + overflowed.astype(jnp.int64))
            return (nxt, accs), (out_pairs, ok_mask, overflowed)

        zero = jnp.int64(0)
        init = ((s_tok0, s_len0, s_word0), (zero, zero, zero))
        (_, (cand, ver, ovf)), (pairs, valid, overflow) = jax.lax.scan(
            step, init, jnp.arange(n_dev, dtype=jnp.int32))
        counters = jnp.stack([cand, ver, ovf])[None]  # (1, 3) per device
        return pairs.reshape(-1, 2), valid.reshape(-1), counters, overflow[None]

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec,) * 6 + (P(),),
        out_specs=(P(axes),) * 4,
        check_vma=False,
    )
    return jax.jit(fn)


def ring_join_sharded(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    words: jnp.ndarray,
    *,
    mesh,
    axis: str | tuple[str, ...],
    sim: str,
    tau: float,
    tokens_s: jnp.ndarray | None = None,
    lengths_s: jnp.ndarray | None = None,
    words_s: jnp.ndarray | None = None,
    cutoff: int = 1 << 30,
    impl: str = "ref",
    capacity_per_step: int | None = None,
):
    """Distributed exact join via a ring sweep.

    R is sharded over ``axis`` and stays fixed per device; every ring step
    rotates the S shard (bitmaps + tokens + lengths) one hop with
    ``collective_permute`` while the local R shard runs the fused bitmap
    filter + exact verification against the S block it currently holds.
    After ``n_dev`` steps every pair has been examined exactly once — the
    upper triangle (i < j) for a self-join (S operands omitted), the full
    R×S grid when ``tokens_s``/``lengths_s``/``words_s`` are given.  The
    permuted operands of step k+1 are independent of step k's math, so XLA's
    latency-hiding scheduler can overlap the ICI transfer with tile compute.

    Candidates are compacted into a fixed ``capacity_per_step`` buffer per
    device — the TPU analogue of Algorithm 8's 2048-entry thread-local lists.
    An overflowing step silently truncates its candidate list (``jnp.nonzero``
    drops everything beyond ``cap``), so it is flagged *per step*: the
    :func:`ring_join` driver re-runs exactly the flagged (device, step) tiles
    densely and merges the results, preserving exactness.  Call that wrapper
    unless you want to handle the escalation yourself.

    Returns ``(pairs, valid, counters, overflow_steps)``:
      pairs: int32[n_dev * steps * cap, 2] global (i, j) ids (garbage where
        ``valid`` is False), sharded over ``axis``.
      valid: bool with matching leading dim — verified-similar slots.
      counters: int64[n_dev, 3] per-device (candidates, verified, overflow).
      overflow_steps: bool[n_dev, n_dev] — [device, step] tiles whose
        candidate count exceeded ``cap`` (their pairs are incomplete).
    """
    from repro.distributed.sharding import join_axes

    rs_join = tokens_s is not None
    if rs_join and (lengths_s is None or words_s is None):
        raise ValueError("R×S ring join needs tokens_s, lengths_s and words_s")
    if not rs_join:
        tokens_s, lengths_s, words_s = tokens, lengths, words

    axes, _axis_name, n_dev = join_axes(mesh, axis)
    n_r = tokens.shape[0]
    n_s = tokens_s.shape[0]
    if n_r % n_dev or n_s % n_dev:
        raise ValueError(
            f"collection sizes {n_r}x{n_s} must divide over {n_dev} devices (pad first)")
    shard_r = n_r // n_dev
    shard_s = n_s // n_dev
    cap = capacity_per_step or max(8 * max(shard_r, shard_s), 128)

    # Integer acceptance thresholds, replicated to every device (f32 math
    # may only prune; membership is decided by this host-built table).
    need_tab = verify.min_overlap_table_dev(
        sim, float(tau), int(tokens.shape[1]), int(tokens_s.shape[1]))

    fn = _ring_sweep_fn(
        mesh, axes, shard_r=shard_r, shard_s=shard_s, cap=int(cap),
        sim=sim, tau=float(tau), cutoff=int(cutoff), impl=impl,
        rs_join=rs_join)
    return fn(tokens, lengths, words, tokens_s, lengths_s, words_s, need_tab)


def ring_join(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    words: jnp.ndarray,
    *,
    mesh,
    axis: str | tuple[str, ...],
    sim: str,
    tau: float,
    tokens_s: jnp.ndarray | None = None,
    lengths_s: jnp.ndarray | None = None,
    words_s: jnp.ndarray | None = None,
    cutoff: int = 1 << 30,
    impl: str = "ref",
    capacity_per_step: int | None = None,
    return_stats: bool = False,
):
    """Exact distributed join: ring sweep + dense re-run of overflowed tiles.

    Drives :func:`ring_join_sharded` and implements the escalation its
    fixed-capacity compaction requires: every flagged ``(device, step)`` tile
    — one R shard against the S shard it held at that step, whose candidate
    count exceeded ``capacity_per_step`` — is recomputed densely (fused
    bitmap filter, host compaction, batched exact verification) and its
    complete pair set replaces the truncated one.  Tiles that did not
    overflow are taken from the ring output as-is, so the re-run cost is
    proportional to the overflowed fraction only.

    Returns the final exact pair set as lexicographically sorted
    ``int64[K, 2]`` global indices — ``(i, j)`` with ``i < j`` for a
    self-join (S operands omitted), ``(r_index, s_index)`` otherwise — i.e.
    exactly :func:`naive_join`'s pairs over the same (padded) arrays.  With
    ``return_stats=True`` also returns ``(counters, overflow_steps)`` as
    numpy arrays (see :func:`ring_join_sharded`); the per-device verified
    counters are reconciled with the dense re-runs, so
    ``counters[:, 1].sum() == len(pairs)`` even under overflow.
    """
    from repro.distributed.sharding import join_axes

    rs_join = tokens_s is not None
    if not rs_join:
        tokens_s, lengths_s, words_s = tokens, lengths, words
    _axes, _name, n_dev = join_axes(mesh, axis)
    shard_r = tokens.shape[0] // n_dev
    shard_s = tokens_s.shape[0] // n_dev

    pairs_d, valid_d, counters_d, overflow_d = ring_join_sharded(
        tokens, lengths, words, mesh=mesh, axis=axis, sim=sim, tau=tau,
        tokens_s=tokens_s if rs_join else None,
        lengths_s=lengths_s if rs_join else None,
        words_s=words_s if rs_join else None,
        cutoff=cutoff, impl=impl, capacity_per_step=capacity_per_step)

    pairs = np.asarray(pairs_d)
    valid = np.asarray(valid_d)
    counters = np.array(counters_d)  # writable: verified gets reconciled below
    overflow = np.asarray(overflow_d)
    cap = pairs.shape[0] // (n_dev * n_dev)
    p4 = pairs.reshape(n_dev, n_dev, cap, 2)
    v3 = valid.reshape(n_dev, n_dev, cap)
    # Complete tiles keep their ring output; overflowed tiles are dropped
    # wholesale (their candidate list was truncated) and recomputed densely.
    out = [p4[v3 & ~overflow[:, :, None]].reshape(-1, 2)]
    for d, t in zip(*np.nonzero(overflow)):
        s_dev = (int(d) - int(t)) % n_dev
        r_sl = slice(int(d) * shard_r, (int(d) + 1) * shard_r)
        s_sl = slice(s_dev * shard_s, (s_dev + 1) * shard_s)
        cand = np.asarray(kops.candidate_matrix(
            words[r_sl], words_s[s_sl], lengths[r_sl], lengths_s[s_sl],
            sim=sim, tau=float(tau), self_join=False,
            cutoff=int(cutoff), impl=impl))
        ii, jj = np.nonzero(cand)
        gi = ii + int(d) * shard_r
        gj = jj + s_dev * shard_s
        if not rs_join:
            keep = gi < gj
            gi, gj = gi[keep], gj[keep]
        n_ok = 0
        if len(gi):
            ok = np.asarray(verify.verify_pairs_rs(
                tokens, lengths, tokens_s, lengths_s,
                jnp.asarray(gi), jnp.asarray(gj), sim, float(tau)))
            n_ok = int(ok.sum())
            if n_ok:
                out.append(np.stack([gi[ok], gj[ok]], axis=1))
        # Reconcile the per-device verified counter: the ring step only saw
        # the <= cap truncated slots of this tile.
        counters[int(d), 1] += n_ok - int(v3[int(d), int(t)].sum())
    merged = np.concatenate(out, axis=0).astype(np.int64)
    merged = merged[np.lexsort((merged[:, 1], merged[:, 0]))]
    if return_stats:
        return merged, counters, overflow
    return merged


def _pad_rows_np(a: np.ndarray, n_total: int, fill) -> np.ndarray:
    if a.shape[0] >= n_total:
        return a
    pad = np.full((n_total - a.shape[0],) + a.shape[1:], fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


def ring_join_prepared(
    prep_r: PreparedCollection,
    prep_s: PreparedCollection | None = None,
    *,
    mesh,
    axis: str | tuple[str, ...],
    sim: str = JACCARD,
    tau: float = 0.8,
    b: int = 128,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    use_cutoff: bool = True,
    impl: str = "ref",
    capacity_per_step: int | None = None,
    return_stats: bool = False,
):
    """Collection-level front end of :func:`ring_join` over prepared inputs.

    Handles everything the array-level driver leaves to the caller: bitmap
    words come from the prepared cache (built at most once per
    ``(b, method, mix)``), collections are padded with empty sets up to a
    multiple of the mesh's device count (empty sets are never similar to
    anything, so padding never changes the result), and the returned pairs
    are remapped from the padded sorted space back to *original* collection
    indices — ``(i, j)`` with ``i < j`` for a self-join, ``(r_index,
    s_index)`` otherwise, lexicographically sorted, exactly
    :func:`naive_join`'s pair set.

    With ``return_stats=True`` returns ``(pairs, counters, overflow_steps)``
    (see :func:`ring_join_sharded` for their shapes).
    """
    # Self-join ONLY when S is omitted (same contract as the blocked driver:
    # an explicit S — even the same object — is a full R×S cross product).
    self_join = prep_s is None
    if self_join:
        prep_s = prep_r
    if method == BITMAP_COMBINED:
        chosen = bm.choose_method(tau, b)
    else:
        chosen = method
    cutoff = expected.cutoff_point(chosen, b, float(tau)) if use_cutoff else 1 << 30

    from repro.distributed.sharding import join_axes

    _axes, _name, n_dev = join_axes(mesh, axis)
    nr, ns = prep_r.num_sets, prep_s.num_sets
    nr_pad = math.ceil(nr / n_dev) * n_dev
    ns_pad = math.ceil(ns / n_dev) * n_dev

    words_r = np.asarray(prep_r.bitmap_words(b, chosen, mix=mix))
    tokens = jnp.asarray(_pad_rows_np(prep_r.tokens, nr_pad, PAD_TOKEN))
    lengths = jnp.asarray(_pad_rows_np(prep_r.lengths, nr_pad, 0))
    # Empty sets hash to all-zero bitmaps, so zero-filled padding rows are
    # exactly what generate_bitmaps would produce for them.
    words = jnp.asarray(_pad_rows_np(words_r, nr_pad, 0))
    if self_join:
        rs_kw = {}
    else:
        words_s = np.asarray(prep_s.bitmap_words(b, chosen, mix=mix))
        rs_kw = dict(
            tokens_s=jnp.asarray(_pad_rows_np(prep_s.tokens, ns_pad, PAD_TOKEN)),
            lengths_s=jnp.asarray(_pad_rows_np(prep_s.lengths, ns_pad, 0)),
            words_s=jnp.asarray(_pad_rows_np(words_s, ns_pad, 0)))

    out = ring_join(tokens, lengths, words, mesh=mesh, axis=axis, sim=sim,
                    tau=float(tau), cutoff=int(cutoff), impl=impl,
                    capacity_per_step=capacity_per_step, return_stats=True,
                    **rs_kw)
    sorted_pairs, counters, overflow = out
    # Padded rows have length 0 and can never appear; keep the guard anyway.
    keep = (sorted_pairs[:, 0] < nr) & (sorted_pairs[:, 1] < ns)
    sorted_pairs = sorted_pairs[keep]
    gi = prep_r.order[sorted_pairs[:, 0]]
    gj = (prep_r.order if self_join else prep_s.order)[sorted_pairs[:, 1]]
    if self_join:
        pairs = np.stack([np.minimum(gi, gj), np.maximum(gi, gj)], axis=1)
    else:
        pairs = np.stack([gi, gj], axis=1)
    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))].astype(np.int64)
    if return_stats:
        return pairs, counters, overflow
    return pairs
