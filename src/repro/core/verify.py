"""Exact verification of candidate pairs.

Device path: :func:`gathered_overlap` gathers candidate pairs' padded token
rows a fixed batch of slots at a time and counts equal tokens over each
pair's ``L x L`` compare grid — one fused compare-and-reduce, with device
memory bounded by the batch and batches without a live slot skipped.
Host path: numpy verification with the early-termination bound of [13]
(used by the faithful CPU algorithm reproductions, where candidate counts are
small and early exit matters).
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds
from repro.core.constants import PAD_TOKEN


# ---------------------------------------------------------------------------
# Device (JAX) path
# ---------------------------------------------------------------------------

def _rows_overlap_dense(tok_r: jnp.ndarray, tok_s: jnp.ndarray) -> jnp.ndarray:
    """int32[B] overlaps of B pairs of sorted PAD-padded rows (int32[B, L])."""
    eq = (tok_r[:, :, None] == tok_s[:, None, :]) & (tok_r[:, :, None] != PAD_TOKEN)
    return jnp.sum(eq, axis=(1, 2), dtype=jnp.int32)


#: Slots per gather batch in :func:`gathered_overlap` — bounds the gathered
#: token rows to ``2 * batch * L`` int32 whatever the candidate capacity.
OVERLAP_BATCH = 8192


def gathered_overlap(tokens_r, idx_r, tokens_s, idx_s, live,
                     batch: int = OVERLAP_BATCH):
    """Exact overlaps of ``tokens_r[idx_r[k]]`` and ``tokens_s[idx_s[k]]``
    for every slot ``k`` where ``live[k]`` -> int32[K] (other slots hold 0).

    Gathers and verifies ``batch`` slots at a time, so device memory is
    bounded by the batch and not by the capacity K, and skips every batch
    with no live slot — the bitmap filter's survivors are usually a small
    fraction of a candidate buffer, and only they pay for verification.
    Each pair's overlap counts equal tokens over the ``L x L`` compare grid
    (tokens are unique within a row, PAD never counts): one fused
    compare-and-reduce with no gather in its inner loop (a batched
    ``searchsorted`` over full-capacity gathers needed 8 GB of temporaries
    at 2^20 slots).
    """
    k = idx_r.shape[0]
    batch = max(min(batch, k), 1)
    nb = -(-k // batch)
    pad = nb * batch - k

    def split(a):
        return jnp.pad(a, (0, pad)).reshape(nb, batch)

    def one(args):
        r, s, m = args
        return jax.lax.cond(
            jnp.any(m),
            lambda: jnp.where(
                m, _rows_overlap_dense(tokens_r[r], tokens_s[s]), 0),
            lambda: jnp.zeros((batch,), jnp.int32))

    o = jax.lax.map(one, (split(idx_r), split(idx_s), split(live)))
    return o.reshape(-1)[:k]


@functools.partial(jax.jit, static_argnames=())
def overlap_many(tokens: jnp.ndarray, idx_r: jnp.ndarray, idx_s: jnp.ndarray) -> jnp.ndarray:
    """Exact overlaps for candidate pairs (idx_r[i], idx_s[i]) of one collection."""
    return gathered_overlap(tokens, idx_r, tokens, idx_s,
                            jnp.ones(idx_r.shape, bool))


class _MinOverlapTableCache:
    """Bounded LRU for device-resident min-overlap tables, safe under
    concurrent probes.

    ``functools.lru_cache`` keeps its *dict* consistent under CPython
    threading, but two threads missing on the same key would both build and
    upload the table — and a long-lived serving session
    (:mod:`repro.serve`) probes from worker threads where that duplicated
    upload is exactly the cost the cache exists to avoid.  This cache
    double-checks under one lock (the table build itself happens outside
    the lock so a slow upload never serializes unrelated probes) and counts
    hits/misses, surfaced through ``JoinSession.stats_summary()``.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._lock = threading.Lock()
        self._data: "collections.OrderedDict" = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, sim: str, tau: float, lmax_r: int, lmax_s: int):
        key = (sim, float(tau), int(lmax_r), int(lmax_s))
        with self._lock:
            if key in self._data:
                self.hits += 1
                self._data.move_to_end(key)
                return self._data[key]
            self.misses += 1
        # Build outside the lock: a concurrent miss on the same key wastes
        # one duplicate upload at worst, but never blocks other keys.
        table = jnp.asarray(bounds.min_overlap_table(sim, tau, lmax_r, lmax_s))
        with self._lock:
            if key not in self._data:
                self._data[key] = table
                while len(self._data) > self.maxsize:
                    self._data.popitem(last=False)
            return self._data[key]

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._data), "maxsize": self.maxsize}

    def clear(self) -> None:
        with self._lock:
            self._data.clear()
            self.hits = 0
            self.misses = 0


_TABLE_CACHE = _MinOverlapTableCache(maxsize=64)


def min_overlap_table_dev(sim: str, tau: float, lmax_r: int, lmax_s: int):
    """Device twin of ``bounds.min_overlap_table`` — cached (bounded,
    lock-guarded LRU, see :class:`_MinOverlapTableCache`) so repeated
    verify/probe calls — one per block pair in the blocked host path, one
    per probe in the serving shape — do not re-upload the same table.
    Shared by every driver's verification site."""
    return _TABLE_CACHE.get(sim, tau, lmax_r, lmax_s)


def min_overlap_cache_stats() -> dict:
    """Hit/miss/entry counters of the min-overlap table cache (surfaced by
    ``repro.serve.JoinSession.stats_summary``)."""
    return _TABLE_CACHE.stats()


_min_overlap_table_dev = min_overlap_table_dev  # internal alias


@functools.partial(jax.jit, static_argnames=("sim",))
def _verify_pairs_jit(tokens, lengths, idx_r, idx_s, table, sim):
    o = overlap_many(tokens, idx_r, idx_s)
    need = bounds.min_overlap_gather(sim, table, lengths[idx_r], lengths[idx_s])
    return o >= need


def verify_pairs(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    idx_r: jnp.ndarray,
    idx_s: jnp.ndarray,
    sim: str,
    tau: float,
) -> jnp.ndarray:
    """bool[K] — whether each candidate pair is truly similar.

    Acceptance is decided by comparing the exact integer overlap against
    the host-built integer :func:`repro.core.bounds.min_overlap_table` —
    never by re-deriving the Table 1 threshold in device float32, whose
    rounding lands a few ulps off the oracle's float64 value and flips
    membership of exactly-at-threshold pairs (e.g. |r| = 28 ⊂ |s| = 35 at
    Jaccard 0.8).  Every driver therefore agrees with ``naive_join``
    bit-for-bit.
    """
    lmax = int(tokens.shape[1])
    tab = _min_overlap_table_dev(sim, float(tau), lmax, lmax)
    return _verify_pairs_jit(tokens, lengths, idx_r, idx_s, tab, sim)


@functools.partial(jax.jit, static_argnames=("sim",))
def _verify_pairs_rs_jit(tokens_r, lengths_r, tokens_s, lengths_s,
                         idx_r, idx_s, table, sim):
    o = gathered_overlap(tokens_r, idx_r, tokens_s, idx_s,
                         jnp.ones(idx_r.shape, bool))
    need = bounds.min_overlap_gather(sim, table, lengths_r[idx_r],
                                     lengths_s[idx_s])
    return o >= need


def verify_pairs_rs(
    tokens_r: jnp.ndarray,
    lengths_r: jnp.ndarray,
    tokens_s: jnp.ndarray,
    lengths_s: jnp.ndarray,
    idx_r: jnp.ndarray,
    idx_s: jnp.ndarray,
    sim: str,
    tau: float,
) -> jnp.ndarray:
    """RS-join variant of :func:`verify_pairs` (same integer-exact
    acceptance table)."""
    tab = _min_overlap_table_dev(sim, float(tau), int(tokens_r.shape[1]),
                                 int(tokens_s.shape[1]))
    return _verify_pairs_rs_jit(tokens_r, lengths_r, tokens_s, lengths_s,
                                idx_r, idx_s, tab, sim)


# ---------------------------------------------------------------------------
# Host (numpy) path — early-termination merge of [13]
# ---------------------------------------------------------------------------

def overlap_early_terminate(r: np.ndarray, s: np.ndarray, required: float) -> int:
    """Sorted-merge overlap with the early-termination condition of [13].

    Stops as soon as the remaining elements cannot reach ``required`` overlap.
    Returns the exact overlap if it is >= required, otherwise a value < required
    (possibly a partial count — callers only compare against ``required``).
    """
    i = j = o = 0
    lr, ls = len(r), len(s)
    while i < lr and j < ls:
        # Early termination: even if every remaining element matched.
        if o + min(lr - i, ls - j) < required:
            return o
        ri, sj = r[i], s[j]
        if ri == sj:
            o += 1
            i += 1
            j += 1
        elif ri < sj:
            i += 1
        else:
            j += 1
    return o


def overlap_numpy(r: np.ndarray, s: np.ndarray) -> int:
    """Vectorised exact overlap (no early termination)."""
    idx = np.searchsorted(s, r)
    idx = np.clip(idx, 0, len(s) - 1)
    return int(np.sum(s[idx] == r)) if len(s) else 0
