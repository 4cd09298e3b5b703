"""The prepared-collection engine: build-once join artifacts + batched probes.

The paper splits bitmap *construction* (Section 3.2, Algorithms 3-5) from
per-pair *filtering*; every driver in this repo used to fuse the two anyway,
re-deriving the length sort, the packed bitmap words and the prefix indexes
on each call.  This module makes the build a first-class, reusable artifact:

* :class:`PreparedCollection` — a length-sorted view of a
  :class:`~repro.core.collection.Collection` with the inverse permutation,
  lazily-cached packed bitmap words keyed by ``(b, method, mix)``, cached
  integer length windows (``bounds.length_window_int``) keyed by
  ``(sim, tau)``, and a cached CPU prefix index keyed by ``(sim, tau, ell)``.
  Build counters record exactly which artifacts were (re)built, so reuse is
  assertable, not just hoped for.
* :func:`prepare` / :func:`as_prepared` — construction helpers; every join
  driver accepts either a plain ``Collection`` or a ``PreparedCollection``.
* :class:`JoinEngine` — the serving shape: prepare R once, stream batches of
  S through :meth:`JoinEngine.probe`, each batch returning pairs plus a
  per-batch :class:`~repro.core.join.JoinStats`.  The driver and its knobs
  come from an explicit :class:`~repro.core.plan.JoinPlan`; executable
  drivers are naive / blocked / ring / indexed (the :mod:`repro.index`
  postings-CSR candidate generator) / sharded-indexed (the same candidate
  path with its postings sharded over a device mesh,
  :mod:`repro.distributed.sharded_index`) / the four CPU algorithms.

``PreparedCollection`` duck-types the read surface of ``Collection``
(``tokens`` / ``lengths`` / ``num_sets`` / ``max_len`` / ``row``) **over the
length-sorted view**; drivers that consume it return pairs in the *original*
collection's indices (they remap through ``order``).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, Optional, Tuple

import numpy as np

from repro import tracing
from repro.core import bitmap as bm
from repro.core import bounds
from repro.core.collection import Collection
from repro.core.constants import BITMAP_COMBINED, JACCARD
from repro.core.filters import BitmapFilter
from repro.core.plan import JoinPlan, JoinPlanner, CPU_DRIVERS


class PreparedCollection:
    """Build-once join artifacts for one collection.

    Construction (via :func:`prepare`) performs the only eager step — the
    stable length sort every driver needs.  Everything else (device arrays,
    packed bitmap words per ``(b, method, mix)``, integer length windows per
    ``(sim, tau)``, CPU prefix indexes per ``(sim, tau, ell)``) is built on
    first use and cached; ``builds`` counts each build so callers can assert
    amortization (see ``benchmarks/bench_engine.py``).
    """

    def __init__(self, source: Collection):
        with tracing.span("prepare.sort"):
            order = np.argsort(source.lengths, kind="stable")
            inverse = np.empty_like(order)
            inverse[order] = np.arange(len(order))
            self.tokens = source.tokens[order]    # length-sorted view
            self.lengths = source.lengths[order]
        self.source = source
        self.order = order          # sorted index -> original index
        self.inverse = inverse      # original index -> sorted index
        # Every cached artifact below is derived from the source arrays; an
        # in-place edit after prepare() would silently serve stale sorts and
        # bitmaps.  Seal both the source and the sorted copies — growth goes
        # through new store segments (repro.store), never mutation.
        for arr in (source.tokens, source.lengths, self.tokens, self.lengths):
            arr.flags.writeable = False
        self.builds: Dict[str, int] = {
            "sort": 1, "bitmap": 0, "window": 0, "prefix_index": 0,
            "postings": 0, "sharded_postings": 0}
        self._device: Optional[Tuple] = None          # (tokens, lengths) jnp
        self._words: Dict[Tuple[int, str, bool], object] = {}
        self._words_np: Dict[Tuple[int, str, bool], np.ndarray] = {}
        self._windows: Dict[Tuple[str, float], Tuple] = {}
        self._prefix: Dict[Tuple[str, float, int], dict] = {}
        self._postings: Dict[Tuple[str, float, int], object] = {}
        self._sharded_postings: Dict[Tuple[str, float, int, int], object] = {}
        self._sorted_collection: Optional[Collection] = None

    # -- Collection duck-typing (over the length-sorted view) ---------------

    @property
    def num_sets(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def max_len(self) -> int:
        return int(self.tokens.shape[1])

    def __len__(self) -> int:
        return self.num_sets

    def row(self, i: int) -> np.ndarray:
        return self.tokens[i, : self.lengths[i]]

    @property
    def sorted_collection(self) -> Collection:
        if self._sorted_collection is None:
            self._sorted_collection = Collection(tokens=self.tokens,
                                                 lengths=self.lengths)
        return self._sorted_collection

    # -- cached artifacts ----------------------------------------------------

    def device_arrays(self):
        """(tokens, lengths) as device (jnp) arrays, cached."""
        if self._device is None:
            import jax.numpy as jnp
            with tracing.span("prepare.upload"):
                self._device = (jnp.asarray(self.tokens),
                                jnp.asarray(self.lengths))
        return self._device

    def bitmap_words(self, b: int, method: str, *, mix: bool = False,
                     tau: Optional[float] = None):
        """Packed ``uint32[N, b//32]`` words over the sorted view, cached per
        ``(b, resolved method, mix)``.  ``method='combined'`` needs ``tau`` to
        resolve via Algorithm 6."""
        if method == BITMAP_COMBINED:
            if tau is None:
                raise ValueError("combined method needs tau to resolve")
            method = bm.choose_method(float(tau), b)
        key = (int(b), method, bool(mix))
        if key not in self._words:
            tokens, lengths = self.device_arrays()
            with tracing.span("prepare.bitmap"):
                self._words[key] = bm.generate_bitmaps(
                    tokens, lengths, b, method=method, mix=mix)
            self.builds["bitmap"] += 1
        return self._words[key]

    def bitmap_words_np(self, b: int, method: str, *, mix: bool = False,
                        tau: Optional[float] = None) -> np.ndarray:
        """Numpy twin of :meth:`bitmap_words` (for the CPU ``BitmapFilter``)."""
        if method == BITMAP_COMBINED:
            if tau is None:
                raise ValueError("combined method needs tau to resolve")
            method = bm.choose_method(float(tau), b)
        key = (int(b), method, bool(mix))
        if key not in self._words_np:
            self._words_np[key] = np.asarray(
                self.bitmap_words(b, method, mix=mix))
        return self._words_np[key]

    def length_window_int(self, sim: str, tau: float):
        """Integer-exact Table 2 windows for every sorted row, cached per
        ``(sim, tau)``.  Returns ``(lo_np, hi_np, lo_jnp, hi_jnp)``."""
        key = (sim, float(tau))
        if key not in self._windows:
            import jax.numpy as jnp
            with tracing.span("prepare.window"):
                lo, hi = bounds.length_window_int(sim, tau, self.lengths)
                self._windows[key] = (lo, hi, jnp.asarray(lo),
                                      jnp.asarray(hi))
            self.builds["window"] += 1
        return self._windows[key]

    def prefix_index(self, sim: str, tau: float, ell: int = 1) -> dict:
        """Cached ℓ-prefix inverted index over the sorted view (the CPU
        algorithms' build artifact)."""
        key = (sim, float(tau), int(ell))
        if key not in self._prefix:
            from repro.core import cpu_algos
            self._prefix[key] = cpu_algos._build_prefix_index(
                self.sorted_collection, sim, tau, ell=ell)
            self.builds["prefix_index"] += 1
        return self._prefix[key]

    def postings(self, sim: str, tau: float, ell: int = 1):
        """Cached CSR ℓ-prefix postings index over the sorted view (the
        ``"indexed"`` driver's build artifact — the device twin of
        :meth:`prefix_index`), built at most once per ``(sim, tau, ell)``."""
        key = (sim, float(tau), int(ell))
        if key not in self._postings:
            from repro.index.postings import build_postings
            with tracing.span("prepare.postings"):
                self._postings[key] = build_postings(self, sim, tau, ell=ell)
            self.builds["postings"] += 1
        return self._postings[key]

    def sharded_postings(self, sim: str, tau: float, ell: int = 1,
                         n_shards: int = 1):
        """Cached token-slab partition of :meth:`postings` (the
        ``"sharded-indexed"`` driver's build artifact), built at most once
        per ``(sim, tau, ell, n_shards)``; the underlying CSR index is
        shared with (and cached by) the single-device driver."""
        key = (sim, float(tau), int(ell), int(n_shards))
        if key not in self._sharded_postings:
            from repro.index.postings import partition_postings
            self._sharded_postings[key] = partition_postings(
                self.postings(sim, tau, ell), n_shards)
            self.builds["sharded_postings"] += 1
        return self._sharded_postings[key]

    def build_counts(self) -> Dict[str, int]:
        """A copy of the build counters
        (sort/bitmap/window/prefix_index/postings/sharded_postings)."""
        return dict(self.builds)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"PreparedCollection(n={self.num_sets}, max_len={self.max_len}, "
                f"builds={self.builds})")


def prepare(col: Collection | PreparedCollection) -> PreparedCollection:
    """Build the reusable join artifact for ``col`` (idempotent)."""
    if isinstance(col, PreparedCollection):
        return col
    return PreparedCollection(col)


def as_prepared(col: Collection | PreparedCollection) -> PreparedCollection:
    """Alias of :func:`prepare`; reads better at driver entry points."""
    return prepare(col)


def prepared_bitmap_filter(
    prep_r: PreparedCollection,
    prep_s: Optional[PreparedCollection] = None,
    *,
    sim: str,
    tau: float,
    b: int = 64,
    method: str = BITMAP_COMBINED,
    mix: bool = False,
    use_cutoff: bool = True,
) -> BitmapFilter:
    """A :class:`~repro.core.filters.BitmapFilter` over prepared collections.

    Reuses the prepared words (no bitmap regeneration); index side R, probe
    side S (self-join when ``prep_s`` is omitted).  Indices fed to
    ``prune_mask`` are in the prepared (length-sorted) space — exactly what
    the CPU algorithms use when handed prepared inputs.
    """
    from repro.core import expected

    chosen = bm.choose_method(float(tau), b) if method == BITMAP_COMBINED else method
    words_r = prep_r.bitmap_words_np(b, chosen, mix=mix)
    cutoff = (expected.cutoff_point(chosen, b, float(tau)) if use_cutoff
              else np.iinfo(np.int32).max)
    kw = {}
    if prep_s is not None and prep_s is not prep_r:
        kw = dict(probe_words=prep_s.bitmap_words_np(b, chosen, mix=mix),
                  probe_lengths=prep_s.lengths)
    return BitmapFilter(words=words_r, lengths=prep_r.lengths, sim=sim,
                        tau=tau, b=b, cutoff=int(cutoff), method=chosen, **kw)


# ---------------------------------------------------------------------------
# JoinEngine: prepare R once, stream probe batches against it
# ---------------------------------------------------------------------------

def _as_store(corpus):
    """``corpus`` if it is a :class:`repro.store.CorpusStore`, else None.
    Imported lazily — :mod:`repro.store` layers *over* this module."""
    if type(corpus).__name__ != "CorpusStore":
        return None
    from repro.store.store import CorpusStore
    return corpus if isinstance(corpus, CorpusStore) else None


@dataclasses.dataclass
class ProbeResult:
    pairs: np.ndarray       # int64[K, 2] (corpus_index, batch_index)
    stats: "object"         # JoinStats for this batch


class JoinEngine:
    """The serving shape: one prepared corpus, many probe batches.

    ``JoinEngine(corpus, sim, tau)`` prepares R once (length sort now; bitmap
    words / windows on the first probe) and resolves a
    :class:`~repro.core.plan.JoinPlan`.  Each :meth:`probe` call joins one
    batch of S against the prepared corpus and returns ``(pairs, JoinStats)``
    with pairs as ``(corpus_index, batch_index)`` in original indices.  The
    corpus-side artifacts are reused across probes — asserted by build
    counters in ``benchmarks/bench_engine.py`` and ``scripts/check.sh``.

    Pass ``mesh=``/``axis=`` to execute a ``ring`` or ``sharded-indexed``
    plan on a real mesh; without one, a ring plan falls back to the blocked
    driver and a sharded-indexed plan to its single-device twin ``indexed``
    (both recorded in ``fallbacks``).

    The corpus may also be a :class:`repro.store.CorpusStore` — the engine
    then adopts the store's plan/sim/tau/mesh and every probe / self-join
    runs the store's segment-union join (base ∪ deltas), so an appendable
    corpus drops in wherever a frozen prepared corpus did.  ``prepared``
    reads through to the store's live base segment across compactions.
    """

    #: Default bound on the per-probe ``JoinStats`` history.  A long-lived
    #: session probes millions of times; an unbounded list was a slow leak.
    HISTORY_LIMIT = 1024

    def __init__(self, corpus: Collection | PreparedCollection,
                 sim: str = JACCARD, tau: float = 0.8, *,
                 plan: Optional[JoinPlan] = None,
                 planner: Optional[JoinPlanner] = None,
                 expected_batch: Optional[int] = None,
                 mesh=None, axis=None,
                 history_limit: Optional[int] = None):
        self.store = _as_store(corpus)
        self._planner = planner or JoinPlanner()
        if self.store is not None:
            store = self.store
            if (sim, float(tau)) not in ((store.sim, store.tau),
                                         (JACCARD, 0.8)):
                raise ValueError(
                    f"engine asked for (sim={sim}, tau={tau}) but the store "
                    f"is (sim={store.sim}, tau={store.tau})")
            if plan is not None and plan != store.plan:
                raise ValueError(
                    "engine plan conflicts with the store's plan; the store "
                    "pins one plan for every segment join")
            self._prepared = store.base.prepared
            self.sim = store.sim
            self.tau = store.tau
            self.plan = store.plan
            self._auto_planned = False
            self.mesh = store.mesh
            self.axis = store.axis
        else:
            self._prepared = prepare(corpus)
            self.sim = sim
            self.tau = float(tau)
            self._auto_planned = plan is None
            if plan is None:
                plan = self._planner.plan(sim, tau,
                                          n_r=self._prepared.num_sets,
                                          n_s=expected_batch)
            self.plan = plan
            self.mesh = mesh
            self.axis = axis
        self.probes = 0
        if history_limit is None:
            history_limit = self.HISTORY_LIMIT
        # Bounded: keeps the newest `history_limit` JoinStats.  The rollup
        # in stats_summary() accumulates over *all* probes regardless.
        self.history: Deque[object] = collections.deque(maxlen=history_limit)
        self.fallbacks: list = []
        self._totals: Dict[str, int] = collections.defaultdict(int)

    @property
    def prepared(self) -> PreparedCollection:
        """The corpus-side artifact: the store's *live* base segment in
        store mode (compaction swaps it; this property never goes stale),
        else the prepared corpus the engine was built on."""
        if self.store is not None:
            return self.store.base.prepared
        return self._prepared

    def attach_store(self, store) -> None:
        """Upgrade a frozen-corpus engine in place to serve ``store``
        (whose base must be this engine's prepared corpus under the same
        plan).  History, fallbacks and the lifetime rollup carry over —
        this is how a resident session absorbs its first ``append()``
        without resetting observability."""
        if store.base.prepared is not self._prepared:
            raise ValueError(
                "store's base segment is not this engine's prepared corpus")
        if (store.sim, store.tau) != (self.sim, self.tau):
            raise ValueError(
                f"store is (sim={store.sim}, tau={store.tau}) but the engine "
                f"serves (sim={self.sim}, tau={self.tau})")
        if store.plan != self.plan:
            raise ValueError("store plan differs from the engine's plan")
        self.store = store
        self._auto_planned = False

    # -- public API ----------------------------------------------------------

    def probe(self, batch: Collection | PreparedCollection, *,
              return_stats: bool = True):
        """Join one batch of S against the prepared corpus.

        Returns ``(pairs, stats)`` (or just pairs with
        ``return_stats=False``); pairs are ``(corpus_index, batch_index)``
        int64 in the original index spaces of both collections.  Batches are
        prepared lazily, only by the drivers that use prepared artifacts
        (blocked / ring / CPU); pass an already-prepared batch to reuse its
        caches across repeated probes.
        """
        pairs, stats = self._execute(batch)
        self.record_probe(stats)
        return (pairs, stats) if return_stats else pairs

    def record_probe(self, stats) -> None:
        """Account one probe's :class:`~repro.core.join.JoinStats`: bump the
        probe counter, append to the bounded history and fold the counters
        into the lifetime rollup.  Called by :meth:`probe` and by the
        serving layer (:mod:`repro.serve`) for coalesced probes it executes
        outside this engine."""
        self.probes += 1
        self.history.append(stats)
        for field in ("total_pairs", "blocks_total", "blocks_skipped",
                      "candidates", "verified_true", "overflow_blocks",
                      "candidates_generated", "postings_expanded"):
            self._totals[field] += getattr(stats, field, 0)

    def stats_summary(self) -> Dict[str, object]:
        """Lifetime rollup over every probe (not just the bounded history):
        summed funnel counters plus derived ratios — the observability
        surface a resident session reports instead of the raw per-probe
        list."""
        t = dict(self._totals)
        total = t.get("total_pairs", 0)
        cand = t.get("candidates", 0)
        return {
            "probes": self.probes,
            "history_len": len(self.history),
            "history_limit": self.history.maxlen,
            "fallbacks": len(self.fallbacks),
            **t,
            "filter_ratio": (1.0 - cand / total) if total else 0.0,
            "precision": (t.get("verified_true", 0) / cand) if cand else 1.0,
        }

    def self_join(self, *, return_stats: bool = False):
        """The corpus joined against itself under this engine's plan."""
        pairs, stats = self._execute(None)
        return (pairs, stats) if return_stats else pairs

    # -- execution -----------------------------------------------------------

    def _execute(self, batch):
        with tracing.span("join"):
            return self._join(batch)

    def _join(self, batch):
        from repro.core import join as join_mod

        if self.store is not None:
            # Segment-union join: the store runs base ∪ per-delta joins
            # through its own per-segment engines (explicit plan, no auto
            # fallbacks) and sums the funnel counters.
            if batch is None:
                return self.store.self_join(return_stats=True)
            return self.store.probe(batch, return_stats=True)

        plan = self.plan
        driver = plan.driver
        if driver == "ring" and self.mesh is None:
            self.fallbacks.append("ring plan without a mesh -> blocked")
            driver = "blocked"
        if driver == "sharded-indexed" and self.mesh is None:
            self.fallbacks.append(
                "sharded-indexed plan without a mesh -> indexed")
            driver = "indexed"
        if (driver == "naive" and self._auto_planned and batch is not None):
            # The auto-planner chose 'naive' from the corpus size alone (the
            # batch size was unknown at plan time); a large batch would make
            # the dense oracle quadratic, so re-check against the planner's
            # own threshold with the real batch in hand.
            cells = self.prepared.num_sets * batch.num_sets
            if cells > self._planner.naive_cells:
                self.fallbacks.append(
                    f"naive plan but this batch gives {cells} cells -> blocked")
                driver = "blocked"

        if driver == "naive":
            # naive_join consumes raw collections — no batch preparation.
            pairs = join_mod.naive_join(self.prepared, batch, self.sim, self.tau)
            n = len(pairs)
            stats = join_mod.JoinStats(total_pairs=n, candidates=n,
                                       verified_true=n,
                                       candidates_generated=n)
            return pairs, stats

        if driver == "blocked":
            return join_mod.blocked_bitmap_join(
                self.prepared, batch, self.sim, self.tau,
                b=plan.b, method=plan.method, mix=plan.mix, block=plan.block,
                impl=plan.impl, use_cutoff=plan.use_cutoff,
                compaction=plan.compaction, capacity=plan.capacity,
                return_stats=True)

        prep_s = None if batch is None else prepare(batch)
        if driver == "indexed":
            from repro.index.candidates import indexed_join_prepared
            return indexed_join_prepared(
                self.prepared, prep_s, sim=self.sim, tau=self.tau,
                b=plan.b, method=plan.method, mix=plan.mix, ell=plan.ell,
                probe_block=plan.block, impl=plan.impl,
                use_cutoff=plan.use_cutoff, capacity=plan.capacity,
                return_stats=True)

        if driver == "sharded-indexed":
            from repro.distributed.sharded_index import (
                sharded_indexed_join_prepared)
            # The driver sums the per-shard funnel counters into the
            # returned JoinStats (the shard-map step emits one counter row
            # per device), so probe() reports the same funnel as "indexed".
            return sharded_indexed_join_prepared(
                self.prepared, prep_s, mesh=self.mesh, axis=self.axis,
                sim=self.sim, tau=self.tau, b=plan.b, method=plan.method,
                mix=plan.mix, ell=plan.ell, probe_block=plan.block,
                impl=plan.impl, use_cutoff=plan.use_cutoff,
                capacity=plan.capacity, return_stats=True)

        if driver == "ring":
            pairs, counters, _overflow = join_mod.ring_join_prepared(
                self.prepared, prep_s, mesh=self.mesh, axis=self.axis,
                sim=self.sim, tau=self.tau, b=plan.b, method=plan.method,
                mix=plan.mix, use_cutoff=plan.use_cutoff, impl=plan.impl,
                capacity_per_step=plan.capacity, return_stats=True)
            # The ring sweep applies no length window: every pair of
            # non-empty sets is bitmap-evaluated exactly once (i < j for a
            # self-join).  total_pairs is that evaluated-grid size, so
            # filter_ratio reports the bitmap's pruning over it.
            nnz_r = int((self.prepared.lengths > 0).sum())
            if prep_s is None:
                total = nnz_r * (nnz_r - 1) // 2
            else:
                total = nnz_r * int((prep_s.lengths > 0).sum())
            stats = join_mod.JoinStats(
                total_pairs=total,
                candidates=int(counters[:, 0].sum()),
                verified_true=len(pairs),
                candidates_generated=total)
            return pairs, stats

        if driver in CPU_DRIVERS:
            from repro.core import cpu_algos
            bf = prepared_bitmap_filter(
                self.prepared, prep_s, sim=self.sim, tau=self.tau, b=plan.b,
                method=plan.method, mix=plan.mix, use_cutoff=plan.use_cutoff)
            astats = cpu_algos.AlgoStats()
            algo = cpu_algos.ALGORITHMS[driver]
            pairs = algo(self.prepared, prep_s, self.sim, self.tau,
                         bitmap=bf, stats=astats)
            stats = join_mod.JoinStats(
                total_pairs=astats.candidates,
                candidates=astats.candidates - astats.bitmap_pruned,
                verified_true=astats.results,
                candidates_generated=astats.candidates)
            return pairs, stats

        raise ValueError(f"unknown driver {driver!r}")  # pragma: no cover
