"""Seeded, vectorised inputs for the benchmark: corpora shaped by a
collection's published statistics, with planted near-duplicate clusters.

Every parameter comes from a configuration file; nothing here names a cell.

* set sizes: ``lognormal`` with the published mean and a ``sigma`` fitted so
  that the largest of ``n_sets`` draws is about the published maximum;
  rounded, clipped to ``[min, max]``, and the largest set set to ``max``;
* each set is a weighted sample without replacement of ``size`` tokens from
  a Zipf(``zipf_a``) law over the ``n_tokens`` of the published universe:
  the first ``size`` distinct values of its Zipf draws, in draw order;
* each planted cluster copies a source row, keeps ``round(2 j n / (1 + j))``
  of its tokens and adds as many tokens of the universe that the source
  lacks, so copies sit at Jaccard ``j`` and the universe keeps its size.

The corpus of a configuration is one fixed data set, drawn from the
configuration's ``corpus_seed``, as a deployment's corpus is one data set.
A run's seed presents its rows in another order (:func:`shuffle`), which
moves every row to other chunks and blocks of the join and changes every
pair's ids.  The shapes the program compiles for (padded width, prefix
vocabulary, postings) are then the same for every seed, so only a
checkout's first run compiles.

``preprocess`` is a vectorised copy of the paper's Section 5 preprocessing
(``repro.core.collection.preprocess``): relabel tokens by ascending global
frequency, sort sets by (size, tokens).  The tests hold both equal.
"""

from __future__ import annotations

import dataclasses

import numpy as np

PAD = np.iinfo(np.int32).max

# Independent random streams under one seed.
_TOKENS, _PLANTED, _ORDER, _SIZES = range(1, 5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Corpus:
    """A preprocessed corpus: ``tokens`` int32[N, L] (rows ascending, PAD
    after the last token), ``lengths`` int32[N], and ``lut`` mapping a raw
    token value to its relabelled id (-1 where the value never occurs)."""

    tokens: np.ndarray
    lengths: np.ndarray
    lut: np.ndarray

    @property
    def num_sets(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def vocab(self) -> int:
        """Relabelled ids run from 0 to ``vocab - 1``."""
        return int((self.lut >= 0).sum())


def zipf_cdf(a: float, n_tokens: int) -> np.ndarray:
    """CDF of the Zipf law over ``n_tokens`` values: ``P(k) ~ (k + 1)^-a``."""
    pmf = np.arange(1, n_tokens + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(pmf / pmf.sum())
    cdf[-1] = 1.0
    return cdf


def set_sizes(cfg: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` set sizes: lognormal with mean ``mean`` and shape ``sigma``,
    rounded and clipped to ``[min, max]``; the largest is ``max``."""
    s = cfg["sizes"]
    sigma = float(s["sigma"])
    mu = np.log(float(s["mean"])) - sigma * sigma / 2
    sizes = np.clip(np.rint(rng.lognormal(mu, sigma, size=n)),
                    s["min"], s["max"]).astype(np.int64)
    if n:
        sizes[np.argmax(sizes)] = s["max"]
    return sizes


class _InverseCdf:
    """Exact inverse of a discrete CDF by table: a uniform draw's bin of
    ``2**bits`` equal-mass bins holds one value, unless a CDF step falls
    inside the bin, in which case the draw is searched for."""

    def __init__(self, cdf: np.ndarray, bits: int = 20):
        self.cdf = cdf
        self.m = 1 << bits
        edges = np.arange(self.m + 1, dtype=np.float64) / self.m
        at = np.searchsorted(cdf, edges, side="right")
        self.lo = at[:-1].astype(np.int32)
        self.exact = at[:-1] == at[1:]

    def __call__(self, u: np.ndarray) -> np.ndarray:
        b = (u * self.m).astype(np.int64)
        v = self.lo[b]
        hard = ~self.exact[b]
        v[hard] = np.searchsorted(self.cdf, u[hard], side="right")
        return v


def _first_distinct(sizes: np.ndarray, draws: np.ndarray, inv: _InverseCdf,
                    n_tokens: int, rng: np.random.Generator):
    """Draw ``draws[i]`` Zipf values for row ``i`` and keep the first
    ``sizes[i]`` distinct ones in draw order -> (row, column, value) of the
    kept tokens and each row's count of distinct values."""
    rows = np.repeat(np.arange(len(sizes)), draws)
    vals = inv(rng.random(len(rows)))
    _, first = np.unique(rows * n_tokens + vals, return_index=True)
    first.sort()
    r = rows[first]
    col = np.arange(len(first)) - np.searchsorted(r, r)
    keep = col < sizes[r]
    return (r[keep], col[keep], vals[first[keep]],
            np.bincount(r, minlength=len(sizes)))


def draw_sets(cfg: dict, sizes: np.ndarray, rng: np.random.Generator,
              chunk: int = 16384) -> np.ndarray:
    """Raw token rows for ``sizes``, each a weighted sample without
    replacement from the Zipf law (see the module docstring).  A row whose
    draws held too few distinct values is drawn again with more draws.
    Returns int32[N, max(sizes)] padded with PAD."""
    t = cfg["tokens"]
    n_tokens = int(t["n_tokens"])
    if int(sizes.max(initial=0)) > n_tokens:
        raise ValueError(f"a set of {sizes.max()} tokens from a universe of "
                         f"{n_tokens}")
    inv = _InverseCdf(zipf_cdf(float(t["zipf_a"]), n_tokens))
    out = np.full((len(sizes), int(sizes.max(initial=1))), PAD, np.int32)
    for c0 in range(0, len(sizes), chunk):
        todo = np.arange(c0, min(c0 + chunk, len(sizes)))
        mult = 3
        while len(todo):
            sz = sizes[todo]
            r, col, v, distinct = _first_distinct(sz, mult * sz + 16, inv,
                                                  n_tokens, rng)
            done = distinct >= sz
            out[todo[r[done[r]]], col[done[r]]] = v[done[r]]
            todo, mult = todo[~done], 2 * mult
    return out


def _lengths(tokens: np.ndarray) -> np.ndarray:
    return (tokens != PAD).sum(axis=1).astype(np.int32)


def plant_clusters(cfg: dict, base: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """Append ``clusters * (cluster_size - 1)`` near-copies of random source
    rows, each at Jaccard ``jaccard`` to its source: a copy keeps
    ``round(2 j n / (1 + j))`` of the source's ``n`` tokens and adds tokens
    of the universe (``tokens.n_tokens``) that the source lacks."""
    p = cfg["planted"]
    n_clusters = int(p["clusters"])
    copies = int(p["cluster_size"]) - 1
    j = float(p["jaccard"])
    universe = np.arange(int(cfg["tokens"]["n_tokens"]))
    lengths = _lengths(base)
    src = rng.integers(0, len(base), size=n_clusters)
    width = max(base.shape[1], 1)
    out = np.full((n_clusters * copies, width), PAD, np.int32)
    k = 0
    for row in src:
        n = int(lengths[row])
        keep = min(max(int(round(2 * j * n / (1 + j))), 1), n)
        others = np.setdiff1d(universe, base[row, :n], assume_unique=True)
        for _ in range(copies):
            kept = rng.choice(base[row, :n], size=keep, replace=False)
            extra = rng.choice(others, size=n - keep, replace=False)
            out[k, :n] = np.sort(np.concatenate([kept, extra]))
            k += 1
    return np.concatenate([base, out], axis=0)


def preprocess(raw: np.ndarray) -> Corpus:
    """Relabel tokens by ascending global frequency (ties by value), sort
    each row, then sort rows by (size, tokens)."""
    live = raw != PAD
    vals, counts = np.unique(raw[live], return_counts=True)
    order = np.lexsort((vals, counts))
    rank = np.empty(len(vals), np.int64)
    rank[order] = np.arange(len(vals))
    lut = np.full(int(vals.max(initial=-1)) + 1, -1, np.int64)
    lut[vals] = rank
    tokens = np.where(live, lut[np.where(live, raw, 0)], PAD)
    tokens = np.sort(tokens, axis=1).astype(np.int32)
    lengths = live.sum(axis=1).astype(np.int32)
    width = int(lengths.max(initial=1))
    tokens = np.ascontiguousarray(tokens[:, :max(width, 1)])
    return Corpus(*_sort_rows(tokens, lengths), lut=lut)


def _sort_rows(tokens: np.ndarray, lengths: np.ndarray):
    """Rows ordered by (length, tokens lexicographically): one byte-string
    sort over big-endian (length, tokens...)."""
    key = np.concatenate([lengths[:, None].astype(np.uint32),
                          tokens.astype(np.uint32)], axis=1)
    key = np.ascontiguousarray(key.astype(">u4"))
    order = np.argsort(key.view(f"V{4 * key.shape[1]}").ravel(),
                       kind="stable")
    return tokens[order], lengths[order]


def make_corpus(cfg: dict) -> Corpus:
    """The configuration's corpus: ``n_sets`` rows in all, drawn from its
    ``corpus_seed``."""
    p = cfg["planted"]
    n_planted = int(p["clusters"]) * (int(p["cluster_size"]) - 1)
    n_base = int(cfg["n_sets"]) - n_planted
    seed = int(cfg["corpus_seed"])
    base = draw_sets(cfg, set_sizes(cfg, n_base, rng_for(seed, _SIZES)),
                     rng_for(seed, _TOKENS))
    return preprocess(plant_clusters(cfg, base, rng_for(seed, _PLANTED)))


def shuffle(corpus: Corpus, seed: int) -> Corpus:
    """The corpus with its rows in the order of ``seed``."""
    order = rng_for(seed, _ORDER).permutation(corpus.num_sets)
    return Corpus(corpus.tokens[order], corpus.lengths[order], corpus.lut)
