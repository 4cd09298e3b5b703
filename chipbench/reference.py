"""The plain reference for exact Jaccard set-similarity joins.

A pair ``(r, s)`` is in the answer iff ``|r ∩ s| / |r ∪ s| >= tau``, with
``tau`` the decimal number the configuration states (0.9 is 9/10, so a pair
at Jaccard exactly 0.9 is in), i.e. iff the overlap ``o`` reaches
``need(|r| + |s|) = ceil(t * (|r| + |s|) / (1 + t))`` for that rational
``t``: in integers, ``o * (p + q) >= p * (|r| + |s|)`` for ``t = p / q``.

Overlaps come from one-hot token matrices and an integer matrix product on
the device: ``onehot(queries) @ onehot(corpus).T`` in blocks of query rows.
The rows that hold a pair come back bit-packed and are unpacked on the host.
Nothing here uses the program: no bitmap, no prefix, no length filter.

``control_pairs`` is the benchmark's control: the same computation with the
exact verification skipped, i.e. every pair whose b-bit bitmap bound
(Theorem 1 of the paper: ``o <= (|r| + |s| - hamming) / 2``) reaches
``need``, as a join that trusted its filter would report.

:class:`HostReference` is the inverted-index reference of the chip smoke
(token -> rows, one ``np.bincount`` per query), kept to cross-check the
device reference at small sizes.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np


def threshold(tau: float):
    """``(num, den)`` with ``o >= need(k)`` iff ``o * den >= num * k``: the
    decimal ``tau = p / q`` gives ``need(k) = ceil(p k / (p + q))``."""
    t = Fraction(repr(float(tau)))
    num, den = t.numerator, t.numerator + t.denominator
    if den > 10 ** 6:
        raise ValueError(f"tau={tau} has more than six decimals")
    return num, den


def need_table(tau: float, max_key: int) -> np.ndarray:
    """``need[k]``: least overlap for Jaccard >= tau at ``|r| + |s| = k``."""
    num, den = threshold(tau)
    return np.array([-(-num * k // den) for k in range(max_key + 1)],
                    dtype=np.int32)


def _onehot(tokens: np.ndarray, lengths: np.ndarray, vocab: int,
            rows: int) -> np.ndarray:
    """int8[rows, vocab] with a 1 per live token below ``vocab``."""
    out = np.zeros((rows, vocab), np.int8)
    live = (np.arange(tokens.shape[1])[None, :] < lengths[:, None]) & (
        tokens < vocab)
    r, c = np.nonzero(live)
    out[r, tokens[r, c]] = 1
    return out


def _bitmap_onehot(tokens, lengths, b: int, rows: int) -> np.ndarray:
    """int8[rows, b]: the Bitmap-Set words of the paper, one column per bit
    (token t sets bit t mod b)."""
    out = np.zeros((rows, b), np.int8)
    live = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
    r, c = np.nonzero(live)
    out[r, tokens[r, c] % b] = 1
    return out


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


class DeviceReference:
    """Exact pairs of query sets against one corpus, on the default device.

    ``dtype`` is the one-hot element type: int8 with int32 products on a
    TPU, float32 on the CPU (exact for counts below 2**24)."""

    def __init__(self, tokens: np.ndarray, lengths: np.ndarray, tau: float,
                 *, block: int = 2048):
        import jax
        import jax.numpy as jnp

        self.jnp = jnp
        on_tpu = jax.default_backend() == "tpu"
        self.dtype = jnp.int8 if on_tpu else jnp.float32
        self.acc = jnp.int32 if on_tpu else jnp.float32
        self.n = int(tokens.shape[0])
        self.npad = _round_up(max(self.n, 1), 32)
        live = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
        self.vocab = _round_up(int(tokens[live].max(initial=0)) + 1, 128)
        self.block = int(block)
        self.tau = float(tau)
        self.tokens = tokens
        self.lengths = np.zeros(self.npad, np.int32)
        self.lengths[:self.n] = lengths
        self.num, self.den = threshold(tau)
        self._step = jax.jit(self._block_step,
                             static_argnames=("self_join", "bound", "num",
                                              "den"))

    def _block_step(self, q, pq, lq, x, pc, lc, q0, *, self_join: bool,
                    bound: bool, num: int, den: int):
        jnp = self.jnp
        o = jnp.dot(q, x.T, preferred_element_type=self.acc).astype(
            jnp.int32)
        key = lq[:, None] + lc[None, :]
        if bound:
            # Theorem 1: overlap <= (|r| + |s| - hamming) / 2, with
            # hamming = |B_r| + |B_s| - 2 |B_r & B_s| and o = |B_r & B_s|.
            o = (key - (pq[:, None] + pc[None, :] - 2 * o)) // 2
        ok = (o * den >= num * key) & (lq[:, None] > 0) & (lc[None, :] > 0)
        if self_join:
            rows = q0 + jnp.arange(q.shape[0])[:, None]
            ok &= jnp.arange(lc.shape[0])[None, :] > rows
        words = ok.reshape(ok.shape[0], -1, 32).astype(jnp.uint32)
        packed = jnp.sum(words << jnp.arange(32, dtype=jnp.uint32), axis=2,
                         dtype=jnp.uint32)
        return packed, jnp.sum(ok, axis=1, dtype=jnp.int32)

    def pairs(self, q_tokens=None, q_lengths=None) -> np.ndarray:
        """int64[K, 2] exact pairs, lexsorted: ``(i, j)`` with ``i < j`` for
        the corpus's self-join (no queries given), else ``(corpus row,
        query row)``."""
        return self._join(lambda t, ln, rows: _onehot(t, ln, self.vocab,
                                                      rows),
                          q_tokens, q_lengths, bound=False)

    def control_pairs(self, b: int, q_tokens=None,
                      q_lengths=None) -> np.ndarray:
        """The control: every pair whose b-bit bitmap bound reaches the
        threshold, with no exact verification."""
        return self._join(lambda t, ln, rows: _bitmap_onehot(t, ln, b, rows),
                          q_tokens, q_lengths, bound=True)

    def _join(self, onehot, q_tokens, q_lengths, *, bound: bool):
        jnp = self.jnp
        n_live = self.lengths[:self.n]
        self_join = q_tokens is None
        if self_join:
            q_tokens, q_lengths = self.tokens, n_live
        xc = onehot(self.tokens, n_live, self.npad)
        x = jnp.asarray(xc, dtype=self.dtype)
        pc = jnp.asarray(xc.sum(axis=1, dtype=np.int32))
        lc = jnp.asarray(self.lengths)
        found = []
        nq = int(q_tokens.shape[0])
        for q0 in range(0, nq, self.block):
            q1 = min(q0 + self.block, nq)
            lq = np.zeros(self.block, np.int32)
            lq[:q1 - q0] = q_lengths[q0:q1]
            qh = onehot(q_tokens[q0:q1], q_lengths[q0:q1], self.block)
            packed, counts = self._step(
                jnp.asarray(qh, dtype=self.dtype),
                jnp.asarray(qh.sum(axis=1, dtype=np.int32)), jnp.asarray(lq),
                x, pc, lc, jnp.int32(q0), self_join=self_join, bound=bound,
                num=self.num, den=self.den)
            hit = np.flatnonzero(np.asarray(counts))
            if not len(hit):
                continue
            # Rows are fetched in power-of-two batches, so the gather
            # compiles once per batch size and not once per count.
            take = np.pad(hit, (0, (1 << int(len(hit) - 1).bit_length())
                                - len(hit)), mode="edge")
            rows = np.ascontiguousarray(np.asarray(
                packed[jnp.asarray(take)]))[:len(hit)]
            bits = np.unpackbits(rows.view(np.uint8), axis=1,
                                 bitorder="little")[:, :self.n]
            r, c = np.nonzero(bits)
            found.append(np.stack([c, q0 + hit[r]], axis=1))
        if not found:
            return np.zeros((0, 2), np.int64)
        pairs = np.concatenate(found).astype(np.int64)
        if self_join:
            pairs = pairs[:, ::-1]
        return pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


class HostReference:
    """Exact overlaps of a token set against a whole collection: an
    inverted index (token -> rows) and one ``np.bincount`` per query."""

    def __init__(self, tokens: np.ndarray, lengths: np.ndarray):
        self.n = int(tokens.shape[0])
        self.lengths = lengths.astype(np.int64)
        live = np.arange(tokens.shape[1])[None, :] < lengths[:, None]
        toks = tokens[live]
        rows = np.repeat(np.arange(self.n), lengths)
        order = np.argsort(toks, kind="stable")
        self.rows = rows[order]
        self.vocab, self.starts = np.unique(toks[order], return_index=True)
        self.ends = np.append(self.starts[1:], len(order))

    def partners(self, query: np.ndarray, tau: float) -> np.ndarray:
        k = np.searchsorted(self.vocab, query)
        inside = k < len(self.vocab)
        k = k[inside][self.vocab[k[inside]] == query[inside]]
        hits = [self.rows[self.starts[t]:self.ends[t]] for t in k]
        o = np.bincount(np.concatenate(hits) if hits else np.zeros(0, int),
                        minlength=self.n)
        need = need_table(tau, len(query) + int(self.lengths.max(initial=0)))
        ok = ((o >= need[len(query) + self.lengths]) & (self.lengths > 0)
              & (len(query) > 0))
        return np.nonzero(ok)[0]
