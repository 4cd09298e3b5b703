"""Bitmap generation (paper Section 3.2) and bit packing utilities.

Three generation methods are implemented, all vectorised in JAX:

* **Bitmap-Set** (Algorithm 3): bit ``h(t)`` is OR-ed for every token.
* **Bitmap-Xor** (Algorithm 4): bit ``h(t)`` is XOR-ed for every token — a bit
  stays set iff an odd number of tokens hash to it.
* **Bitmap-Next** (Algorithm 5): linear probing — each token sets the first
  unset bit at or cyclically after ``h(t)``; the bitmap has exactly
  ``min(n, b)`` ones.

Bitmaps are stored **packed** as ``uint32[N, W]`` with ``W = b // 32``; bit
``i`` of set ``n`` lives at word ``i // 32``, bit ``i % 32`` (little-endian
within the word).  All public entry points accept the padded
:class:`~repro.core.collection.Collection` layout (``tokens`` int32[N, L] with
``PAD_TOKEN`` padding + ``lengths``).

The default hash is the paper's ``h(t) = t mod b`` (Section 5.1); an optional
multiplicative (Knuth) mixer is available for adversarial id distributions.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import expected
from repro.core.constants import (
    BITMAP_COMBINED,
    BITMAP_NEXT,
    BITMAP_SET,
    BITMAP_XOR,
    PAD_TOKEN,
)

_KNUTH = np.uint32(2654435761)


def hash_positions(tokens: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """``h(t)``: map tokens to bit positions in ``[0, b)``.

    Args:
      tokens: int32[...] token ids (PAD_TOKEN allowed — callers mask validity).
      b: bitmap size in bits.
      mix: apply a multiplicative mixer before the modulo (off by default to
        match the paper's ``h(t) = t mod b``).
    """
    t = tokens.astype(jnp.uint32)
    if mix:
        t = t * _KNUTH
        t = t ^ (t >> jnp.uint32(16))
    return (t % jnp.uint32(b)).astype(jnp.int32)


def _combined_words(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int,
                    mix: bool, combine) -> jnp.ndarray:
    """uint32[N, b // 32] — bit ``h(t)`` of every valid token, combined into
    its word with ``combine`` (``lax.bitwise_or`` for Bitmap-Set,
    ``lax.bitwise_xor`` for Bitmap-Xor).

    A fused select-and-reduce over (N, L, W), not a scatter: XLA compiles a
    scatter for a TPU in time that grows with N (about a minute at 10^5
    rows), and this form's work grows with the word count W = b / 32.
    """
    l = tokens.shape[1]
    valid = (tokens != PAD_TOKEN) & (jnp.arange(l)[None, :] < lengths[:, None])
    pos = hash_positions(tokens, b, mix)
    # Invalid slots name no word and never match.
    word = jnp.where(valid, pos >> 5, -1)
    bit = jnp.uint32(1) << (pos & 31).astype(jnp.uint32)
    sel = jnp.where(word[:, :, None] == jnp.arange(b // 32, dtype=jnp.int32),
                    bit[:, :, None], jnp.uint32(0))
    return jax.lax.reduce(sel, jnp.uint32(0), combine, (1,))


@functools.partial(jax.jit, static_argnames=("b", "mix"))
def bitmap_set_words(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """Bitmap-Set as packed uint32[N, b // 32] words."""
    return _combined_words(tokens, lengths, b, mix, jax.lax.bitwise_or)


@functools.partial(jax.jit, static_argnames=("b", "mix"))
def bitmap_xor_words(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """Bitmap-Xor as packed uint32[N, b // 32] words."""
    return _combined_words(tokens, lengths, b, mix, jax.lax.bitwise_xor)


@functools.partial(jax.jit, static_argnames=("b", "mix"))
def bitmap_set_bits(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """Bitmap-Set as a bool[N, b] bit matrix."""
    return unpack_bits(bitmap_set_words(tokens, lengths, b, mix), b)


@functools.partial(jax.jit, static_argnames=("b", "mix"))
def bitmap_xor_bits(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """Bitmap-Xor as a bool[N, b] bit matrix."""
    return unpack_bits(bitmap_xor_words(tokens, lengths, b, mix), b)


@functools.partial(jax.jit, static_argnames=("b", "mix"))
def bitmap_next_bits(tokens: jnp.ndarray, lengths: jnp.ndarray, b: int, mix: bool = False) -> jnp.ndarray:
    """Bitmap-Next as a bool[N, b] bit matrix.

    Linear probing is inherently sequential per set, so we ``lax.scan`` over
    the (padded) token axis and ``vmap`` over sets.  Each probe is resolved in
    O(b) branch-free work: among unset bits, pick the one minimising the
    cyclic distance ``(i - h(t)) mod b``.  Saturated bitmaps (n >= b) come out
    all-ones, matching Algorithm 5's early exit.
    """
    n, l = tokens.shape
    pos = hash_positions(tokens, b, mix)
    valid = (tokens != PAD_TOKEN) & (jnp.arange(l)[None, :] < lengths[:, None])
    idx = jnp.arange(b, dtype=jnp.int32)

    def per_set(pos_row: jnp.ndarray, valid_row: jnp.ndarray) -> jnp.ndarray:
        def step(bits, pv):
            p, v = pv
            dist = (idx - p) % b
            dist = jnp.where(bits, b, dist)  # occupied bits are never chosen
            j = jnp.argmin(dist)
            new_bits = bits.at[j].set(True)
            return jnp.where(v, new_bits, bits), None

        bits0 = jnp.zeros((b,), dtype=bool)
        bits, _ = jax.lax.scan(step, bits0, (pos_row, valid_row))
        return bits

    return jax.vmap(per_set)(pos, valid)


def _validate_width(b: int) -> None:
    """Reject widths that would silently mis-pack (b <= 0, or bits that do
    not fill whole uint32 words)."""
    if not isinstance(b, (int, np.integer)):
        raise ValueError(f"bitmap width must be an int, got {type(b).__name__}")
    if b <= 0 or b % 32:
        raise ValueError(
            f"bitmap width b={b} must be a positive multiple of 32 "
            f"(bitmaps are packed into uint32 words)")


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """bool[N, b] -> uint32[N, b//32] (little-endian bit order within words)."""
    n, b = bits.shape
    _validate_width(b)
    w = b // 32
    shaped = bits.reshape(n, w, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None, :]
    return jnp.sum(shaped * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray, b: int | None = None) -> jnp.ndarray:
    """uint32[N, W] -> bool[N, 32*W]."""
    n, w = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    bits = ((words[:, :, None] >> shifts) & jnp.uint32(1)).astype(bool)
    bits = bits.reshape(n, w * 32)
    if b is not None:
        bits = bits[:, :b]
    return bits


def popcount32(v: jnp.ndarray) -> jnp.ndarray:
    """SWAR population count on uint32 lanes (branch-free, VPU-friendly).

    TPUs have no scalar POPCNT; this is the classic bit-slice reduction that
    vectorises across the 8x128 vector unit. Returns uint32.
    """
    v = v.astype(jnp.uint32)
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def popcount_rows(words: jnp.ndarray) -> jnp.ndarray:
    """uint32[N, W] -> int32[N] total ones per row."""
    return jnp.sum(popcount32(words).astype(jnp.int32), axis=-1)


_GENERATORS = {
    BITMAP_SET: bitmap_set_bits,
    BITMAP_XOR: bitmap_xor_bits,
    BITMAP_NEXT: bitmap_next_bits,
}

_WORD_GENERATORS = {
    BITMAP_SET: bitmap_set_words,
    BITMAP_XOR: bitmap_xor_words,
}


def choose_method(tau_jaccard: float, b: int = 64) -> str:
    """Bitmap-Combined policy (Algorithm 6), thresholds derived from Eq. 4-6.

    The paper hard-codes the crossovers (Next below ~0.56, Set in the middle,
    Xor above ~0.73) observed for b >= 64; we recompute them from the
    expected-bound equations so the policy stays correct for any ``b``.
    """
    lo, hi = expected.combined_crossovers(b)
    if tau_jaccard <= lo:
        return BITMAP_NEXT
    if tau_jaccard >= hi:
        return BITMAP_XOR
    return BITMAP_SET


def generate_bitmaps(
    tokens: jnp.ndarray,
    lengths: jnp.ndarray,
    b: int,
    method: str = BITMAP_COMBINED,
    tau_jaccard: float | None = None,
    mix: bool = False,
    packed: bool = True,
) -> jnp.ndarray:
    """Generate bitmaps for a padded collection.

    Args:
      tokens: int32[N, L] padded tokens.
      lengths: int32[N].
      b: bitmap width in bits (multiple of 32).
      method: 'set' | 'xor' | 'next' | 'combined'.
      tau_jaccard: required when method == 'combined'.
      packed: return packed uint32[N, b//32] (default) or bool[N, b].

    Raises:
      ValueError: if ``b`` is not a positive multiple of 32 (widths that
        would silently mis-pack into uint32 words), or for an unknown method.
    """
    _validate_width(b)
    if method == BITMAP_COMBINED:
        if tau_jaccard is None:
            raise ValueError("combined method needs tau_jaccard")
        method = choose_method(tau_jaccard, b)
    if method not in _GENERATORS:
        raise ValueError(f"unknown bitmap method {method!r}; "
                         f"one of {sorted(_GENERATORS)} or 'combined'")
    if packed and method in _WORD_GENERATORS:
        return _WORD_GENERATORS[method](tokens, lengths, b, mix)
    bits = _GENERATORS[method](tokens, lengths, b, mix)
    return pack_bits(bits) if packed else bits


def hamming_packed(words_r: jnp.ndarray, words_s: jnp.ndarray) -> jnp.ndarray:
    """Pairwise Hamming distance between two packed bitmap matrices.

    uint32[NR, W] x uint32[NS, W] -> int32[NR, NS].  Pure-jnp reference path
    (the Pallas kernels in ``repro.kernels`` implement the tiled version).
    """
    x = words_r[:, None, :] ^ words_s[None, :, :]
    return jnp.sum(popcount32(x).astype(jnp.int32), axis=-1)
