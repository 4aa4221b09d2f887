"""Exact visited-set as a batched bitset.

The paper's hash-set ``visited`` becomes a ``(B, ceil(n/32))`` uint32 bitmask.
For n = 1M that is 31 KiB per query — trivially VMEM/HBM friendly, exact, and
race-free under the invariant maintained by the search loop:

  * bits are only set for ids that tested *unvisited* in the same step, and
  * within one step each row's id list is duplicate-free (graph adjacency
    rows are unique; padding is masked),

so a scatter-*add* of the fresh bit values equals a scatter-*or* (no carries),
which is what `jnp`'s indexed-add gives us without needing a bitwise-or
scatter primitive.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

WORD_BITS = 32
# The fused kernels probe a bitset in aligned 128-word (one vreg row)
# windows; a bitset wider than one window is allocated in whole windows so
# they need not pad it on every call.
WINDOW_WORDS = 128


def n_words(n: int) -> int:
    words = (n + WORD_BITS - 1) // WORD_BITS
    if words <= WINDOW_WORDS:
        return words
    return -(-words // WINDOW_WORDS) * WINDOW_WORDS


def visited_init(batch: int, n: int) -> Array:
    return jnp.zeros((batch, n_words(n)), dtype=jnp.uint32)


def visited_test(words: Array, ids: Array) -> Array:
    """(B, W) x (B, M) -> (B, M) bool. Padding ids (<0) report as visited."""
    safe = jnp.maximum(ids, 0)
    w = safe // WORD_BITS
    b = (safe % WORD_BITS).astype(jnp.uint32)
    word = jnp.take_along_axis(words, w, axis=-1)
    hit = (word >> b) & jnp.uint32(1)
    return jnp.where(ids >= 0, hit.astype(bool), True)


def visited_set(words: Array, ids: Array, mask: Array) -> Array:
    """Set bits for ``ids`` where ``mask`` holds.

    Caller contract (checked by property tests): every (row, id) pair with
    ``mask`` set must currently be unvisited and appear at most once in
    ``ids[row]``.
    """
    safe = jnp.maximum(ids, 0)
    w = safe // WORD_BITS
    b = (safe % WORD_BITS).astype(jnp.uint32)
    bits = jnp.where(mask & (ids >= 0), jnp.uint32(1) << b, jnp.uint32(0))
    batch_idx = jnp.arange(words.shape[0], dtype=jnp.int32)[:, None]
    return words.at[batch_idx, w].add(bits)


def visited_count(words: Array) -> Array:
    """(B,) number of set bits — i.e. vertices touched per query."""
    x = words
    # SWAR popcount per uint32 word.
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    x = (x * jnp.uint32(0x01010101)) >> 24
    return jnp.sum(x.astype(jnp.int32), axis=-1)
