"""Preallocated KV cache.

Replaces the reference's concat-per-token cache
(cake-core/src/models/llama3/cache.rs:93-122), which grows by ``Tensor::cat`` each
step (O(n^2) copies) and has a buggy sliding-window trim (cache.rs:105-116, see
SURVEY.md §2.6). Here the cache is a fixed-shape array pair written in place with
``dynamic_update_slice`` — jit-compatible, donatable, and O(1) per token.

Layout: [n_layers, batch, n_kv_heads, max_seq, head_dim] — **head-major**: each
KV head's sequence is contiguous, so the decode-attention kernel's per-head block
DMA (ops/pallas/decode_attention.py) streams one contiguous stride per block
instead of gathering across an interleaved head axis. The leading layer axis lets
``lax.scan`` over stacked layer params carry the matching cache slice, and a
pipeline stage simply holds the [own_layers, ...] shard of the same structure.

Causality makes explicit length tracking unnecessary for reads: slots at index
> current position are masked by the position-comparison causal mask, so only the
write position ``pos`` must be carried (as a scalar, not a shape).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.obs.taxonomy import CACHE_WRITE


class KVCache(NamedTuple):
    """Fixed-shape KV storage for a contiguous run of layers."""

    k: jnp.ndarray  # [n_layers, batch, n_kv_heads, max_seq, head_dim]
    v: jnp.ndarray

    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def batch_size(self) -> int:
        return self.k.shape[1]

    @property
    def max_seq_len(self) -> int:
        return self.k.shape[3]


SEQ_MULTIPLE = 128  # one TPU lane tile: keeps decode-kernel blocks full-width


def init_cache(
    n_layers: int,
    batch: int,
    max_seq_len: int,
    n_kv_heads: int,
    head_dim: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> KVCache:
    """Allocate a zeroed cache; the seq dim is rounded up to SEQ_MULTIPLE.

    The padding slots are invisible (causal masking / length pruning never reads
    past the live prefix) and keep ops/pallas/decode_attention.py at its full
    128-row block size for any user-requested ``max_seq_len``.
    """
    padded = -(-max_seq_len // SEQ_MULTIPLE) * SEQ_MULTIPLE
    shape = (n_layers, batch, n_kv_heads, padded, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def write_layer(
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    row: jnp.ndarray | int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write a [batch, chunk, n_kv, head_dim] chunk at sequence offset ``pos``.

    Operates on one layer's [batch, n_kv, max_seq, head_dim] slice (the layer axis
    is scanned over in the model). ``pos`` is a traced scalar. ``row`` offsets
    the write down the batch axis when ``k_new`` carries a WINDOW of the
    cache's rows (the 1F1B interleaved pipeline's per-group decode,
    models/llama/batch.py row_offset mode).
    """
    with jax.named_scope(CACHE_WRITE):
        start = (row, 0, pos, 0)
        k_new = jnp.moveaxis(k_new, 1, 2).astype(k_cache.dtype)
        v_new = jnp.moveaxis(v_new, 1, 2).astype(v_cache.dtype)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new, start)
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new, start)
        return k_cache, v_cache


# ------------------------------------------------------------- rolling cache
#
# Sliding-window models (Mistral family) never attend past `window` keys, so
# the cache need only hold the last `window + chunk_budget` positions:
# position p lives in slot p % cache_len, and the slot's absolute position is
# reconstructed at read time (slot contents are unambiguous because cache_len
# exceeds the window plus the largest chunk written in one dispatch — a chunk
# write can only evict keys already outside every live query's window). This
# bounds KV memory by the window, not the sequence length: a 32K-context
# Mistral-7B with window 4096 stores 4608 slots instead of 32768.


def write_layer_rolling(
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    valid_len: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write a chunk at slots ``(pos + j) % cache_len`` for j < valid_len.

    Padded tail tokens (j >= valid_len, from prefill buckets) are DROPPED —
    in a rolling cache a clamped garbage write would destroy live keys
    instead of landing in dead future slots like the dense layout.
    """
    with jax.named_scope(CACHE_WRITE):
        cache_len = k_cache.shape[2]
        chunk = k_new.shape[1]
        j = jnp.arange(chunk)
        slots = jnp.where(j < valid_len, (pos + j) % cache_len, cache_len)
        k_new = jnp.moveaxis(k_new, 1, 2).astype(k_cache.dtype)
        v_new = jnp.moveaxis(v_new, 1, 2).astype(v_cache.dtype)
        k_cache = k_cache.at[:, :, slots, :].set(k_new, mode="drop")
        v_cache = v_cache.at[:, :, slots, :].set(v_new, mode="drop")
        return k_cache, v_cache


ROLLING_DEAD = jnp.int32(2**30)  # sentinel: slot never written (masked out)


def rolling_kv_positions(
    cache_len: int, pos: jnp.ndarray, valid_len: jnp.ndarray
) -> jnp.ndarray:
    """Absolute position of each rolling-cache slot, [cache_len] int32.

    Slot s holds the unique position q ≡ s (mod cache_len) in
    (p_max - cache_len, p_max], where p_max = pos + valid_len - 1 is the
    newest position just written. Slots never written (q < 0) get a large
    sentinel so the causal mask excludes them.
    """
    p_max = pos + valid_len - 1
    s = jnp.arange(cache_len, dtype=jnp.int32)
    q = p_max - ((p_max - s) % cache_len)
    return jnp.where(q >= 0, q, ROLLING_DEAD)
