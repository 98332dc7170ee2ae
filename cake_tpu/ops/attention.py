"""Grouped-query attention (XLA einsum path).

Functional equivalent of the reference's ``CausalSelfAttention``
(cake-core/src/models/llama3/attention.rs): GQA with no-bias projections
(attention.rs:133-150), scores computed with an f32 upcast (attention.rs:96-100),
causal masking (attention.rs:102-113), softmax, weighted sum.

Design differences (TPU-first):
  * No ``repeat_kv`` materialization (attention.rs:125-130): query heads are grouped
    against their KV head with a 5-D einsum, so the MXU sees the grouped matmul
    directly and no [b, n_q, s, hd] KV copy is ever built.
  * The causal mask is a position comparison computed inline (no memoized mask
    tensors as in cache.rs:79-90) — jit-friendly and shape-free.
  * One softmax body serves both K/V layouts: ``gqa_attention_hm`` reads the KV
    cache's head-major storage directly (models/llama/cache.py) and
    ``gqa_attention`` is a moveaxis wrapper for fresh seq-major K/V — XLA fuses
    the transpose into the einsum, and the two paths cannot diverge numerically.

These are also the numerics oracle for the Pallas kernels
(ops/pallas/{flash,decode}_attention.py), which replace them on TPU.
"""

from __future__ import annotations

import jax.numpy as jnp


def widen_qkv(q, k, v):
    """Mixed cache/activation precision: compute in the WIDER dtype.

    Narrow storage (f8 cache_dtype) casts up on read — the cast fuses into
    the cache read (on-VREG inside the Pallas kernels), so HBM still streams
    the narrow bytes; f8 does not participate in jnp's implicit promotion,
    so the cast must be explicit. A WIDER cache (f32 KV under bf16
    activations) upgrades the query instead — truncating it would make the
    wide cache pure memory waste. THE one promotion rule, shared by the XLA
    path, the sp online-softmax, and both Pallas kernels."""
    if k.dtype == q.dtype:
        return q, k, v
    wide = (
        k.dtype
        if jnp.dtype(k.dtype).itemsize > jnp.dtype(q.dtype).itemsize
        else q.dtype
    )
    return q.astype(wide), k.astype(wide), v.astype(wide)


def block_query_end(positions, block: int):
    """The last position of the block of ``block`` positions that each of
    ``positions`` lies in: the query term of the block-causal mask (``k <=
    block_query_end(q)``), in position space or, for rows whose left pads are
    whole blocks, in slot space alike (the Pallas chunk kernels')."""
    return (positions // block + 1) * block - 1


def gqa_attention_hm(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    window: int | None = None,
    window_flag: jnp.ndarray | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    block: int | None = None,
) -> jnp.ndarray:
    """Causal grouped-query attention, K/V head-major (the cache layout).

    Args:
      q: [batch, q_len, n_q_heads, head_dim]
      k/v: [batch, n_kv_heads, kv_len, head_dim] (models/llama/cache.py layout)
      q_positions: [batch, q_len] absolute positions of the queries
      k_positions: [batch, kv_len] absolute positions of the keys
      window: sliding-window size (Mistral): keys more than ``window - 1``
        positions behind the query are masked out. None = full causal.
      window_flag: traced scalar bool gating the window per call — Gemma-2's
        alternating pattern threads a per-layer flag through the layer scan
        (False = full causal even though ``window`` is set).
      scale: score scale override (Gemma-2 query_pre_attn_scalar**-0.5);
        None = head_dim**-0.5.
      softcap: tanh soft-capping of scores BEFORE masking (Gemma-2
        attn_logit_softcapping).
      block: STATIC — the BLOCK-CAUSAL mask of a model that generates by
        diffusion over blocks of this many positions (``block_query_end``):
        a query sees every key below the END of its own block, so attention
        is bidirectional inside a block and causal over the earlier ones.
        None = causal (every other model; nothing is traced for it).

    Returns:
      [batch, q_len, n_q_heads, head_dim] in q's dtype.
    """
    b, q_len, n_q, head_dim = q.shape
    n_kv = k.shape[1]
    group = n_q // n_kv
    if scale is None:
        scale = head_dim**-0.5
    out_dtype = q.dtype
    q, k, v = widen_qkv(q, k, v)

    qg = q.reshape(b, q_len, n_kv, group, head_dim)
    # [b, n_kv, group, q_len, kv_len] — f32 upcast matches attention.rs:96-100.
    scores = jnp.einsum(
        "bqkgh,bksh->bkgqs", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores.astype(jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)

    if block is not None:
        q_positions = block_query_end(q_positions, block)
    causal = k_positions[:, None, :] <= q_positions[:, :, None]  # [b, q_len, kv_len]
    if window is not None:
        # HF convention: position p attends to [p - window + 1, p].
        in_window = k_positions[:, None, :] > q_positions[:, :, None] - window
        if window_flag is not None:
            in_window = in_window | ~window_flag
        causal &= in_window
    scores = jnp.where(causal[:, None, None, :, :], scores, -jnp.inf)

    # All-masked rows (possible for padded bucket-tail queries in rolling mode
    # when chunk - valid_len >= window) have max == -inf; clamp the row max and
    # guard the denominator so those rows come out as zeros instead of NaNs
    # (exp(-inf - 0) is exactly 0, so 0/1 zeros the whole row).
    row_max = jnp.max(scores, axis=-1, keepdims=True)
    row_max = jnp.where(jnp.isfinite(row_max), row_max, 0.0)
    weights = jnp.exp(scores - row_max)
    denom = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / jnp.where(denom > 0.0, denom, 1.0)
    # att @ v runs in the input dtype (candle converts att back before the matmul).
    out = jnp.einsum("bkgqs,bksh->bqkgh", weights.astype(v.dtype), v)
    return out.reshape(b, q_len, n_q, head_dim).astype(out_dtype)


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    window: int | None = None,
    window_flag: jnp.ndarray | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    block: int | None = None,
) -> jnp.ndarray:
    """``gqa_attention_hm`` for fresh seq-major K/V [batch, kv_len, n_kv, head_dim]
    (projection outputs during prefill)."""
    return gqa_attention_hm(
        q, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2), q_positions, k_positions,
        window=window, window_flag=window_flag, scale=scale, softcap=softcap,
        **({} if block is None else {"block": block}),
    )


def mla_prefill_attention(
    q_nope: jnp.ndarray,  # [b, t, heads, nope]
    q_rope: jnp.ndarray,  # [b, t, heads, rope]   (after RoPE)
    k_nope: jnp.ndarray,  # [b, t, heads, nope]   (ckv Wuk, a head)
    k_rope: jnp.ndarray,  # [b, t, rope]          (after RoPE; all heads share it)
    v: jnp.ndarray,  # [b, t, heads, v_dim]
    live: jnp.ndarray,  # [b, t] bool: window positions that are the row's tokens
    *,
    scale: float,
    starts: jnp.ndarray,  # [b] first live window position (may be < 0: clamped)
    lengths: jnp.ndarray,  # [b] one past the last live window position
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Expanded latent attention over a window's OWN keys and values:
    ordinary causal multi-head attention whose query and key are a
    no-position part beside a rotary part (``s_h = (q_nope_h . k_nope_h +
    q_rope_h . k_rope) * scale``) and whose values are narrower than its
    keys. Causality is by window position, which left pads shift equally for
    a row's queries and keys. The one softmax body of this module serves the
    XLA path (values padded to the keys' width, sliced after); on the chip
    the Pallas chunk kernel does (ops/pallas/chunk_prefill.py: every width
    padded to whole 128-lane tiles, which changes no score and no sum).
    Returns [b, t, heads, v_dim]."""
    b, t, n, _ = q_nope.shape
    v_dim = v.shape[-1]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, n, k_rope.shape[-1]))],
        axis=-1,
    )
    d = q.shape[-1]

    def widen(x, to):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, to - x.shape[-1]),))

    if use_pallas:
        from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention

        d_pad = -(-d // 128) * 128
        out = chunk_prefill_attention(
            widen(q, d_pad),
            jnp.moveaxis(widen(k, d_pad), 2, 1),
            jnp.moveaxis(widen(v, d_pad), 2, 1),
            jnp.zeros((b,), jnp.int32), jnp.maximum(lengths, 1), None,
            jnp.maximum(starts, 0), scale=scale,
        )
    else:
        idx = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        out = gqa_attention(
            q, k, widen(v, d), idx, jnp.where(live, idx, 2**30), scale=scale
        )
    return out[..., :v_dim]
