"""Llama-family decoder model as pure functions over a param pytree.

Covers the reference's model layer (cake-core/src/models/llama3/{llama,transformer,
attention,mlp}.rs) redesigned TPU-first, and widens it to the whole dense
Llama lineage: Qwen2 (QKV projection bias) and Mistral (sliding-window
attention, decoupled head_dim) run through the SAME block functions, selected
purely by config fields (models/llama/config.py).

  * Params are a pytree of arrays; per-layer weights are STACKED along a leading
    layer axis so a block range runs as one ``lax.scan`` — one compiled loop, not
    ``num_hidden_layers`` unrolled HLO copies (reference walks boxed blocks in a Rust
    loop, llama.rs:81-117).
  * A "block range" [lo, hi) is the unit of sharding, mirroring the reference's
    `Shardable = Transformer` design (llama.rs:171) — a pipeline stage holds the
    stacked params and KV cache for its contiguous range.
  * Decoder block is pre-norm: rms_1 -> GQA attention -> +residual -> rms_2 ->
    SwiGLU -> +residual (transformer.rs:48-70).
  * Prefill (chunk of tokens at offset 0) and decode (1 token at traced ``pos``)
    are two static shapes of the same functions; logits come out f32 at the last
    valid position only (llama.rs:119-137).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama.cache import (
    KVCache,
    rolling_kv_positions,
    write_layer,
    write_layer_rolling,
)
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.obs.taxonomy import (
    EMBED, FEED_FORWARD, HEAD, MIXER, MIXER_IN, MIXER_OUT, SHARED_EXPERT,
)
from cake_tpu.ops.attention import gqa_attention, gqa_attention_hm
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.mlp import swiglu, swiglu_gu, swiglu_gu_from
from cake_tpu.ops.moe import moe_swiglu
from cake_tpu.ops.pallas.fused_norm_matmul import fused_norm_matmul
from cake_tpu.ops.quant import qmat, weight_out_dim
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention
from cake_tpu.ops.pallas.decode_attention import decode_attention
from cake_tpu.ops.pallas.flash_attention import flash_attention
from cake_tpu.ops.rope import apply_rope, model_rope_tables


def resolve_attention_impl(impl: str) -> str:
    """Resolve "auto" to "pallas" on TPU, "xla" elsewhere (trace-time choice)."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown attention_impl {impl!r}")
    return impl

Params = dict[str, Any]

# Per-layer weight names. Linear weights are stored [in, out] (transposed from the
# HF/safetensors [out, in] layout) so application is a plain ``x @ w``.
LAYER_WEIGHTS = (
    "wq",       # [hidden, n_q * head_dim]
    "wk",       # [hidden, n_kv * head_dim]
    "wv",       # [hidden, n_kv * head_dim]
    "wo",       # [n_q * head_dim, hidden]
    "w_gate",   # [hidden, intermediate]
    "w_up",     # [hidden, intermediate]
    "w_down",   # [intermediate, hidden]
    "ln_attn",  # [hidden]   input_layernorm
    "ln_mlp",   # [hidden]   post_attention_layernorm
)

# Qwen2-family extras: QKV projection biases (o_proj has none). Present in the
# layer tree only when config.attention_bias is set.
LAYER_BIASES = (
    "bq",  # [n_q * head_dim]
    "bk",  # [n_kv * head_dim]
    "bv",  # [n_kv * head_dim]
)


def init_params(
    config: LlamaConfig,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
) -> Params:
    """Random-init params (for tests and compile checks; real runs load safetensors)."""
    h, inter, v = config.hidden_size, config.intermediate_size, config.vocab_size
    hd, n_q, n_kv = config.head_dim, config.num_attention_heads, config.num_key_value_heads
    n = config.num_hidden_layers
    keys = iter(jax.random.split(key, 24))

    def w(k, *shape):
        fan_in = shape[-2] if len(shape) > 1 else shape[-1]
        return (jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5).astype(dtype)

    n_e = config.num_local_experts
    if n_e:
        # MoE (Mixtral / Qwen2-MoE): expert weights stacked
        # [n_layers, n_experts, in, out]; the router stays full precision
        # like the norms (it is tiny and its softmax decides routing).
        e_inter = config.moe_intermediate_size or inter
        mlp_weights = {
            "router": w(next(keys), n, h, n_e),
            "w_gate": w(next(keys), n, n_e, h, e_inter),
            "w_up": w(next(keys), n, n_e, h, e_inter),
            "w_down": w(next(keys), n, n_e, e_inter, h),
        }
        if config.shared_expert_intermediate_size:
            s_i = config.shared_expert_intermediate_size
            mlp_weights.update(
                sh_gate=w(next(keys), n, h, s_i),
                sh_up=w(next(keys), n, h, s_i),
                sh_down=w(next(keys), n, s_i, h),
                se_gate=w(next(keys), n, h, 1),
            )
    else:
        mlp_weights = {
            "w_gate": w(next(keys), n, h, inter),
            "w_up": w(next(keys), n, h, inter),
            "w_down": w(next(keys), n, inter, h),
        }
    # Gemma stores norm weights zero-centered (applied as 1 + w) — identity
    # init is zeros there, ones elsewhere.
    norm_init = jnp.zeros if config.rmsnorm_offset else jnp.ones
    layers = {
        "wq": w(next(keys), n, h, n_q * hd),
        "wk": w(next(keys), n, h, n_kv * hd),
        "wv": w(next(keys), n, h, n_kv * hd),
        "wo": w(next(keys), n, n_q * hd, h),
        **mlp_weights,
        "ln_attn": norm_init((n, h), dtype),
        "ln_mlp": norm_init((n, h), dtype),
    }
    if config.post_block_norms:
        layers["ln_post_attn"] = norm_init((n, h), dtype)
        layers["ln_post_mlp"] = norm_init((n, h), dtype)
    if config.qk_norm:  # Qwen3 / Gemma-3: per-head q/k RMSNorm weights
        layers["q_norm"] = norm_init((n, hd), dtype)
        layers["k_norm"] = norm_init((n, hd), dtype)
    if config.sliding_pattern is not None:  # Gemma-3 5:1 local/global layers
        layers["win_flag"] = jnp.asarray(config.sliding_pattern)
    if config.rope_local_base_freq is not None:
        # Sliding layers rope at the LOCAL theta (plane 1 of the stacked
        # tables, ops/rope.model_rope_tables); full layers at the global.
        if config.sliding_pattern is None:
            raise ValueError(
                "rope_local_base_freq needs sliding_pattern (which layers "
                "take the local rope) — a dual-rope config without the "
                "pattern is underspecified"
            )
        layers["rope_sel"] = jnp.asarray(config.sliding_pattern, jnp.int32)
    if config.alt_sliding_window:
        layers["win_flag"] = (jnp.arange(n) % 2) == 0
    if config.attention_bias:
        layers["bq"] = w(next(keys), n, 1, n_q * hd)[:, 0]
        layers["bk"] = w(next(keys), n, 1, n_kv * hd)[:, 0]
        layers["bv"] = w(next(keys), n, 1, n_kv * hd)[:, 0]
    return {
        "embed": w(next(keys), v, h),
        "layers": layers,
        "ln_f": norm_init((h,), dtype),
        "lm_head": w(next(keys), h, v),
    }


def embed_tokens(
    tree: Params, tokens: jnp.ndarray, config: LlamaConfig
) -> jnp.ndarray:
    """Token embedding lookup — THE one entry for every execution backend.

    Gemma-family models scale embeddings by sqrt(hidden_size)
    (config.embedding_scale); the multiplier is cast to the embedding dtype
    first, matching the HF normalizer's rounding.
    """
    with jax.named_scope(EMBED):
        x = tree["embed"][tokens]
        if config.embedding_scale is not None:
            x = x * jnp.asarray(config.embedding_scale, x.dtype)
        return x


def is_cached_prefill(pos: int, width: int) -> bool:
    """The ONE predicate for selecting the cache-prefix attention variant: a
    multi-token chunk arriving at a nonzero offset (chunked prefill
    continuation). Every execution backend must use this, not its own copy —
    the static flag decides which attention path compiles."""
    return pos > 0 and width > 1


def slice_layers(layers: Params, lo: int, hi: int) -> Params:
    """Take the stacked-param shard for block range [lo, hi)."""
    return {k: w[lo:hi] for k, w in layers.items()}


def layer_head_counts(lp: Params, config: LlamaConfig) -> tuple[int, int]:
    """(n_q, n_kv) heads held by THIS layer tree — the one inference shared by
    every block body. Under tensor parallelism a shard holds heads/tp of each;
    with fused QKV (ops/fuse.py) the shard fraction is recovered from the
    fused output width via the global config head ratio (tp divides both head
    counts — parallel/tensor.validate_tp)."""
    hd = config.head_dim
    if "wqkv" in lp:
        out_sum = weight_out_dim(lp["wqkv"])
        unit = config.num_attention_heads + 2 * config.num_key_value_heads
        t = (unit * hd) // out_sum
        return config.num_attention_heads // t, config.num_key_value_heads // t
    return weight_out_dim(lp["wq"]) // hd, weight_out_dim(lp["wk"]) // hd


def block_qkv_flat(
    lp: Params,
    x: jnp.ndarray,
    config: LlamaConfig,
    fusion: tuple | None = None,
) -> jnp.ndarray:
    """rms_1 -> FUSED QKV projection -> +bias, UNSPLIT: [b, chunk, qkv_dim].

    The projection half of block_qkv for layer trees carrying the prep-time
    ``wqkv`` (ops/fuse.py). ``fusion`` is a resolved (set, impl) pair from
    ops/fuse.resolve_fusion (None = resolve from the config): with "norm"
    enabled the input norm folds into the projection
    (ops/pallas/fused_norm_matmul.py).
    """
    if fusion is None:
        fusion = resolve_fusion(config)
    fusions, fimpl = fusion
    if "ln_attn" not in lp:
        # No norm on the branch's input (OLMo's block norms its output).
        qkv = qmat(x, lp["wqkv"])
    elif "norm" in fusions:
        qkv = fused_norm_matmul(
            x, lp["ln_attn"], lp["wqkv"],
            eps=config.rms_norm_eps, offset=config.rmsnorm_offset,
            impl=fimpl,
        )
    else:
        h = rms_norm(x, lp["ln_attn"], config.rms_norm_eps, config.rmsnorm_offset)
        qkv = qmat(h, lp["wqkv"])
    if "bqkv" in lp:
        qkv = qkv + lp["bqkv"].astype(qkv.dtype)
    return qkv


def block_qkv(
    lp: Params,
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
    config: LlamaConfig,
    k_positions: jnp.ndarray | None = None,
    fusion: tuple | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared head of every attention variant: rms_1 -> QKV projection ->
    RoPE on q/k (v un-roped). ONE copy — the local/pipeline/tp paths
    (block_forward), the sequence-parallel bodies (parallel/sequence.py), and
    batched generation (models/llama/batch.py) must not drift in block
    arithmetic.

    The layer tree may carry the prep-time FUSED projection ``wqkv``
    (ops/fuse.py) instead of wq/wk/wv: one matmul, split afterwards —
    column-identical numerics, one HBM-bound op instead of three.

    ``k_positions`` (default: ``positions``) lets left-padded batches rope keys
    with sentinel positions on pad slots (clamped table gather; the garbage
    values are mask-excluded as keys). ``cos``/``sin`` may be pre-gathered
    3-D rows (ops/rope.apply_rope) ONLY when q and k share ``positions``."""
    with jax.named_scope(MIXER_IN):
        b, chunk, _ = x.shape
        hd = config.head_dim
        n_q, n_kv = layer_head_counts(lp, config)
        if "rope_sel" in lp and config.use_rope:
            # Dual-rope families (Gemma-3): plane 0 = global rope, 1 = local.
            # The SAME leading-axis select serves stacked tables [2, seq, hd/2]
            # and stacked pre-gathered rows [2, b, s, hd/2], so both the
            # per-layer and once-per-step gather paths stay family-agnostic.
            cos = cos[lp["rope_sel"]]
            sin = sin[lp["rope_sel"]]
        assert not (
            config.use_rope and cos.ndim == 3 and k_positions is not None
        ), (
            "pre-gathered rope rows cannot serve distinct k_positions"
        )
        if "wqkv" in lp:
            # The "norm" fusion site (ops/pallas/fused_norm_matmul.py) lives
            # inside block_qkv_flat; unfused layer trees (no wqkv) keep the
            # plain path — serving backends always run fuse_params weights.
            qkv = block_qkv_flat(lp, x, config, fusion)
            qw, kw = n_q * hd, n_kv * hd
            q = qkv[..., :qw]
            k = qkv[..., qw : qw + kw]
            v = qkv[..., qw + kw :]
        else:
            h = x
            if "ln_attn" in lp:
                h = rms_norm(x, lp["ln_attn"], config.rms_norm_eps, config.rmsnorm_offset)
            q, k, v = qmat(h, lp["wq"]), qmat(h, lp["wk"]), qmat(h, lp["wv"])
            if "bq" in lp:  # Qwen2-family QKV bias (config.attention_bias)
                q = q + lp["bq"].astype(q.dtype)
                k = k + lp["bk"].astype(k.dtype)
                v = v + lp["bv"].astype(v.dtype)
        if config.qk_norm_whole:
            # OLMo-2/3: one RMSNorm over the WHOLE q (and k) projection, all
            # heads together, before the heads are told apart.
            q = rms_norm(q, lp["q_norm"], config.rms_norm_eps, config.rmsnorm_offset)
            k = rms_norm(k, lp["k_norm"], config.rms_norm_eps, config.rmsnorm_offset)
        q = q.reshape(b, chunk, n_q, hd)
        k = k.reshape(b, chunk, n_kv, hd)
        v = v.reshape(b, chunk, n_kv, hd)
        if "q_norm" in lp and not config.qk_norm_whole:
            # Qwen3 family: head_dim-wide RMSNorm on every q/k head AFTER the
            # projection, BEFORE RoPE (HF Qwen3Attention.forward — "only on the
            # head dim"). The weight is shared across heads, so tensor-parallel
            # head sharding replicates it untouched.
            q = rms_norm(q, lp["q_norm"], config.rms_norm_eps, config.rmsnorm_offset)
            k = rms_norm(k, lp["k_norm"], config.rms_norm_eps, config.rmsnorm_offset)
        if not config.use_rope:
            # No positional term at all (Jamba's attention: the state layers
            # around it carry the order); ``cos``/``sin`` may be None.
            return q, k, v
        return (
            apply_rope(q, cos, sin, positions),
            apply_rope(k, cos, sin, positions if k_positions is None else k_positions),
            v,
        )


def block_finish(
    lp: Params,
    x: jnp.ndarray,
    attn: jnp.ndarray,
    config: LlamaConfig,
    tp_axis: str | None = None,
    moe_valid: jnp.ndarray | None = None,
    moe_dispatch: str = "auto",
    fusion: tuple | None = None,
    moe_counts: bool = False,
    moe_layer=None,
):
    """Shared tail: out-projection + residual, rms_2 -> SwiGLU + residual,
    with the tensor-parallel psums at the two partial-sum points. Which norms
    run is the tree's (placement as data: ``ln_mlp`` on the feed-forward's
    input, ``ln_post_attn`` / ``ln_post_mlp`` on a branch's output; Gemma-2
    has all, OLMo's block the output ones alone). A layer
    tree carrying a "router" runs the Mixtral MoE MLP instead of the dense
    SwiGLU (experts sharded over tp; same partial-sum + psum convention).
    ``moe_valid`` ([b, chunk] bool) marks pad slots whose routed assignments
    must not consume expert capacity (ops/moe.py capacity dispatch).
    ``fusion`` (resolved (set, impl), ops/fuse.resolve_fusion; None = from
    the config): "norm" folds rms_2 into the fused gate|up projection
    (ops/pallas/fused_norm_matmul.py) on the dense ``w_gu`` path —
    bit-identical either way. ``moe_counts`` (a tree with a "router" only):
    return (x, ``moe.held_counts`` of the layer's dispatch) for the decode
    program's account of its expert load. ``moe_layer``: the tree's routed
    experts are a RUN of layers' stacks and this is the traced index of the
    one to use (``ops/moe.moe_swiglu``'s ``layer``)."""
    b, chunk, _ = x.shape
    off = config.rmsnorm_offset
    if fusion is None:
        fusion = resolve_fusion(config)
    fusions, fimpl = fusion
    with jax.named_scope(MIXER_OUT):
        o = qmat(attn.reshape(b, chunk, -1), lp["wo"]).astype(x.dtype)
        if tp_axis is not None:
            o = jax.lax.psum(o, tp_axis)
        if "ln_post_attn" in lp:
            # Gemma-2 post-attention norm: applied to the branch output (after
            # the tp psum — norming a partial sum would be wrong) before the
            # residual add.
            o = rms_norm(o, lp["ln_post_attn"], config.rms_norm_eps, off)
        x = x + o
    with jax.named_scope(FEED_FORWARD):
        if "norm" in fusions and "w_gu" in lp and "router" not in lp and "ln_mlp" in lp:
            # rms_2 folded into the gate|up matmul; the epilogue is the literal
            # swiglu_gu tail, so the branch is byte-identical to the unfused one.
            gu = fused_norm_matmul(
                x, lp["ln_mlp"], lp["w_gu"],
                eps=config.rms_norm_eps, offset=off, impl=fimpl,
            )
            mlp = swiglu_gu_from(
                gu, lp["w_down"], config.hidden_activation
            ).astype(x.dtype)
            if tp_axis is not None:
                mlp = jax.lax.psum(mlp, tp_axis)
            if "ln_post_mlp" in lp:
                mlp = rms_norm(mlp, lp["ln_post_mlp"], config.rms_norm_eps, off)
            return x + mlp
        # No ``ln_mlp`` = no norm on the feed-forward's input (OLMo's block norms
        # its output instead: ``ln_post_mlp`` below).
        h = rms_norm(x, lp["ln_mlp"], config.rms_norm_eps, off) if "ln_mlp" in lp else x
        if "router" in lp:
            mlp = moe_swiglu(
                h, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                config.num_experts_per_tok, tp_axis=tp_axis,
                norm_topk=config.norm_topk_prob, valid=moe_valid,
                dispatch=moe_dispatch, scoring=config.moe_scoring,
                scale=config.routed_scaling_factor,
                expert_offset=config.expert_offset, with_counts=moe_counts,
                layer=moe_layer, router_bias=lp.get("router_bias"),
                n_group=config.n_group, topk_group=config.topk_group,
            )
            if moe_counts:
                mlp, counts = mlp
            mlp = mlp.astype(x.dtype)
            if "sh_gu" in lp or "sh_gate" in lp:
                # The always-on shared expert (the same on every tp shard and on
                # every rank of an expert-parallel deployment), a scope its own.
                with jax.named_scope(SHARED_EXPERT):
                    if "sh_gu" in lp:  # fused gate|up (ops/fuse.py)
                        shared = swiglu_gu(h, lp["sh_gu"], lp["sh_down"])
                    else:
                        shared = swiglu(h, lp["sh_gate"], lp["sh_up"], lp["sh_down"])
                    if "se_gate" in lp:
                        # Qwen2-MoE's and Qwen3-Next's learned sigmoid gate on it.
                        shared = shared * jax.nn.sigmoid(qmat(h, lp["se_gate"]))
                    mlp = mlp + shared.astype(x.dtype)
        elif "w_gu" in lp:  # fused gate|up (ops/fuse.py): one matmul, split after
            mlp = swiglu_gu(
                h, lp["w_gu"], lp["w_down"], activation=config.hidden_activation
            ).astype(x.dtype)
        else:
            mlp = swiglu(
                h, lp["w_gate"], lp["w_up"], lp["w_down"],
                activation=config.hidden_activation,
            ).astype(x.dtype)
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
        if "ln_post_mlp" in lp:
            mlp = rms_norm(mlp, lp["ln_post_mlp"], config.rms_norm_eps, off)
        return (x + mlp, counts) if moe_counts else x + mlp


def block_forward(
    lp: Params,
    x: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
    pos: jnp.ndarray,
    config: LlamaConfig,
    tp_axis: str | None = None,
    cached_prefill: bool = False,
    rolling: bool = False,
    valid_len: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decoder block over a token chunk.

    Args:
      lp: this layer's weights (unstacked). Head counts are inferred from the
        projection shapes, NOT the config — under tensor parallelism each shard
        holds num_heads/tp of them (parallel/tensor.py).
      x: [batch, chunk, hidden] activations.
      k_cache/v_cache: [batch, n_kv, max_seq, head_dim] this layer's KV store
        (head-major, models/llama/cache.py).
      cos/sin: rope tables.
      positions: [batch, chunk] absolute positions of the chunk tokens.
      pos: scalar write offset (== positions[:, 0]).
      tp_axis: mesh axis name for Megatron-style tensor parallelism: the
        attention out-projection and the MLP down-projection produce partial
        sums over the sharded head/intermediate dims, reduced here with psum
        before each residual add. None = single-shard weights, no collectives.
      rolling: STATIC — the cache is a rolling window buffer (slot = pos %
        cache_len, cache.py); requires config.sliding_window. Unifies the
        prefill/decode attention variants into one cache read with
        reconstructed slot positions.
      valid_len: scalar count of real (non-padded) tokens in the chunk —
        needed when rolling so padded bucket tails don't evict live keys.

    Returns (x_out, k_cache, v_cache).
    """
    b, chunk, _ = x.shape

    q, k, v = block_qkv(lp, x, cos, sin, positions, config)

    win = config.sliding_window
    # Gemma-family attention knobs: score scale decoupled from head_dim,
    # tanh soft-capping, and a per-layer window gate carried IN the layer
    # tree ("win_flag", set at load/init for the alternating local/global
    # pattern) so it rides layer slicing/stacking through every backend.
    attn_kw = dict(
        window=win,
        window_flag=lp.get("win_flag"),
        scale=config.attn_scale,
        softcap=config.attn_logit_softcap,
    )
    if rolling:
        # The rolling ring cache stays on the XLA path deliberately: its
        # buffer is already window-sized (reads are O(window) by
        # construction, the pruning a kernel would add), and slot positions
        # are permuted by the ring wrap, which breaks the contiguous-block
        # interval pruning the Pallas kernels are built on.
        assert win is not None, "rolling cache requires sliding_window"
        vl = jnp.int32(chunk) if valid_len is None else valid_len
        k_cache, v_cache = write_layer_rolling(k_cache, v_cache, k, v, pos, vl)
        with jax.named_scope(MIXER):
            kv_pos = rolling_kv_positions(k_cache.shape[2], pos, vl)
            kv_positions = jnp.broadcast_to(
                kv_pos[None, :], (b, k_cache.shape[2])
            )
            attn = gqa_attention_hm(
                q, k_cache, v_cache, positions, kv_positions, **attn_kw
            )
        x = block_finish(lp, x, attn, config, tp_axis=tp_axis)
        return x, k_cache, v_cache

    k_cache, v_cache = write_layer(k_cache, v_cache, k, v, pos)

    with jax.named_scope(MIXER):
        impl = resolve_attention_impl(config.attention_impl)
        # Per-family attention knobs threaded into the Pallas kernels: sliding
        # window (static, per-layer traced gate), scale override, tanh softcap.
        pallas_kw = dict(
            window=win,
            window_flag=lp.get("win_flag"),
            scale=config.attn_scale,
            softcap=config.attn_logit_softcap,
        )
        if chunk > 1 and cached_prefill:
            # Prefill CONTINUATION: a chunk at pos > 0 attends to the whole live
            # cache prefix (which already contains this chunk's keys, written
            # above). This is what lets long prompts prefill in bounded chunks
            # instead of one giant compile. The Pallas kernel streams only the
            # live, causally-needed cache blocks; the XLA fallback reads the full
            # cache and hides dead slots behind the position mask.
            if impl == "pallas":
                q_starts = jnp.broadcast_to(pos, (b,)).astype(jnp.int32)
                attn = chunk_prefill_attention(
                    q, k_cache, v_cache, q_starts, q_starts + chunk, **pallas_kw
                )
            else:
                kv_positions = jnp.broadcast_to(
                    jnp.arange(k_cache.shape[2], dtype=jnp.int32)[None, :],
                    (b, k_cache.shape[2]),
                )
                attn = gqa_attention_hm(
                    q, k_cache, v_cache, positions, kv_positions, **attn_kw
                )
        elif chunk > 1:
            # Prefill from offset 0 (callers pass pos=0 when cached_prefill is
            # False): the chunk attends only within itself — avoids materializing
            # [chunk, max_seq] score rows against an empty cache.
            if impl == "pallas":
                attn = flash_attention(q, k, v, **pallas_kw)
            else:
                attn = gqa_attention(q, k, v, positions, positions, **attn_kw)
        else:
            # Decode: attend over the live cache prefix. The Pallas kernel prunes
            # blocks past pos (and behind the window); the XLA path reads the
            # whole cache and hides dead slots behind the position mask.
            if impl == "pallas":
                lengths = jnp.broadcast_to(pos + 1, (b,)).astype(jnp.int32)
                attn = decode_attention(
                    q, k_cache, v_cache, lengths, None, **pallas_kw
                )
            else:
                kv_positions = jnp.broadcast_to(
                    jnp.arange(k_cache.shape[2], dtype=jnp.int32)[None, :],
                    (b, k_cache.shape[2]),
                )
                attn = gqa_attention_hm(
                    q, k_cache, v_cache, positions, kv_positions, **attn_kw
                )

    x = block_finish(lp, x, attn, config, tp_axis=tp_axis)
    return x, k_cache, v_cache


def blocks_forward(
    layers: Params,
    x: jnp.ndarray,
    kv: KVCache,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    pos: jnp.ndarray,
    config: LlamaConfig,
    valid: jnp.ndarray | None = None,
    tp_axis: str | None = None,
    cached_prefill: bool = False,
    rolling: bool = False,
    valid_len: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Run a stacked block range as one ``lax.scan`` over the layer axis.

    This is the unit a pipeline stage executes: the reference ships a contiguous
    layer run to a worker as one batch op (llama.rs:95-114, worker.rs:218-229);
    here the run is one compiled scan.

    ``valid`` (optional [n_layers] bool) gates each layer's contribution — used
    by ragged pipeline stages padded with inert layers (parallel/pipeline.py).
    ``tp_axis`` threads through to block_forward's tensor-parallel reductions.
    ``rolling``/``valid_len`` select the rolling-window cache layout
    (block_forward).
    """
    b, chunk, _ = x.shape
    positions = pos + jnp.broadcast_to(
        jnp.arange(chunk, dtype=jnp.int32)[None, :], (b, chunk)
    )
    # Positions are layer-invariant: gather the rope rows ONCE per step
    # instead of once per layer inside the scan (apply_rope's 3-D form).
    # (The rolling path's reconstructed ring positions feed only the
    # attention mask, never rope — q/k always rope at ``positions``.)
    # Stacked dual-rope tables gather BOTH planes; block_qkv selects.
    if cos.ndim == 3:
        cos, sin = cos[:, positions], sin[:, positions]
    else:
        cos, sin = cos[positions], sin[positions]

    def body(carry, per_layer):
        x = carry
        lp, k_c, v_c, ok = per_layer
        x_new, k_c, v_c = block_forward(
            lp, x, k_c, v_c, cos, sin, positions, pos, config,
            tp_axis=tp_axis, cached_prefill=cached_prefill,
            rolling=rolling, valid_len=valid_len,
        )
        x = x_new if valid is None else jnp.where(ok, x_new, x)
        return x, (k_c, v_c)

    ok = jnp.ones((kv.n_layers,), bool) if valid is None else valid
    x, (k_out, v_out) = jax.lax.scan(body, x, (layers, kv.k, kv.v, ok))
    return x, KVCache(k=k_out, v=v_out)


def _final_softcap(logits: jnp.ndarray, config: LlamaConfig) -> jnp.ndarray:
    """Gemma-2 final-logit soft-capping (no-op for every other family)."""
    cap = config.final_logit_softcap
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def head_forward(
    params: Params,
    x: jnp.ndarray,
    seq_len: jnp.ndarray,
    config: LlamaConfig,
    fusion: tuple | None = None,
) -> jnp.ndarray:
    """Final norm + LM head at the last valid position -> [batch, vocab] f32.

    Shared by the local and pipelined paths so their numerics can't diverge.
    Slices BEFORE ln_f/lm_head so the vocab projection runs on [batch, 1, hidden]
    (llama.rs:119-137 slices the last position the same way). ``fusion``
    ((set, impl) from ops/fuse.resolve_fusion; None = from the config):
    "norm" folds ln_f into the lm_head projection
    (ops/pallas/fused_norm_matmul.py) — tied embeddings keep the unfused
    path (the transposed weight would materialize a copy per call).
    """
    with jax.named_scope(HEAD):
        x_last = jax.lax.dynamic_slice_in_dim(x, seq_len - 1, 1, axis=1)
        if fusion is None:
            fusion = resolve_fusion(config)
        fusions, fimpl = fusion
        if "norm" in fusions and not config.tie_word_embeddings:
            logits = fused_norm_matmul(
                x_last, params["ln_f"], params["lm_head"],
                eps=config.rms_norm_eps, offset=config.rmsnorm_offset,
                impl=fimpl,
            )[:, 0, :].astype(jnp.float32)
            return _final_softcap(logits, config)
        x_last = rms_norm(
            x_last, params["ln_f"], config.rms_norm_eps, config.rmsnorm_offset
        )
        lm_head = params["embed"].T if config.tie_word_embeddings else params["lm_head"]
        logits = qmat(x_last[:, 0, :], lm_head).astype(jnp.float32)
        return _final_softcap(logits, config)


def head_forward_all(
    params: Params,
    x: jnp.ndarray,
    config: LlamaConfig,
) -> jnp.ndarray:
    """Final norm + LM head at EVERY chunk position -> [batch, chunk, vocab] f32.

    Used by speculative verification (models/llama/speculative.py): one chunked
    forward scores all draft positions at once. Same ln_f/lm_head weights as
    head_forward — numerics cannot diverge.
    """
    with jax.named_scope(HEAD):
        x = rms_norm(x, params["ln_f"], config.rms_norm_eps, config.rmsnorm_offset)
        lm_head = params["embed"].T if config.tie_word_embeddings else params["lm_head"]
        return _final_softcap(qmat(x, lm_head).astype(jnp.float32), config)


def forward_all_logits(
    params: Params,
    tokens: jnp.ndarray,
    kv: KVCache,
    pos: jnp.ndarray,
    config: LlamaConfig,
    cached_prefill: bool = True,
) -> tuple[jnp.ndarray, KVCache]:
    """Full-model forward returning logits at every chunk position.

    The speculative-verify primitive: feed [last_token, draft_0..draft_{K-1}]
    at offset ``pos`` and read each position's next-token distribution.
    """
    cos, sin = model_rope_tables(config, kv.max_seq_len)
    x = embed_tokens(params, tokens, config)
    x, kv = blocks_forward(
        params["layers"], x, kv, cos, sin, pos, config, cached_prefill=cached_prefill
    )
    return head_forward_all(params, x, config), kv


def forward(
    params: Params,
    tokens: jnp.ndarray,
    kv: KVCache,
    pos: jnp.ndarray,
    seq_len: jnp.ndarray,
    config: LlamaConfig,
    cached_prefill: bool = False,
    rolling: bool = False,
    rope_len: int | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Full-model forward: embed -> blocks -> ln_f -> lm_head at last valid position.

    Args:
      tokens: [batch, chunk] int32 (chunk may be padded; see seq_len).
      kv: full-depth KVCache.
      pos: scalar offset of tokens[:, 0] in the sequence.
      seq_len: scalar count of VALID tokens in the chunk (logits taken at
        seq_len - 1, cf. llama.rs:119-137 last-position slice).
      cached_prefill: STATIC — chunk > 1 arriving at pos > 0 (a long prompt
        prefilling in bounded chunks); selects cache-prefix attention.
      rolling: STATIC — kv is a rolling window buffer smaller than the
        logical sequence bound (sliding-window models; cache.py).
      rope_len: STATIC — RoPE table length; REQUIRED when rolling (positions
        exceed the physical cache length, which otherwise sizes the table).

    Returns (logits [batch, vocab] f32, updated KVCache).
    """
    cos, sin = model_rope_tables(config, rope_len if rope_len is not None else kv.max_seq_len)
    x = embed_tokens(params, tokens, config)
    x, kv = blocks_forward(
        params["layers"], x, kv, cos, sin, pos, config,
        cached_prefill=cached_prefill, rolling=rolling, valid_len=seq_len,
    )
    return head_forward(params, x, seq_len, config), kv


def count_params(params: Params) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
