"""Llama model configuration.

Parses the HuggingFace ``config.json`` schema, covering the same field subset the
reference framework reads (reference: cake-core/src/models/llama3/config.rs:13-26,
45-58) plus the fields needed for Llama 3.1+ rope scaling.

Unlike the reference (which hard-caps MAX_SEQ_LEN at 4096, config.rs:6), the max
sequence length here is a runtime choice: ``max_position_embeddings`` from the
checkpoint is the default ceiling, and callers size their KV caches explicitly.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any


# Every HF ``model_type`` from_hf_dict takes. The "unsupported" message is
# built from this tuple, so a family cannot be in one and not the other.
SUPPORTED_MODEL_TYPES = (
    "llama", "qwen2", "mistral", "mixtral", "qwen2_moe",
    "gemma", "gemma2", "phi3", "qwen3", "qwen3_moe", "gemma3_text", "jamba",
    "pangu_ultra_moe", "olmo_hybrid", "laguna", "deepseek_v32", "lfm2_moe",
    "qwen3_next", "sdar_moe",
)

# The two kinds a decoder layer's token mixer can be (``layer_kinds``).
ATTENTION, STATE = "attention", "state"
# Which mixer a STATE layer runs (``state_mixer``): Mamba-1's diagonal
# selective scan over a vector state a channel (ops/ssm.py), or a gated delta
# rule over a matrix state a head (ops/delta_rule.py), or a gated short
# convolution whose state is the convolution's window ALONE
# (ops/short_conv.py: no float32 state at all).
MAMBA, GATED_DELTA, SHORT_CONV = "mamba", "gated_delta", "short_conv"
# The two kinds its feed-forward can be (``ff_kinds``).
DENSE, SPARSE = "dense", "sparse"
# The kinds an ATTENTION layer can be (``attention_kinds``): every key the
# causal mask admits, or only those inside ``sliding_window``. A model with
# both keeps K and V in a pool a kind (``cache_kind`` "kv+kinds").
FULL, SLIDING = "full", "sliding"
# What a lane keeps on the device between programs (``cache_kind``): K and V
# a KV head, those beside a recurrent state, one latent a token, or K and V
# in a pool a kind of attention layer (a windowed kind's pages are freed
# behind the window).
CACHE_KV, CACHE_KV_STATE, CACHE_LATENT = "kv", "kv+state", "latent"
CACHE_KV_KINDS = "kv+kinds"
# Prompts are laid out in buckets of this many slots (``batch.BUCKET_MULTIPLE``
# is this number): a diffusion block's length has to divide it.
BLOCK_MULTIPLE = 16
# A latent a token and, behind the SAME block table, the key of a learned
# index that chooses which cached tokens a query attends (``deepseek_v32``).
CACHE_LATENT_INDEX = "latent+index"
# How a model generates (``generation``): one token a step from the last
# one's logits, or a BLOCK of ``block_length`` slots at a time by diffusion:
# the block starts as ``mask_token_id``, each denoising pass runs the model
# over the block (bidirectional inside it, causal over the earlier blocks) and
# reveals some slots, and a last pass commits the finished block's K and V.
AUTOREGRESSIVE, BLOCK_DIFFUSION = "autoregressive", "block_diffusion"
# Which masked slots a denoising pass reveals (``remask``).
REMASK_RULES = ("sequential", "low_confidence_static", "low_confidence_dynamic")


def _expert_share(d: dict, held_key: str, first_key: str, default: int) -> tuple[int, int, int]:
    """(held, ranked, first) of a configuration that may hold a SHARE of its
    routed experts: ``held_key`` counts the experts held, ``<held_key>_total``
    (absent = the same: the whole model) what the router ranks, ``first_key``
    where the held ones start among them."""
    held = int(d.get(held_key, default))
    ranked = int(d.get(f"{held_key}_total", held))
    first = int(d.get(first_key, 0))
    if not 0 <= first <= ranked - held:
        raise ValueError(
            f"experts {first}..{first + held - 1} are held of {ranked}: "
            f"{first_key} + {held_key} must not pass {held_key}_total"
        )
    return held, ranked, first


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """Llama 3.1-style rope frequency scaling (absent => plain RoPE)."""

    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    rope_type: str = "llama3"


@dataclasses.dataclass(frozen=True)
class KindRope:
    """One attention kind's rotary term (``LlamaConfig.kind_ropes``): plain
    (``factor`` 1) or YaRN (HF ``rope_type: yarn``: the inverse frequencies
    blended between ``theta^(-2i/d)`` and that over ``factor`` by a ramp from
    ``beta_fast`` to ``beta_slow`` rotations over
    ``original_max_position_embeddings``, cos and sin multiplied by
    ``attention_factor``), over the first ``rotary_dim`` numbers of a head
    (``partial_rotary_factor``; the rest pass through)."""

    theta: float
    rotary_dim: int
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    """Architecture hyperparameters for a Llama-family decoder-only model.

    "Family" is wider than the reference's Llama-3-only scope: the same
    decoder core (RMSNorm -> GQA+RoPE -> gated MLP) runs Llama 3.x, Qwen2/2.5
    (QKV bias), Mistral (sliding window, explicit head_dim), Mixtral and
    Qwen2-MoE (sparse MoE), Gemma and Gemma-2 (GeGLU, (1+w) norms, embedding
    scale, soft-caps, alternating window), and Phi-3 (fused checkpoint
    tensors), dispatched by HF ``model_type`` — each pinned against
    transformers (tests/test_model_families.py, test_moe.py, test_gemma.py).
    """

    hidden_size: int = 4096
    intermediate_size: int = 14336
    vocab_size: int = 128256
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    max_position_embeddings: int = 8192
    bos_token_id: int = 128000
    eos_token_ids: tuple[int, ...] = (128001, 128009)
    tie_word_embeddings: bool = False
    rope_scaling: RopeScaling | None = None
    # HF model_type: "llama", "qwen2", or "mistral" — selects the chat
    # template (chat.py) and defaults; the decoder core is shared.
    model_type: str = "llama"
    # Qwen2: q/k/v projections carry a bias (o_proj does not).
    attention_bias: bool = False
    # Mistral: keys/values further than this behind the query are masked.
    # None = full causal. The preallocated cache still stores the whole
    # sequence (no rolling buffer); the window is enforced by masking.
    sliding_window: int | None = None
    # Mistral-Nemo style: head_dim decoupled from hidden_size // heads.
    head_dim_override: int | None = None
    # Qwen3 / Gemma-3: per-head RMSNorm on q and k after projection, before
    # RoPE (head_dim-wide weights q_norm/k_norm in every layer; Gemma-3's
    # use the (1+w) offset convention via rmsnorm_offset).
    qk_norm: bool = False
    # Gemma-3 dual rope: sliding layers rope at this theta (unscaled), full
    # layers at rope_theta (+ rope_scaling). None = single rope.
    rope_local_base_freq: float | None = None
    # Per-layer sliding flags (Gemma-3 layer_types 5:1 pattern). None = the
    # family default (gemma2's even/odd comes from alt_sliding_window).
    sliding_pattern: tuple[bool, ...] | None = None
    # Sparse MoE (Mixtral / Qwen2-MoE): 0 = dense MLP; > 0 = number of
    # experts, with num_experts_per_tok of them combined per token
    # (ops/moe.py).
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # Renormalize the top-k routing probabilities to sum 1 (Mixtral always
    # does; Qwen2-MoE ships norm_topk_prob, usually false).
    norm_topk_prob: bool = True
    # Qwen2-MoE: experts use their own intermediate size (None = the dense
    # intermediate_size, as in Mixtral) and an always-on shared expert with
    # a learned sigmoid gate.
    moe_intermediate_size: int | None = None
    shared_expert_intermediate_size: int | None = None
    # Gemma family knobs. hidden_activation: the MLP gate activation ("silu"
    # = SwiGLU everywhere else, "gelu_tanh" = Gemma's GeGLU). rmsnorm_offset:
    # norm weights stored zero-centered, applied as (1 + w).
    # embedding_scale: embeddings multiplied by sqrt(hidden) after lookup.
    hidden_activation: str = "silu"
    rmsnorm_offset: bool = False
    embedding_scale: float | None = None
    # Gemma-2 extras: tanh soft-capping of attention scores / final logits,
    # an attention scale decoupled from head_dim (query_pre_attn_scalar),
    # post-attention/post-MLP norms, and the alternating local/global window
    # pattern (even layers sliding, odd global).
    attn_logit_softcap: float | None = None
    final_logit_softcap: float | None = None
    query_pre_attn_scalar: int | None = None
    post_block_norms: bool = False
    alt_sliding_window: bool = False
    # Attention kernel selection: "auto" uses the Pallas kernels
    # (ops/pallas/{flash,decode}_attention.py) on TPU and the XLA einsum path
    # elsewhere; "pallas"/"xla" force one (tests force both for parity checks).
    attention_impl: str = "auto"
    # Decode hot-path op fusion (ops/fuse.py parse_fusion_spec): "none", or
    # "<set>[@impl]" with set ⊆ {norm, tail} (or "all") selecting which op
    # fusions run, and impl ∈ {auto, pallas, xla} selecting the kernels vs
    # their XLA twins ("auto" = pallas on TPU). The twins are the unfused
    # ops; a kernel agrees with its twin to rounding. Like attention_impl
    # this is a runtime knob, never an HF field.
    fusion_impl: str = "none"
    # Hybrid stacks: which layers mix tokens by attention and which by a
    # recurrent state (the layer's mixer: ``state_mixer``). Two sources, one
    # reader (``layer_kinds``): an explicit list (``layer_types``, Olmo-Hybrid)
    # or a period (Jamba: layer i is attention when ``i % attn_layer_period ==
    # attn_layer_offset``). Neither = every layer is attention (every other
    # family).
    layer_types: tuple[str, ...] | None = None
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    state_mixer: str = MAMBA
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # The gated delta rule's sizes (HF ``linear_*``): heads of
    # ``linear_key_head_dim`` keys and ``linear_value_head_dim`` values, a
    # causal convolution of ``linear_conv_kernel_dim`` taps over q, k and v,
    # and whether beta reaches 2 (a negative eigenvalue of I - beta k k^T).
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False
    # The gated short convolution's taps (HF ``conv_L_cache``; ``lfm2_moe``):
    # a depthwise causal convolution over ``hidden_size`` channels.
    short_conv_taps: int = 3
    # False = attention carries no positional term at all (Jamba,
    # Olmo-Hybrid: the state layers carry the order).
    use_rope: bool = True
    # The share of a head's numbers the rotary term turns (HF
    # ``partial_rotary_factor``): the first ``rotary_dim``, the rest pass.
    partial_rotary_factor: float = 1.0
    # Norm placement, as data. ``pre_block_norms``: a norm on each branch's
    # INPUT (``ln_attn`` / ``ln_mlp``; every family but OLMo's).
    # ``post_block_norms`` (above): one on each branch's OUTPUT before the
    # residual add; Gemma-2 has both, OLMo-2/3's block the second INSTEAD of
    # the first. ``qk_norm_whole``: q and k are normed over the whole
    # projection width (OLMo), not a head (``qk_norm``).
    pre_block_norms: bool = True
    qk_norm_whole: bool = False
    # Latent attention (MLA; ``pangu_ultra_moe``): queries and keys/values
    # are projected through low-rank latents, a head's query and key are a
    # no-position part beside a rotary part that all heads' keys share, and
    # the cache holds ``kv_lora_rank + qk_rope_head_dim`` numbers a token a
    # layer, whatever the number of heads. ``kv_lora_rank`` 0 = not latent.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Layers below this index keep the dense feed-forward in a model whose
    # others are sparse (``ff_kinds``). 0 = every layer is of one kind.
    first_k_dense_replace: int = 0
    # A share of the routed experts: ``num_local_experts`` are HELD here,
    # the router ranks ``router_experts`` (0 = the held ones: the whole
    # model) and the first held one is ``expert_offset`` of those. What the
    # absent experts would add to a token is left out (expert parallelism
    # without its exchange: ops/moe.py).
    router_experts: int = 0
    expert_offset: int = 0
    # Router scores: "softmax" over all experts, or "sigmoid" of each
    # logit (then renormalised over the chosen by ``norm_topk_prob``);
    # the combine weights are multiplied by ``routed_scaling_factor``.
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # Group-limited choice (the DeepSeek-V3 family's ``noaux_tc``): the ranked
    # experts lie in ``n_group`` equal groups, a group's score is the sum of
    # its two largest, the best ``topk_group`` groups stay and the experts are
    # chosen inside them. ``router_bias``: a learned correction (a layer's
    # ``router_bias`` [ranked]) added to the scores for CHOOSING only; the
    # combine weights are the scores without it. 1 / False = neither.
    n_group: int = 1
    topk_group: int = 1
    router_bias: bool = False
    # A learned index over the cached tokens (``deepseek_v32``): a layer
    # scores every cached token for each query with ``index_n_heads`` heads
    # of ``index_head_dim`` against ONE index key a token, and attention
    # reads the ``index_topk`` best and no other. 0 = attention reads all.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The rotary term of a latent model's 64 rotary numbers when it is not
    # plain ``rope_theta`` (YaRN: ``ops/rope.yarn_frequencies``), and the
    # factor ``m`` whose square multiplies the score scale (``mla_scale``).
    latent_rope: KindRope | None = None
    latent_mscale: float = 1.0
    # Attention layers of more than one kind (``laguna``): per layer, which
    # kind (``attention_types``: FULL or SLIDING; None = one kind, the
    # family's), how many query heads (``heads_per_layer``; None =
    # ``num_attention_heads`` everywhere) and, a kind, its rotary term
    # (``kind_ropes``: (kind, KindRope) pairs). A SLIDING layer's window is
    # ``sliding_window``.
    attention_types: tuple[str, ...] | None = None
    heads_per_layer: tuple[int, ...] | None = None
    kind_ropes: tuple[tuple[str, KindRope], ...] | None = None
    # A gate on the attention output before ``o_proj``: ``attn_gate_act`` of
    # a linear map of the layer's normed input. "per-head": one scalar a
    # query head (``wg`` [hidden, heads]; ``laguna``); "per-number": one a
    # number of the output (``wg`` [hidden, heads * head_dim], in a checkpoint
    # the second half of each head's ``q_proj`` rows; ``qwen3_next``). None =
    # no gate.
    attn_gate: str | None = None
    attn_gate_act: str = "sigmoid"
    # The feed-forward of every layer as a list (``mlp_layer_types``); None =
    # by ``first_k_dense_replace``.
    ff_types: tuple[str, ...] | None = None
    # How the model generates (``AUTOREGRESSIVE`` / ``BLOCK_DIFFUSION``): a
    # fact of the checkpoint, never a caller's to set against it. For block
    # diffusion: the block's length, the id a masked slot holds, and the
    # serving defaults a flag may override (``--denoise-steps``, ``--remask``,
    # ``--confidence-threshold``): passes a block before its commit, and which
    # slots a pass reveals (``REMASK_RULES``).
    generation: str = AUTOREGRESSIVE
    block_length: int = 0
    mask_token_id: int = -1
    denoising_steps: int = 0
    remask: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    # Chat-template override (--chat-template; not an HF field). None = pick
    # by model_type. Needed for Llama-2-chat checkpoints, whose config.json
    # is indistinguishable from base Llama (chat.DIALOG_ENCODERS keys).
    chat_template: str | None = None

    @property
    def dialog_template(self) -> str:
        return self.chat_template or self.model_type

    @property
    def attn_scale(self) -> float | None:
        """Score scale override (None = head_dim**-0.5): THE one mapping of
        Gemma-2's query_pre_attn_scalar, shared by every execution backend."""
        if self.query_pre_attn_scalar is None:
            return None
        return float(self.query_pre_attn_scalar) ** -0.5

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """The token mixer of every layer, in the model's order: THE one
        per-layer fact model, cache, loader and backend ask, whichever way
        the checkpoint said it (a list or a period)."""
        if self.layer_types is not None:
            return self.layer_types
        p = self.attn_layer_period
        return tuple(
            ATTENTION if not p or i % p == self.attn_layer_offset else STATE
            for i in range(self.num_hidden_layers)
        )

    @property
    def has_state_layers(self) -> bool:
        return STATE in self.layer_kinds

    def layers_of(self, kind: str) -> tuple[int, ...]:
        """Absolute indices of the layers of one kind."""
        return tuple(i for i, k in enumerate(self.layer_kinds) if k == kind)

    @property
    def layer_runs(self) -> tuple[tuple[str, int, int], ...]:
        """Maximal runs of layers alike in mixer kind AND feed-forward kind
        (``run_ff_kinds`` says the second) in the model's order, as (kind,
        lo, hi) over that KIND's own stack: jamba2-3b walks state[0:7],
        attention[0:1], state[7:20], attention[1:2], state[20:26]; a model
        whose leading layers are dense and the rest sparse (``lfm2_moe``)
        state[0:2], attention[0:1], state[2:5], ..."""
        runs: list[tuple[str, int, int]] = []
        seen = {ATTENTION: 0, STATE: 0}
        last = None
        for k, f in zip(self.layer_kinds, self.ff_kinds, strict=True):
            if runs and last == (k, f):
                runs[-1] = (k, runs[-1][1], runs[-1][2] + 1)
            else:
                runs.append((k, seen[k], seen[k] + 1))
            last = (k, f)
            seen[k] += 1
        return tuple(runs)

    @property
    def run_ff_kinds(self) -> tuple[str, ...]:
        """The feed-forward kind of each of ``layer_runs``, in their order."""
        ff = self.ff_kinds
        return tuple(ff[self.layers_of(k)[lo]] for k, lo, _ in self.layer_runs)

    @property
    def ff_kinds(self) -> tuple[str, ...]:
        """The feed-forward of every layer: the second per-layer fact."""
        if self.ff_types is not None:
            return self.ff_types
        if not self.num_local_experts:
            return (DENSE,) * self.num_hidden_layers
        return tuple(
            DENSE if i < self.first_k_dense_replace else SPARSE
            for i in range(self.num_hidden_layers)
        )

    @property
    def ff_runs(self) -> tuple[tuple[str, int, int], ...]:
        """Maximal runs of one feed-forward kind, as (kind, lo, hi) over the
        model's own layer indices: a latent model's layers stack by these
        (models/llama/latent.py), as a hybrid's by ``layer_runs``."""
        runs: list[tuple[str, int, int]] = []
        for i, k in enumerate(self.ff_kinds):
            if runs and runs[-1][0] == k:
                runs[-1] = (k, runs[-1][1], i + 1)
            else:
                runs.append((k, i, i + 1))
        return tuple(runs)

    @property
    def cache_kind(self) -> str:
        """What a lane keeps on the device: THE fact the backend's leaf, the
        programs' shapes and the capability check are chosen by."""
        if self.kv_lora_rank:
            return CACHE_LATENT_INDEX if self.index_topk else CACHE_LATENT
        if self.attention_types is not None:
            return CACHE_KV_KINDS
        return CACHE_KV_STATE if self.has_state_layers else CACHE_KV

    @property
    def attention_kinds(self) -> tuple[str, ...]:
        """The kinds of attention layer the model has, FULL first: one pool
        and one block table a kind (``paged_cache.PagePools``)."""
        if self.attention_types is None:
            return (FULL,)
        return tuple(k for k in (FULL, SLIDING) if k in self.attention_types)

    def kind_window(self, kind: str) -> int | None:
        """Keys a layer of ``kind`` admits behind its query; None = all."""
        return self.sliding_window if kind == SLIDING else None

    def kind_layers(self, kind: str) -> tuple[int, ...]:
        """Absolute indices of the attention layers of one kind."""
        return tuple(
            i for i, k in enumerate(self.attention_types or ()) if k == kind
        )

    @property
    def stack_runs(self) -> tuple[tuple[str, str, int, int, int], ...]:
        """Maximal runs of layers alike in attention kind AND feed-forward,
        as (kind, ff, lo, hi, first): ``lo..hi`` the model's own indices,
        ``first`` the run's first layer among its KIND's (its pool layer).
        Layers of different head counts cannot share a stacked scan
        (models/llama/kinds.py stacks by these)."""
        runs: list[tuple[str, str, int, int, int]] = []
        seen = dict.fromkeys(self.attention_kinds, 0)
        for i, (k, f) in enumerate(zip(self.attention_types, self.ff_kinds)):
            if runs and runs[-1][:2] == (k, f):
                runs[-1] = (*runs[-1][:3], i + 1, runs[-1][4])
            else:
                runs.append((k, f, i, i + 1, seen[k]))
            seen[k] += 1
        return tuple(runs)

    @property
    def latent_width(self) -> int:
        """Numbers a token's latent takes in the pool: the compressed K/V and
        the shared rotary key, padded to whole 128-lane tiles (512 + 64 ->
        640: what is STORED; the padding is zeros)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def mla_scale(self) -> float:
        """Latent attention's score scale: of the expanded head, times the
        square of YaRN's ``m`` where the rotary term is scaled."""
        dims = self.qk_nope_head_dim + self.qk_rope_head_dim
        return dims ** -0.5 * self.latent_mscale ** 2

    @property
    def n_router_experts(self) -> int:
        return self.router_experts or self.num_local_experts

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def state_shape(self) -> tuple[int, int] | None:
        """One lane's float32 state in one state layer, as the cache lays it
        out (the minor axis whole 128-lane tiles at published widths):
        Mamba's [d_state, d_inner]; the delta rule's [dk, H * dv], head h's
        S^T in columns h * dv .. (h + 1) * dv. None: the mixer keeps no such
        state (a short convolution's lane state is its window alone)."""
        if self.state_mixer == SHORT_CONV:
            return None
        if self.state_mixer == GATED_DELTA:
            return (
                self.linear_key_head_dim,
                self.linear_num_value_heads * self.linear_value_head_dim,
            )
        return (self.mamba_d_state, self.mamba_d_inner)

    @property
    def conv_window(self) -> tuple[int, int]:
        """(inputs kept, channels) of a state layer's causal convolution:
        Mamba's over u; the delta rule's over q, k and v side by side; the
        short convolution's over ``B * u``, ``hidden_size`` wide."""
        if self.state_mixer == SHORT_CONV:
            return (self.short_conv_taps - 1, self.hidden_size)
        if self.state_mixer == GATED_DELTA:
            channels = (
                2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim
            )
            return (self.linear_conv_kernel_dim - 1, channels)
        return (self.mamba_d_conv - 1, self.mamba_d_inner)

    @property
    def state_bytes_per_lane(self) -> int:
        """Recurrent state one lane holds over all state layers, whatever
        their mixer: the float32 state (``state_shape``; none for a mixer
        that keeps none) and the convolution's window (counted at 2 bytes,
        the served type)."""
        rows, cols = self.state_shape or (0, 0)
        kept, channels = self.conv_window
        per_layer = 4 * rows * cols + 2 * kept * channels
        return per_layer * len(self.layers_of(STATE))

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def num_query_groups(self) -> int:
        """Query heads per KV head (GQA group size)."""
        return self.num_attention_heads // self.num_key_value_heads

    def __post_init__(self) -> None:
        if self.head_dim_override is None and (
            self.hidden_size % self.num_attention_heads
        ):
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by "
                f"num_attention_heads {self.num_attention_heads} "
                "(set head_dim explicitly in config.json to decouple them)"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} not divisible by "
                f"num_key_value_heads {self.num_key_value_heads}"
            )

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "LlamaConfig":
        """Build from a parsed HF ``config.json`` dict.

        Mirrors the normalization in the reference's ``LlamaConfig::into_config``
        (config.rs:45-58): missing ``num_key_value_heads`` falls back to MHA, rope
        theta defaults, and eos may be a scalar or a list.
        """
        eos = d.get("eos_token_id", 128001)
        if isinstance(eos, int):
            eos_ids: tuple[int, ...] = (eos,)
        else:
            eos_ids = tuple(int(e) for e in eos)
        heads = int(d.get("num_attention_heads", 32))
        rs = None
        raw_rs = d.get("rope_scaling")
        if raw_rs and raw_rs.get("rope_type", raw_rs.get("type")) == "linear":
            # Plain linear frequency scaling (Gemma-3 global rope).
            rs = RopeScaling(
                factor=float(raw_rs.get("factor", 8.0)), rope_type="linear"
            )
        if raw_rs and raw_rs.get("rope_type", raw_rs.get("type")) == "llama3":
            rs = RopeScaling(
                factor=float(raw_rs.get("factor", 8.0)),
                low_freq_factor=float(raw_rs.get("low_freq_factor", 1.0)),
                high_freq_factor=float(raw_rs.get("high_freq_factor", 4.0)),
                original_max_position_embeddings=int(
                    raw_rs.get("original_max_position_embeddings", 8192)
                ),
            )
        model_type = str(d.get("model_type", "llama"))
        if model_type not in SUPPORTED_MODEL_TYPES:
            if model_type == "gemma3":
                raise ValueError(
                    "model_type 'gemma3' is the MULTIMODAL wrapper config; "
                    "use a text-only checkpoint (model_type 'gemma3_text') — "
                    "its fields live under the wrapper's text_config"
                )
            raise ValueError(
                f"unsupported model_type {model_type!r} "
                f"(supported: {', '.join(SUPPORTED_MODEL_TYPES)})"
            )
        if model_type == "jamba":
            return cls._jamba_from_hf_dict(d, eos_ids)
        if model_type == "pangu_ultra_moe":
            return cls._pangu_from_hf_dict(d, eos_ids)
        if model_type == "deepseek_v32":
            return cls._deepseek_v32_from_hf_dict(d, eos_ids)
        if model_type == "olmo_hybrid":
            return cls._olmo_hybrid_from_hf_dict(d, eos_ids)
        if model_type == "laguna":
            return cls._laguna_from_hf_dict(d, eos_ids)
        if model_type == "lfm2_moe":
            return cls._lfm2_moe_from_hf_dict(d, eos_ids)
        if model_type == "qwen3_next":
            return cls._qwen3_next_from_hf_dict(d, eos_ids)
        if model_type == "sdar_moe":
            return cls._sdar_moe_from_hf_dict(d)
        if model_type == "phi3" and d.get("rope_scaling"):
            # Phi-3 128k variants use longrope (per-dim su-scaled factors);
            # only the base-rope variants (4k/8k) are supported.
            raise ValueError(
                "phi3 rope_scaling (longrope) is not supported; use a "
                "base-context Phi-3 checkpoint"
            )
        if model_type in ("qwen2_moe", "qwen3_moe"):
            # Layers can individually opt out of MoE via these knobs; only
            # the uniform all-sparse shape (every shipped Qwen-MoE model)
            # is supported — mixed dense/sparse stacks are an explicit error.
            if int(d.get("decoder_sparse_step", 1)) != 1 or d.get(
                "mlp_only_layers"
            ):
                raise ValueError(
                    f"{model_type} with decoder_sparse_step != 1 or "
                    "mlp_only_layers needs per-layer dense/sparse mixing, "
                    "which this framework does not support"
                )
        n_layers = int(d.get("num_hidden_layers", 32))
        sliding_pattern = None
        if model_type == "gemma3_text":
            lt = d.get("layer_types")
            if lt is None:
                # Real checkpoints often ship only sliding_window_pattern
                # (default 6): every pattern-th layer is full attention.
                # Built over n_layers so the pattern length always matches
                # the actual stack depth.
                swp = int(d.get("sliding_window_pattern", 6))
                lt = [
                    "full_attention"
                    if swp > 0 and (i + 1) % swp == 0
                    else "sliding_attention"
                    for i in range(n_layers)
                ]
            sliding_pattern = tuple(t == "sliding_attention" for t in lt)
        head_dim = d.get("head_dim")
        if head_dim is None and model_type in ("qwen3", "qwen3_moe", "gemma3_text"):
            # HF class defaults regardless of hidden_size/heads (the
            # honor-the-class-default rule): Qwen3 128, Gemma3 256.
            head_dim = 256 if model_type == "gemma3_text" else 128
        hidden = int(d.get("hidden_size", 4096))
        if head_dim is not None and int(head_dim) * heads == hidden:
            head_dim = None  # redundant with the derived value
        sw = d.get("sliding_window")
        # Qwen2 ships sliding_window in config.json but gates it off with
        # use_sliding_window (default false) — honor the gate. When on,
        # transformers applies the window only to layers >= max_window_layers;
        # the common shipped shape (max_window_layers == num_hidden_layers)
        # means NO layer is windowed. A window on SOME layers needs a pool a
        # kind of layer (``cache_kind`` "kv+kinds": ``laguna`` is parsed into
        # one); this family's parser builds none, so the mixed shape is an
        # explicit error rather than wrong numerics.
        if model_type in ("qwen2", "qwen2_moe", "qwen3", "qwen3_moe"):
            if not d.get("use_sliding_window", False):
                sw = None
            else:
                mwl = int(d.get("max_window_layers", n_layers))
                if mwl >= n_layers:
                    sw = None  # threshold never reached: full causal everywhere
                elif mwl > 0:
                    raise ValueError(
                        f"{model_type} max_window_layers={mwl} < "
                        f"num_hidden_layers={n_layers} needs a window on "
                        "some layers only, which this framework serves for "
                        "model_type 'laguna' alone (models/llama/kinds.py)"
                    )
        if model_type == "gemma3_text" and sw is None:
            sw = 4096  # HF Gemma3TextConfig class default
        # Explicit null is treated like absence (HF default 5632), but an
        # explicit 0 means "shared expert disabled" and must survive parsing
        # (model.py gates the shared-expert weights on truthiness).
        se_size = d.get("shared_expert_intermediate_size", 5632)
        se_size = 5632 if se_size is None else int(se_size)
        return cls(
            hidden_size=hidden,
            intermediate_size=int(d.get("intermediate_size", 14336)),
            vocab_size=int(d.get("vocab_size", 128256)),
            num_hidden_layers=int(d.get("num_hidden_layers", 32)),
            num_attention_heads=heads,
            num_key_value_heads=int(d.get("num_key_value_heads", heads)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-5)),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            max_position_embeddings=int(d.get("max_position_embeddings", 8192)),
            bos_token_id=int(d.get("bos_token_id", 128000)),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(
                # Gemma ties embeddings BY DEFAULT, so its config.json omits
                # the field (it matches the HF base default of True).
                d.get(
                    "tie_word_embeddings",
                    model_type in ("gemma", "gemma2", "gemma3_text"),
                )
            ),
            rope_scaling=rs,
            model_type=model_type,
            attention_bias=bool(
                d.get("attention_bias", model_type in ("qwen2", "qwen2_moe"))
            ),
            sliding_window=None if sw is None else int(sw),
            head_dim_override=None if head_dim is None else int(head_dim),
            num_local_experts=(
                int(d.get("num_local_experts", 8))
                if model_type == "mixtral"
                else int(d.get("num_experts", 60))
                if model_type == "qwen2_moe"
                else int(d.get("num_experts", 128))
                if model_type == "qwen3_moe"
                else 0
            ),
            num_experts_per_tok=int(
                # HF defaults differ by family: Mixtral 2, Qwen2-MoE 4,
                # Qwen3-MoE 8.
                d.get(
                    "num_experts_per_tok",
                    {"qwen2_moe": 4, "qwen3_moe": 8}.get(model_type, 2),
                )
            ),
            norm_topk_prob=bool(
                # HF class defaults: Mixtral always renormalizes; BOTH Qwen
                # MoE configs default False (shipped Qwen3-MoE checkpoints
                # set True explicitly — honor the field, not the brand).
                d.get(
                    "norm_topk_prob",
                    model_type not in ("qwen2_moe", "qwen3_moe"),
                )
            ),
            moe_intermediate_size=(
                int(d["moe_intermediate_size"])
                if model_type in ("qwen2_moe", "qwen3_moe")
                and "moe_intermediate_size" in d
                else None
            ),
            qk_norm=model_type in ("qwen3", "qwen3_moe", "gemma3_text"),
            rope_local_base_freq=(
                float(d.get("rope_local_base_freq", 10000.0))
                if model_type == "gemma3_text"
                else None
            ),
            sliding_pattern=sliding_pattern,
            shared_expert_intermediate_size=(
                se_size if model_type == "qwen2_moe" else None
            ),
            hidden_activation=(
                "gelu_tanh"
                if model_type in ("gemma", "gemma2", "gemma3_text")
                else "silu"
            ),
            rmsnorm_offset=model_type in ("gemma", "gemma2", "gemma3_text"),
            embedding_scale=(
                float(hidden) ** 0.5
                if model_type in ("gemma", "gemma2", "gemma3_text")
                else None
            ),
            attn_logit_softcap=(
                float(d["attn_logit_softcapping"])
                if model_type == "gemma2"
                and d.get("attn_logit_softcapping") is not None
                else None
            ),
            final_logit_softcap=(
                float(d["final_logit_softcapping"])
                if model_type == "gemma2"
                and d.get("final_logit_softcapping") is not None
                else None
            ),
            query_pre_attn_scalar=(
                int(d.get("query_pre_attn_scalar") or 256)
                if model_type in ("gemma2", "gemma3_text")
                else None
            ),
            post_block_norms=model_type in ("gemma2", "gemma3_text"),
            alt_sliding_window=model_type == "gemma2",
        )

    @classmethod
    def _jamba_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: jamba`` (HF JambaConfig): Mamba-1 mixers beside
        attention without a positional term, dense SwiGLU everywhere. Its own
        parser, so the other families' parsing above stays as it was."""
        if int(d.get("num_experts", 16)) > 1:
            raise ValueError(
                f"jamba with num_experts={d.get('num_experts', 16)} needs "
                "Jamba's MoE feed-forward, which this framework does not "
                "bring (dense num_experts=1 checkpoints only)"
            )
        if d.get("sliding_window") is not None:
            raise ValueError("jamba with a sliding_window is not supported")
        if d.get("mamba_proj_bias", False):
            raise ValueError(
                "jamba with mamba_proj_bias=true is not supported (the "
                "mixer's in/out projections are read without a bias)"
            )
        hidden = int(d.get("hidden_size", 4096))
        heads = int(d.get("num_attention_heads", 32))
        dt_rank = d.get("mamba_dt_rank", "auto")
        if dt_rank == "auto":
            dt_rank = -(-hidden // 16)
        return cls(
            hidden_size=hidden,
            intermediate_size=int(d.get("intermediate_size", 14336)),
            vocab_size=int(d.get("vocab_size", 65536)),
            num_hidden_layers=int(d.get("num_hidden_layers", 32)),
            num_attention_heads=heads,
            num_key_value_heads=int(d.get("num_key_value_heads", 8)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=int(
                d.get("max_position_embeddings", 262144)
            ),
            bos_token_id=int(d.get("bos_token_id", 1)),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            model_type="jamba",
            attn_layer_period=int(d.get("attn_layer_period", 8)),
            attn_layer_offset=int(d.get("attn_layer_offset", 4)),
            mamba_d_state=int(d.get("mamba_d_state", 16)),
            mamba_d_conv=int(d.get("mamba_d_conv", 4)),
            mamba_expand=int(d.get("mamba_expand", 2)),
            mamba_dt_rank=int(dt_rank),
            use_rope=False,
        )

    @classmethod
    def _olmo_hybrid_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: olmo_hybrid``: ``layer_types`` lists gated-delta-rule
        layers (``linear_attention``) beside softmax attention without a
        positional term (``full_attention``), dense SwiGLU everywhere, the
        OLMo-2/3 block (a norm on each branch's output and none on its input;
        q and k normed over the whole projection)."""
        kinds = {"linear_attention": STATE, "full_attention": ATTENTION}
        if "eos_token_id" not in d:
            eos_ids = (100257,)  # the OLMo-2 tokenizer's <|endoftext|>
        n_layers = int(d.get("num_hidden_layers", 32))
        raw = d.get("layer_types")
        if raw is None or len(raw) != n_layers or set(raw) - set(kinds):
            raise ValueError(
                f"olmo_hybrid needs layer_types: {n_layers} entries "
                f"(num_hidden_layers) of {sorted(kinds)}, got {raw!r}"
            )
        theta = (d.get("rope_parameters") or {}).get("rope_theta", d.get("rope_theta"))
        if theta is not None:
            raise ValueError(
                f"olmo_hybrid with rope_theta={theta} needs a rotary term on "
                "its attention layers, which this framework does not bring "
                "(rope_theta null checkpoints only)"
            )
        if d.get("attention_bias", False):
            raise ValueError("olmo_hybrid with attention_bias is not supported")
        heads = int(d.get("num_attention_heads", 30))
        lin_k = int(d.get("linear_num_key_heads", heads))
        lin_v = int(d.get("linear_num_value_heads", lin_k))
        if lin_k != lin_v:
            raise ValueError(
                f"olmo_hybrid with linear_num_key_heads={lin_k} != "
                f"linear_num_value_heads={lin_v} needs grouped delta-rule "
                "heads, which this framework does not bring"
            )
        head_dim = d.get("head_dim")
        hidden = int(d.get("hidden_size", 3840))
        if head_dim is not None and int(head_dim) * heads == hidden:
            head_dim = None
        return cls(
            hidden_size=hidden,
            intermediate_size=int(d.get("intermediate_size", 11008)),
            vocab_size=int(d.get("vocab_size", 100352)),
            num_hidden_layers=n_layers,
            num_attention_heads=heads,
            num_key_value_heads=int(d.get("num_key_value_heads", heads)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=int(d.get("max_position_embeddings", 65536)),
            bos_token_id=int(d.get("bos_token_id", eos_ids[0])),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            model_type="olmo_hybrid",
            head_dim_override=None if head_dim is None else int(head_dim),
            layer_types=tuple(kinds[t] for t in raw),
            state_mixer=GATED_DELTA,
            linear_num_key_heads=lin_k,
            linear_num_value_heads=lin_v,
            linear_key_head_dim=int(d.get("linear_key_head_dim", 96)),
            linear_value_head_dim=int(d.get("linear_value_head_dim", 192)),
            linear_conv_kernel_dim=int(d.get("linear_conv_kernel_dim", 4)),
            linear_allow_neg_eigval=bool(d.get("linear_allow_neg_eigval", False)),
            use_rope=False,
            pre_block_norms=False,
            post_block_norms=True,
            qk_norm_whole=True,
        )

    @classmethod
    def _lfm2_moe_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: lfm2_moe`` (LiquidAI LFM2-8B-A1B): ``layer_types``
        lists gated short convolutions (``conv``: ``conv_L_cache`` taps over
        ``B * u``, the lane's state the window alone) beside grouped-query
        attention (``full_attention``) with a norm a head on q and k BEFORE a
        rotary term; the first ``num_dense_layers`` feed-forwards are dense
        SwiGLU, the rest ``num_experts`` routed experts (sigmoid scores, a
        selection bias where ``use_expert_bias``, no groups, no shared
        expert), ALL held here. What the config does not say (the tied head,
        the ids) is DATA here, overridden by the config's own keys."""
        kinds = {"conv": STATE, "full_attention": ATTENTION}
        n_layers = int(d.get("num_hidden_layers", 24))
        raw = d.get("layer_types")
        if raw is None or len(raw) != n_layers or set(raw) - set(kinds):
            raise ValueError(
                f"lfm2_moe needs layer_types: {n_layers} entries "
                f"(num_hidden_layers) of {sorted(kinds)}, got {raw!r}"
            )
        if d.get("conv_bias", False):
            raise ValueError(
                "lfm2_moe with conv_bias=true is not supported (the short "
                "convolution and its projections are read without a bias)"
            )
        if d.get("rope_scaling"):
            raise ValueError("lfm2_moe with rope_scaling is not supported")
        heads = int(d.get("num_attention_heads", 32))
        hidden = int(d.get("hidden_size", 2048))
        head_dim = d.get("head_dim")
        if head_dim is not None and int(head_dim) * heads == hidden:
            head_dim = None
        theta = (d.get("rope_parameters") or {}).get(
            "rope_theta", d.get("rope_theta", 1000000.0))
        if "eos_token_id" not in d:
            eos_ids = (7,)  # <|im_end|>
        return cls(
            hidden_size=hidden,
            intermediate_size=int(d.get("intermediate_size", 7168)),
            vocab_size=int(d.get("vocab_size", 65536)),
            num_hidden_layers=n_layers,
            num_attention_heads=heads,
            num_key_value_heads=int(d.get("num_key_value_heads", 8)),
            rms_norm_eps=float(d.get("norm_eps", 1e-5)),
            rope_theta=float(theta),
            max_position_embeddings=int(d.get("max_position_embeddings", 128000)),
            bos_token_id=int(d.get("bos_token_id", 1)),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(
                d.get("tie_word_embeddings", d.get("tie_embedding", True))),
            model_type="lfm2_moe",
            head_dim_override=None if head_dim is None else int(head_dim),
            layer_types=tuple(kinds[t] for t in raw),
            state_mixer=SHORT_CONV,
            short_conv_taps=int(d.get("conv_L_cache", 3)),
            qk_norm=True,
            first_k_dense_replace=int(d.get("num_dense_layers", 2)),
            num_local_experts=int(d.get("num_experts", 32)),
            num_experts_per_tok=int(d.get("num_experts_per_tok", 4)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            moe_intermediate_size=int(d.get("moe_intermediate_size", 1792)),
            moe_scoring="sigmoid",
            router_bias=bool(d.get("use_expert_bias", True)),
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        )

    @classmethod
    def _qwen3_next_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: qwen3_next`` (Qwen3-Next-80B-A3B): gated-delta-rule
        layers (``linear_attention``: ``linear_num_value_heads`` value heads
        in groups on ``linear_num_key_heads`` key heads) beside gated
        grouped-query attention (``full_attention``: a norm a head on q and k
        before a rotary term over ``partial_rotary_factor`` of the head, a
        sigmoid gate a number of the output), by ``layer_types`` where the
        file has the list, else every ``full_attention_interval``-th layer
        full; a pre-norm block whose norms are ``(1 + w)`` (not the delta
        rule's output norm); every layer ``num_experts`` softmax-routed
        experts beside a shared one behind a sigmoid gate. ``num_experts``
        counts the experts HELD; ``num_experts_total`` (absent = the same:
        the whole model) what the router ranks and ``first_expert`` where the
        held ones start among them. What the config does not say (which
        norms take the 1, the gate's form, beta without a factor 2, the ids)
        is DATA here."""
        kinds = {"linear_attention": STATE, "full_attention": ATTENTION}
        n_layers = int(d.get("num_hidden_layers", 48))
        raw = d.get("layer_types")
        if raw is None:
            interval = int(d.get("full_attention_interval", 4))
            raw = [
                "full_attention" if (i + 1) % interval == 0 else "linear_attention"
                for i in range(n_layers)
            ]
        if len(raw) != n_layers or set(raw) - set(kinds):
            raise ValueError(
                f"qwen3_next needs layer_types: {n_layers} entries "
                f"(num_hidden_layers) of {sorted(kinds)}, got {raw!r}"
            )
        if int(d.get("decoder_sparse_step", 1)) != 1 or d.get("mlp_only_layers"):
            raise ValueError(
                "qwen3_next with decoder_sparse_step != 1 or mlp_only_layers "
                "needs dense layers among the sparse, which this parser does "
                "not build"
            )
        if d.get("rope_scaling"):
            raise ValueError("qwen3_next with rope_scaling is not supported")
        if d.get("attention_bias", False):
            raise ValueError("qwen3_next with attention_bias is not supported")
        if d.get("use_sliding_window", False):
            raise ValueError("qwen3_next with use_sliding_window is not supported")
        lin_k = int(d.get("linear_num_key_heads", 16))
        lin_v = int(d.get("linear_num_value_heads", 32))
        if lin_v % lin_k:
            raise ValueError(
                f"qwen3_next with linear_num_value_heads={lin_v} needs whole "
                f"groups on linear_num_key_heads={lin_k}"
            )
        held, ranked, first = _expert_share(d, "num_experts", "first_expert", 512)
        heads = int(d.get("num_attention_heads", 16))
        hidden = int(d.get("hidden_size", 2048))
        head_dim = int(d.get("head_dim", 256))
        if "eos_token_id" not in d:
            eos_ids = (151645,)  # <|im_end|>
        return cls(
            hidden_size=hidden,
            intermediate_size=int(d.get("intermediate_size", 5120)),
            vocab_size=int(d.get("vocab_size", 151936)),
            num_hidden_layers=n_layers,
            num_attention_heads=heads,
            num_key_value_heads=int(d.get("num_key_value_heads", 2)),
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            rope_theta=float(d.get("rope_theta", 10000000.0)),
            max_position_embeddings=int(d.get("max_position_embeddings", 262144)),
            bos_token_id=int(d.get("bos_token_id", 151643)),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            model_type="qwen3_next",
            head_dim_override=None if head_dim * heads == hidden else head_dim,
            layer_types=tuple(kinds[t] for t in raw),
            state_mixer=GATED_DELTA,
            linear_num_key_heads=lin_k,
            linear_num_value_heads=lin_v,
            linear_key_head_dim=int(d.get("linear_key_head_dim", 128)),
            linear_value_head_dim=int(d.get("linear_value_head_dim", 128)),
            linear_conv_kernel_dim=int(d.get("linear_conv_kernel_dim", 4)),
            linear_allow_neg_eigval=False,
            partial_rotary_factor=float(d.get("partial_rotary_factor", 0.25)),
            qk_norm=True,
            rmsnorm_offset=True,
            attn_gate="per-number",
            num_local_experts=held,
            router_experts=ranked,
            expert_offset=first,
            num_experts_per_tok=int(d.get("num_experts_per_tok", 10)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            moe_intermediate_size=int(d.get("moe_intermediate_size", 512)),
            shared_expert_intermediate_size=int(
                d.get("shared_expert_intermediate_size", 512)) or None,
            moe_scoring="softmax",
        )

    @classmethod
    def _laguna_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: laguna`` (poolside Laguna): ``layer_types`` mixes
        ``full_attention`` and ``sliding_attention`` layers of different
        query-head counts (``num_attention_heads_per_layer``) on the same KV
        heads, each kind with its own rotary term (``rope_parameters``), a
        gate a head on the attention output (``gating``), and
        ``mlp_layer_types`` dense or sparse (sigmoid scores, the chosen
        renormalised and scaled, a shared expert). ``num_experts`` counts the
        experts HELD; ``num_experts_total`` (absent = the same: the whole
        model) what the router ranks and ``first_expert`` where the held
        ones start among them. What the config does not say (the score
        function, the gate's activation, no q/k norm) is DATA here:
        ``moe_scoring``, ``attn_gate_act``, ``qk_norm`` (a checkpoint whose
        config says ``use_qk_norm`` carries ``q_norm`` / ``k_norm`` a layer)."""
        kinds = {"full_attention": FULL, "sliding_attention": SLIDING}
        ffs = {"dense": DENSE, "sparse": SPARSE}
        n_layers = int(d.get("num_hidden_layers", 48))
        raw = d.get("layer_types")
        if raw is None or len(raw) != n_layers or set(raw) - set(kinds):
            raise ValueError(
                f"laguna needs layer_types: {n_layers} entries "
                f"(num_hidden_layers) of {sorted(kinds)}, got {raw!r}"
            )
        heads = d.get("num_attention_heads_per_layer") or (
            [int(d.get("num_attention_heads", 48))] * n_layers
        )
        n_kv = int(d.get("num_key_value_heads", 8))
        if len(heads) != n_layers or any(int(h) % n_kv for h in heads):
            raise ValueError(
                f"laguna needs num_attention_heads_per_layer: {n_layers} "
                f"multiples of num_key_value_heads={n_kv}, got {heads!r}"
            )
        by_kind = {k: {int(h) for h, t in zip(heads, raw) if kinds[t] == k}
                   for k in kinds.values()}
        if any(len(v) > 1 for v in by_kind.values()):
            raise ValueError(
                "laguna with layers of one kind but different head counts "
                f"is not supported (by kind: {by_kind})"
            )
        mlp = d.get("mlp_layer_types") or (
            ["dense" if i in (d.get("mlp_only_layers") or []) else "sparse"
             for i in range(n_layers)]
        )
        if len(mlp) != n_layers or set(mlp) - set(ffs):
            raise ValueError(
                f"laguna needs mlp_layer_types: {n_layers} entries of "
                f"{sorted(ffs)}, got {mlp!r}"
            )
        gating = d.get("gating")
        if gating not in (None, False, "per-head"):
            raise ValueError(
                f"laguna with gating={gating!r} is not supported (per-head "
                "or none)"
            )
        if float(d.get("moe_router_logit_softcapping") or 0):
            raise ValueError("laguna with a router soft-cap is not supported")
        if d.get("moe_apply_router_weight_on_input", False):
            raise ValueError(
                "laguna with moe_apply_router_weight_on_input is not supported"
            )
        if d.get("attention_bias", False):
            raise ValueError("laguna with attention_bias is not supported")
        head_dim = int(d.get("head_dim", 128))
        ropes = []
        for name, kind in kinds.items():
            if kind not in by_kind or not by_kind[kind]:
                continue
            r = (d.get("rope_parameters") or {}).get(name)
            if r is None:
                raise ValueError(f"laguna needs rope_parameters[{name!r}]")
            rope_type = r.get("rope_type", "default")
            if rope_type not in ("default", "yarn"):
                raise ValueError(
                    f"laguna with rope_type={rope_type!r} is not supported"
                )
            yarn = rope_type == "yarn"
            ropes.append((kind, KindRope(
                theta=float(r.get("rope_theta", 10000.0)),
                rotary_dim=int(head_dim * float(r.get("partial_rotary_factor", 1))),
                factor=float(r["factor"]) if yarn else 1.0,
                original_max_position_embeddings=int(
                    r.get("original_max_position_embeddings", 0)) if yarn else 0,
                beta_fast=float(r.get("beta_fast", 32)),
                beta_slow=float(r.get("beta_slow", 1)),
                attention_factor=float(r.get("attention_factor", 1.0)) if yarn else 1.0,
            )))
        held, ranked, first = _expert_share(d, "num_experts", "first_expert", 256)
        window = d.get("sliding_window")
        if by_kind[SLIDING] and not window:
            raise ValueError("laguna with sliding layers needs sliding_window")
        full_heads = next(iter(by_kind[FULL] or by_kind[SLIDING]))
        shared = int(d.get("shared_expert_intermediate_size") or 0)
        return cls(
            hidden_size=int(d.get("hidden_size", 3072)),
            intermediate_size=int(d.get("intermediate_size", 12288)),
            vocab_size=int(d.get("vocab_size", 100352)),
            num_hidden_layers=n_layers,
            num_attention_heads=full_heads,
            num_key_value_heads=n_kv,
            rms_norm_eps=float(d.get("rms_norm_eps", 1e-6)),
            max_position_embeddings=int(
                d.get("max_position_embeddings", 1048576)
            ),
            bos_token_id=int(d.get("bos_token_id", 1)),
            eos_token_ids=eos_ids if "eos_token_id" in d else (2,),
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            model_type="laguna",
            head_dim_override=head_dim,
            sliding_window=None if not window else int(window),
            attention_types=tuple(kinds[t] for t in raw),
            heads_per_layer=tuple(int(h) for h in heads),
            kind_ropes=tuple(ropes),
            attn_gate="per-head" if gating else None,
            qk_norm=bool(d.get("use_qk_norm", False)),
            ff_types=tuple(ffs[t] for t in mlp),
            num_local_experts=held,
            router_experts=ranked,
            expert_offset=first,
            num_experts_per_tok=int(d.get("num_experts_per_tok", 10)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            moe_intermediate_size=int(d.get("moe_intermediate_size", 1024)),
            shared_expert_intermediate_size=shared or None,
            moe_scoring="sigmoid",
            routed_scaling_factor=float(d.get("moe_routed_scaling_factor", 1.0)),
        )

    @classmethod
    def _pangu_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: pangu_ultra_moe`` (openPangu-Ultra-MoE): latent
        attention, leading dense layers then sparse ones with a shared
        expert, sigmoid routing, four norms a layer. ``n_routed_experts``
        counts the experts HELD; ``n_routed_experts_total`` (absent = the
        same: the whole model) what the router ranks and
        ``first_routed_expert`` where the held ones start among them."""
        for key in ("n_group", "topk_group"):
            if int(d.get(key) or 1) > 1:
                raise ValueError(
                    f"pangu_ultra_moe with {key}={d[key]} needs group-limited "
                    "routing, which this framework does not bring"
                )
        if d.get("rope_scaling"):
            raise ValueError("pangu_ultra_moe with rope_scaling is not supported")
        if not d.get("sandwich_norm", True):
            raise ValueError(
                "pangu_ultra_moe without sandwich_norm is not supported"
            )
        return cls(
            **cls._mla_share_fields(d, eos_ids, hidden=7680, vocab=153600,
                                    eps=1e-5, theta=25600000.0, context=131072),
            model_type="pangu_ultra_moe",
            post_block_norms=True,
        )

    @classmethod
    def _mla_share_fields(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...], *, hidden: int,
        vocab: int, eps: float, theta: float, context: int,
    ) -> dict[str, Any]:
        """What the DeepSeek-V3 family's configs share, as constructor
        fields: MLA's sizes, leading dense layers, sigmoid routing beside a
        shared expert, and this repository's three keys for a rank's share of
        the experts. The defaults that differ by model are the caller's."""
        held, ranked, first = _expert_share(d, "n_routed_experts", "first_routed_expert", 256)
        heads = int(d.get("num_attention_heads", 128))
        moe_inter = int(d.get("moe_intermediate_size", 2048))
        return dict(
            hidden_size=int(d.get("hidden_size", hidden)),
            intermediate_size=int(d.get("intermediate_size", 18432)),
            vocab_size=int(d.get("vocab_size", vocab)),
            num_hidden_layers=int(d.get("num_hidden_layers", 61)),
            num_attention_heads=heads,
            num_key_value_heads=heads,
            rms_norm_eps=float(d.get("rms_norm_eps", eps)),
            rope_theta=float(d.get("rope_theta", theta)),
            max_position_embeddings=int(
                d.get("max_position_embeddings", context)
            ),
            bos_token_id=int(d.get("bos_token_id", 0)),
            eos_token_ids=eos_ids,
            tie_word_embeddings=bool(d.get("tie_word_embeddings", False)),
            head_dim_override=int(d.get("qk_nope_head_dim", 128))
            + int(d.get("qk_rope_head_dim", 64)),
            q_lora_rank=int(d.get("q_lora_rank", 1536)),
            kv_lora_rank=int(d.get("kv_lora_rank", 512)),
            qk_nope_head_dim=int(d.get("qk_nope_head_dim", 128)),
            qk_rope_head_dim=int(d.get("qk_rope_head_dim", 64)),
            v_head_dim=int(d.get("v_head_dim", 128)),
            first_k_dense_replace=int(d.get("first_k_dense_replace", 3)),
            num_local_experts=held,
            router_experts=ranked,
            expert_offset=first,
            num_experts_per_tok=int(d.get("num_experts_per_tok", 8)),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            moe_intermediate_size=moe_inter,
            shared_expert_intermediate_size=(
                int(d.get("n_shared_experts", 1)) * moe_inter or None
            ),
            moe_scoring="sigmoid",
            routed_scaling_factor=float(d.get("routed_scaling_factor", 2.5)),
        )

    @classmethod
    def _deepseek_v32_from_hf_dict(
        cls, d: dict[str, Any], eos_ids: tuple[int, ...]
    ) -> "LlamaConfig":
        """``model_type: deepseek_v32`` (DeepSeek-V3.2-Exp): Pangu's latent
        attention and expert share without the sandwich norms, with YaRN over
        the rotary numbers (``rope_scaling``), group-limited routing with a
        correction bias (``n_group``, ``topk_group``, ``topk_method:
        noaux_tc``) and a learned index that chooses the ``index_topk``
        cached tokens a query attends (``index_n_heads`` x
        ``index_head_dim``)."""
        if str(d.get("scoring_func", "sigmoid")) != "sigmoid":
            raise ValueError(
                f"deepseek_v32 with scoring_func={d['scoring_func']!r} is not "
                "supported (sigmoid is)"
            )
        method = str(d.get("topk_method", "noaux_tc"))
        if method != "noaux_tc":
            raise ValueError(
                f"deepseek_v32 with topk_method={method!r} is not supported "
                "(noaux_tc is)"
            )
        fields = cls._mla_share_fields(
            d, eos_ids, hidden=7168, vocab=129280, eps=1e-6, theta=10000.0,
            context=163840,
        )
        n_group, topk_group = int(d.get("n_group", 8)), int(d.get("topk_group", 4))
        ranked = fields["router_experts"]
        if ranked % n_group or not 1 <= topk_group <= n_group:
            raise ValueError(
                f"{ranked} ranked experts in n_group={n_group} groups of which "
                f"topk_group={topk_group} stay: the groups must be equal and "
                "topk_group between 1 and n_group"
            )
        if fields["num_experts_per_tok"] > topk_group * (ranked // n_group):
            raise ValueError(
                "num_experts_per_tok exceeds the experts of topk_group groups"
            )
        rope, mscale = None, 1.0
        scaling = d.get("rope_scaling")
        if scaling:
            kind = scaling.get("type", scaling.get("rope_type"))
            if kind != "yarn":
                raise ValueError(
                    f"deepseek_v32 with rope_scaling type {kind!r} is not "
                    "supported (yarn is)"
                )
            factor = float(scaling["factor"])
            m, m_all = (
                float(scaling.get(k, 1) or 1) for k in ("mscale", "mscale_all_dim")
            )
            if m != m_all:
                raise ValueError(
                    "deepseek_v32 with mscale != mscale_all_dim would scale cos "
                    "and sin, which is not supported"
                )
            rope = KindRope(
                theta=fields["rope_theta"],
                rotary_dim=fields["qk_rope_head_dim"],
                factor=factor,
                original_max_position_embeddings=int(
                    scaling["original_max_position_embeddings"]
                ),
                beta_fast=float(scaling.get("beta_fast", 32)),
                beta_slow=float(scaling.get("beta_slow", 1)),
            )
            # YaRN's attention temperature, squared into the score scale.
            if factor > 1:
                mscale = 0.1 * m_all * math.log(factor) + 1.0
        else:
            rope = KindRope(
                theta=fields["rope_theta"], rotary_dim=fields["qk_rope_head_dim"]
            )
        index_dim = int(d.get("index_head_dim", 128))
        if index_dim < fields["qk_rope_head_dim"]:
            raise ValueError("index_head_dim is smaller than qk_rope_head_dim")
        return cls(
            **fields,
            model_type="deepseek_v32",
            n_group=n_group,
            topk_group=topk_group,
            router_bias=True,
            index_n_heads=int(d.get("index_n_heads", 64)),
            index_head_dim=index_dim,
            index_topk=int(d.get("index_topk", 2048)),
            latent_rope=rope,
            latent_mscale=mscale,
        )

    @classmethod
    def _sdar_moe_from_hf_dict(cls, d: dict[str, Any]) -> "LlamaConfig":
        """``model_type: sdar_moe`` (JetLM's SDAR-30B-A3B-Chat): ``qwen3_moe``'s
        block, layer for layer (q/k norm a head before the rope, softmax
        scores over every expert, the chosen ones renormalised, no shared
        expert, plain K and V), generating by diffusion over blocks. The
        catalog row gives neither the block length nor the mask id: both are
        ASSUMED defaults here (4, the family's released setting; 151669) that
        the file's own ``block_length`` / ``mask_token_id`` override. What
        the parser cannot serve is refused by name."""
        for key, want in (("rope_scaling", None), ("attention_bias", False),
                          ("use_sliding_window", False)):
            if d.get(key, want) not in (want, None):
                raise ValueError(
                    f"sdar_moe with {key}={d[key]!r} is not supported: the "
                    f"block-diffusion path serves {key}={want!r} only"
                )
        base = cls.from_hf_dict({**d, "model_type": "qwen3_moe"})
        block = int(d.get("block_length", 4))
        mask_id = int(d.get("mask_token_id", 151669))
        if block < 1 or BLOCK_MULTIPLE % block:
            raise ValueError(
                f"sdar_moe block_length={block} must divide {BLOCK_MULTIPLE}: "
                "prompts are laid out in buckets of that many slots, and a "
                "block may not straddle a lane's left pad"
            )
        if not 0 <= mask_id < base.vocab_size:
            raise ValueError(
                f"sdar_moe mask_token_id={mask_id} lies outside the "
                f"vocabulary of {base.vocab_size}"
            )
        steps = int(d.get("denoising_steps", block))
        if not 1 <= steps <= block:
            raise ValueError(
                f"sdar_moe denoising_steps={steps} must lie in 1..block_length "
                f"({block}): a pass reveals at least one slot"
            )
        return dataclasses.replace(
            base, model_type="sdar_moe", generation=BLOCK_DIFFUSION,
            block_length=block, mask_token_id=mask_id, denoising_steps=steps,
        )

    @classmethod
    def from_model_dir(
        cls, model_dir: str | Path, *, attention_impl: str | None = None
    ) -> "LlamaConfig":
        """Load ``config.json`` from a model directory (config.rs:28-42).

        ``attention_impl`` overrides the kernel choice (not an HF field, so it
        never comes from the checkpoint; "auto"/None keeps the default).
        """
        path = Path(model_dir) / "config.json"
        with open(path) as f:
            config = cls.from_hf_dict(json.load(f))
        # Real instruct checkpoints carry their FULL stop-token list in
        # generation_config.json (Llama-3-Instruct: [128001, 128008, 128009]
        # there, while config.json says just 128001 — without the merge,
        # generation would run through <|eot_id|> instead of stopping, the
        # behavior transformers gets from GenerationConfig). Union, config
        # ids first. The reference reads config.json only (config.rs:13-26)
        # and so inherits exactly this bug on instruct checkpoints.
        gen_path = Path(model_dir) / "generation_config.json"
        if gen_path.exists():
            with open(gen_path) as f:
                gen_eos = json.load(f).get("eos_token_id")
            if gen_eos is not None:
                if isinstance(gen_eos, int):
                    gen_eos = [gen_eos]
                merged = list(config.eos_token_ids)
                merged += [int(e) for e in gen_eos if int(e) not in merged]
                config = dataclasses.replace(
                    config, eos_token_ids=tuple(merged)
                )
        if attention_impl not in (None, "auto"):
            if attention_impl not in ("pallas", "xla"):
                raise ValueError(f"unknown attention_impl {attention_impl!r}")
            config = dataclasses.replace(config, attention_impl=attention_impl)
        return config

    @classmethod
    def tiny(cls, **overrides: Any) -> "LlamaConfig":
        """A minuscule config for tests (random weights, CPU-friendly)."""
        kw: dict[str, Any] = dict(
            hidden_size=64,
            intermediate_size=128,
            vocab_size=512,
            num_hidden_layers=4,
            num_attention_heads=4,
            num_key_value_heads=2,
            rms_norm_eps=1e-5,
            rope_theta=10000.0,
            max_position_embeddings=256,
            # Special ids match tokenizer.ByteTokenizer (256 = begin_of_text,
            # 259 = eot, 260 = end_of_text).
            bos_token_id=256,
            eos_token_ids=(259, 260),
        )
        kw.update(overrides)
        return cls(**kw)

    def to_hf_dict(self) -> dict[str, Any]:
        arch = {
            "llama": "LlamaForCausalLM",
            "qwen2": "Qwen2ForCausalLM",
            "mistral": "MistralForCausalLM",
            "mixtral": "MixtralForCausalLM",
            "qwen2_moe": "Qwen2MoeForCausalLM",
            "gemma": "GemmaForCausalLM",
            "gemma2": "Gemma2ForCausalLM",
            "gemma3_text": "Gemma3ForCausalLM",
            "phi3": "Phi3ForCausalLM",
            "qwen3": "Qwen3ForCausalLM",
            "qwen3_moe": "Qwen3MoeForCausalLM",
            "jamba": "JambaForCausalLM",
            "pangu_ultra_moe": "PanguUltraMoEForCausalLM",
            "olmo_hybrid": "OlmoHybridForCausalLM",
            "laguna": "LagunaForCausalLM",
            "deepseek_v32": "DeepseekV32ForCausalLM",
            "lfm2_moe": "Lfm2MoeForCausalLM",
            "qwen3_next": "Qwen3NextForCausalLM",
            "sdar_moe": "SDARMoeForCausalLM",
        }[self.model_type]
        d: dict[str, Any] = {
            "architectures": [arch],
            "model_type": self.model_type,
            "hidden_size": self.hidden_size,
            "intermediate_size": self.intermediate_size,
            "vocab_size": self.vocab_size,
            "num_hidden_layers": self.num_hidden_layers,
            "num_attention_heads": self.num_attention_heads,
            "num_key_value_heads": self.num_key_value_heads,
            "rms_norm_eps": self.rms_norm_eps,
            "rope_theta": self.rope_theta,
            "max_position_embeddings": self.max_position_embeddings,
            "bos_token_id": self.bos_token_id,
            "eos_token_id": list(self.eos_token_ids)
            if len(self.eos_token_ids) > 1
            else self.eos_token_ids[0],
            "tie_word_embeddings": self.tie_word_embeddings,
        }
        # Emitted unconditionally: from_hf_dict defaults attention_bias by
        # family (True for qwen2), so omitting a False would flip on reload.
        d["attention_bias"] = self.attention_bias
        if self.sliding_window is not None:
            d["sliding_window"] = self.sliding_window
            if self.model_type in ("qwen2", "qwen2_moe", "qwen3", "qwen3_moe"):
                d["use_sliding_window"] = True
                # All layers windowed; without this, from_hf_dict's default
                # (max_window_layers = num_hidden_layers) gates the window off.
                d["max_window_layers"] = 0
        if self.head_dim_override is not None:
            d["head_dim"] = self.head_dim_override
        if self.model_type == "laguna":
            del d["rope_theta"]
            names = {FULL: "full_attention", SLIDING: "sliding_attention"}
            d.update(
                head_dim=self.head_dim,
                layer_types=[names[k] for k in self.attention_types],
                num_attention_heads_per_layer=list(self.heads_per_layer),
                mlp_layer_types=list(self.ff_kinds),
                gating=self.attn_gate or False,
                use_qk_norm=self.qk_norm,
                rope_parameters={
                    names[k]: {
                        "rope_type": "yarn" if r.factor != 1.0 else "default",
                        "rope_theta": r.theta,
                        "partial_rotary_factor": r.rotary_dim / self.head_dim,
                        **({"factor": r.factor,
                            "original_max_position_embeddings":
                                r.original_max_position_embeddings,
                            "beta_fast": r.beta_fast, "beta_slow": r.beta_slow,
                            "attention_factor": r.attention_factor}
                           if r.factor != 1.0 else {}),
                    } for k, r in self.kind_ropes
                },
                num_experts=self.num_local_experts,
                num_experts_total=self.n_router_experts,
                first_expert=self.expert_offset,
                num_experts_per_tok=self.num_experts_per_tok,
                norm_topk_prob=self.norm_topk_prob,
                moe_intermediate_size=self.moe_intermediate_size,
                shared_expert_intermediate_size=(
                    self.shared_expert_intermediate_size or 0
                ),
                moe_routed_scaling_factor=self.routed_scaling_factor,
            )
        elif self.model_type in ("pangu_ultra_moe", "deepseek_v32"):
            del d["num_key_value_heads"]
            if self.model_type == "deepseek_v32":
                r = self.latent_rope
                d.update(
                    n_group=self.n_group, topk_group=self.topk_group,
                    scoring_func="sigmoid", topk_method="noaux_tc",
                    index_n_heads=self.index_n_heads,
                    index_head_dim=self.index_head_dim,
                    index_topk=self.index_topk,
                )
                if r.factor != 1.0:
                    d["rope_scaling"] = {
                        "type": "yarn", "factor": r.factor,
                        "original_max_position_embeddings":
                            r.original_max_position_embeddings,
                        "beta_fast": r.beta_fast, "beta_slow": r.beta_slow,
                        "mscale": 1, "mscale_all_dim": 1,
                    }
            else:
                d["sandwich_norm"] = True
            d.update(
                num_key_value_heads=self.num_attention_heads,
                q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim,
                first_k_dense_replace=self.first_k_dense_replace,
                n_routed_experts=self.num_local_experts,
                n_routed_experts_total=self.n_router_experts,
                first_routed_expert=self.expert_offset,
                n_shared_experts=(
                    (self.shared_expert_intermediate_size or 0)
                    // self.moe_intermediate_size
                ),
                moe_intermediate_size=self.moe_intermediate_size,
                num_experts_per_tok=self.num_experts_per_tok,
                norm_topk_prob=self.norm_topk_prob,
                routed_scaling_factor=self.routed_scaling_factor,
            )
        elif self.model_type == "lfm2_moe":
            del d["rms_norm_eps"], d["attention_bias"]
            d.update(
                norm_eps=self.rms_norm_eps,
                layer_types=[
                    "full_attention" if k == ATTENTION else "conv"
                    for k in self.layer_kinds
                ],
                conv_L_cache=self.short_conv_taps, conv_bias=False,
                num_dense_layers=self.first_k_dense_replace,
                num_experts=self.num_local_experts,
                num_experts_per_tok=self.num_experts_per_tok,
                norm_topk_prob=self.norm_topk_prob,
                moe_intermediate_size=self.moe_intermediate_size,
                use_expert_bias=self.router_bias,
                routed_scaling_factor=self.routed_scaling_factor,
            )
        elif self.model_type == "qwen3_next":
            del d["attention_bias"]
            d.update(
                head_dim=self.head_dim,
                partial_rotary_factor=self.partial_rotary_factor,
                layer_types=[
                    "full_attention" if k == ATTENTION else "linear_attention"
                    for k in self.layer_kinds
                ],
                linear_num_key_heads=self.linear_num_key_heads,
                linear_num_value_heads=self.linear_num_value_heads,
                linear_key_head_dim=self.linear_key_head_dim,
                linear_value_head_dim=self.linear_value_head_dim,
                linear_conv_kernel_dim=self.linear_conv_kernel_dim,
                decoder_sparse_step=1, mlp_only_layers=[],
                num_experts=self.num_local_experts,
                num_experts_total=self.n_router_experts,
                first_expert=self.expert_offset,
                num_experts_per_tok=self.num_experts_per_tok,
                norm_topk_prob=self.norm_topk_prob,
                moe_intermediate_size=self.moe_intermediate_size,
                shared_expert_intermediate_size=(
                    self.shared_expert_intermediate_size or 0),
            )
        elif self.num_local_experts:
            if self.model_type in ("qwen2_moe", "qwen3_moe", "sdar_moe"):
                d["num_experts"] = self.num_local_experts
                d["norm_topk_prob"] = self.norm_topk_prob
                if self.moe_intermediate_size is not None:
                    d["moe_intermediate_size"] = self.moe_intermediate_size
                if self.shared_expert_intermediate_size is not None:
                    d["shared_expert_intermediate_size"] = (
                        self.shared_expert_intermediate_size
                    )
            else:
                d["num_local_experts"] = self.num_local_experts
            d["num_experts_per_tok"] = self.num_experts_per_tok
        if self.generation == BLOCK_DIFFUSION:
            d.update(block_length=self.block_length, mask_token_id=self.mask_token_id,
                     head_dim=self.head_dim)
        if self.model_type in ("gemma", "gemma2"):
            d["hidden_activation"] = "gelu_pytorch_tanh"
            d["head_dim"] = self.head_dim
        if self.model_type == "gemma2":
            d["attn_logit_softcapping"] = self.attn_logit_softcap
            d["final_logit_softcapping"] = self.final_logit_softcap
            d["query_pre_attn_scalar"] = self.query_pre_attn_scalar
        if self.model_type == "gemma3_text":
            d["rope_local_base_freq"] = self.rope_local_base_freq
            d["query_pre_attn_scalar"] = self.query_pre_attn_scalar
            d["head_dim"] = self.head_dim
            if self.sliding_pattern is not None:
                d["layer_types"] = [
                    "sliding_attention" if f else "full_attention"
                    for f in self.sliding_pattern
                ]
        if self.model_type == "jamba":
            del d["rope_theta"], d["attention_bias"]
            d.update(
                attn_layer_period=self.attn_layer_period,
                attn_layer_offset=self.attn_layer_offset,
                mamba_d_state=self.mamba_d_state,
                mamba_d_conv=self.mamba_d_conv,
                mamba_expand=self.mamba_expand,
                mamba_dt_rank=self.mamba_dt_rank,
                mamba_conv_bias=True,
                mamba_proj_bias=False,
                num_experts=1,
                num_experts_per_tok=1,
            )
        if self.model_type == "olmo_hybrid":
            del d["rope_theta"]
            d.update(
                rope_parameters={"rope_theta": None},
                layer_types=[
                    "full_attention" if k == ATTENTION else "linear_attention"
                    for k in self.layer_kinds
                ],
                linear_num_key_heads=self.linear_num_key_heads,
                linear_num_value_heads=self.linear_num_value_heads,
                linear_key_head_dim=self.linear_key_head_dim,
                linear_value_head_dim=self.linear_value_head_dim,
                linear_conv_kernel_dim=self.linear_conv_kernel_dim,
                linear_allow_neg_eigval=self.linear_allow_neg_eigval,
            )
        if self.rope_scaling is not None and self.rope_scaling.rope_type == "linear":
            d["rope_scaling"] = {
                "rope_type": "linear",
                "factor": self.rope_scaling.factor,
            }
        elif self.rope_scaling is not None:
            d["rope_scaling"] = {
                "rope_type": "llama3",
                "factor": self.rope_scaling.factor,
                "low_freq_factor": self.rope_scaling.low_freq_factor,
                "high_freq_factor": self.rope_scaling.high_freq_factor,
                "original_max_position_embeddings": (
                    self.rope_scaling.original_max_position_embeddings
                ),
            }
        return d
