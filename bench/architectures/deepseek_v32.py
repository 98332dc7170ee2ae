"""``model_type: deepseek_v32`` (DeepSeek-V3.2-Exp): tensors, plain reference,
template and costs, for the whole model or for one rank's share of an
expert-parallel deployment.

For a layer's input ``x`` [L, hidden] (RMSNorm with weight, eps
``rms_norm_eps``; all of it float32 at matmul precision ``highest``):

    block:  h = x + Attn(N1(x));   y = h + FF(N2(h))
    MLA:  u = N1(x);  cq = rms(u Wqa; q_a_layernorm)             [q_lora_rank]
          q = cq Wqb -> a head [q_nope | q_rope]          [nope 128 | rope 64]
          [ckv | k_rope] = u Wkva;  ckv = rms(ckv; kv_a_layernorm)
          q_rope, k_rope = RoPE(.)            one rotary key for all heads
          [k_nope_h | v_h] = ckv Wkvb  a head
          s_h[t, s] = (q_nope_h . k_nope_h + q_rope_h . k_rope) * scale
          scale = (nope + rope)^-0.5 * m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
          RoPE: YaRN over the ``rope`` rotary numbers, pairs (i, i + rope/2):
          inv_freq blended between theta^(-2i/d) and that over ``factor`` by a
          ramp from ``beta_fast`` to ``beta_slow`` turns over the original
          context; cos and sin unscaled (mscale == mscale_all_dim)
    index:  q_I = cq W_Iq -> ``index_n_heads`` heads of ``index_head_dim``
          k_I = LayerNorm(u W_Ik; weight, bias, eps 1e-6)    ONE key a token
          RoPE (the same frequencies) on the first ``rope`` numbers of each
          w = (u W_Iw) * index_n_heads^-0.5 * index_head_dim^-0.5
          I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])          s <= t
          S_t = the min(index_topk, t + 1) positions of largest I[t, .],
                ties to the smaller position (a full stable sort)
          softmax and sum of s_h[t, .] over s in S_t ONLY
          o_h = sum p v_h;  Attn = concat_h(o_h) Wo
    FF, layers below first_k_dense_replace:  SwiGLU of intermediate_size
    FF, the others:  p = sigmoid(N2(h) Wg^T)               [every ranked expert]
          choose by p + b (e_score_correction_bias): a group's score is the
          sum of its two largest p + b among its experts, the best
          ``topk_group`` of ``n_group`` groups stay, the ``num_experts_per_tok``
          largest p + b inside them are chosen; weights p (WITHOUT b) of the
          chosen / (their sum + 1e-20) * routed_scaling_factor
          FF = SwiGLU_shared(u) + sum_{e chosen, e HELD} w_e SwiGLU_e(u)
    head: rms(.; model.norm) then the untied lm_head

**The share** is Pangu's file's: ``n_routed_experts`` counts the experts
HELD (their tensors under their own numbers), ``n_routed_experts_total`` those
the router ranks and groups, ``first_routed_expert`` the first held;
``vocab_size`` is the slice held. What an absent expert would add is left out,
here as in the program.

**Departures from the published model** (each also in the configuration's
``assumed``): bf16 where the checkpoint stores FP8 and the index computes in
FP8; no Hadamard rotation of q_I and k_I (an orthogonal map before the FP8
rounding: it leaves q_I . k_I as it is); rotate-half pairing in both ropes;
ties in ``I`` to the smaller position; **the multi-token-prediction module
(``num_nextn_predict_layers``) is not served and not here**: it drafts token
t + 2 and adds nothing to the next token's logits.

``FAULT`` is None here and in every run that counts. A test, or a scratch
copy for a control on the chip, sets one of ``FAULTS`` to make the reference
wrong in one way.
"""

from __future__ import annotations

import math
import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
FAULTS = ("dense_attention", "random_selection", "index_key_unrotated",
          "softmax_scores", "no_group_limit", "no_shared_expert")
FAULT = None
_INDEX_LN_EPS = 1e-6

# ------------------------------------------------------------------ tensors


def is_sparse(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def held_experts(cfg: dict) -> range:
    """The routed experts this rank holds, by their own numbers."""
    first = cfg.get("first_routed_expert", 0)
    return range(first, first + cfg["n_routed_experts"])


def ranked_experts(cfg: dict) -> int:
    return cfg.get("n_routed_experts_total", cfg["n_routed_experts"])


def attention_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    q, kv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {
        "self_attn.q_a_proj.weight": (q, h),
        "self_attn.q_b_proj.weight": (n * (nope + rope), q),
        "self_attn.kv_a_proj_with_mqa.weight": (kv + rope, h),
        "self_attn.kv_b_proj.weight": (n * (nope + v), kv),
        "self_attn.o_proj.weight": (h, n * v),
    }


def index_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    h, heads, dim = cfg["hidden_size"], cfg["index_n_heads"], cfg["index_head_dim"]
    return {
        "self_attn.indexer.wq_b.weight": (heads * dim, cfg["q_lora_rank"]),
        "self_attn.indexer.wk.weight": (dim, h),
        "self_attn.indexer.weights_proj.weight": (heads, h),
    }


def swiglu_shapes(prefix: str, h: int, inter: int) -> dict[str, tuple[int, ...]]:
    return {f"{prefix}.gate_proj.weight": (inter, h), f"{prefix}.up_proj.weight": (inter, h),
            f"{prefix}.down_proj.weight": (h, inter)}


def feed_forward_shapes(cfg: dict, i: int) -> dict[str, tuple[int, ...]]:
    h = cfg["hidden_size"]
    if not is_sparse(cfg, i):
        return swiglu_shapes("mlp", h, cfg["intermediate_size"])
    inter = cfg["moe_intermediate_size"]
    shapes = {"mlp.gate.weight": (ranked_experts(cfg), h),
              "mlp.gate.e_score_correction_bias": (ranked_experts(cfg),)}
    for e in held_experts(cfg):
        shapes.update(swiglu_shapes(f"mlp.experts.{e}", h, inter))
    if cfg["n_shared_experts"]:
        shapes.update(swiglu_shapes("mlp.shared_experts", h, cfg["n_shared_experts"] * inter))
    return shapes


def norm_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    h = cfg["hidden_size"]
    return {
        "input_layernorm.weight": (h,), "post_attention_layernorm.weight": (h,),
        "self_attn.q_a_layernorm.weight": (cfg["q_lora_rank"],),
        "self_attn.kv_a_layernorm.weight": (cfg["kv_lora_rank"],),
        "self_attn.indexer.k_norm.weight": (cfg["index_head_dim"],),
    }


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this file writes deepseek_v32 with an untied head only")
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """Attention, the index, the layer's kind of feed-forward (the router's
    correction bias drawn ``normal`` like a weight), the two norms of the
    block, MLA's two inner ones and the index key's LayerNorm (weight ones,
    bias zeros). No tensor of the MTP module."""
    table = {n: (s, "normal") for n, s in attention_shapes(cfg).items()}
    table.update({n: (s, "normal") for n, s in index_shapes(cfg).items()})
    table.update({n: (s, "normal") for n, s in feed_forward_shapes(cfg, i).items()})
    table.update({n: (s, "ones") for n, s in norm_shapes(cfg).items()})
    table["self_attn.indexer.k_norm.bias"] = ((cfg["index_head_dim"],), "zeros")
    return {f"model.layers.{i}.{n}": v for n, v in table.items()}


# ----------------------------------------------------------------- template

UNKNOWN_WORD = None
_BOS, _EOS = "<｜begin▁of▁sentence｜>", "<｜end▁of▁sentence｜>"
_MARKERS = ("<｜User｜>", "<｜Assistant｜>")


def require_program(cfg: dict) -> None:
    """Fail at once, before a 9 GB checkpoint is drawn and written, on a
    checkout whose program cannot parse this ``model_type`` (the parent of
    the PR that brought it). The program's parser module imports no JAX, so
    the benchmark's parent process may ask it."""
    from cake_tpu.models.llama.config import SUPPORTED_MODEL_TYPES

    if cfg["model_type"] not in SUPPORTED_MODEL_TYPES:
        raise RuntimeError(
            f"this checkout's cake_tpu does not take model_type {cfg['model_type']!r}: "
            f"unsupported model_type {cfg['model_type']!r} "
            f"(it takes {', '.join(SUPPORTED_MODEL_TYPES)}): the cell cannot run here"
        )


def special_words(cfg: dict) -> dict[int, str]:
    """bos and eos at the configuration's ids; the template's two role words
    are words of the vocabulary here, at the first ids that are free. The
    first thing either process asks of this file, so the place of
    ``require_program``."""
    require_program(cfg)
    words = {cfg["bos_token_id"]: _BOS, cfg["eos_token_id"]: _EOS}
    free = (i for i in range(cfg["vocab_size"]) if i not in words)
    for marker in _MARKERS:
        words[next(free)] = marker
    return words


def chat_text(user: str) -> str:
    """The DeepSeek-V3 family's template for one user turn, as
    ``cake_tpu/models/llama/chat.py`` renders ``deepseek_v32`` (written from
    memory; ``assumed`` in the configuration)."""
    return f"{_BOS}<｜User｜>{user}<｜Assistant｜>"


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids[_BOS], ids["<｜User｜>"], *prompt_ids, ids["<｜Assistant｜>"]]


# -------------------------------------------------------------------- costs


def _count(shapes: dict) -> int:
    return sum(int(np.prod(s)) for s in shapes.values())


def layer_parameters(cfg: dict, i: int) -> int:
    return _count({n: s for n, (s, _) in layer_tensors(cfg, i).items()})


def parameters(cfg: dict) -> int:
    """Every parameter this rank holds: layers, embedding, final norm, head."""
    layers = sum(layer_parameters(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers + _count({n: s for n, (s, _) in top_tensors(cfg).items()})


def parameter_groups(cfg: dict) -> dict[str, int]:
    """The count by what it is, for the configuration's ``deployment`` and
    the test that pins it."""
    h = cfg["hidden_size"]
    return {
        "mla": _count(attention_shapes(cfg)), "index": _count(index_shapes(cfg)),
        "dense_ff": _count(swiglu_shapes("m", h, cfg["intermediate_size"])),
        "shared": _count(swiglu_shapes("s", h, cfg["n_shared_experts"] * cfg["moe_intermediate_size"])),
        "router": ranked_experts(cfg) * h,
        "held_experts": len(held_experts(cfg)) * expert_parameters(cfg),
        "embedding_and_head": 2 * cfg["vocab_size"] * h,
        "all": parameters(cfg),
    }


def expert_parameters(cfg: dict) -> int:
    """One routed expert's."""
    return _count(swiglu_shapes("e", cfg["hidden_size"], cfg["moe_intermediate_size"]))


def expert_bytes(cfg: dict, dtype: str) -> int:
    return expert_parameters(cfg) * ITEMSIZE[dtype]


def sparse_layers(cfg: dict) -> int:
    return sum(is_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a decode step reads WHATEVER THE ROUTING and for any
    batch: every layer's attention, index, norms, router, shared expert or
    dense feed-forward, the final norm and the head (the embedding is a
    lookup of one row a lane). No routed expert is counted (which of them a
    step reads depends on its tokens), neither are the pools."""
    h = cfg["hidden_size"]
    routed = sparse_layers(cfg) * len(held_experts(cfg)) * expert_parameters(cfg)
    embed = cfg["vocab_size"] * h
    return (parameters(cfg) - routed - embed) * ITEMSIZE[dtype]


def cache_bytes_per_token(cfg: dict, dtype: str) -> dict[str, int]:
    """What ONE layer keeps of a cached token: the latent as the arithmetic
    needs it, as the pool stores it (whole 128-lane tiles) and the index key."""
    needed = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return {"latent_needed": needed * ITEMSIZE[dtype],
            "latent_stored": -(-needed // 128) * 128 * ITEMSIZE[dtype],
            "index": cfg["index_head_dim"] * ITEMSIZE[dtype]}


def index_scores_cost(cfg: dict, rows: float, scanned_tokens: float,
                      dtype: str) -> tuple[float, float]:
    """(operations, bytes) of the WORK of scoring ``scanned_tokens`` cached
    tokens for ``rows`` queries in one layer: a token's index key is read
    once and meets each of the index heads (a product of ``index_head_dim``
    and one weighted add of the result); every row's index queries and head
    weights come in, a float32 score a scanned token goes out."""
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    ops = 2.0 * heads * dim * scanned_tokens
    io = rows * (heads * dim * ITEMSIZE[dtype] + heads * 4) + scanned_tokens * 4
    return ops, scanned_tokens * dim * ITEMSIZE[dtype] + io


def sparse_attention_cost(cfg: dict, rows: float, chosen_tokens: float,
                          dtype: str) -> tuple[float, float]:
    """(operations, bytes) of the WORK of attending ``chosen_tokens`` chosen
    tokens for ``rows`` queries in one layer, in the absorbed form: a head's
    score against a chosen latent is ``kv_lora_rank + qk_rope_head_dim``
    multiply-adds and its weighted sum ``kv_lora_rank``; a chosen token's
    STORED row is read once for all heads (1,280 B: a gather moves whole
    rows); every row's queries come in and its sums go out."""
    n, rank, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    ops = 2.0 * n * (2 * rank + rope) * chosen_tokens
    io = rows * n * (2 * rank + rope) * ITEMSIZE[dtype]
    return ops, chosen_tokens * cache_bytes_per_token(cfg, dtype)["latent_stored"] + io


# ---------------------------------------------------------------- reference


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """Inverse frequencies [rope / 2] of the rotary numbers, float64."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    scaling = cfg.get("rope_scaling")
    if not scaling or float(scaling["factor"]) == 1.0:
        return 1.0 / freq
    factor, orig = float(scaling["factor"]), scaling["original_max_position_embeddings"]

    def turns_dim(turns: float) -> float:
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_dim(scaling["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (1.0 / (factor * freq)) * ramp + (1.0 / freq) * (1 - ramp)


def score_scale(cfg: dict) -> float:
    dims = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    scaling = cfg.get("rope_scaling")
    m = 1.0
    if scaling and float(scaling["factor"]) > 1.0:
        m = 0.1 * float(scaling.get("mscale_all_dim", 1) or 1) * math.log(float(scaling["factor"])) + 1.0
    return dims ** -0.5 * m * m


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, first, inv_freq):
    """The leading ``2 * len(inv_freq)`` numbers of x [L, ..., d] rotated at
    positions first..first+L-1, pairs (i, i + half); the rest pass through."""
    import jax.numpy as jnp

    half = inv_freq.shape[0]
    ang = (first + jnp.arange(x.shape[0], dtype=jnp.float32))[:, None] * inv_freq[None, :]
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), half)
    x1, x2, rest = x[..., :half], x[..., half: 2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest], -1)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _keys(x, w, inv_freq, *, cfg, fault):
    """What a layer keeps of every token: (u, cq, ckv, k_rope, k_I)."""
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    u = _rms_norm(x, w["input_layernorm.weight"], eps)
    cq = _rms_norm(u @ w["self_attn.q_a_proj.weight"].T, w["self_attn.q_a_layernorm.weight"], eps)
    kv = u @ w["self_attn.kv_a_proj_with_mqa.weight"].T
    ckv = _rms_norm(kv[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_rope = _rope(kv[:, rank:], 0, inv_freq)
    k = u @ w["self_attn.indexer.wk.weight"].T
    mean = jnp.mean(k, -1, keepdims=True)
    k = (k - mean) * jnp.reciprocal(jnp.sqrt(jnp.mean((k - mean) ** 2, -1, keepdims=True) + _INDEX_LN_EPS))
    k_i = k * w["self_attn.indexer.k_norm.weight"] + w["self_attn.indexer.k_norm.bias"]
    if fault != "index_key_unrotated":
        k_i = _rope(k_i, 0, inv_freq)
    return u, cq, ckv, k_rope, k_i


def _chosen(index, first, topk, *, fault, seed):
    """[B, L] bool: S_t of the block's queries at positions first.., by a full
    stable sort of -I (ties to the smaller position)."""
    import jax
    import jax.numpy as jnp

    n_q, length = index.shape
    seen = jnp.arange(length)[None, :] <= (first + jnp.arange(n_q))[:, None]
    if fault == "dense_attention":
        return seen
    if fault == "random_selection":
        index = jax.random.uniform(jax.random.key(seed), index.shape)
    order = jnp.argsort(-jnp.where(seen, index, -jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)  # a position's place in the order
    return seen & (rank < topk)


def _attend(u, cq, first, ckv, k_rope, k_i, w, inv_freq, *, cfg, fault):
    """Attention of a block of queries (rows of ``u``/``cq`` at positions
    ``first``..) against the whole sequence's keys: ([B, heads * v] before
    o_proj, the block's chosen sets [B, L])."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    n = cfg["num_attention_heads"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    heads, dim = cfg["index_n_heads"], cfg["index_head_dim"]
    n_q = u.shape[0]
    q = (cq @ w["self_attn.q_b_proj.weight"].T).reshape(n_q, n, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], first, inv_freq)
    q_i = _rope((cq @ w["self_attn.indexer.wq_b.weight"].T).reshape(n_q, heads, dim), first, inv_freq)
    w_i = (u @ w["self_attn.indexer.weights_proj.weight"].T) * heads ** -0.5 * dim ** -0.5

    def index_head(total, args):  # one index head at a time: [B, L]
        qj, wj = args
        return total + wj[:, None] * jax.nn.relu(qj @ k_i.T), None

    index, _ = jax.lax.scan(
        index_head, jnp.zeros((n_q, k_i.shape[0]), jnp.float32),
        (q_i.transpose(1, 0, 2), w_i.T))
    chosen = _chosen(index, first, cfg["index_topk"], fault=fault, seed=n_q)
    kvb = w["self_attn.kv_b_proj.weight"].reshape(n, nope + vd, -1)
    scale = score_scale(cfg)

    def head(args):  # one head at a time: [B, L] scores
        qn, qr, wkv = args
        k_nope, v = ckv @ wkv[:nope].T, ckv @ wkv[nope:].T
        s = (qn @ k_nope.T + qr @ k_rope.T) * scale
        return jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), -1) @ v

    o = jax.lax.map(head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2), kvb))
    return o.transpose(1, 0, 2).reshape(n_q, -1), chosen


def _routing(u, gate, bias, *, cfg, fault):
    """[L, every ranked expert] combine weights, zero where not chosen."""
    import jax
    import jax.numpy as jnp

    logits = u @ gate.astype(jnp.float32).T
    scores = jax.nn.softmax(logits, -1) if fault == "softmax_scores" else jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    n_group = 1 if fault == "no_group_limit" else cfg["n_group"]
    if n_group > 1:
        grouped = choice.reshape(choice.shape[0], n_group, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, best = jax.lax.top_k(group_score, cfg["topk_group"])
        stays = jnp.sum(jax.nn.one_hot(best, n_group), -2) > 0
        choice = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(choice.shape)
    _, top_e = jax.lax.top_k(choice, cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, -1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(top_e, logits.shape[-1]) * top_s[..., None], -2)


def _add_swiglu(acc, u, weight, gate, up, down):
    """``acc + weight * SwiGLU(u)``, the weights widened."""
    import jax.numpy as jnp

    f32 = jnp.float32
    return acc + weight[:, None] * _swiglu(u, gate.astype(f32), up.astype(f32), down.astype(f32))


# Columns of a SwiGLU applied at once, and queries a block of the attention
# takes: the reference runs in the process that serves (beside 12 GB of
# served weights and pools on the chip), so a dense feed-forward of 18432 is
# summed over blocks of its inner dimension, the experts are applied one at a
# time and an 8,000-token probe's attention goes 1,024 queries at a time
# (scores [1024, L] a head). Sums in another order, nothing else.
_FF_BLOCK = 4608
_QUERY_BLOCK = 1024


def forward_logits(reader, cfg: dict, sequences: list[list[int]],
                   first_rows: list[int] | None = None,
                   timing: dict | None = None,
                   chosen_out: list | None = None) -> list[np.ndarray]:
    """Float32 logits [L, vocab] of every sequence (from position
    ``first_rows[k]`` on, if given), each tensor read once from the
    checkpoint and applied to all sequences. ``chosen_out`` (tests): a list
    that receives, a layer, a list a sequence of the chosen sets [L, L]."""
    import functools

    import jax
    import jax.numpy as jnp

    static = {k: v for k, v in cfg.items() if isinstance(v, (int, float, bool))}
    static["rope_scaling"] = cfg.get("rope_scaling")
    keys = jax.jit(functools.partial(_keys, cfg=static, fault=FAULT))
    attend = jax.jit(functools.partial(_attend, cfg=static, fault=FAULT))
    routing = jax.jit(functools.partial(_routing, cfg=static, fault=FAULT))
    add_swiglu = jax.jit(_add_swiglu)
    eps = cfg["rms_norm_eps"]
    residual = jax.jit(lambda x, o, wo, norm: (
        (h := x + o @ wo.astype(jnp.float32).T), _rms_norm(h, norm.astype(jnp.float32), eps)))
    inv_freq = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)

    def swiglu_blocks(prefix, inter):
        """(gate, up, down) of one SwiGLU, a block of its columns at a time."""
        gate, up, down = (reader(f"{prefix}.{n}_proj.weight") for n in ("gate", "up", "down"))
        for lo in range(0, inter, _FF_BLOCK):
            hi = min(inter, lo + _FF_BLOCK)
            yield jnp.asarray(gate[lo:hi]), jnp.asarray(up[lo:hi]), jnp.asarray(down[:, lo:hi])

    def attention(x, w):
        """(h, N2(h)) of one sequence and, for tests, its chosen sets."""
        u, cq, ckv, k_rope, k_i = keys(x, w, inv_freq)
        outs, sets = [], []
        for lo in range(0, x.shape[0], _QUERY_BLOCK):
            hi = min(x.shape[0], lo + _QUERY_BLOCK)
            o, chosen = attend(u[lo:hi], cq[lo:hi], jnp.int32(lo), ckv, k_rope, k_i, w, inv_freq)
            outs.append(o)
            if chosen_out is not None:
                sets.append(np.asarray(chosen))
        h, u2 = residual(x, jnp.concatenate(outs), w["self_attn.o_proj.weight"],
                         w["post_attention_layernorm.weight"])
        return h, u2, (np.concatenate(sets) if sets else None)

    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(reader("model.embed_tokens.weight"))
        xs = [embed[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        del embed
        for i in range(cfg["num_hidden_layers"]):
            t0 = time.perf_counter()
            prefix = f"model.layers.{i}."
            w = {n: jnp.asarray(reader(prefix + n))
                 for n in (*attention_shapes(cfg), *index_shapes(cfg), *norm_shapes(cfg),
                           "self_attn.indexer.k_norm.bias")}
            hu = [attention(x, w) for x in xs]
            if chosen_out is not None:
                chosen_out.append([c for _, _, c in hu])
            ones = [jnp.ones((x.shape[0],), jnp.float32) for x in xs]
            ffs = [jnp.zeros_like(x) for x in xs]

            def add(prefix, inter, weights):
                for block in swiglu_blocks(prefix, inter):
                    for k, (_, u, _) in enumerate(hu):
                        ffs[k] = add_swiglu(ffs[k], u, weights[k], *block)

            if not is_sparse(cfg, i):
                add(prefix + "mlp", cfg["intermediate_size"], ones)
            else:
                inter = cfg["moe_intermediate_size"]
                gate = jnp.asarray(reader(prefix + "mlp.gate.weight"))
                bias = jnp.asarray(reader(prefix + "mlp.gate.e_score_correction_bias"))
                combine = [routing(u, gate, bias) for _, u, _ in hu]
                for e in held_experts(cfg):  # an absent expert's part is left out
                    add(f"{prefix}mlp.experts.{e}", inter, [c[:, e] for c in combine])
                if cfg["n_shared_experts"] and FAULT != "no_shared_expert":
                    add(prefix + "mlp.shared_experts", cfg["n_shared_experts"] * inter, ones)
            xs = jax.block_until_ready([h + ff for (h, _, _), ff in zip(hu, ffs)])
            del w, hu, ffs
            if timing is not None:  # the reads are mapped files: all of it is the layer's
                timing.setdefault("load_s", []).append(0.0)
                timing.setdefault("layer_s", []).append(time.perf_counter() - t0)
        norm = jnp.asarray(reader("model.norm.weight"))
        head = jnp.asarray(reader("lm_head.weight"))
        final = jax.jit(  # weights as arguments: a closure would bake them in
            lambda x, norm, head: _rms_norm(x, norm.astype(jnp.float32), eps)
            @ head.astype(jnp.float32).T
        )
        first_rows = first_rows or [0] * len(xs)
        return [np.asarray(final(x[r:], norm, head)) for x, r in zip(xs, first_rows)]
