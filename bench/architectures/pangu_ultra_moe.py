"""``model_type: pangu_ultra_moe`` (openPangu-Ultra-MoE-718B): tensors, plain
reference, template and costs, for the whole model or for one rank's share
of an expert-parallel deployment.

For a layer's input ``x`` [L, hidden] (RMSNorm eps ``rms_norm_eps``; all of
it float32 at matmul precision ``highest``):

    sandwich_norm:  h = x + N2(Attn(N1(x)));   y = h + N4(FF(N3(h)))
    MLA:  cq = rms(u Wqa; q_a_layernorm)                         [q_lora_rank]
          q = cq Wqb -> a head [q_nope | q_rope]          [nope 128 | rope 64]
          [ckv | k_rope] = u Wkva;  ckv = rms(ckv; kv_a_layernorm)
          k_rope = RoPE(k_rope)               one rotary key for all heads
          [k_nope_h | v_h] = ckv Wkvb  a head
          s_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope) / sqrt(nope + rope)
          causal softmax;  o_h = sum p v_h;  Attn = concat_h(o_h) Wo
          RoPE: theta ``rope_theta``, no scaling, pairs (i, i + rope/2)
    FF, layers below first_k_dense_replace:  SwiGLU of intermediate_size
    FF, the others:  s = sigmoid(u Wg^T)                  [every ranked expert]
          top-k of s;  w = s[top] / (sum s[top] + 1e-20) * routed_scaling_factor
          FF = SwiGLU_shared(u) + sum_{e in top-k, e HELD} w_e SwiGLU_e(u)
    head: rms(.; model.norm) then the untied lm_head

**The share.** ``n_routed_experts`` counts the experts HELD (their tensors
are in the checkpoint under their own numbers), ``n_routed_experts_total``
those the router ranks (absent: the same, the whole model) and
``first_routed_expert`` the first held. The router keeps its width and its
experts a token; what an absent expert would add to a token is left out,
here as in the program, and that partial result goes on to the next layer.
The shared expert, attention and the router are whole on every rank.
``vocab_size`` is the slice held: ids, logits and the head are over it.

**Not here, on purpose: the multi-token-prediction module**
(``num_nextn_predict_layers``). It is one more block behind the last layer
that drafts token t+2 from the last hidden state and the embedding of token
t+1; it adds nothing to the next token's logits, the program does not serve
it (a step that yields more than one token a sequence is ROADMAP M4), no
tensor of it is drawn and the key stays in the configuration as published.

Departures from the published model: none in the arithmetic above, which is
the DeepSeek-V3 family's as far as the config's keys say; what the config
does not say is ``assumed`` in the configuration file (sigmoid scoring
without groups or bias, the order of the four norms, the rotary pairing,
tensor names, the template). The checkpoint's tensors are read in the type
they were written in and widened. Nothing of ``cake_tpu``; no cache, no
kernel, no batching. What a file like this one owes the benchmark is in
``bench/architectures/__init__.py``.

``FAULT`` is None here and in every run that counts. A test, or a scratch
copy of this file for a control on the chip, sets it to make the reference
wrong in one way: ``softmax_scores`` ranks and weighs by a softmax over the
router's logits, ``no_shared_expert`` leaves the shared expert out,
``k_rope_unrotated`` leaves the shared rotary key without its rotation.
"""

from __future__ import annotations

import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
FAULTS = ("softmax_scores", "no_shared_expert", "k_rope_unrotated")
FAULT = None

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


def swiglu_shapes(prefix: str, h: int, inter: int) -> dict[str, tuple[int, ...]]:
    return {f"{prefix}.gate_proj.weight": (inter, h), f"{prefix}.up_proj.weight": (inter, h),
            f"{prefix}.down_proj.weight": (h, inter)}


def feed_forward_shapes(cfg: dict, i: int) -> dict[str, tuple[int, ...]]:
    h = cfg["hidden_size"]
    if not is_sparse(cfg, i):
        return swiglu_shapes("mlp", h, cfg["intermediate_size"])
    inter = cfg["moe_intermediate_size"]
    shapes = {"mlp.gate.weight": (ranked_experts(cfg), h)}
    for e in held_experts(cfg):
        shapes.update(swiglu_shapes(f"mlp.experts.{e}", h, inter))
    if cfg["n_shared_experts"]:
        shapes.update(swiglu_shapes("mlp.shared_experts", h, cfg["n_shared_experts"] * inter))
    return shapes


def norm_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    h = cfg["hidden_size"]
    return {
        "input_layernorm.weight": (h,), "post_attention_layernorm.weight": (h,),
        "pre_mlp_layernorm.weight": (h,), "post_mlp_layernorm.weight": (h,),
        "self_attn.q_a_layernorm.weight": (cfg["q_lora_rank"],),
        "self_attn.kv_a_layernorm.weight": (cfg["kv_lora_rank"],),
    }


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this file writes pangu_ultra_moe with an untied head only")
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """Attention, the layer's kind of feed-forward, the four norms of the
    sandwich and MLA's two inner ones. No tensor of the MTP module."""
    table = {n: (s, "normal") for n, s in attention_shapes(cfg).items()}
    table.update({n: (s, "normal") for n, s in feed_forward_shapes(cfg, i).items()})
    table.update({n: (s, "ones") for n, s in norm_shapes(cfg).items()})
    return {f"model.layers.{i}.{n}": v for n, v in table.items()}


# ----------------------------------------------------------------- template

UNKNOWN_WORD = None
_MARKERS = ("[unused9]", "[unused10]", "系统：", "用户：", "助手：")


def require_program(cfg: dict) -> None:
    """Fail at once, before a 10 GB checkpoint is drawn and written, on a
    checkout whose program cannot parse this ``model_type`` (the parent of
    the PR that brought it): the run would end the same way minutes later,
    when ``cake_tpu.cli.main`` reads ``config.json``. The program's parser
    module imports no JAX, so the benchmark's parent process may ask it."""
    from cake_tpu.models.llama.config import SUPPORTED_MODEL_TYPES

    if cfg["model_type"] not in SUPPORTED_MODEL_TYPES:
        raise RuntimeError(
            f"this checkout's cake_tpu does not take model_type {cfg['model_type']!r} "
            f"(it takes {', '.join(SUPPORTED_MODEL_TYPES)}): the cell cannot run here"
        )


def special_words(cfg: dict) -> dict[int, str]:
    """bos and eos at the configuration's ids; the template's markers are
    words of the vocabulary here, at the first ids that are free. The first
    thing either process asks of this file, so the place of
    ``require_program``."""
    require_program(cfg)
    words = {cfg["bos_token_id"]: "<s>", cfg["eos_token_id"]: "</s>"}
    free = (i for i in range(cfg["vocab_size"]) if i not in words)
    for marker in _MARKERS:
        words[next(free)] = marker
    return words


def chat_text(user: str) -> str:
    """openPangu's template for one user turn, as
    ``cake_tpu/models/llama/chat.py`` renders ``pangu_ultra_moe`` (written
    from memory; ``assumed`` in the configuration)."""
    return f"<s>[unused9]用户：{user}[unused10][unused9]助手："


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids["<s>"], ids["[unused9]"], ids["用户："], *prompt_ids,
            ids["[unused10]"], ids["[unused9]"], ids["助手："]]


# -------------------------------------------------------------------- costs


def _count(shapes: dict) -> int:
    return sum(int(np.prod(s)) for s in shapes.values())


def layer_parameters(cfg: dict, i: int) -> int:
    return _count({n: s for n, (s, _) in layer_tensors(cfg, i).items()})


def parameters(cfg: dict) -> int:
    """Every parameter this rank holds: layers, embedding, final norm, head."""
    layers = sum(layer_parameters(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers + _count({n: s for n, (s, _) in top_tensors(cfg).items()})


def expert_parameters(cfg: dict) -> int:
    """One routed expert's."""
    return _count(swiglu_shapes("e", cfg["hidden_size"], cfg["moe_intermediate_size"]))


def expert_bytes(cfg: dict, dtype: str) -> int:
    return expert_parameters(cfg) * ITEMSIZE[dtype]


def sparse_layers(cfg: dict) -> int:
    return sum(is_sparse(cfg, i) for i in range(cfg["num_hidden_layers"]))


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a decode step reads WHATEVER THE ROUTING and for any
    batch: every layer's attention, norms, router, shared expert or dense
    feed-forward, the final norm and the head (the embedding is a lookup of
    one row a lane). **No routed expert is counted**: which of them a step
    reads depends on its tokens (``decode_expert_stream_pct`` counts those
    from the program's own account), so the share of peak bandwidth made
    from this is a floor and cannot pass 100% when few lanes are live.
    Neither is the latent pool."""
    h = cfg["hidden_size"]
    routed = sparse_layers(cfg) * len(held_experts(cfg)) * expert_parameters(cfg)
    embed = cfg["vocab_size"] * h
    return (parameters(cfg) - routed - embed) * ITEMSIZE[dtype]


def latent_bytes_per_token(cfg: dict, dtype: str) -> int:
    """What the arithmetic reads of one cached token in one layer: the
    compressed K/V and the shared rotary key (the pool stores them padded to
    whole lane tiles; the padding is not counted as needed)."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * ITEMSIZE[dtype]


def latent_decode_attention_cost(cfg: dict, lanes: float, cached_tokens: float,
                                 dtype: str) -> tuple[float, float]:
    """(operations, bytes) of ONE call of the absorbed decode kernel over
    ``lanes`` rows that hold ``cached_tokens`` latents together: a head's
    score against a latent is ``kv_lora_rank + qk_rope_head_dim``
    multiply-adds and its weighted sum ``kv_lora_rank``; a latent is read
    once for all heads; every row's queries come in and its sums go out."""
    n, rank, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    ops = 2.0 * n * (2 * rank + rope) * cached_tokens
    io = lanes * n * (2 * rank + rope) * ITEMSIZE[dtype]
    return ops, cached_tokens * latent_bytes_per_token(cfg, dtype) + io


# ---------------------------------------------------------------- reference


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, theta):
    """x [L, ..., d] at positions 0..L-1, pairs (i, i + d/2)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), d // 2)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _attention(x, w, *, cfg, fault):
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps, n = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    rank, nope, rope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    length = x.shape[0]
    u = _rms_norm(x, w["input_layernorm.weight"], eps)
    cq = _rms_norm(u @ w["self_attn.q_a_proj.weight"].T, w["self_attn.q_a_layernorm.weight"], eps)
    q = (cq @ w["self_attn.q_b_proj.weight"].T).reshape(length, n, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"])
    kv = u @ w["self_attn.kv_a_proj_with_mqa.weight"].T
    ckv = _rms_norm(kv[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_rope = kv[:, rank:]
    if fault != "k_rope_unrotated":
        k_rope = _rope(k_rope, cfg["rope_theta"])
    kvb = (ckv @ w["self_attn.kv_b_proj.weight"].T).reshape(length, n, -1)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]

    def head(args):  # one head at a time: [L, L] scores
        qn, qr, kn, vh = args
        s = (qn @ kn.T + qr @ k_rope.T) / np.sqrt(nope + rope)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1) @ vh

    o = jax.lax.map(head, tuple(a.transpose(1, 0, 2) for a in (q_nope, q_rope, k_nope, v)))
    attn = o.transpose(1, 0, 2).reshape(length, -1) @ w["self_attn.o_proj.weight"].T
    h = x + _rms_norm(attn, w["post_attention_layernorm.weight"], eps)
    return h, _rms_norm(h, w["pre_mlp_layernorm.weight"], eps)


def _routing(u, gate, *, cfg, fault):
    """[L, every ranked expert] combine weights, zero where not chosen."""
    import jax
    import jax.numpy as jnp

    logits = u @ gate.astype(jnp.float32).T
    scores = jax.nn.softmax(logits, -1) if fault == "softmax_scores" else jax.nn.sigmoid(logits)
    top_s, top_e = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(top_e, logits.shape[-1]) * top_s[..., None], -2)


def _add_swiglu(acc, u, weight, gate, up, down):
    """``acc + weight * SwiGLU(u)``, the weights widened."""
    import jax.numpy as jnp

    f32 = jnp.float32
    return acc + weight[:, None] * _swiglu(u, gate.astype(f32), up.astype(f32), down.astype(f32))


# Columns of a SwiGLU applied at once: the reference runs in the process
# that serves (beside 11 GB of served weights on the chip), so a dense
# feed-forward of 18432 is summed over blocks of its inner dimension and the
# experts are applied one at a time. A sum in another order, nothing else.
_FF_BLOCK = 4608


def forward_logits(reader, cfg: dict, sequences: list[list[int]],
                   first_rows: list[int] | None = None,
                   timing: dict | None = None) -> list[np.ndarray]:
    """Float32 logits [L, vocab] of every sequence (from position
    ``first_rows[k]`` on, if given), each tensor read once from the
    checkpoint and applied to all sequences: a layer's attention, then its
    feed-forward a block of columns or an expert at a time."""
    import functools

    import jax
    import jax.numpy as jnp

    static = {k: v for k, v in cfg.items() if isinstance(v, (int, float, bool))}
    attention = jax.jit(functools.partial(_attention, cfg=static, fault=FAULT))
    routing = jax.jit(functools.partial(_routing, cfg=static, fault=FAULT))
    add_swiglu = jax.jit(_add_swiglu)
    eps = cfg["rms_norm_eps"]
    finish = jax.jit(lambda h, ff, norm: h + _rms_norm(ff, norm.astype(jnp.float32), eps))

    def swiglu_blocks(prefix, inter):
        """(gate, up, down) of one SwiGLU, a block of its columns at a time."""
        gate, up, down = (reader(f"{prefix}.{n}_proj.weight") for n in ("gate", "up", "down"))
        for lo in range(0, inter, _FF_BLOCK):
            hi = min(inter, lo + _FF_BLOCK)
            yield jnp.asarray(gate[lo:hi]), jnp.asarray(up[lo:hi]), jnp.asarray(down[:, lo:hi])

    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(reader("model.embed_tokens.weight"))
        xs = [embed[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        del embed
        for i in range(cfg["num_hidden_layers"]):
            t0 = time.perf_counter()
            prefix = f"model.layers.{i}."
            w = {n: jnp.asarray(reader(prefix + n))
                 for n in (*attention_shapes(cfg), *norm_shapes(cfg))}
            hu = [attention(x, w) for x in xs]
            ones = [jnp.ones((x.shape[0],), jnp.float32) for x in xs]
            ffs = [jnp.zeros_like(x) for x in xs]

            def add(prefix, inter, weights):
                for block in swiglu_blocks(prefix, inter):
                    for k, (_, u) in enumerate(hu):
                        ffs[k] = add_swiglu(ffs[k], u, weights[k], *block)

            if not is_sparse(cfg, i):
                add(prefix + "mlp", cfg["intermediate_size"], ones)
            else:
                inter = cfg["moe_intermediate_size"]
                gate = jnp.asarray(reader(prefix + "mlp.gate.weight"))
                combine = [routing(u, gate) for _, u in hu]
                for e in held_experts(cfg):  # an absent expert's part is left out
                    add(f"{prefix}mlp.experts.{e}", inter, [c[:, e] for c in combine])
                if cfg["n_shared_experts"] and FAULT != "no_shared_expert":
                    add(prefix + "mlp.shared_experts", cfg["n_shared_experts"] * inter, ones)
            xs = jax.block_until_ready([
                finish(h, ff, w["post_mlp_layernorm.weight"]) for (h, _), ff in zip(hu, ffs)])
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
