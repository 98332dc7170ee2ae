"""``model_type: qwen2_moe`` for the tests: the architecture file a later PR
would add to ``bench/architectures/`` (the fixture copies it into the
temporary benchmark; it is no part of the committed one, which has no
configuration of this type).

The reference is the Qwen2-MoE decoder as published (HF
``Qwen2MoeSparseMoeBlock``): RMSNorm, q/k/v projections with biases, rotary
embedding over the two halves of a head, grouped-query attention with a
plain causal mask, then a sparse block: a router over all experts, softmax
in float32, the top ``num_experts_per_tok`` experts weighted by their
probabilities (renormalised only where ``norm_topk_prob``), plus a shared
expert behind a sigmoid gate. Untied head. ChatML with Qwen2's default
system prompt, as ``cake_tpu/models/llama/chat.py`` renders this type.
``FAULT`` is for the tests that want this reference wrong in one place; a
server started with ``ZBENCH_REFERENCE_FAULT`` set judges with it.
"""

from __future__ import annotations

import os
import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
FAULT = os.environ.get("ZBENCH_REFERENCE_FAULT")  # "topk_off_by_one" | "no_shared_expert"

# ------------------------------------------------------------------ tensors


def _sizes(cfg: dict) -> tuple[int, int, int]:
    h = cfg["hidden_size"]
    head_dim = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return h, cfg["num_attention_heads"] * head_dim, cfg["num_key_value_heads"] * head_dim


def top_tensors(cfg: dict) -> dict:
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict:
    h, q, kv = _sizes(cfg)
    moe, shared = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    t = {
        "self_attn.q_proj.weight": ((q, h), "normal"),
        "self_attn.q_proj.bias": ((q,), "normal"),
        "self_attn.k_proj.weight": ((kv, h), "normal"),
        "self_attn.k_proj.bias": ((kv,), "normal"),
        "self_attn.v_proj.weight": ((kv, h), "normal"),
        "self_attn.v_proj.bias": ((kv,), "normal"),
        "self_attn.o_proj.weight": ((h, q), "normal"),
        "mlp.gate.weight": ((cfg["num_experts"], h), "normal"),
        "mlp.shared_expert.gate_proj.weight": ((shared, h), "normal"),
        "mlp.shared_expert.up_proj.weight": ((shared, h), "normal"),
        "mlp.shared_expert.down_proj.weight": ((h, shared), "normal"),
        "mlp.shared_expert_gate.weight": ((1, h), "normal"),
        "input_layernorm.weight": ((h,), "ones"),
        "post_attention_layernorm.weight": ((h,), "ones"),
    }
    for e in range(cfg["num_experts"]):
        t[f"mlp.experts.{e}.gate_proj.weight"] = ((moe, h), "normal")
        t[f"mlp.experts.{e}.up_proj.weight"] = ((moe, h), "normal")
        t[f"mlp.experts.{e}.down_proj.weight"] = ((h, moe), "normal")
    return {f"model.layers.{i}.{n}": spec for n, spec in t.items()}


# ----------------------------------------------------------------- template

UNKNOWN_WORD = None  # Qwen2's vocabulary has no unknown word
SYSTEM = "You are a helpful assistant."  # one word here: never drawn, never served


def special_words(cfg: dict) -> dict[int, str]:
    """``<|endoftext|>`` and ``<|im_end|>`` where the configuration puts them
    (bos and eos); the rest of the template at the free ids after them."""
    words = {cfg["bos_token_id"]: "<|endoftext|>", cfg["eos_token_id"]: "<|im_end|>"}
    free = (i for i in range(max(words) + 1, cfg["vocab_size"]) if i not in words)
    for w in ("<|im_start|>", "system", "user", "assistant", SYSTEM):
        words[next(free)] = w
    return words


def chat_text(user: str) -> str:
    return (f"<|im_start|>system\n{SYSTEM}<|im_end|>\n<|im_start|>user\n{user}<|im_end|>\n"
            "<|im_start|>assistant\n")


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    start, end = ids["<|im_start|>"], ids["<|im_end|>"]
    return [start, ids["system"], ids[SYSTEM], end, start, ids["user"], *prompt_ids, end,
            start, ids["assistant"]]


# -------------------------------------------------------------------- costs


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes a chip must read to decode one token for ONE lane: attention,
    router, shared expert, norms, the ``num_experts_per_tok`` experts a token
    is sent to, final norm and head. A batch reads up to every expert."""
    h, q, kv = _sizes(cfg)
    moe, shared = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    attn = h * (q + 2 * kv) + (q + 2 * kv) + q * h
    sparse = cfg["num_experts"] * h + cfg["num_experts_per_tok"] * 3 * h * moe
    per_layer = attn + sparse + 3 * h * shared + h + 2 * h
    return (cfg["num_hidden_layers"] * per_layer + h + cfg["vocab_size"] * h) * ITEMSIZE[dtype]


# ---------------------------------------------------------------- reference


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, theta):
    """x: [L, heads, d] at positions 0..L-1."""
    import jax.numpy as jnp

    n, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + half * sin


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _layer(x, w, *, n_q, n_kv, n_experts, top_k, renormalise, eps, theta, fault):
    """One decoder layer over a whole sequence x: [L, hidden]; ``w`` maps the
    names of ``layer_tensors`` (after ``model.layers.<i>.``) to arrays."""
    import jax
    import jax.numpy as jnp

    f32 = lambda name: w[name].astype(jnp.float32)
    n = x.shape[0]
    hn = _rms_norm(x, f32("input_layernorm.weight"), eps)
    proj = lambda p, heads: (
        hn @ f32(f"self_attn.{p}_proj.weight").T + f32(f"self_attn.{p}_proj.bias")
    ).reshape(n, heads, -1)
    q, k, v = proj("q", n_q), proj("k", n_kv), proj("v", n_kv)
    d = q.shape[-1]
    q, k = _rope(q, theta), _rope(k, theta)
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    k, v = (jnp.repeat(t, n_q // n_kv, axis=1) for t in (k, v))  # each query head its own
    s = jnp.einsum("ihd,jhd->hij", q, k) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
    attn = jnp.einsum("hij,jhd->ihd", p, v).reshape(n, n_q * d)
    x = x + attn @ f32("self_attn.o_proj.weight").T

    hn = _rms_norm(x, f32("post_attention_layernorm.weight"), eps)
    probs = jax.nn.softmax(hn @ f32("mlp.gate.weight").T, -1)  # [L, experts]
    take = top_k - 1 if fault == "topk_off_by_one" else top_k
    top_p, top_e = jax.lax.top_k(probs, take)
    if renormalise:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    # every expert on every token, weighted by what the router gave it (0 for most)
    weight = jnp.zeros_like(probs).at[jnp.arange(n)[:, None], top_e].set(top_p)
    out = jnp.zeros_like(hn)
    for e in range(n_experts):
        y = _swiglu(hn, *(f32(f"mlp.experts.{e}.{m}_proj.weight") for m in ("gate", "up", "down")))
        out = out + weight[:, e:e + 1] * y
    if fault != "no_shared_expert":
        shared = _swiglu(hn, *(f32(f"mlp.shared_expert.{m}_proj.weight")
                               for m in ("gate", "up", "down")))
        out = out + jax.nn.sigmoid(hn @ f32("mlp.shared_expert_gate.weight").T) * shared
    return x + out


def forward_logits(reader, cfg: dict, sequences: list[list[int]],
                   first_rows: list[int] | None = None,
                   timing: dict | None = None) -> list[np.ndarray]:
    """Float32 logits [L, vocab] of every sequence (from position
    ``first_rows[k]`` on, if given), one layer of weights on the device at a
    time."""
    import functools

    import jax
    import jax.numpy as jnp

    layer = jax.jit(functools.partial(
        _layer, n_q=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        renormalise=cfg.get("norm_topk_prob", False), eps=cfg["rms_norm_eps"],
        theta=cfg["rope_theta"], fault=FAULT,
    ))
    prefix = "model.layers.0."
    names = [n.removeprefix(prefix) for n in layer_tensors(cfg, 0)]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(reader("model.embed_tokens.weight"))
        xs = [embed[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        del embed
        for i in range(cfg["num_hidden_layers"]):
            t0 = time.perf_counter()
            w = {n: jnp.asarray(reader(f"model.layers.{i}.{n}")) for n in names}
            jax.block_until_ready(w)
            t1 = time.perf_counter()
            xs = jax.block_until_ready([layer(x, w) for x in xs])
            if timing is not None:
                timing.setdefault("load_s", []).append(t1 - t0)
                timing.setdefault("layer_s", []).append(time.perf_counter() - t1)
        norm = jnp.asarray(reader("model.norm.weight"))
        head = jnp.asarray(reader("lm_head.weight"))
        final = jax.jit(
            lambda x, norm, head: _rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
            @ head.astype(jnp.float32).T
        )
        first_rows = first_rows or [0] * len(xs)
        return [np.asarray(final(x[r:], norm, head)) for x, r in zip(xs, first_rows)]
