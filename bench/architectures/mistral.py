"""``model_type: mistral``: tensors, plain reference, template and costs.

The reference is the Mistral decoder as published: RMSNorm, rotary
embedding over the two halves of a head (HF's ``rotate_half``), grouped-query
attention, causal mask with a sliding window (key j serves query i when
i - window < j <= i), SwiGLU MLP, untied head. Straight ``jax.numpy`` in
float32 with matmul precision ``highest``; no cache, no kernel, no batching;
nothing of ``cake_tpu``. What a file like this one owes the benchmark is in
``bench/architectures/__init__.py``.
"""

from __future__ import annotations

import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}

# ------------------------------------------------------------------ tensors


def layer_shapes(cfg: dict) -> dict[str, tuple[int, int]]:
    """HF tensor names (after ``model.layers.<i>.``) -> [out, in] shapes."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    head_dim = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * head_dim, cfg["num_key_value_heads"] * head_dim
    return {
        "self_attn.q_proj.weight": (q, h),
        "self_attn.k_proj.weight": (kv, h),
        "self_attn.v_proj.weight": (kv, h),
        "self_attn.o_proj.weight": (h, q),
        "mlp.gate_proj.weight": (inter, h),
        "mlp.up_proj.weight": (inter, h),
        "mlp.down_proj.weight": (h, inter),
    }


NORMS = ("input_layernorm.weight", "post_attention_layernorm.weight")


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """Every layer alike: the seven matrices, then the two norms."""
    table = {f"model.layers.{i}.{n}": (s, "normal") for n, s in layer_shapes(cfg).items()}
    table.update({f"model.layers.{i}.{n}": ((cfg["hidden_size"],), "ones") for n in NORMS})
    return table


# ----------------------------------------------------------------- template

UNKNOWN_WORD = "<unk>"


def special_words(cfg: dict) -> dict[int, str]:
    """Mistral's special ids as its config.json gives them (bos 1, eos 2);
    the instruction markers are ordinary words of the vocabulary here, at
    the first two ids that are free."""
    words = {0: UNKNOWN_WORD, cfg["bos_token_id"]: "<s>", cfg["eos_token_id"]: "</s>"}
    free = (i for i in range(cfg["vocab_size"]) if i not in words)
    for marker in ("[INST]", "[/INST]"):
        words[next(free)] = marker
    return words


def chat_text(user: str) -> str:
    """Mistral's instruction template for one user turn, as published (and
    as ``cake_tpu/models/llama/chat.py`` renders it)."""
    return f"<s>[INST] {user} [/INST]"


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    """The ids the server's tokenizer makes of ``chat_text`` around the
    prompt's words."""
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids["<s>"], ids["[INST]"], *prompt_ids, ids["[/INST]"]]


# -------------------------------------------------------------------- costs


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a chip that holds the whole model must read to decode
    one token for any batch: every layer's seven matrices and two norms, the
    final norm and the output head. The embedding is a lookup of one row a
    lane and the KV cache depends on the contexts: neither is counted, so
    the share of peak bandwidth made from this is a floor on the traffic."""
    h = cfg["hidden_size"]
    per_layer = sum(o * i for o, i in layer_shapes(cfg).values()) + 2 * h
    total = cfg["num_hidden_layers"] * per_layer + h + cfg["vocab_size"] * h
    return total * ITEMSIZE[dtype]


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


def _layer(x, w, *, n_q, n_kv, eps, theta, window):
    """One decoder layer over a whole sequence x: [L, hidden]; ``w`` maps the
    HF names of ``layer_shapes`` and ``NORMS`` to arrays."""
    import jax
    import jax.numpy as jnp

    f32 = lambda name: w[name].astype(jnp.float32)
    n = x.shape[0]
    hn = _rms_norm(x, f32("input_layernorm.weight"), eps)
    q = (hn @ f32("self_attn.q_proj.weight").T).reshape(n, n_q, -1)
    k = (hn @ f32("self_attn.k_proj.weight").T).reshape(n, n_kv, -1)
    v = (hn @ f32("self_attn.v_proj.weight").T).reshape(n, n_kv, -1)
    d = q.shape[-1]
    q, k = _rope(q, theta), _rope(k, theta)
    i, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window

    def group(args):  # one key/value head with the query heads that share it
        qg, kg, vg = args  # [L, g, d], [L, d], [L, d]
        s = jnp.einsum("igd,jd->gij", qg, kg) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("gij,jd->igd", p, vg)

    qg = q.reshape(n, n_kv, n_q // n_kv, d).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = out.transpose(1, 0, 2, 3).reshape(n, n_q * d)
    x = x + attn @ f32("self_attn.o_proj.weight").T
    hn = _rms_norm(x, f32("post_attention_layernorm.weight"), eps)
    gate = jax.nn.silu(hn @ f32("mlp.gate_proj.weight").T)
    return x + (gate * (hn @ f32("mlp.up_proj.weight").T)) @ f32("mlp.down_proj.weight").T


def forward_logits(reader, cfg: dict, sequences: list[list[int]],
                   first_rows: list[int] | None = None,
                   timing: dict | None = None) -> list[np.ndarray]:
    """Float32 logits [L, vocab] of every sequence (from position
    ``first_rows[k]`` on, if given), each layer's weights read once from the
    checkpoint and applied to all sequences."""
    import functools

    import jax
    import jax.numpy as jnp

    layer = jax.jit(functools.partial(
        _layer, n_q=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
        window=cfg.get("sliding_window"),
    ))
    names = (*layer_shapes(cfg), *NORMS)
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
        final = jax.jit(  # weights as arguments: a closure would bake them in
            lambda x, norm, head: _rms_norm(x, norm.astype(jnp.float32), cfg["rms_norm_eps"])
            @ head.astype(jnp.float32).T
        )
        first_rows = first_rows or [0] * len(xs)
        return [np.asarray(final(x[r:], norm, head)) for x, r in zip(xs, first_rows)]
