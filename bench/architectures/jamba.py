"""``model_type: jamba`` with ``num_experts`` 1: tensors, plain reference,
template and costs.

The reference is the Jamba decoder as published (AI21's Jamba, HF
``JambaForCausalLM``; the mixer is Mamba-1 with Jamba's RMSNorms on dt, B
and C). Layer ``i`` is an attention layer when ``i % attn_layer_period ==
attn_layer_offset`` and a state-space layer otherwise; every layer's
feed-forward is a dense SwiGLU; the head is tied to the embedding.

For layer ``i`` with input ``x`` [L, hidden]:

    h = rms(x; input_layernorm)
    state-space:  [u, z] = split(h @ in_proj.T)
                  u = silu(conv(u))    u'_t = b + sum_k w[:, k] * u_{t-3+k},
                                       zeros before the sequence
                  [dt_r, B, C] = split(u @ x_proj.T)
                  dt = softplus(rms(dt_r; dt_layernorm) @ dt_proj.T + bias)
                  B, C = rms(B; b_layernorm), rms(C; c_layernorm)
                  s_t = exp(dt_t[:, None] * A) * s_{t-1}
                        + (dt_t * u_t)[:, None] * B_t[None, :],  A = -exp(A_log)
                  y_t = s_t @ C_t + D * u_t
                  m = (y * silu(z)) @ out_proj.T
    attention:    q, k, v without bias, grouped heads, NO positional term,
                  causal softmax at head_dim ** -0.5, o_proj
    x = x + m;  x = x + SwiGLU(rms(x; pre_ff_layernorm))

Straight ``jax.numpy`` in float32 with matmul precision ``highest``; the scan
a plain ``lax.scan`` over time; no cache, no kernel, no batching; nothing of
``cake_tpu``. Departures from the published model: none in the arithmetic;
the checkpoint's tensors are read in the type they were written in (bf16 on
the chip) and widened, as every reference here does. What a file like this
one owes the benchmark is in ``bench/architectures/__init__.py``.

``FAULT`` is None here and in every run that counts. A test, or a scratch
copy of this file for a control on the chip, sets it to make the reference
wrong in one way (PERF.md, PR 28): ``no_recurrence`` drops ``s_{t-1}``,
``no_conv_history`` drops the convolution's three earlier taps,
``no_inner_norms`` leaves out the dt/B/C norms.
"""

from __future__ import annotations

import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
FAULTS = ("no_recurrence", "no_conv_history", "no_inner_norms")
FAULT = None

# ------------------------------------------------------------------ tensors


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _sizes(cfg: dict) -> dict[str, int]:
    h = cfg["hidden_size"]
    return {
        "h": h, "inter": cfg["intermediate_size"],
        "d": cfg["mamba_expand"] * h, "n": cfg["mamba_d_state"],
        "k": cfg["mamba_d_conv"], "r": cfg["mamba_dt_rank"],
        "hd": h // cfg["num_attention_heads"],
    }


def feed_forward_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    z = _sizes(cfg)
    return {
        "feed_forward.gate_proj.weight": (z["inter"], z["h"]),
        "feed_forward.up_proj.weight": (z["inter"], z["h"]),
        "feed_forward.down_proj.weight": (z["h"], z["inter"]),
    }


def attention_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    z = _sizes(cfg)
    q, kv = cfg["num_attention_heads"] * z["hd"], cfg["num_key_value_heads"] * z["hd"]
    return {
        "self_attn.q_proj.weight": (q, z["h"]),
        "self_attn.k_proj.weight": (kv, z["h"]),
        "self_attn.v_proj.weight": (kv, z["h"]),
        "self_attn.o_proj.weight": (z["h"], q),
    }


def mixer_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """The state-space mixer's tensors with their draws. At
    ``initializer_range`` 0.02, A is about -1 and dt about softplus(N(0,
    0.25)), about 0.7: a state halves about every step, so the recurrence is
    visible and not degenerate."""
    z = _sizes(cfg)
    return {
        "mamba.in_proj.weight": ((2 * z["d"], z["h"]), "normal"),
        "mamba.conv1d.weight": ((z["d"], 1, z["k"]), "normal"),
        "mamba.conv1d.bias": ((z["d"],), "normal"),
        "mamba.x_proj.weight": ((z["r"] + 2 * z["n"], z["d"]), "normal"),
        "mamba.dt_proj.weight": ((z["d"], z["r"]), "normal"),
        "mamba.dt_proj.bias": ((z["d"],), "zeros"),
        "mamba.A_log": ((z["d"], z["n"]), "normal"),
        "mamba.D": ((z["d"],), "ones"),
        "mamba.out_proj.weight": ((z["h"], z["d"]), "normal"),
        "mamba.dt_layernorm.weight": ((z["r"],), "ones"),
        "mamba.b_layernorm.weight": ((z["n"],), "ones"),
        "mamba.c_layernorm.weight": ((z["n"],), "ones"),
    }


NORMS = ("input_layernorm.weight", "pre_ff_layernorm.weight")


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Tied head: no ``lm_head`` entry, and the embedding is drawn ``head``."""
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("this file writes Jamba with a tied head only")
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "head"),
        "model.final_layernorm.weight": ((h,), "ones"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The mixer of the layer's kind, the feed-forward, the two norms."""
    if is_attention(cfg, i):
        mixer = {n: (s, "normal") for n, s in attention_shapes(cfg).items()}
    else:
        mixer = mixer_tensors(cfg)
    table = {f"model.layers.{i}.{n}": v for n, v in mixer.items()}
    table.update({f"model.layers.{i}.{n}": (s, "normal")
                  for n, s in feed_forward_shapes(cfg).items()})
    table.update({f"model.layers.{i}.{n}": ((cfg["hidden_size"],), "ones") for n in NORMS})
    return table


# ----------------------------------------------------------------- template

UNKNOWN_WORD = "<|unk|>"
_MARKERS = (UNKNOWN_WORD, "<|bom|>", "<|eom|>", "<|system|>", "<|user|>", "<|assistant|>")


def special_words(cfg: dict) -> dict[int, str]:
    """Jamba's special ids as HF ``JambaConfig`` defaults give them (pad 0,
    bos 1, eos 2); the template's markers are words of the vocabulary here,
    at the first ids that are free."""
    words = {cfg["pad_token_id"]: "<|pad|>", cfg["bos_token_id"]: "<|startoftext|>",
             cfg["eos_token_id"]: "<|endoftext|>"}
    free = (i for i in range(cfg["vocab_size"]) if i not in words)
    for marker in _MARKERS:
        words[next(free)] = marker
    return words


def chat_text(user: str) -> str:
    """The Jamba-1.5 family's template for one user turn, as
    ``cake_tpu/models/llama/chat.py`` renders ``jamba`` (written from
    memory; ``assumed`` in the configuration)."""
    return f"<|startoftext|><|bom|><|user|> {user}<|eom|><|bom|><|assistant|> "


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids["<|startoftext|>"], ids["<|bom|>"], ids["<|user|>"], *prompt_ids,
            ids["<|eom|>"], ids["<|bom|>"], ids["<|assistant|>"]]


# -------------------------------------------------------------------- costs


def layer_parameters(cfg: dict, i: int) -> int:
    return sum(int(np.prod(shape)) for shape, _ in layer_tensors(cfg, i).values())


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a chip that holds the whole model must read to decode
    one token for any batch: all layers by kind (mixer, feed-forward, norms),
    the final norm, and the tied head once (the embedding's other use is a
    lookup of one row a lane). Neither the page pool nor the recurrent state
    is counted here (``state_bytes_per_lane``), so the share of peak
    bandwidth made from this is a floor on the traffic."""
    h = cfg["hidden_size"]
    total = sum(layer_parameters(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return (total + h + cfg["vocab_size"] * h) * ITEMSIZE[dtype]


def state_bytes_per_lane(cfg: dict) -> int:
    """Recurrent state one lane holds, as the program keeps it: per state
    layer the scan's float32 ``s`` [d_inner, d_state] and the convolution's
    last ``d_conv - 1`` inputs in the served type (2 bytes). A decode step
    reads and writes all of it for every live lane."""
    z = _sizes(cfg)
    layers = sum(not is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return layers * z["d"] * (4 * z["n"] + 2 * (z["k"] - 1))


# ---------------------------------------------------------------- reference


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _feed_forward(x, w, eps):
    import jax

    hn = _rms_norm(x, w["pre_ff_layernorm.weight"], eps)
    gate = jax.nn.silu(hn @ w["feed_forward.gate_proj.weight"].T)
    return x + (gate * (hn @ w["feed_forward.up_proj.weight"].T)) @ w["feed_forward.down_proj.weight"].T


def _attention_layer(x, w, *, n_q, n_kv, eps):
    """Grouped-query attention with no positional term at all."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    n = x.shape[0]
    hn = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = (hn @ w["self_attn.q_proj.weight"].T).reshape(n, n_q, -1)
    k = (hn @ w["self_attn.k_proj.weight"].T).reshape(n, n_kv, -1)
    v = (hn @ w["self_attn.v_proj.weight"].T).reshape(n, n_kv, -1)
    d = q.shape[-1]
    seen = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def group(args):  # one key/value head with the query heads that share it
        qg, kg, vg = args
        s = jnp.einsum("igd,jd->gij", qg, kg) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("gij,jd->igd", p, vg)

    qg = q.reshape(n, n_kv, n_q // n_kv, d).transpose(1, 0, 2, 3)
    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = out.transpose(1, 0, 2, 3).reshape(n, n_q * d)
    x = x + attn @ w["self_attn.o_proj.weight"].T
    return _feed_forward(x, w, eps)


def _state_layer(x, w, *, n, r, eps, fault):
    """The Mamba-1 mixer with Jamba's inner norms, one step of the
    recurrence a step of ``lax.scan``."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    length = x.shape[0]
    hn = _rms_norm(x, w["input_layernorm.weight"], eps)
    uz = hn @ w["mamba.in_proj.weight"].T
    d = uz.shape[-1] // 2
    u, z = uz[:, :d], uz[:, d:]
    taps = w["mamba.conv1d.weight"][:, 0, :]  # [d, k]
    k = taps.shape[-1]
    padded = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), u], 0)
    first_tap = k - 1 if fault == "no_conv_history" else 0
    u = w["mamba.conv1d.bias"] + sum(
        taps[:, j] * padded[j:j + length] for j in range(first_tap, k))
    u = jax.nn.silu(u)
    dbc = u @ w["mamba.x_proj.weight"].T
    dt_r, b_in, c_out = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    if fault != "no_inner_norms":
        dt_r = _rms_norm(dt_r, w["mamba.dt_layernorm.weight"], eps)
        b_in = _rms_norm(b_in, w["mamba.b_layernorm.weight"], eps)
        c_out = _rms_norm(c_out, w["mamba.c_layernorm.weight"], eps)
    dt = jax.nn.softplus(dt_r @ w["mamba.dt_proj.weight"].T + w["mamba.dt_proj.bias"])
    a = -jnp.exp(w["mamba.A_log"])  # [d, n]
    keep = 0.0 if fault == "no_recurrence" else 1.0

    def step(s, xs):
        dt_t, u_t, b_t, c_t = xs
        s = keep * jnp.exp(dt_t[:, None] * a) * s + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, s @ c_t

    _, y = jax.lax.scan(step, jnp.zeros((d, n), jnp.float32), (dt, u, b_in, c_out))
    y = y + w["mamba.D"] * u
    x = x + (y * jax.nn.silu(z)) @ w["mamba.out_proj.weight"].T
    return _feed_forward(x, w, eps)


def forward_logits(reader, cfg: dict, sequences: list[list[int]],
                   first_rows: list[int] | None = None,
                   timing: dict | None = None) -> list[np.ndarray]:
    """Float32 logits [L, vocab] of every sequence (from position
    ``first_rows[k]`` on, if given), each layer's weights read once from the
    checkpoint and applied to all sequences."""
    import functools

    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    layers = {
        True: jax.jit(functools.partial(
            _attention_layer, n_q=cfg["num_attention_heads"],
            n_kv=cfg["num_key_value_heads"], eps=eps)),
        False: jax.jit(functools.partial(
            _state_layer, n=cfg["mamba_d_state"], r=cfg["mamba_dt_rank"], eps=eps,
            fault=FAULT)),
    }
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(reader("model.embed_tokens.weight"))
        xs = [embed[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        del embed
        for i in range(cfg["num_hidden_layers"]):
            t0 = time.perf_counter()
            prefix = f"model.layers.{i}."
            w = {n[len(prefix):]: jnp.asarray(reader(n)) for n in layer_tensors(cfg, i)}
            jax.block_until_ready(w)
            t1 = time.perf_counter()
            layer = layers[is_attention(cfg, i)]
            xs = jax.block_until_ready([layer(x, w) for x in xs])
            if timing is not None:
                timing.setdefault("load_s", []).append(t1 - t0)
                timing.setdefault("layer_s", []).append(time.perf_counter() - t1)
        norm = jnp.asarray(reader("model.final_layernorm.weight"))
        head = jnp.asarray(reader("model.embed_tokens.weight"))  # tied
        final = jax.jit(  # weights as arguments: a closure would bake them in
            lambda x, norm, head: _rms_norm(x, norm.astype(jnp.float32), eps)
            @ head.astype(jnp.float32).T
        )
        first_rows = first_rows or [0] * len(xs)
        return [np.asarray(final(x[r:], norm, head)) for x, r in zip(xs, first_rows)]
