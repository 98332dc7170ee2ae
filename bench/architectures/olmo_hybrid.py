"""``model_type: olmo_hybrid``: tensors, plain reference, template and costs.

Olmo-Hybrid (allenai): ``layer_types`` says, layer by layer, whether a layer
mixes tokens by a gated delta rule (``linear_attention``: Gated DeltaNet, as
the flash-linear-attention / HF ``linear_*`` key family writes it) or by
softmax attention (``full_attention``); every layer's feed-forward is a dense
SwiGLU; the head is not tied. THIS FILE IS THE STATEMENT of the block where
the catalog's ``config`` is silent (the configuration's ``assumed`` lists each
reading); the program follows it.

The block is OLMo-2/3's on both kinds of layer: no norm on a branch's input,
an RMSNorm on each branch's OUTPUT before the residual add:

    x = x + rms(mixer(x); post_attention_layernorm)
    x = x + rms(SwiGLU(x); post_feedforward_layernorm)

``full_attention``, input ``x`` [L, hidden]:

    q, k, v = x Wq, x Wk, x Wv        no bias; 30 heads on 30 KV heads of 128
    q = rms(q; q_norm)  k = rms(k; k_norm)    over the WHOLE projection width
    causal softmax at head_dim ** -0.5, NO positional term (rope_theta null:
    the recurrent layers carry the order), then o_proj

``linear_attention``, ``H`` heads of ``dk`` keys and ``dv`` values:

    q = x Wq [H dk]   k = x Wk [H dk]   v = x Wv [H dv]   z = x Wg [H dv]
    b = x Wb [H]      a = x Wa [H]
    (q, k, v) <- silu(causal depthwise conv over time, 4 taps a channel, no
                 bias, zeros before the sequence)
    per head:  q <- q / sqrt(|q|^2 + 1e-6) * dk ** -0.5
               k <- k / sqrt(|k|^2 + 1e-6)
    beta  = 2 sigmoid(b)       (linear_allow_neg_eigval: I - beta k k^T has
                                eigenvalues in (-1, 1]); else sigmoid(b)
    alpha = exp(-exp(A_log) softplus(a + dt_bias))        a head, in (0, 1)
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
                               S in R^{dv x dk} a head, float32, S_0 = 0
    o_t = S_t q_t
    y = rms(o; o_norm [dv]) * silu(z)  a head     m = concat(y) Wo

Straight ``jax.numpy`` in float32 with matmul precision ``highest``; the
delta rule TOKEN BY TOKEN, one ``lax.scan`` step a position exactly as the
equations above: no chunking, no cache, no kernel, no batching; nothing of
``cake_tpu``. Departures from the published model: none known in the
arithmetic (every reading of what the config does not say is in ``assumed``);
the 1e-6 under the root is flash-linear-attention's ``l2norm`` (a row of
zeros, which only a pad is, normalises to zeros); the checkpoint's tensors
are read in the type they were written in (bf16 on the chip) and widened, as
every reference here does. What a file like this one owes the benchmark is
in ``bench/architectures/__init__.py``.

``FAULT`` is None here and in every run that counts. A test, or a scratch
copy of this file for a control on the chip, sets it to make the reference
wrong in one way: ``beta_sigmoid`` takes ``beta = sigmoid(b)`` (no negative
eigenvalue), ``no_decay`` leaves ``alpha`` out (1), ``no_recurrence`` drops
``S_{t-1}``.
"""

from __future__ import annotations

import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
FAULTS = ("beta_sigmoid", "no_decay", "no_recurrence")
FAULT = None
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"

# ------------------------------------------------------------------ tensors


def is_attention(cfg: dict, i: int) -> bool:
    kind = cfg["layer_types"][i]
    if kind not in (LINEAR, FULL):
        raise ValueError(f"layer {i}: unknown layer type {kind!r}")
    return kind == FULL


def _sizes(cfg: dict) -> dict[str, int]:
    h = cfg["hidden_size"]
    heads = cfg["linear_num_value_heads"]
    if cfg["linear_num_key_heads"] != heads:
        raise ValueError("this file writes the delta rule with as many key heads as value heads")
    return {
        "h": h, "inter": cfg["intermediate_size"], "heads": heads,
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "taps": cfg["linear_conv_kernel_dim"],
        "hd": cfg.get("head_dim") or h // cfg["num_attention_heads"],
    }


def feed_forward_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    z = _sizes(cfg)
    return {
        "mlp.gate_proj.weight": (z["inter"], z["h"]),
        "mlp.up_proj.weight": (z["inter"], z["h"]),
        "mlp.down_proj.weight": (z["h"], z["inter"]),
    }


def attention_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    z = _sizes(cfg)
    q, kv = cfg["num_attention_heads"] * z["hd"], cfg["num_key_value_heads"] * z["hd"]
    return {
        "self_attn.q_proj.weight": ((q, z["h"]), "normal"),
        "self_attn.k_proj.weight": ((kv, z["h"]), "normal"),
        "self_attn.v_proj.weight": ((kv, z["h"]), "normal"),
        "self_attn.o_proj.weight": ((z["h"], q), "normal"),
        "self_attn.q_norm.weight": ((q,), "ones"),
        "self_attn.k_norm.weight": ((kv,), "ones"),
    }


def linear_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """The gated delta rule's tensors with their draws. ``A_log`` and
    ``dt_bias`` drawn normal at ``initializer_range``: exp(A_log) is about 1
    and the gate about softplus(a), so alpha spreads over (0.2, 0.9) once the
    residual stream is of unit size (every branch's output is normed): the
    recurrence is visible and does not forget at once; b spreads as a does,
    so beta = 2 sigmoid(b) lies on both sides of 1."""
    z = _sizes(cfg)
    qk, vv = z["heads"] * z["dk"], z["heads"] * z["dv"]
    return {
        "linear_attn.q_proj.weight": ((qk, z["h"]), "normal"),
        "linear_attn.k_proj.weight": ((qk, z["h"]), "normal"),
        "linear_attn.v_proj.weight": ((vv, z["h"]), "normal"),
        "linear_attn.g_proj.weight": ((vv, z["h"]), "normal"),
        "linear_attn.a_proj.weight": ((z["heads"], z["h"]), "normal"),
        "linear_attn.b_proj.weight": ((z["heads"], z["h"]), "normal"),
        "linear_attn.q_conv1d.weight": ((qk, 1, z["taps"]), "normal"),
        "linear_attn.k_conv1d.weight": ((qk, 1, z["taps"]), "normal"),
        "linear_attn.v_conv1d.weight": ((vv, 1, z["taps"]), "normal"),
        "linear_attn.A_log": ((z["heads"],), "normal"),
        "linear_attn.dt_bias": ((z["heads"],), "normal"),
        "linear_attn.o_norm.weight": ((z["dv"],), "ones"),
        "linear_attn.o_proj.weight": ((z["h"], vv), "normal"),
    }


NORMS = ("post_attention_layernorm.weight", "post_feedforward_layernorm.weight")


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this file writes olmo_hybrid with an untied head only")
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The mixer of the layer's kind, the feed-forward, the two norms."""
    mixer = attention_tensors(cfg) if is_attention(cfg, i) else linear_tensors(cfg)
    table = {f"model.layers.{i}.{n}": v for n, v in mixer.items()}
    table.update({f"model.layers.{i}.{n}": (s, "normal")
                  for n, s in feed_forward_shapes(cfg).items()})
    table.update({f"model.layers.{i}.{n}": ((cfg["hidden_size"],), "ones") for n in NORMS})
    return table


# ----------------------------------------------------------------- template

UNKNOWN_WORD = "<|unk|>"
_MARKERS = (UNKNOWN_WORD, "<|system|>", "<|user|>", "<|assistant|>")


def special_words(cfg: dict) -> dict[int, str]:
    """``<|endoftext|>`` opens the template and ends an answer (one id, as in
    the OLMo-2 tokenizer); the template's markers, plain text to that
    tokenizer, are words of the vocabulary here, at the first ids that are
    free."""
    words = {cfg["pad_token_id"]: "<|pad|>", cfg["eos_token_id"]: "<|endoftext|>"}
    free = (i for i in range(cfg["vocab_size"]) if i not in words)
    for marker in _MARKERS:
        words[next(free)] = marker
    return words


def chat_text(user: str) -> str:
    """The OLMo-2 (Tulu) template for one user turn, as
    ``cake_tpu/models/llama/chat.py`` renders ``olmo_hybrid`` (written from
    memory; ``assumed`` in the configuration)."""
    return f"<|endoftext|><|user|>\n{user}\n<|assistant|>\n"


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids["<|endoftext|>"], ids["<|user|>"], *prompt_ids, ids["<|assistant|>"]]


# -------------------------------------------------------------------- costs


def layer_parameters(cfg: dict, i: int) -> int:
    return sum(int(np.prod(shape)) for shape, _ in layer_tensors(cfg, i).values())


def parameters(cfg: dict) -> int:
    top = sum(int(np.prod(shape)) for shape, _ in top_tensors(cfg).values())
    return top + sum(layer_parameters(cfg, i) for i in range(cfg["num_hidden_layers"]))


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a chip that holds these layers must read to decode
    one token for any batch: all layers by kind, the final norm, the head
    (the embedding is a lookup of one row a lane). Neither the page pool nor
    the recurrent state is counted here (``state_bytes_per_lane``)."""
    h = cfg["hidden_size"]
    total = sum(layer_parameters(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return (total + h + cfg["vocab_size"] * h) * ITEMSIZE[dtype]


def state_bytes_per_lane(cfg: dict) -> int:
    """Recurrent state one lane holds, as the program keeps it: per linear
    layer the float32 ``S`` of every head ([dk, H dv]) and the convolution's
    last ``taps - 1`` inputs of q, k and v in the served type (2 bytes)."""
    z = _sizes(cfg)
    layers = sum(not is_attention(cfg, i) for i in range(cfg["num_hidden_layers"]))
    channels = z["heads"] * (2 * z["dk"] + z["dv"])
    return layers * (4 * z["heads"] * z["dk"] * z["dv"] + 2 * (z["taps"] - 1) * channels)


DELTA_CHUNK = 64  # positions a chunk of the chunkwise form (ops/delta_rule.py)


def gated_delta_rule_cost(cfg: dict, rows: float, tokens: float, dtype: str) -> tuple[float, float]:
    """(operations, bytes) of ONE call of the chunkwise delta rule (one
    layer) over ``rows`` rows of ``tokens`` live positions each, as the
    chunkwise (WY / UT transform) algorithm states them at chunks of C = 64,
    a head and a chunk: K K^T and Q K^T (2 C^2 dk each), the unit triangular
    solve (C^3 / 3 multiply-adds) and its product with [beta V | beta Gamma K]
    (2 C^2 (dk + dv)), the three products with the carried state (2 C dk dv
    each: W S, Q S, K^T U) and the chunk's own output (2 C^2 dv). Bytes: q,
    k, v and the gates in and o out, float32 as the mixer hands them over,
    and each row's state read and written once. The operations are float32
    products (the state is float32), held against the chip's bf16 peak."""
    z = _sizes(cfg)
    c, dk, dv, heads = DELTA_CHUNK, z["dk"], z["dv"], z["heads"]
    chunk = (4 * c * c * dk + 2 * c ** 3 / 3 + 2 * c * c * (dk + dv)
             + 6 * c * dk * dv + 2 * c * c * dv)
    ops = rows * heads * (tokens / c) * chunk
    per_token = heads * (2 * dk + 2 * dv + 2) * 4
    moved = rows * (tokens * per_token + 2 * 4 * heads * dk * dv)
    return ops, moved


def gated_delta_step_cost(cfg: dict, lanes: float, dtype: str) -> tuple[float, float]:
    """(operations, bytes) of ONE call of the one-token update (one layer)
    over ``lanes`` rows: S k, the rank-one update with the decay, S q (7 dk dv
    a head); every row's state read and written once, q, k, v and the two
    gates in and o out in float32."""
    z = _sizes(cfg)
    dk, dv, heads = z["dk"], z["dv"], z["heads"]
    ops = lanes * heads * 7 * dk * dv
    moved = lanes * 4 * heads * (2 * dk * dv + 2 * dk + 2 * dv + 2)
    return ops, moved


# ---------------------------------------------------------------- reference


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _feed_forward(x, w, eps):
    import jax

    gate = jax.nn.silu(x @ w["mlp.gate_proj.weight"].T)
    m = (gate * (x @ w["mlp.up_proj.weight"].T)) @ w["mlp.down_proj.weight"].T
    return x + _rms_norm(m, w["post_feedforward_layernorm.weight"], eps)


def _attention_layer(x, w, *, n_q, n_kv, eps):
    """Softmax attention with q and k normed over the whole projection and
    no positional term."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    n = x.shape[0]
    q = _rms_norm(x @ w["self_attn.q_proj.weight"].T, w["self_attn.q_norm.weight"], eps)
    k = _rms_norm(x @ w["self_attn.k_proj.weight"].T, w["self_attn.k_norm.weight"], eps)
    v = x @ w["self_attn.v_proj.weight"].T
    q, k, v = q.reshape(n, n_q, -1), k.reshape(n, n_kv, -1), v.reshape(n, n_kv, -1)
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
    m = attn @ w["self_attn.o_proj.weight"].T
    x = x + _rms_norm(m, w["post_attention_layernorm.weight"], eps)
    return _feed_forward(x, w, eps)


def _linear_layer(x, w, *, heads, dk, dv, neg_eigval, eps, fault):
    """The gated delta rule, one step of the recurrence a step of
    ``lax.scan``."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    length = x.shape[0]

    def conv(u, taps):  # u [L, c], taps [c, 1, K]: causal, depthwise, no bias
        taps = taps[:, 0, :]
        k = taps.shape[-1]
        padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[-1]), jnp.float32), u], 0)
        return jax.nn.silu(sum(taps[:, j] * padded[j:j + length] for j in range(k)))

    def unit(u):  # [L, H, d] -> each head's vector to length one
        return u * jax.lax.rsqrt(jnp.sum(u * u, -1, keepdims=True) + L2_EPS)

    q = conv(x @ w["linear_attn.q_proj.weight"].T, w["linear_attn.q_conv1d.weight"])
    k = conv(x @ w["linear_attn.k_proj.weight"].T, w["linear_attn.k_conv1d.weight"])
    v = conv(x @ w["linear_attn.v_proj.weight"].T, w["linear_attn.v_conv1d.weight"])
    z = x @ w["linear_attn.g_proj.weight"].T
    q = unit(q.reshape(length, heads, dk)) * dk ** -0.5
    k = unit(k.reshape(length, heads, dk))
    v = v.reshape(length, heads, dv)
    b = jax.nn.sigmoid(x @ w["linear_attn.b_proj.weight"].T)
    beta = 2.0 * b if neg_eigval and fault != "beta_sigmoid" else b
    gate = jax.nn.softplus(x @ w["linear_attn.a_proj.weight"].T + w["linear_attn.dt_bias"])
    alpha = jnp.exp(-jnp.exp(w["linear_attn.A_log"]) * gate)  # [L, H]
    if fault == "no_decay":
        alpha = jnp.ones_like(alpha)
    keep = 0.0 if fault == "no_recurrence" else 1.0

    def step(s, xs):  # s [H, dv, dk]
        q_t, k_t, v_t, a_t, b_t = xs
        s = keep * a_t[:, None, None] * s
        err = v_t - jnp.einsum("hvk,hk->hv", s, k_t)
        s = s + jnp.einsum("hv,hk->hvk", b_t[:, None] * err, k_t)
        return s, jnp.einsum("hvk,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dv, dk), jnp.float32), (q, k, v, alpha, beta))
    y = _rms_norm(o, w["linear_attn.o_norm.weight"], eps) * jax.nn.silu(z.reshape(length, heads, dv))
    m = y.reshape(length, heads * dv) @ w["linear_attn.o_proj.weight"].T
    x = x + _rms_norm(m, w["post_attention_layernorm.weight"], eps)
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

    eps, z = cfg["rms_norm_eps"], _sizes(cfg)
    layers = {
        True: jax.jit(functools.partial(
            _attention_layer, n_q=cfg["num_attention_heads"],
            n_kv=cfg["num_key_value_heads"], eps=eps)),
        False: jax.jit(functools.partial(
            _linear_layer, heads=z["heads"], dk=z["dk"], dv=z["dv"],
            neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)), eps=eps,
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
        norm = jnp.asarray(reader("model.norm.weight"))
        head = jnp.asarray(reader("lm_head.weight"))
        final = jax.jit(  # weights as arguments: a closure would bake them in
            lambda x, norm, head: _rms_norm(x, norm.astype(jnp.float32), eps)
            @ head.astype(jnp.float32).T
        )
        first_rows = first_rows or [0] * len(xs)
        return [np.asarray(final(x[r:], norm, head)) for x, r in zip(xs, first_rows)]
