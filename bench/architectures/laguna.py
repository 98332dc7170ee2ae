"""``model_type: laguna`` (poolside Laguna-S-2.1): tensors, plain reference,
template and costs, for the whole model or for one rank's share of an
expert-parallel deployment.

For layer ``l`` of kind ``layer_types[l]`` with ``H =
num_attention_heads_per_layer[l]`` query heads on ``num_key_value_heads`` KV
heads of ``d = head_dim``, and its input ``x`` [L, hidden] (RMSNorm eps
``rms_norm_eps``, no biases; all of it float32 at matmul precision
``highest``):

    x' = rms(x; input_layernorm)
    q = x' Wq [H, d]     k = x' Wk [n_kv, d]     v = x' Wv [n_kv, d]
    q, k = rope_kind(q, k, pos)    rope_parameters[layer_types[l]]:
        the first ``partial_rotary_factor * d`` numbers of a head rotated in
        HF's rotate-half pairs (i, i + rot/2), the rest untouched; inverse
        frequencies ``theta^(-2i/rot)``, under ``rope_type: yarn`` blended
        with those over ``factor`` by the ramp between the dims that turn
        ``beta_fast`` and ``beta_slow`` times in
        ``original_max_position_embeddings``, cos and sin multiplied by
        ``attention_factor``
    a_h = softmax(q_h k_g(h)^T / sqrt(d) + mask) v_g(h),   g(h) = h // (H / n_kv)
        mask: causal; sliding_attention: and key j > i - sliding_window
    a_h <- GATE_ACT(x' Wg)_h * a_h                               Wg [hidden, H]
    x <- x + concat_h(a_h) Wo
    u = rms(x; post_attention_layernorm)
    mlp_layer_types[l] dense:   x <- x + SwiGLU(u), width intermediate_size
    sparse: s = SCORES(u Wr) [every ranked expert]; T = top-k(s);
            w_e = moe_routed_scaling_factor * s_e / (sum_T s + 1e-20)
            x <- x + sum_{e in T, e HELD} w_e SwiGLU_e(u) + SwiGLU_shared(u)
    logits = rms(x; model.norm) lm_head^T                              (untied)

**The share.** ``num_experts`` counts the experts HELD (their tensors are in
the checkpoint under their own numbers), ``num_experts_total`` those the
router ranks (absent: the same, the whole model) and ``first_expert`` the
first held. The router keeps its width and its experts a token; what an
absent expert would add to a token is left out, here as in the program, and
that partial result goes on to the next layer. The shared expert, attention
and the router are whole on every rank. ``vocab_size`` is the slice held.

**Assumed** (the config does not say; each is DATA below and in the
program's parser, ``cake_tpu/models/llama/config.py``, so a correction from
the model's own ``modeling_laguna.py`` is a line each):

  * ``GATE_ACT = "sigmoid"``: the gate is the published head-wise form, the
    sigmoid of a linear map of the layer's normed input, one scalar a query
    head, on the head's attention output before ``o_proj`` (the config says
    only ``gating: per-head``); its tensor is ``self_attn.g_proj.weight``;
  * ``SCORES = "sigmoid"``: router scores are sigmoids of the logits, the
    chosen renormalised by their sum and scaled, no selection bias, no
    groups (``norm_topk_prob`` true with ``moe_routed_scaling_factor`` 2.5 is
    the DeepSeek-V3 family's pair; the config names no ``scoring_func``;
    ``moe_router_logit_softcapping`` 0 means none);
  * ``QK_NORM = False``: no norm on q or k (no key names one); true, a layer
    carries ``self_attn.q_norm`` / ``k_norm`` [head_dim] applied a head
    before the rope (the program's parser key: ``use_qk_norm``);
  * the shared expert is ungated (no key names a gate), under
    ``mlp.shared_expert``; the routed ones under ``mlp.experts.N``, the
    router ``mlp.gate``;
  * YaRN and the partial rotary as HF's ``rope_parameters`` computes them
    (``_compute_yarn_parameters``, truncated correction range).

Departures from the published description: none in the arithmetic above.
Nothing of ``cake_tpu``; no cache, no kernel, no batching. Attention runs a
block of queries at a time and a feed-forward a block of columns or an
expert at a time (sums in another order, nothing else): the reference runs
in the process that serves, beside 10 GB on the chip, over probes of 3000
tokens. What a file like this owes the benchmark is in
``bench/architectures/__init__.py``.

``FAULT`` is None here and in every run that counts. A test, or a scratch
copy of this file for a control on the chip, sets it to make the reference
wrong in one way (``FAULTS``).
"""

from __future__ import annotations

import math
import time

import numpy as np

ITEMSIZE = {"bf16": 2, "f32": 4}
GATE_ACT = "sigmoid"
SCORES = "sigmoid"
QK_NORM = False
FAULTS = ("no_gate", "no_window", "plain_rope", "full_rotary", "no_shared_expert",
          "softmax_scores")
FAULT = None

# ------------------------------------------------------------------ tensors


def is_sparse(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][i] == "sparse"


def is_sliding(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def heads(cfg: dict, i: int) -> int:
    return cfg["num_attention_heads_per_layer"][i]


def held_experts(cfg: dict) -> range:
    """The routed experts this rank holds, by their own numbers."""
    first = cfg.get("first_expert", 0)
    return range(first, first + cfg["num_experts"])


def ranked_experts(cfg: dict) -> int:
    return cfg.get("num_experts_total", cfg["num_experts"])


def attention_shapes(cfg: dict, i: int) -> dict[str, tuple[int, ...]]:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    n, kv = heads(cfg, i), cfg["num_key_value_heads"]
    shapes = {
        "self_attn.q_proj.weight": (n * d, h),
        "self_attn.k_proj.weight": (kv * d, h),
        "self_attn.v_proj.weight": (kv * d, h),
        "self_attn.o_proj.weight": (h, n * d),
    }
    if cfg.get("gating"):
        shapes["self_attn.g_proj.weight"] = (n, h)
    return shapes


def norm_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    qk = {"self_attn.q_norm.weight": (d,), "self_attn.k_norm.weight": (d,)}
    return {**dict.fromkeys(NORMS, (h,)), **(qk if QK_NORM else {})}


def swiglu_shapes(prefix: str, h: int, inter: int) -> dict[str, tuple[int, ...]]:
    return {f"{prefix}.gate_proj.weight": (inter, h), f"{prefix}.up_proj.weight": (inter, h),
            f"{prefix}.down_proj.weight": (h, inter)}


def feed_forward_shapes(cfg: dict, i: int) -> dict[str, tuple[int, ...]]:
    h = cfg["hidden_size"]
    if not is_sparse(cfg, i):
        return swiglu_shapes("mlp", h, cfg["intermediate_size"])
    shapes = {"mlp.gate.weight": (ranked_experts(cfg), h)}
    for e in held_experts(cfg):
        shapes.update(swiglu_shapes(f"mlp.experts.{e}", h, cfg["moe_intermediate_size"]))
    if cfg.get("shared_expert_intermediate_size"):
        shapes.update(swiglu_shapes("mlp.shared_expert", h, cfg["shared_expert_intermediate_size"]))
    return shapes


NORMS = ("input_layernorm.weight", "post_attention_layernorm.weight")


def top_tensors(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("this file writes laguna with an untied head only")
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "model.embed_tokens.weight": ((vocab, h), "normal"),
        "model.norm.weight": ((h,), "ones"),
        "lm_head.weight": ((vocab, h), "head"),
    }


def layer_tensors(cfg: dict, i: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The layer's attention at its own head count (with the gate), its kind
    of feed-forward, the two norms."""
    table = {n: (s, "normal") for n, s in attention_shapes(cfg, i).items()}
    table.update({n: (s, "normal") for n, s in feed_forward_shapes(cfg, i).items()})
    table.update({n: (s, "ones") for n, s in norm_shapes(cfg).items()})
    return {f"model.layers.{i}.{n}": v for n, v in table.items()}


# ----------------------------------------------------------------- template

UNKNOWN_WORD = None
_MARKERS = ("<|system|>", "<|user|>", "<|assistant|>")


def require_program(cfg: dict) -> None:
    """Fail at once, before a 6 GB checkpoint is drawn and written, on a
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
    """One user turn as ``cake_tpu/models/llama/chat.py`` renders ``laguna``
    (a role-tagged frame a message; ``assumed`` in the configuration)."""
    return f"<s><|user|>\n{user}\n<|assistant|>\n"


def chat_ids(cfg: dict, prompt_ids: list[int]) -> list[int]:
    ids = {w: i for i, w in special_words(cfg).items()}
    return [ids["<s>"], ids["<|user|>"], *prompt_ids, ids["<|assistant|>"]]


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
    batch: every layer's attention, gate, norms, router, shared expert or
    dense feed-forward, the final norm and the head (the embedding is a
    lookup of one row a lane). **No routed expert is counted**
    (``laguna_expert_stream_pct`` counts those from the program's own
    account), **nor any of K and V** (``mixed_decode_attention_cost`` below)."""
    routed = sparse_layers(cfg) * len(held_experts(cfg)) * expert_parameters(cfg)
    embed = cfg["vocab_size"] * cfg["hidden_size"]
    return (parameters(cfg) - routed - embed) * ITEMSIZE[dtype]


def kv_bytes_per_token_layer(cfg: dict, dtype: str) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE[dtype]


def mixed_decode_attention_cost(cfg: dict, lanes: float, cached_tokens: float,
                                page_size: int, dtype: str) -> tuple[float, float]:
    """(operations, bytes) of ONE decode step's ``paged_decode_attention``
    calls over all layers, both kinds, for ``lanes`` live rows that hold
    ``cached_tokens`` tokens together. A full layer reads every cached token's
    K and V once for all heads; a sliding layer at most ``sliding_window``
    tokens and a page a lane (the kernel copies whole pages, the window's
    first among them), and no more than the lane holds. A head's score
    against a key and its weighted sum of a value are ``2 * head_dim``
    multiply-adds together; every row's queries come in and its sums go
    out."""
    d, per = cfg["head_dim"], kv_bytes_per_token_layer(cfg, dtype)
    mean = cached_tokens / max(lanes, 1e-9)
    ops = io = read = 0.0
    for i in range(cfg["num_hidden_layers"]):
        tokens = cached_tokens
        if is_sliding(cfg, i):
            tokens = lanes * min(mean, cfg["sliding_window"] + page_size)
        read += tokens * per
        ops += 2.0 * heads(cfg, i) * 2 * d * tokens
        io += 2.0 * lanes * heads(cfg, i) * d * ITEMSIZE[dtype]
    return ops, read + io


# ---------------------------------------------------------------- reference


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * jnp.reciprocal(jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def rope_inverse_frequencies(rope: dict, head_dim: int) -> np.ndarray:
    """[rot / 2] of one ``rope_parameters`` entry, as HF computes them
    (``default``; ``yarn``: ``_compute_yarn_parameters`` with the truncated
    correction range)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    pos_freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") != "yarn" or FAULT == "plain_rope":
        return 1.0 / pos_freqs
    factor, orig = float(rope["factor"]), rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return rot * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


def _rope(x, rope: dict, head_dim: int):
    """x [L, heads, d] at positions 0..L-1."""
    import jax.numpy as jnp

    if FAULT == "full_rotary":
        rope = {**rope, "partial_rotary_factor": 1}
    inv = jnp.asarray(rope_inverse_frequencies(rope, head_dim), jnp.float32)
    rot = 2 * inv.shape[0]
    scale = rope.get("attention_factor", 1.0) if (
        rope.get("rope_type", "default") == "yarn" and FAULT != "plain_rope") else 1.0
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


_Q_BLOCK = 128  # queries attended at once: [heads, 128, L] float32 scores


def _attention(x, w, *, cfg, n_heads, sliding, rope, fault):
    """A layer's attention branch: (x + attention, rms of it for the
    feed-forward)."""
    import jax
    import jax.numpy as jnp

    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps, d, kv = cfg["rms_norm_eps"], cfg["head_dim"], cfg["num_key_value_heads"]
    length = x.shape[0]
    u = _rms_norm(x, w["input_layernorm.weight"], eps)
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(length, n_heads, d)
    k = (u @ w["self_attn.k_proj.weight"].T).reshape(length, kv, d)
    if QK_NORM:  # a norm a head before the rope
        q = _rms_norm(q, w["self_attn.q_norm.weight"], eps)
        k = _rms_norm(k, w["self_attn.k_norm.weight"], eps)
    q, k = _rope(q, rope, d), _rope(k, rope, d)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(length, kv, d)
    group = n_heads // kv
    k = jnp.repeat(k, group, axis=1)  # head h reads KV head h // group
    v = jnp.repeat(v, group, axis=1)
    keys = jnp.arange(length)[None, :]
    pad = (-length) % _Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _Q_BLOCK, n_heads, d)

    def block(args):  # one block of queries against every key
        qs, first = args
        rows = first + jnp.arange(_Q_BLOCK)[:, None]
        seen = keys <= rows
        if sliding and fault != "no_window":
            seen &= keys > rows - cfg["sliding_window"]
        s = jnp.einsum("qhd,khd->hqk", qs, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    firsts = jnp.arange(qb.shape[0]) * _Q_BLOCK
    a = jax.lax.map(block, (qb, firsts)).reshape(-1, n_heads, d)[:length]
    if "self_attn.g_proj.weight" in w and fault != "no_gate":
        act = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[GATE_ACT]
        a = a * act(u @ w["self_attn.g_proj.weight"].T)[:, :, None]
    h = x + a.reshape(length, -1) @ w["self_attn.o_proj.weight"].T
    return h, _rms_norm(h, w["post_attention_layernorm.weight"], eps)


def _routing(u, gate, *, cfg, fault):
    """[L, every ranked expert] combine weights, zero where not chosen."""
    import jax
    import jax.numpy as jnp

    logits = u @ gate.astype(jnp.float32).T
    soft = SCORES == "softmax" or fault == "softmax_scores"
    scores = jax.nn.softmax(logits, -1) if soft else jax.nn.sigmoid(logits)
    top_s, top_e = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg.get("moe_routed_scaling_factor", 1.0)
    return jnp.sum(jax.nn.one_hot(top_e, logits.shape[-1]) * top_s[..., None], -2)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _add_swiglu(acc, u, weight, gate, up, down):
    """``acc + weight * SwiGLU(u)``, the weights widened."""
    import jax.numpy as jnp

    f32 = jnp.float32
    return acc + weight[:, None] * _swiglu(u, gate.astype(f32), up.astype(f32), down.astype(f32))


_FF_BLOCK = 4096  # columns of a SwiGLU applied at once


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
    routing = jax.jit(functools.partial(_routing, cfg=static, fault=FAULT))
    add_swiglu = jax.jit(_add_swiglu)
    attentions = {}  # one jit a (heads, kind)

    def attention(i):
        key = (heads(cfg, i), is_sliding(cfg, i))
        if key not in attentions:
            rope = cfg["rope_parameters"][cfg["layer_types"][i]]
            attentions[key] = jax.jit(functools.partial(
                _attention, cfg=static, n_heads=key[0], sliding=key[1],
                rope=dict(rope), fault=FAULT))
        return attentions[key]

    def swiglu_blocks(prefix, inter):
        gate, up, down = (reader(f"{prefix}.{n}_proj.weight") for n in ("gate", "up", "down"))
        for lo in range(0, inter, _FF_BLOCK):
            hi = min(inter, lo + _FF_BLOCK)
            yield jnp.asarray(gate[lo:hi]), jnp.asarray(up[lo:hi]), jnp.asarray(down[:, lo:hi])

    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(reader("model.embed_tokens.weight"))
        xs = [embed[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        del embed
        for i in range(cfg["num_hidden_layers"]):
            t0 = time.perf_counter()
            prefix = f"model.layers.{i}."
            w = {n: jnp.asarray(reader(prefix + n))
                 for n in (*attention_shapes(cfg, i), *norm_shapes(cfg))}
            hu = [attention(i)(x, w) for x in xs]
            ones = [jnp.ones((x.shape[0],), jnp.float32) for x in xs]
            ffs = [jnp.zeros_like(x) for x in xs]

            def add(prefix, inter, weights):
                for block in swiglu_blocks(prefix, inter):
                    for k, (_, u) in enumerate(hu):
                        ffs[k] = add_swiglu(ffs[k], u, weights[k], *block)

            if not is_sparse(cfg, i):
                add(prefix + "mlp", cfg["intermediate_size"], ones)
            else:
                gate = jnp.asarray(reader(prefix + "mlp.gate.weight"))
                combine = [routing(u, gate) for _, u in hu]
                for e in held_experts(cfg):  # an absent expert's part is left out
                    add(f"{prefix}mlp.experts.{e}", cfg["moe_intermediate_size"],
                        [c[:, e] for c in combine])
                if cfg.get("shared_expert_intermediate_size") and FAULT != "no_shared_expert":
                    add(prefix + "mlp.shared_expert", cfg["shared_expert_intermediate_size"], ones)
            xs = jax.block_until_ready([h + ff for (h, _), ff in zip(hu, ffs)])
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
