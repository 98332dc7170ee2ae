"""The plain reference and the comparison that decides ``correct``.

The Mistral decoder as published: RMSNorm, rotary embedding over the two
halves of a head (HF's ``rotate_half``), grouped-query attention, causal mask
with a sliding window (key j serves query i when i - window < j <= i), SwiGLU
MLP, untied head. Straight ``jax.numpy`` in float32 with matmul precision
``highest``; no cache, no kernel, no batching; nothing of ``cake_tpu``.

The API returns text, not logits, and with random weights the largest logit
changes on rounding, so served tokens cannot be compared for equality. The
reference is run teacher-forced over prompt + served tokens, one layer of
weights on the device at a time, and at every served position the reference's
logit of the served token must lie within a tolerance of its largest logit,
in units of that position's logit spread (standard deviation over the
vocabulary). The tolerance is the configuration's (``judge`` in its file,
with the reason): it depends on the depth and the type a model is served in. A served token is the largest logit of the served arithmetic, so
its deficit under the reference is at most twice the logit error of that
arithmetic.
"""

from __future__ import annotations

import time

import numpy as np

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
    HF names of ``checkpoint.layer_shapes`` and ``NORMS`` to arrays."""
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

    from bench.checkpoint import NORMS, layer_shapes

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


def judge(reader, cfg: dict, tol: float, probes: list[dict]) -> dict:
    """``probes``: [{"context": ids the server saw, "served": ids it sent}].
    Returns {"correct", "worst", "tolerance", "positions", "per_probe"}."""
    timing: dict = {}
    logits = forward_logits(
        reader, cfg, [p["context"] + p["served"] for p in probes],
        [len(p["context"]) - 1 for p in probes], timing,
    )
    per_probe = []
    for p, lg in zip(probes, logits):
        rows = lg[:len(p["served"])]
        served = rows[np.arange(len(rows)), p["served"]]
        deficit = (rows.max(-1) - served) / rows.std(-1)
        per_probe.append(float(deficit.max()) if len(deficit) else float("inf"))
    worst = max(per_probe) if per_probe else float("inf")
    return {
        "correct": bool(worst <= tol), "worst": worst, "tolerance": tol,
        "positions": sum(len(p["served"]) for p in probes), "per_probe": per_probe,
        # the first layer's time holds the compile; the rest is the work
        "load_s": sum(timing["load_s"]), "first_layer_s": timing["layer_s"][0],
        "other_layers_s": sum(timing["layer_s"][1:]),
    }


def greedy(reader, cfg: dict, context: list[int], n_new: int) -> list[int]:
    """Greedy continuation by repeated full forward passes; for tests."""
    ids = list(context)
    for _ in range(n_new):
        ids.append(int(forward_logits(reader, cfg, [ids])[0][-1].argmax()))
    return ids[len(context):]
