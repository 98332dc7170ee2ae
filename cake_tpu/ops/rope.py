"""Rotary position embeddings.

Covers the role of the reference's precomputed cos/sin tables
(cake-core/src/models/llama3/cache.rs:24-48: ``theta^(-i/d)`` frequencies sized to
MAX_SEQ_LEN) and the rope application inside attention (attention.rs:25-35).

Convention: HuggingFace "rotate-half" layout (q/k split into two contiguous halves),
matching HF-exported safetensors weights. Tables are computed once in f32; application
gathers rows by position so the same jitted function serves prefill (a vector of
positions) and decode (one position broadcast per batch row).

Also implements Llama 3.1 frequency rescaling (``rope_scaling`` in config.json),
which the reference (pinned to Llama 3.0) lacks.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama.config import RopeScaling


def rope_frequencies(
    head_dim: int,
    theta: float,
    scaling: RopeScaling | None = None,
) -> np.ndarray:
    """Inverse frequencies [head_dim//2], with optional rescaling
    (Llama-3.1 "llama3" smooth interpolation, or plain "linear" — Gemma-3's
    global-rope factor)."""
    inv_freq = 1.0 / (
        theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    )
    if scaling is not None and scaling.rope_type == "linear":
        return (inv_freq / scaling.factor).astype(np.float32)
    if scaling is not None:
        # Llama 3.1 "rope_type: llama3" smooth low/high-frequency interpolation.
        low_wavelen = scaling.original_max_position_embeddings / scaling.low_freq_factor
        high_wavelen = (
            scaling.original_max_position_embeddings / scaling.high_freq_factor
        )
        wavelen = 2.0 * np.pi / inv_freq
        scaled = np.where(wavelen > low_wavelen, inv_freq / scaling.factor, inv_freq)
        smooth = (
            scaling.original_max_position_embeddings / wavelen
            - scaling.low_freq_factor
        ) / (scaling.high_freq_factor - scaling.low_freq_factor)
        mid = (1.0 - smooth) * inv_freq / scaling.factor + smooth * inv_freq
        is_mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        inv_freq = np.where(is_mid, mid, scaled)
    return inv_freq.astype(np.float32)


def rope_table(
    head_dim: int,
    max_seq_len: int,
    theta: float,
    scaling: RopeScaling | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute (cos, sin), each [max_seq_len, head_dim//2], in f32."""
    inv_freq = rope_frequencies(head_dim, theta, scaling)
    t = np.arange(max_seq_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)  # [max_seq, head_dim//2]
    return jnp.asarray(np.cos(freqs)), jnp.asarray(np.sin(freqs))


def model_rope_tables(config, max_seq_len: int):
    """THE rope-table builder every runner uses (one call site per backend).

    Single-rope families get the plain [max_seq, hd//2] tables (over the
    first ``config.rotary_dim`` numbers of a head where the rotary term is
    partial: ``apply_rope`` passes the rest through). Dual-rope
    families (Gemma-3: ``rope_local_base_freq``) get STACKED [2, max_seq,
    hd//2] tables — plane 0 the global rope (with any rope_scaling), plane 1
    the local rope (unscaled, HF reassigns only the theta) — selected per
    layer by the ``rope_sel`` layer-tree metadata inside block_qkv, so the
    scanned bodies stay family-agnostic."""
    if getattr(config, "rope_local_base_freq", None) is None:
        return rope_table(
            config.rotary_dim, max_seq_len, config.rope_theta, config.rope_scaling,
        )
    cos_g, sin_g = rope_table(
        config.head_dim, max_seq_len, config.rope_theta, config.rope_scaling
    )
    cos_l, sin_l = rope_table(
        config.head_dim, max_seq_len, config.rope_local_base_freq, None
    )
    return jnp.stack([cos_g, cos_l]), jnp.stack([sin_g, sin_l])


def yarn_frequencies(rope) -> np.ndarray:
    """Inverse frequencies [rotary_dim // 2] of one attention kind's rotary
    term (``config.KindRope``), as HF's ``rope_type: yarn`` computes them:
    plain ``theta^(-2i/d)`` where ``factor`` is 1; else each blended between
    that (extrapolation, the fast dims) and that over ``factor``
    (interpolation, the slow ones) by a linear ramp between the dims that
    turn ``beta_fast`` and ``beta_slow`` times over the original context."""
    d = rope.rotary_dim
    pos_freqs = rope.theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extrapolation = 1.0 / pos_freqs
    if rope.factor == 1.0:
        return extrapolation.astype(np.float32)
    interpolation = 1.0 / (rope.factor * pos_freqs)
    orig = rope.original_max_position_embeddings

    def correction_dim(rotations: float) -> float:
        return d * np.log(orig / (rotations * 2 * np.pi)) / (2 * np.log(rope.theta))

    low = max(np.floor(correction_dim(rope.beta_fast)), 0)
    high = min(np.ceil(correction_dim(rope.beta_slow)), d - 1)
    if low == high:
        high += 0.001  # HF's guard against a zero-width ramp
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv_freq = interpolation * (1 - extrapolated) + extrapolation * extrapolated
    return inv_freq.astype(np.float32)


def kind_rope_rows(rope, positions: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin), each [*positions.shape, rotary_dim // 2] in f32, of one
    attention kind's rotary term at ``positions``, ``attention_factor`` on
    both: the second rope beside the first where a model's kinds differ
    (models/llama/kinds.py). Computed from the positions, not gathered from
    a table: a table a kind over a 16,384-slot lane is 12 MB of constants in
    every served program. ``apply_rope`` takes the rows as pre-gathered,
    rotates the first ``rotary_dim`` numbers of a head and passes the rest
    through."""
    inv_freq = jnp.asarray(yarn_frequencies(rope))
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    scale = jnp.float32(rope.attention_factor)
    return jnp.cos(freqs) * scale, jnp.sin(freqs) * scale


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
) -> jnp.ndarray:
    """Rotate q or k.

    Args:
      x: [batch, seq, heads, head_dim]
      cos/sin: [max_seq, head_dim//2] precomputed tables, OR pre-gathered
        [batch, seq, head_dim//2] rows (``positions`` then ignored) — the
        stacked-layer scans gather once per step instead of once per layer
        (model.blocks_forward / batch.batched_blocks_forward).
      positions: [batch, seq] int32 absolute positions

    A table narrower than ``head_dim // 2`` rotates the first ``2 * width``
    numbers of a head only (a partial rotary, pairs (i, i + width)); the
    rest pass through.
    """
    dtype = x.dtype
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        turned = apply_rope(x[..., :rot], cos, sin, positions)
        return jnp.concatenate((turned, x[..., rot:]), axis=-1)
    if cos.ndim == 3:  # pre-gathered per-token rows
        c = cos[:, :, None, :]  # [b, s, 1, hd/2]
        s = sin[:, :, None, :]
    else:
        c = cos[positions][:, :, None, :]  # [b, s, 1, hd/2]
        s = sin[positions][:, :, None, :]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate((x1 * c - x2 * s, x2 * c + x1 * s), axis=-1)
    return out.astype(dtype)
