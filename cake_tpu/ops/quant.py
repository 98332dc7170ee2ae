"""Weight-only int8 and int4 quantization.

Beyond the reference (bf16/f16 weights only). Single-stream decode is bound by
HBM weight reads; int8 storage halves that traffic and int4 halves it again.
Weights dequantize inside the matmul — XLA on TPU fuses the int8->bf16 convert
into the dot's operand load, so no full-precision copy of the weight ever
materializes in HBM.

Representations, both two-leaf NamedTuple pytrees:

``QuantWeight`` — int8, per-output-channel symmetric:

    w:     int8  [..., in, out]   (stacked layer axes preserved)
    scale: f32   [..., 1, out]    per-output-channel symmetric scale

``Quant4Weight`` — int4, per-(in-group, output-channel) symmetric:

    w:     int8  [..., in/2, out]  two nibbles per byte: byte i holds logical
                                   in-rows 2i (low nibble) and 2i+1 (high),
                                   so a CONTIGUOUS slice of the packed in-axis
                                   is a contiguous slice of the logical
                                   in-axis — row-parallel tensor-parallel
                                   sharding works exactly like the plain array
    scale: f32   [..., G, out]     per-group scales, G = in / group_size
                                   along the REDUCTION dim (4-bit needs finer
                                   scale granularity than per-channel; 128 is
                                   the standard group size)

``qmat(x, w)`` is the ONE matmul entry point: it accepts a plain array
(existing behavior, ``x @ w``), a QuantWeight, or a Quant4Weight, so every
linear site in the model works with all representations and the quantized
paths cannot drift.

The int4 matmul never interleaves the weight: the two nibble planes multiply
the even-/odd-strided halves of the ACTIVATION (tiny in decode) —

    out = sum_g scale[g] * (x_even[g] @ lo_nibbles[g] + x_odd[g] @ hi[g])

so HBM streams only the packed bytes; the shifts/converts fuse into the dot's
operand load like the int8 convert does.

Accuracy: symmetric absmax rounding (int8: absmax/127 per channel; int4:
absmax/7 per 128-row group). Quantization changes numerics (no token-equality
oracle vs full precision); tests bound the per-matmul error, pin end-to-end
determinism, and hold end-to-end quality (top-1 agreement and per-position KL
vs the f32 model, tests/test_quant.py). int4 carries ~8x the weight noise of
int8 — the standard RTN-group-128 trade (AWQ/GPTQ-class calibration is out of
scope; activations stay bf16/f32).

Accumulation dtype: ``qmat`` computes ``x @ w.astype(x.dtype)``. The int8/int4
-> activation-dtype convert is LOSSLESS even in bf16 (8 mantissa bits
represent every integer in [-127, 127] exactly), and TPU matmuls accumulate
bf16 operand products in f32 on the MXU — so on the XLA paths the only
quantization error is the weight rounding itself, not the arithmetic. Pinned
against the dequantize-then-f32-matmul reference in tests.

Cross-path caveat (int4 Pallas kernel, ``CAKE_INT4_KERNEL=1``): the kernel in
``ops/pallas/int4_matmul.py`` applies the f32 group scales to the unpacked
nibbles BEFORE casting to the activation dtype for the MXU dot, so
scale*weight products pay one bf16 rounding that the XLA ``_qmat4`` path
(exact integer nibbles in bf16, f32 scales applied to the accumulated output)
does not. The two int4 paths are therefore numerically equivalent only per
backend: token streams can differ across the kernel toggle, and the
"rounding-only" guarantee above holds exactly on the XLA path while the
kernel path adds one bf16 product rounding per element (bounded by the
kernel-vs-oracle tolerance tests in tests/test_quant.py —
test_int4_pallas_kernel_bf16_accumulation and siblings).
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class QuantWeight(NamedTuple):
    """Int8 weight + per-output-channel scale; a pytree of two leaves."""

    w: jnp.ndarray  # int8 [..., in, out]
    scale: jnp.ndarray  # f32  [..., 1, out]


def quantize_weight(w: jnp.ndarray) -> QuantWeight:
    """Per-output-channel symmetric int8 quantization of [..., in, out]."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)  # [..., 1, out]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantWeight(w=q, scale=scale)


class Quant4Weight(NamedTuple):
    """Packed int4 weight + per-(in-group, out-channel) scale (two leaves)."""

    w: jnp.ndarray  # int8 [..., in//2, out], nibble-packed (see module doc)
    scale: jnp.ndarray  # f32  [..., G, out]

    @property
    def in_dim(self) -> int:
        return 2 * self.w.shape[-2]


DEFAULT_GROUP_SIZE = 128


def _group_size_for(in_dim: int, group_size: int) -> int:
    """Largest usable group size: divides in_dim, stays even (nibble pairs
    must not straddle groups), and keeps G = in/gs >= 4 so row-parallel tp
    splits of the scale stay shard-aligned even on tiny test widths (real
    model dims are untouched: in >= 512 keeps the requested 128)."""
    g = min(group_size, max(2, in_dim // 4))
    while in_dim % g or g % 2:
        g -= 1
        if g < 2:
            return in_dim
    return g


def quantize4_weight(
    w: jnp.ndarray, group_size: int = DEFAULT_GROUP_SIZE
) -> Quant4Weight:
    """Group-wise symmetric int4 quantization of [..., in, out]."""
    in_dim = w.shape[-2]
    if in_dim % 2:
        raise ValueError(f"int4 packing needs an even in dim, got {in_dim}")
    gs = _group_size_for(in_dim, group_size)
    lead, out = w.shape[:-2], w.shape[-1]
    w32 = w.astype(jnp.float32).reshape(*lead, in_dim // gs, gs, out)
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -7, 7).astype(jnp.int8)
    q = q.reshape(*lead, in_dim, out)
    # byte i = (row 2i+1) << 4 | (row 2i) & 0xF — adjacent pairing keeps
    # contiguous packed slices == contiguous logical slices for row-split tp.
    packed = jnp.bitwise_or(
        jnp.left_shift(q[..., 1::2, :], 4),
        jnp.bitwise_and(q[..., 0::2, :], jnp.int8(0x0F)),
    )
    return Quant4Weight(w=packed, scale=scale[..., 0, :])


def unpack4(packed: jnp.ndarray, dtype=jnp.int8):
    """The two nibble planes of a packed int4 array, sign-extended.

    Returns (lo, hi) — logical even / odd in-rows — each the packed shape."""
    lo = jnp.right_shift(jnp.left_shift(packed, 4), 4)
    hi = jnp.right_shift(packed, 4)  # arithmetic on int8: sign extends
    return lo.astype(dtype), hi.astype(dtype)


class QuantS4Weight(NamedTuple):
    """Native ``jnp.int4`` weight + per-(group, out) scale — the ALTERNATIVE
    int4 runtime representation (``CAKE_INT4_REPR=s4``).

    One of three formulations of the same quantization: the Pallas kernel
    and the XLA grouped path both stream byte-packed nibbles (Quant4Weight)
    and pay an unpack; this one stores rows as XLA's native s4 so the
    convert-into-dot needs no unpack at all — IF the backend actually
    bit-packs s4 in HBM (not measured). Runtime-only: the checkpoint format
    stays packed Quant4Weight; conversion happens at quantize/prep time. Not
    threaded through the tp/pipeline partition specs — single-chip paths
    (the local runner) only.
    """

    w: jnp.ndarray  # int4 [..., in, out]
    scale: jnp.ndarray  # f32 [..., G, out]

    @property
    def in_dim(self) -> int:
        return self.w.shape[-2]


def to_native_int4(qw: Quant4Weight) -> QuantS4Weight:
    """Unpack a byte-packed Quant4Weight into the native-s4 representation
    (exact: nibbles are integers; the reshape interleaves even/odd rows
    back into logical order)."""
    lo, hi = unpack4(qw.w, jnp.int8)
    lead, out = qw.w.shape[:-2], qw.w.shape[-1]
    full = jnp.stack([lo, hi], axis=-2).reshape(*lead, qw.in_dim, out)
    return QuantS4Weight(w=full.astype(jnp.int4), scale=qw.scale)


def weight_out_dim(w) -> int:
    """Output dim of a linear weight, plain or quantized (head-count inference
    in model.block_qkv works identically for all representations)."""
    return (
        w.w.shape[-1]
        if isinstance(w, (QuantWeight, Quant4Weight, QuantS4Weight))
        else w.shape[-1]
    )


def _qmat4(x: jnp.ndarray, w: Quant4Weight) -> jnp.ndarray:
    """Grouped int4 matmul: per-group partial dots, scaled f32 combine.

    The weight is consumed as its two nibble planes (never interleaved);
    the even/odd strided split lands on the activation instead, which is
    [.., in]-small. Group partials accumulate on the MXU in f32; scales are
    applied per (group, out-channel) before the final sum over groups."""
    p, s = w.w, w.scale  # [...w, P, out], [...w, G, out]
    half, out = p.shape[-2], p.shape[-1]
    groups = s.shape[-2]
    pg = half // groups  # packed rows per group
    lo, hi = unpack4(p, x.dtype)
    wlead = p.shape[:-2]
    lo = lo.reshape(*wlead, groups, pg, out)
    hi = hi.reshape(*wlead, groups, pg, out)
    xlead = x.shape[:-1]
    xe = x[..., 0::2].reshape(*xlead, groups, 1, pg)
    xo = x[..., 1::2].reshape(*xlead, groups, 1, pg)
    part = (xe @ lo + xo @ hi)[..., 0, :]  # [..., G, out]
    # Scale-multiply and the sum over up to ~112 groups stay in f32 (the
    # scales already are); bf16 rounding here would be error the int8 path's
    # single post-matmul scale does not pay. One cast back at the end.
    part = part.astype(jnp.float32) * s
    return part.sum(axis=-2).astype(x.dtype)


def _qmat_s4(x: jnp.ndarray, w: QuantS4Weight) -> jnp.ndarray:
    """Grouped matmul on the native-s4 representation: the convert-into-dot
    needs no nibble unpack; group partials accumulate in f32 and scales
    apply per (group, out) before the sum over groups — the same exact-int
    + f32-combine numerics as _qmat4, with one interleaved dot per group
    instead of two strided ones."""
    in_dim, out = w.w.shape[-2], w.w.shape[-1]
    groups = w.scale.shape[-2]
    gs = in_dim // groups
    wlead = w.w.shape[:-2]
    wb = w.w.astype(x.dtype).reshape(*wlead, groups, gs, out)
    xlead = x.shape[:-1]
    xg = x.reshape(*xlead, groups, 1, gs)
    part = (xg @ wb)[..., 0, :]  # [..., G, out]
    part = part.astype(jnp.float32) * w.scale
    return part.sum(axis=-2).astype(x.dtype)


# The Pallas int4 kernel serves EVERY int4 matmul on real TPU — decode,
# verify chunks, and prefill widths alike (its grid tiles rows). One path
# per backend keeps numerics independent of batch/chunk shape, preserving
# the byte-parity invariants (engine row == serialized run, fused ==
# stepwise, chunked == dense prefill). The XLA grouped formulation (_qmat4)
# stays the oracle and the CPU/odd-shape fallback.


def _int4_kernel_ok(x: jnp.ndarray, w: "Quant4Weight") -> bool:
    if os.environ.get("CAKE_INT4_KERNEL") == "0":
        return False
    # Mosaic lowers for the TPU only; every other backend takes the XLA path.
    if jax.default_backend() != "tpu":
        return False
    if w.w.ndim != 2 or x.ndim < 1:
        return False
    out = w.w.shape[-1]
    # Lane-aligned shapes only; everything real (h, inter, vocab) qualifies.
    return out % 128 == 0


def qmat(x: jnp.ndarray, w) -> jnp.ndarray:
    """``x @ w`` for plain arrays, QuantWeight, or Quant4Weight (dequant
    fused into the dot)."""
    if isinstance(w, QuantWeight):
        out = x @ w.w.astype(x.dtype)
        return out * w.scale.reshape(w.scale.shape[:-2] + (w.scale.shape[-1],)).astype(
            x.dtype
        )
    if isinstance(w, Quant4Weight):
        if _int4_kernel_ok(x, w):
            from cake_tpu.ops.pallas.int4_matmul import int4_matmul

            lead = x.shape[:-1]
            y = int4_matmul(x.reshape(-1, x.shape[-1]), w.w, w.scale)
            return y.reshape(*lead, y.shape[-1])
        return _qmat4(x, w)
    if isinstance(w, QuantS4Weight):
        return _qmat_s4(x, w)
    return x @ w


# Linear layer weights to quantize (models/llama/model.py LAYER_WEIGHTS minus
# the norms); embedding stays full precision (it's a gather, not a matmul).
# Includes the Qwen2-MoE shared expert; the MoE router and its scalar sigmoid
# gate stay full precision (tiny, and routing decisions are precision-
# sensitive).
_QUANT_LAYER_KEYS = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "sh_gate", "sh_up", "sh_down",
    # Prep-time fused projections (ops/fuse.py): quantization commutes with
    # fusion (per-output-channel scales), so either order is valid.
    "wqkv", "w_gu", "sh_gu",
)


# MoE EXPERT stacks stay int8 under mode="int4": their einsum/ragged_dot
# dispatch paths (ops/moe.py) read the per-expert [E, 1, out] int8 scale
# layout, and all-experts decode streams every expert regardless of routing,
# so the 4-bit win there is smaller than on the dense hot path. Documented
# mixed mode; the shared expert (a dense SwiGLU) does go int4.
_MOE_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _quantize_one(w, mode: str):
    return quantize4_weight(w) if mode == "int4" else quantize_weight(w)


def apply_runtime_int4_repr(params: dict) -> dict:
    """Convert packed int4 leaves to the native-s4 runtime representation
    when ``CAKE_INT4_REPR=s4``.

    Called by SINGLE-CHIP runtime prep only (LocalForwardStep) — not
    by the offline quantizer (the checkpoint format stays packed
    Quant4Weight) and not by the tp/pipeline placement paths (the partition
    specs reject QuantS4Weight with an actionable error)."""
    if os.environ.get("CAKE_INT4_REPR") != "s4":
        return params

    def conv(leaf):
        return to_native_int4(leaf) if isinstance(leaf, Quant4Weight) else leaf

    return jax.tree.map(
        conv,
        params,
        is_leaf=lambda x: isinstance(x, (QuantWeight, Quant4Weight)),
    )


def tree_quantization(params: dict) -> str | None:
    """The quantization mode a param tree already carries, or None.

    int4 wins the label when present (the mixed int4 mode stores MoE expert
    stacks as int8 by design)."""
    leaves = jax.tree.leaves(
        params,
        is_leaf=lambda x: isinstance(
            x, (QuantWeight, Quant4Weight, QuantS4Weight)
        ),
    )
    if any(isinstance(l, (Quant4Weight, QuantS4Weight)) for l in leaves):
        return "int4"
    if any(isinstance(l, QuantWeight) for l in leaves):
        return "int8"
    return None


def quantize_layer_tree(layers: dict, mode: str = "int8") -> dict:
    """Quantize a bare stacked-layer tree (a worker's block range)."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantize mode {mode!r}")
    if tree_quantization(layers):
        raise ValueError(
            "layer tree is already quantized "
            f"({tree_quantization(layers)}); re-quantizing would corrupt it"
        )
    moe = "router" in layers
    out = {}
    for k, v in layers.items():
        if k not in _QUANT_LAYER_KEYS:
            out[k] = v
        elif mode == "int4" and moe and k in _MOE_EXPERT_KEYS:
            out[k] = quantize_weight(v)
        else:
            out[k] = _quantize_one(v, mode)
    return out


def quantize_params(params: dict, mode: str = "int8") -> dict:
    """Quantize every linear weight in a model param tree (int8 or int4).

    Layer weights keep their stacked [n_layers, in, out] layout; lm_head is
    quantized when present (untied); embedding and norms stay full precision.
    """
    out = dict(params)
    out["layers"] = quantize_layer_tree(params["layers"], mode)
    if "lm_head" in params:
        out["lm_head"] = _quantize_one(params["lm_head"], mode)
    return out


def dequantize_weight(qw, dtype=jnp.float32) -> jnp.ndarray:
    """Materialize the full-precision weight (tests/debugging only)."""
    if isinstance(qw, QuantS4Weight):
        lead, (in_dim, out) = qw.w.shape[:-2], qw.w.shape[-2:]
        groups = qw.scale.shape[-2]
        full = qw.w.astype(jnp.float32).reshape(
            *lead, groups, in_dim // groups, out
        )
        full = full * qw.scale[..., :, None, :]
        return full.reshape(*lead, in_dim, out).astype(dtype)
    if isinstance(qw, Quant4Weight):
        lo, hi = unpack4(qw.w, jnp.float32)
        lead, out = qw.w.shape[:-2], qw.w.shape[-1]
        in_dim = qw.in_dim
        full = jnp.stack([lo, hi], axis=-2)  # [..., P, 2, out]
        full = full.reshape(*lead, in_dim, out)
        groups = qw.scale.shape[-2]
        full = full.reshape(*lead, groups, in_dim // groups, out)
        full = full * qw.scale[..., :, None, :]
        return full.reshape(*lead, in_dim, out).astype(dtype)
    return (qw.w.astype(jnp.float32) * qw.scale).astype(dtype)


def quantized_bytes(params: dict) -> int:
    """Total parameter bytes under the current representation.

    Native-s4 leaves count 0.5 B/weight (the stream the representation is
    meant to achieve): ml_dtypes reports int4 itemsize as 1, which would
    misread s4 as no smaller than int8."""
    total = 0
    for a in jax.tree.leaves(params):
        n = int(np.prod(a.shape))
        total += -(-n // 2) if a.dtype == jnp.int4 else n * a.dtype.itemsize
    return total
