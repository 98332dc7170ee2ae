"""Prep-time weight fusion: QKV -> ONE matmul, gate/up -> ONE matmul.

Why: batch-1 decode is HBM-bound, and the measured model-level utilization
(int8, 2026-07-30, before PR 1) sat at 0.72 of the isolated-matmul 0.91 because of
per-layer FIXED cost — every op in the scanned layer body pays dispatch and
tiling setup regardless of size. The reference dispatches q/k/v and gate/up
as five separate matmuls per layer (cake-core/src/models/llama3/attention.rs:
133-150, mlp.rs:15-32); here the projections sharing an input are concatenated
along their OUTPUT dim at weight-prep time, so the layer body runs

    wqkv  [in, (n_q + 2*n_kv) * hd]   instead of wq / wk / wv
    w_gu  [in, 2 * intermediate]      instead of w_gate / w_up

Same bytes streamed from HBM, ~3 fewer ops per layer, and each surviving op
is larger (fixed cost amortizes better). Numerics are unchanged: each output
column of a matmul is an independent dot product over the input dim, so
concatenation along the output dim cannot alter any column's accumulation
order (tests pin fused-vs-unfused token streams exactly).

Composition rules (all verified by tests/test_fuse.py):

  * Quantization commutes: per-OUTPUT-channel int8 scales ride their columns
    through the concat, so fuse(quantize(w)) == quantize(fuse(w)) exactly.
    ``QuantWeight`` leaves fuse component-wise (w and scale alike).
  * Tensor parallelism composes via SHARD-MAJOR ordering: with ``tp=t`` the
    fused array is laid out [q_0|k_0|v_0 | q_1|k_1|v_1 | ...] so a contiguous
    1/t column split (jax.sharding can express nothing else) hands shard s
    exactly its heads' q/k/v — identical to sharding the unfused weights.
    In-shard split sizes are recovered from the global config head ratio
    (model.layer_head_counts).
  * Layer/stage stacking is transparent: concat is along the LAST dim, so any
    leading [n_layers] / [S, L_pad] axes ride through (pipeline.pad_stages).

MoE layer trees fuse only the attention projections (and the Qwen2-MoE
shared expert's gate/up); the expert weights keep their [E, in, out] layout
for the grouped dispatch in ops/moe.py. The transform is idempotent and
runtime-only — checkpoints on disk keep the HF per-projection layout
(io/safetensors_io.py), matching the reference's storage schema.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.ops.quant import Quant4Weight, QuantS4Weight, QuantWeight

FUSED_QKV = "wqkv"
FUSED_QKV_BIAS = "bqkv"
FUSED_GU = "w_gu"
FUSED_SHARED_GU = "sh_gu"

# ----------------------------------------------------- op-level decode fusion
#
# The weight fusions above remove per-layer DISPATCHES; the decode step still
# round-trips activations through HBM at every XLA op boundary. The op-level
# fusion pass (the operation-fusion study in PAPERS.md, arxiv 2502.17728)
# closes two of those boundaries with Pallas kernels:
#
#   "norm"    ops/pallas/fused_norm_matmul.py — RMSNorm folded into the
#             projection it feeds (attn input norm -> wqkv, post-attn norm ->
#             w_gu, final norm -> lm_head): the normalized activation never
#             materializes in HBM.
#   "tail"    ops/pallas/fused_sample_tail.py — repeat-penalty ring +
#             temperature + top-k mask + categorical draw in one kernel over
#             the vocab tile grid (top-p keeps the XLA sort path behind a
#             documented fallback).
#
# (A third, "ingest" — head split + rope + K/V cache write as one slot-sized
# DMA — was removed in PR 22: the slot is the sublane-tiled dim of the
# head-major cache, and Mosaic takes no one-row slice of it.)
#
# Selection rides ``LlamaConfig.fusion_impl`` (beside ``attention_impl``),
# a ``<set>[@<impl>]`` spec parsed here — THE one grammar shared by the
# config field, ServeConfig, and the --fusion CLI flag. The XLA twins
# literally reuse the unfused ops, so a twin stream IS the unfused stream;
# a kernel agrees with its twin to rounding. On the CPU interpreter at f32
# that is almost always every bit, which tests/test_fused_decode.py checks
# on token streams; on the chip it is a tolerance
# (ops/pallas/check.py).

FUSION_NAMES = ("norm", "tail")
FUSION_IMPLS = ("auto", "pallas", "xla")


def parse_fusion_spec(spec: str) -> tuple[frozenset, str]:
    """Parse a fusion spec -> (fusion set, impl).

    Grammar: ``none`` | ``<set>[@<impl>]`` where ``<set>`` is ``all`` or a
    comma list drawn from {norm, tail} and ``<impl>`` is auto (the
    default: Pallas on TPU, the XLA twins elsewhere), pallas, or xla.
    Examples: ``all``, ``norm,tail``, ``all@pallas``, ``tail@xla``.
    """
    spec = (spec or "none").strip()
    if spec == "none":
        return frozenset(), "auto"
    impl = "auto"
    if "@" in spec:
        spec, impl = spec.split("@", 1)
        if impl not in FUSION_IMPLS:
            raise ValueError(
                f"unknown fusion impl {impl!r} (expected one of "
                f"{'/'.join(FUSION_IMPLS)})"
            )
    if spec == "all":
        return frozenset(FUSION_NAMES), impl
    names = [n.strip() for n in spec.split(",") if n.strip()]
    for n in names:
        if n not in FUSION_NAMES:
            raise ValueError(
                f"unknown fusion {n!r} (expected 'none', 'all', or a comma "
                f"list from {'/'.join(FUSION_NAMES)}, optionally '@impl')"
            )
    if not names:
        raise ValueError(f"empty fusion spec {spec!r}")
    return frozenset(names), impl


def resolve_fusion(config, allow_pallas: bool = True) -> tuple[frozenset, str]:
    """(enabled fusions, resolved impl in {"pallas", "xla"}) for a config.

    The trace-time twin of model.resolve_attention_impl: "auto" resolves to
    the Pallas kernels on TPU and the XLA twins elsewhere. ``allow_pallas``
    force-selects the twins — the same gate the attention kernels use for
    execution modes that cannot hand-place a Mosaic custom call (the dp-mesh
    GSPMD path).
    """
    fusions, impl = parse_fusion_spec(getattr(config, "fusion_impl", "none"))
    if not fusions:
        return fusions, "xla"
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if not allow_pallas:
        impl = "xla"
    return fusions, impl


def _concat_out(ws: list, tp: int):
    """Concatenate along the output (last) dim, shard-major for ``tp`` > 1.

    Accepts plain arrays, QuantWeight, or Quant4Weight (fused component-wise:
    the quantized weight and its scale — [..., 1, out] per-channel int8 or
    [..., G, out] per-group int4 — carry the same column permutation; the
    int4 in-dim nibble packing and group structure are untouched by an
    output-dim concat)."""
    if isinstance(ws[0], (QuantWeight, Quant4Weight, QuantS4Weight)):
        return type(ws[0])(
            w=_concat_out([w.w for w in ws], tp),
            scale=_concat_out([w.scale for w in ws], tp),
        )
    if tp == 1:
        return jnp.concatenate(ws, axis=-1)
    parts = []
    for s in range(tp):
        for w in ws:
            if w.shape[-1] % tp:
                raise ValueError(
                    f"output dim {w.shape[-1]} does not divide over tp={tp}"
                )
            c = w.shape[-1] // tp
            parts.append(w[..., s * c : (s + 1) * c])
    return jnp.concatenate(parts, axis=-1)


def is_fused(layers) -> bool:
    if isinstance(layers, (list, tuple)):  # one tree a run (hybrid stacks)
        return all(is_fused(run) for run in layers)
    return FUSED_QKV in layers or FUSED_GU in layers


def fuse_layer_tree(layers, tp: int = 1):
    """Fuse a stacked layer tree (any leading axes). Idempotent. A hybrid
    stack (models/llama/hybrid.py) is a list of such trees, one a run of
    layers of one kind: each is fused on its own (a state layer has no
    q/k/v, and its feed-forward fuses like any dense one)."""
    if isinstance(layers, (list, tuple)):
        return [fuse_layer_tree(run, tp) for run in layers]
    if is_fused(layers):
        return layers
    out = dict(layers)
    if "wq" in out:
        out[FUSED_QKV] = _concat_out(
            [out.pop("wq"), out.pop("wk"), out.pop("wv")], tp
        )
        if "bq" in out:
            out[FUSED_QKV_BIAS] = _concat_out(
                [out.pop("bq"), out.pop("bk"), out.pop("bv")], tp
            )
    if "router" in out:
        # MoE: expert weights keep their grouped layout; the always-on
        # shared expert (Qwen2-MoE) is a dense SwiGLU and fuses like one.
        if "sh_gate" in out:
            out[FUSED_SHARED_GU] = _concat_out(
                [out.pop("sh_gate"), out.pop("sh_up")], tp
            )
    elif "w_gate" in out:
        out[FUSED_GU] = _concat_out([out.pop("w_gate"), out.pop("w_up")], tp)
    return out


def fuse_params(params: dict, tp: int = 1) -> dict:
    """Fuse a full model param tree (embed/ln_f/lm_head untouched)."""
    out = dict(params)
    out["layers"] = fuse_layer_tree(params["layers"], tp)
    return out


def _split_out(w, sizes: list[int], tp: int):
    """Inverse of _concat_out (tests / tooling only)."""
    if isinstance(w, (QuantWeight, Quant4Weight, QuantS4Weight)):
        ws = _split_out(w.w, sizes, tp)
        ss = _split_out(w.scale, sizes, tp)
        return [type(w)(w=a, scale=b) for a, b in zip(ws, ss)]
    outs = [[] for _ in sizes]
    off = 0
    for _ in range(tp):
        for i, sz in enumerate(sizes):
            c = sz // tp
            outs[i].append(w[..., off : off + c])
            off += c
    return [jnp.concatenate(p, axis=-1) if tp > 1 else p[0] for p in outs]


def unfuse_layer_tree(layers: dict, config, tp: int = 1) -> dict:
    """Recover the per-projection layout (round-trip oracle for tests)."""
    if not is_fused(layers):
        return layers
    out = dict(layers)
    hd = config.head_dim
    qw = config.num_attention_heads * hd
    kw = config.num_key_value_heads * hd
    out["wq"], out["wk"], out["wv"] = _split_out(
        out.pop(FUSED_QKV), [qw, kw, kw], tp
    )
    if FUSED_QKV_BIAS in out:
        out["bq"], out["bk"], out["bv"] = _split_out(
            out.pop(FUSED_QKV_BIAS), [qw, kw, kw], tp
        )
    if FUSED_GU in out:
        gu = out.pop(FUSED_GU)
        inter = (
            gu.w.shape[-1]
            if isinstance(gu, (QuantWeight, Quant4Weight, QuantS4Weight))
            else gu.shape[-1]
        ) // 2
        out["w_gate"], out["w_up"] = _split_out(gu, [inter, inter], tp)
    if FUSED_SHARED_GU in out:
        gu = out.pop(FUSED_SHARED_GU)
        inter = (
            gu.w.shape[-1]
            if isinstance(gu, (QuantWeight, Quant4Weight, QuantS4Weight))
            else gu.shape[-1]
        ) // 2
        out["sh_gate"], out["sh_up"] = _split_out(gu, [inter, inter], tp)
    return out
