"""Safetensors weight loading: checkpoint directory -> stacked param pytree.

Covers the reference's loading path (cake-core/src/utils/mod.rs:32-104): resolve the
file list from ``model.safetensors.index.json``'s weight_map, fall back to a single
``model.safetensors``, and mmap — only tensors actually requested are materialized.

TPU-first differences:
  * Per-layer weights land STACKED [n_layers, ...] (see models/llama/model.py), and a
    worker loading a block range [lo, hi) stacks only its own layers — the equivalent
    of the reference worker loading only its topology-assigned blocks
    (worker.rs:95-108).
  * Linear weights are transposed from HF's [out, in] to [in, out] once at load.
  * Loading is zero-copy up to the dtype cast: numpy mmap views feed jnp.asarray.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama.capability import refuse_unsupported
from cake_tpu.models.llama.config import (
    CACHE_KV_KINDS, CACHE_KV_STATE, CACHE_LATENT, CACHE_LATENT_INDEX, LlamaConfig,
)
from cake_tpu.models.llama.model import Params

INDEX_FILE = "model.safetensors.index.json"
SINGLE_FILE = "model.safetensors"

# HF tensor-name templates for one decoder layer, keyed by our stacked-param name.
# transpose=True for linear weights stored [out, in] in the checkpoint.
_LAYER_TEMPLATES: dict[str, tuple[str, bool]] = {
    "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
    "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
    "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
    "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
    "w_gate": ("model.layers.{i}.mlp.gate_proj.weight", True),
    "w_up": ("model.layers.{i}.mlp.up_proj.weight", True),
    "w_down": ("model.layers.{i}.mlp.down_proj.weight", True),
    "ln_attn": ("model.layers.{i}.input_layernorm.weight", False),
    "ln_mlp": ("model.layers.{i}.post_attention_layernorm.weight", False),
}

# Optional per-layer tensors: QKV biases (Qwen2 family, config.attention_bias).
# Loaded only when present in the checkpoint; [out]-shaped, no transpose.
_LAYER_BIAS_TEMPLATES: dict[str, tuple[str, bool]] = {
    "bq": ("model.layers.{i}.self_attn.q_proj.bias", False),
    "bk": ("model.layers.{i}.self_attn.k_proj.bias", False),
    "bv": ("model.layers.{i}.self_attn.v_proj.bias", False),
}

# Qwen3 family: per-head q/k RMSNorm weights ([head_dim], no transpose),
# loaded only when present in the checkpoint.
_QK_NORM_TEMPLATES: dict[str, tuple[str, bool]] = {
    "q_norm": ("model.layers.{i}.self_attn.q_norm.weight", False),
    "k_norm": ("model.layers.{i}.self_attn.k_norm.weight", False),
}

# Gemma-2 layers carry four norms; these override/extend the two-norm
# templates when present in the checkpoint.
_GEMMA2_NORM_TEMPLATES: dict[str, tuple[str, bool]] = {
    "ln_mlp": ("model.layers.{i}.pre_feedforward_layernorm.weight", False),
    "ln_post_attn": ("model.layers.{i}.post_attention_layernorm.weight", False),
    "ln_post_mlp": ("model.layers.{i}.post_feedforward_layernorm.weight", False),
}

# MoE layers: the dense-MLP templates are replaced by a router plus
# per-expert SwiGLU weights, stacked [n_experts, in, out] at load. Mixtral
# and Qwen2-MoE use different tensor names (and the latter adds an always-on
# shared expert); the layout is detected from the checkpoint itself.
_MOE_LAYOUTS: dict[str, dict] = {
    "mixtral": {
        "router": "model.layers.{i}.block_sparse_moe.gate.weight",
        "experts": {
            "w_gate": "model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight",
            "w_up": "model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight",
            "w_down": "model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight",
        },
        "shared": {},
    },
    "qwen2_moe": {
        "router": "model.layers.{i}.mlp.gate.weight",
        "experts": {
            "w_gate": "model.layers.{i}.mlp.experts.{e}.gate_proj.weight",
            "w_up": "model.layers.{i}.mlp.experts.{e}.up_proj.weight",
            "w_down": "model.layers.{i}.mlp.experts.{e}.down_proj.weight",
        },
        "shared": {
            "sh_gate": "model.layers.{i}.mlp.shared_expert.gate_proj.weight",
            "sh_up": "model.layers.{i}.mlp.shared_expert.up_proj.weight",
            "sh_down": "model.layers.{i}.mlp.shared_expert.down_proj.weight",
            "se_gate": "model.layers.{i}.mlp.shared_expert_gate.weight",
        },
    },
}

_DTYPES = {
    "F64": np.float64,
    "F32": np.float32,
    "F16": np.float16,
    "BF16": None,  # no numpy bf16; handled as uint16 view -> jnp
    "I64": np.int64,
    "I32": np.int32,
    "I16": np.int16,
    "I8": np.int8,
    "U8": np.uint8,
    "BOOL": np.bool_,
}


class SafetensorsReader:
    """Lazy mmap'd reader over one or more safetensors files.

    The file format is simple enough (8-byte LE header length, JSON header, raw
    little-endian tensor data) that reading it directly beats pulling in a
    framework dependency; this also lets bf16 tensors pass through to JAX without
    a float32 detour.
    """

    def __init__(self, paths: list[Path]):
        self._entries: dict[str, tuple[np.memmap, dict]] = {}
        self._mmaps: list[np.memmap] = []
        # Seconds ``jax()`` spent paging tensors in from the files.
        self.read_s = 0.0
        for path in paths:
            with open(path, "rb") as f:
                header_len = int.from_bytes(f.read(8), "little")
                header = json.loads(f.read(header_len))
            data_offset = 8 + header_len
            mm = np.memmap(path, dtype=np.uint8, mode="r", offset=data_offset)
            self._mmaps.append(mm)
            for name, meta in header.items():
                if name == "__metadata__":
                    continue
                self._entries[name] = (mm, meta)

    def names(self) -> Iterator[str]:
        return iter(self._entries)

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def shape(self, name: str) -> tuple[int, ...]:
        return tuple(self._entries[name][1]["shape"])

    def st_dtype(self, name: str) -> str:
        """The tensor's safetensors dtype tag (e.g. "F32", "BF16")."""
        return self._entries[name][1]["dtype"]

    def numpy(self, name: str) -> np.ndarray:
        """Raw view of a tensor (bf16 comes back as a uint16 view)."""
        mm, meta = self._entries[name]
        lo, hi = meta["data_offsets"]
        buf = mm[lo:hi]
        shape = tuple(meta["shape"])
        st_dtype = meta["dtype"]
        if st_dtype == "BF16":
            return buf.view(np.uint16).reshape(shape)
        np_dtype = _DTYPES.get(st_dtype)
        if np_dtype is None:
            raise ValueError(f"unsupported safetensors dtype {st_dtype!r}")
        return buf.view(np_dtype).reshape(shape)

    def jax(self, name: str, dtype: jnp.dtype, transpose: bool = False) -> jnp.ndarray:
        mm, meta = self._entries[name]
        t0 = time.perf_counter()
        arr = self.numpy(name)
        # Touch one byte a page: the file system's part of the load ends
        # here, and the transfer below reads memory that is mapped.
        arr.reshape(-1).view(np.uint8)[::4096].max(initial=0)
        self.read_s += time.perf_counter() - t0
        if meta["dtype"] == "BF16":
            x = jnp.asarray(arr).view(jnp.bfloat16)
        else:
            x = jnp.asarray(arr)
        if transpose:
            x = x.T
        return x.astype(dtype)


def resolve_checkpoint_files(model_dir: str | Path) -> list[Path]:
    """File list from the index's weight_map, else the single-file fallback
    (utils/mod.rs:32-82)."""
    model_dir = Path(model_dir)
    index = model_dir / INDEX_FILE
    if index.exists():
        with open(index) as f:
            weight_map: dict[str, str] = json.load(f)["weight_map"]
        return [model_dir / fname for fname in sorted(set(weight_map.values()))]
    single = model_dir / SINGLE_FILE
    if single.exists():
        return [single]
    raise FileNotFoundError(f"no {INDEX_FILE} or {SINGLE_FILE} in {model_dir}")


def open_checkpoint(model_dir: str | Path) -> SafetensorsReader:
    return SafetensorsReader(resolve_checkpoint_files(model_dir))


_PHI3_QKV_TEMPLATE = "model.layers.{i}.self_attn.qkv_proj.weight"
_PHI3_GATE_UP_TEMPLATE = "model.layers.{i}.mlp.gate_up_proj.weight"


def _has_tensor(reader: SafetensorsReader, name: str) -> bool:
    """Present as plain OR quantized storage (hf_tensor_dict suffixes)."""
    return name in reader or name + ".q8" in reader or name + ".q4" in reader


def _read_stacked(
    reader: SafetensorsReader,
    names: list[str],
    dtype: jnp.dtype,
    transpose: bool,
):
    """Stack one weight across layers; reconstructs quantized leaves.

    Quantized tensors (``.q8``/``.q4`` + ``.scale``, written by
    hf_tensor_dict from a quantize_params tree) are stored in compute
    orientation and round-trip bit-identically — no dequantize, no re-cast.
    A tree-level stack over read_weight, so the suffix dispatch lives once.
    """
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[read_weight(reader, n, dtype, transpose) for n in names],
    )


def _read_stacked2(
    reader: SafetensorsReader,
    names2d: list[list[str]],
    dtype: jnp.dtype,
):
    """[n_layers, n_experts, ...] stacking of MoE expert weights, quantized
    or plain (expert stacks are int8 under the mixed int4 mode) — a
    per-layer _read_stacked plus one tree-level stack, so the suffix logic
    exists once."""
    rows = [_read_stacked(reader, row, dtype, True) for row in names2d]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


def read_weight(
    reader: SafetensorsReader,
    name: str,
    dtype: jnp.dtype,
    transpose: bool = False,
):
    """One weight by HF name — plain array or reconstructed quantized leaf
    (callers that read head tensors directly, e.g. runtime/master.py).
    Reads the single tensor directly: no stack/unstack transient."""
    from cake_tpu.ops.quant import Quant4Weight, QuantWeight

    for suf, cls in ((".q4", Quant4Weight), (".q8", QuantWeight)):
        if name + suf in reader:
            return cls(
                w=jnp.asarray(reader.numpy(name + suf)),
                scale=jnp.asarray(reader.numpy(name + ".scale")),
            )
    return reader.jax(name, dtype, transpose=transpose)


def load_layer_params(
    reader: SafetensorsReader,
    lo: int,
    hi: int,
    dtype: jnp.dtype = jnp.bfloat16,
    config: LlamaConfig | None = None,
) -> Params:
    """Load block range [lo, hi) as stacked [hi-lo, ...] per-weight arrays.

    Quantized checkpoints (io/quantizer.py) reconstruct their
    QuantWeight/Quant4Weight leaves directly — the full-precision weights
    never materialize (an int4 8B loads ~4 GB of packed bytes, not 15)."""
    out: Params = {}
    templates = dict(_LAYER_TEMPLATES)
    for key, entry in (
        *_LAYER_BIAS_TEMPLATES.items(),
        *_QK_NORM_TEMPLATES.items(),
    ):
        if entry[0].format(i=lo) in reader:
            templates[key] = entry
    if _GEMMA2_NORM_TEMPLATES["ln_mlp"][0].format(i=lo) in reader:
        # Gemma-2/3 four-norm layout: HF's post_attention_layernorm is a real
        # POST-attention norm there (in Llama it is the pre-MLP norm), and
        # the pre-MLP norm is pre_feedforward_layernorm.
        templates.update(_GEMMA2_NORM_TEMPLATES)
        if _QK_NORM_TEMPLATES["q_norm"][0].format(i=lo) in reader:
            # Gemma-3 (four norms + qk-norm): the 5:1 window pattern and the
            # per-layer rope plane come from the config (layer_types is not
            # a tensor), sliced to this block range so stages/workers keep
            # absolute layer parity.
            if config is None or config.sliding_pattern is None:
                raise ValueError(
                    "gemma3 checkpoint needs the model config (layer_types "
                    "drives per-layer windows and rope selection)"
                )
            flags = config.sliding_pattern[lo:hi]
            out["win_flag"] = jnp.asarray(flags)
            out["rope_sel"] = jnp.asarray(flags, jnp.int32)
        else:
            # Gemma-2: the alternating local/global pattern is positional.
            out["win_flag"] = (jnp.arange(lo, hi) % 2) == 0
    layout = next(
        (
            lay
            for lay in _MOE_LAYOUTS.values()
            if lay["router"].format(i=lo) in reader
        ),
        None,
    )
    if layout is not None:
        for key in layout["experts"]:
            del templates[key]  # dense-MLP names are absent in MoE checkpoints
        n_experts = 0
        while _has_tensor(
            reader, layout["experts"]["w_gate"].format(i=lo, e=n_experts)
        ):
            n_experts += 1
        out["router"] = jnp.stack(
            [
                reader.jax(layout["router"].format(i=i), dtype, transpose=True)
                for i in range(lo, hi)
            ]
        )
        for key, tmpl in layout["experts"].items():
            out[key] = _read_stacked2(
                reader,
                [
                    [tmpl.format(i=i, e=e) for e in range(n_experts)]
                    for i in range(lo, hi)
                ],
                dtype,
            )
        # Shared-expert tensors: the config is the authority. An explicit
        # shared_expert_intermediate_size=0 skips them; a nonzero size with
        # absent tensors is an incomplete checkpoint and must fail loudly
        # (the read raises on the missing name). With no config, trust the
        # checkpoint's own layout.
        se = None if config is None else config.shared_expert_intermediate_size
        for key, tmpl in layout["shared"].items():
            if se == 0 or (
                se is None and not _has_tensor(reader, tmpl.format(i=lo))
            ):
                continue
            out[key] = _read_stacked(
                reader,
                [tmpl.format(i=i) for i in range(lo, hi)],
                dtype,
                True,
            )
    fused_qkv = _PHI3_QKV_TEMPLATE.format(i=lo) in reader
    if fused_qkv:
        # Phi-3 fuses q|k|v rows into one tensor (and gate|up likewise);
        # split at load so the model core sees the standard layout. The
        # split points need the head geometry, so the config is required.
        if config is None:
            raise ValueError(
                "fused qkv_proj checkpoint (phi3) needs the model config "
                "to split projections"
            )
        for key in ("wq", "wk", "wv", "w_gate", "w_up"):
            del templates[key]
        hd = config.head_dim
        n_q = config.num_attention_heads * hd
        n_kv = config.num_key_value_heads * hd
        qs, ks, vs, gs, us = [], [], [], [], []
        for i in range(lo, hi):
            qkv = reader.jax(_PHI3_QKV_TEMPLATE.format(i=i), dtype, transpose=True)
            if qkv.shape[1] != n_q + 2 * n_kv:
                raise ValueError(
                    f"layer {i}: fused qkv width {qkv.shape[1]} does not "
                    f"match config geometry q={n_q} + 2*kv={2 * n_kv} — "
                    "config.json and checkpoint disagree"
                )
            qs.append(qkv[:, :n_q])
            ks.append(qkv[:, n_q : n_q + n_kv])
            vs.append(qkv[:, n_q + n_kv :])
            gu = reader.jax(
                _PHI3_GATE_UP_TEMPLATE.format(i=i), dtype, transpose=True
            )
            if gu.shape[1] % 2:
                raise ValueError(
                    f"layer {i}: fused gate_up width {gu.shape[1]} is odd"
                )
            inter = gu.shape[1] // 2
            gs.append(gu[:, :inter])
            us.append(gu[:, inter:])
        out["wq"] = jnp.stack(qs)
        out["wk"] = jnp.stack(ks)
        out["wv"] = jnp.stack(vs)
        out["w_gate"] = jnp.stack(gs)
        out["w_up"] = jnp.stack(us)
    for key, (tmpl, transpose) in templates.items():
        out[key] = _read_stacked(
            reader,
            [tmpl.format(i=i) for i in range(lo, hi)],
            dtype,
            transpose,
        )
    return out


# Hybrid stacks (models/llama/hybrid.py), a table a model type. Jamba: HF
# names as transformers' JambaForCausalLM writes them -> (key in the run's
# tree, how the tensor is turned into the layout the model holds). "T" =
# [out, in] -> [in, out].
_JAMBA_FFN = {
    "w_gate": ("feed_forward.gate_proj.weight", "T"),
    "w_up": ("feed_forward.up_proj.weight", "T"),
    "w_down": ("feed_forward.down_proj.weight", "T"),
    "ln_attn": ("input_layernorm.weight", None),
    "ln_mlp": ("pre_ff_layernorm.weight", None),
}
_JAMBA_TEMPLATES = {
    "attention": {
        "wq": ("self_attn.q_proj.weight", "T"),
        "wk": ("self_attn.k_proj.weight", "T"),
        "wv": ("self_attn.v_proj.weight", "T"),
        "wo": ("self_attn.o_proj.weight", "T"),
        **_JAMBA_FFN,
    },
    "state": {
        "in_proj": ("mamba.in_proj.weight", "T"),
        # [d_inner, 1, d_conv] -> [d_conv, d_inner]
        "conv_w": ("mamba.conv1d.weight", "conv"),
        "conv_b": ("mamba.conv1d.bias", None),
        "x_proj": ("mamba.x_proj.weight", "T"),
        "dt_ln": ("mamba.dt_layernorm.weight", None),
        "b_ln": ("mamba.b_layernorm.weight", None),
        "c_ln": ("mamba.c_layernorm.weight", None),
        "dt_proj": ("mamba.dt_proj.weight", "T"),
        "dt_bias": ("mamba.dt_proj.bias", None),
        # [d_inner, d_state] -> [d_state, d_inner]
        "A_log": ("mamba.A_log", "T"),
        "D": ("mamba.D", None),
        "wo": ("mamba.out_proj.weight", "T"),
        **_JAMBA_FFN,
    },
}
# ``model_type: olmo_hybrid``: the OLMo-2/3 names for the block, and for the
# gated delta rule the names flash-linear-attention's GatedDeltaNet writes
# (``bench/architectures/olmo_hybrid.py`` is their statement). A TUPLE of
# names is joined along the tree's last axis, each turned first: q | k | v | z
# are one ``in_proj``, a | b one ``ab_proj``, the three convolutions one.
_OLMO_FFN = {
    "w_gate": ("mlp.gate_proj.weight", "T"),
    "w_up": ("mlp.up_proj.weight", "T"),
    "w_down": ("mlp.down_proj.weight", "T"),
    "ln_post_attn": ("post_attention_layernorm.weight", None),
    "ln_post_mlp": ("post_feedforward_layernorm.weight", None),
}
_OLMO_HYBRID_TEMPLATES = {
    "attention": {
        "wq": ("self_attn.q_proj.weight", "T"),
        "wk": ("self_attn.k_proj.weight", "T"),
        "wv": ("self_attn.v_proj.weight", "T"),
        "wo": ("self_attn.o_proj.weight", "T"),
        "q_norm": ("self_attn.q_norm.weight", None),
        "k_norm": ("self_attn.k_norm.weight", None),
        **_OLMO_FFN,
    },
    "state": {
        "in_proj": (
            tuple(f"linear_attn.{n}_proj.weight" for n in "qkvg"), "T",
        ),
        "ab_proj": (
            ("linear_attn.a_proj.weight", "linear_attn.b_proj.weight"), "T",
        ),
        # three of [channels, 1, taps] -> [taps, all channels]
        "conv_w": (
            tuple(f"linear_attn.{n}_conv1d.weight" for n in "qkv"), "conv",
        ),
        "A_log": ("linear_attn.A_log", None),
        "dt_bias": ("linear_attn.dt_bias", None),
        "o_norm": ("linear_attn.o_norm.weight", None),
        "wo": ("linear_attn.o_proj.weight", "T"),
        **_OLMO_FFN,
    },
}
# ``model_type: lfm2_moe`` (names ASSUMED: written from memory of
# transformers' ``Lfm2MoeForCausalLM``; the catalog row carries the config
# alone). The mixer's table by kind and, beside it, the feed-forward's by
# ITS kind (``config.run_ff_kinds``): SwiGLU as w1 (gate), w3 (up), w2
# (down). "experts" = one tensor a HELD expert (``{e}``: its own number on
# disk), each turned, stacked.
_LFM2_NORMS = {
    "ln_attn": ("operator_norm.weight", None),
    "ln_mlp": ("ffn_norm.weight", None),
}
_LFM2_MOE_TEMPLATES = {
    "attention": {
        "wq": ("self_attn.q_proj.weight", "T"),
        "wk": ("self_attn.k_proj.weight", "T"),
        "wv": ("self_attn.v_proj.weight", "T"),
        "wo": ("self_attn.out_proj.weight", "T"),
        "q_norm": ("self_attn.q_layernorm.weight", None),
        "k_norm": ("self_attn.k_layernorm.weight", None),
        **_LFM2_NORMS,
    },
    "state": {
        "in_proj": ("conv.in_proj.weight", "T"),
        # [channels, 1, taps] -> [taps, channels]
        "conv_w": ("conv.conv.weight", "conv"),
        "wo": ("conv.out_proj.weight", "T"),
        **_LFM2_NORMS,
    },
    "dense": {
        "w_gate": ("feed_forward.w1.weight", "T"),
        "w_up": ("feed_forward.w3.weight", "T"),
        "w_down": ("feed_forward.w2.weight", "T"),
    },
    "sparse": {
        "router": ("feed_forward.gate.weight", "T"),
        "router_bias": ("feed_forward.expert_bias", None),
        "w_gate": ("feed_forward.experts.{e}.w1.weight", "experts"),
        "w_up": ("feed_forward.experts.{e}.w3.weight", "experts"),
        "w_down": ("feed_forward.experts.{e}.w2.weight", "experts"),
    },
}
# ``model_type: qwen3_next`` (names ASSUMED: written from memory of
# transformers' ``Qwen3NextForCausalLM``). Three of its tensors are laid out
# a head at a time and are RE-ORDERED here, once, into the tree's layouts, so
# that no served program permutes (``_by_head`` says how): ``in_proj_qkvz``
# [q dk | k dk | v g dv | z g dv] a KEY head (g value heads a key head) ->
# ``in_proj`` q | k | v | z; ``in_proj_ba`` [b g | a g] a key head ->
# ``ab_proj`` a | b; ``q_proj`` [q | gate] a query head -> ``wq`` and the
# gate's matrix ``wg``. The convolution's channels are q | k | v already.
_QWEN3_NEXT_FFN = {
    "ln_attn": ("input_layernorm.weight", None),
    "ln_mlp": ("post_attention_layernorm.weight", None),
    "router": ("mlp.gate.weight", "T"),
    "sh_gate": ("mlp.shared_expert.gate_proj.weight", "T"),
    "sh_up": ("mlp.shared_expert.up_proj.weight", "T"),
    "sh_down": ("mlp.shared_expert.down_proj.weight", "T"),
    "se_gate": ("mlp.shared_expert_gate.weight", "T"),
    "w_gate": ("mlp.experts.{e}.gate_proj.weight", "experts"),
    "w_up": ("mlp.experts.{e}.up_proj.weight", "experts"),
    "w_down": ("mlp.experts.{e}.down_proj.weight", "experts"),
}
_QWEN3_NEXT_TEMPLATES = {
    "attention": {
        "wq": ("self_attn.q_proj.weight", "q|gate:0"),
        "wg": ("self_attn.q_proj.weight", "q|gate:1"),
        "wk": ("self_attn.k_proj.weight", "T"),
        "wv": ("self_attn.v_proj.weight", "T"),
        "wo": ("self_attn.o_proj.weight", "T"),
        "q_norm": ("self_attn.q_norm.weight", None),
        "k_norm": ("self_attn.k_norm.weight", None),
        **_QWEN3_NEXT_FFN,
    },
    "state": {
        "in_proj": ("linear_attn.in_proj_qkvz.weight", "qkvz"),
        "ab_proj": ("linear_attn.in_proj_ba.weight", "ba"),
        "conv_w": ("linear_attn.conv1d.weight", "conv"),
        "A_log": ("linear_attn.A_log", None),
        "dt_bias": ("linear_attn.dt_bias", None),
        "o_norm": ("linear_attn.norm.weight", None),
        "wo": ("linear_attn.out_proj.weight", "T"),
        **_QWEN3_NEXT_FFN,
    },
}
# model_type -> (tables a mixer kind and, where the model's feed-forwards
# differ, a feed-forward kind; the final norm's name)
_HYBRID_TABLES = {
    "jamba": (_JAMBA_TEMPLATES, "model.final_layernorm.weight"),
    "olmo_hybrid": (_OLMO_HYBRID_TEMPLATES, "model.norm.weight"),
    "lfm2_moe": (_LFM2_MOE_TEMPLATES, "model.embedding_norm.weight"),
    "qwen3_next": (_QWEN3_NEXT_TEMPLATES, "model.norm.weight"),
}


_HEAD_MAJOR = ("qkvz", "ba", "q|gate:0", "q|gate:1")


def _by_head(config: LlamaConfig, how: str) -> tuple[int, list[int], list[int]]:
    """A tensor laid out a head at a time, as (heads, the widths of a head's
    parts in the checkpoint's order, the order the tree joins them in: part
    ``p`` of every head side by side, then the next part)."""
    if how.startswith("q|gate"):
        return config.num_attention_heads, [config.head_dim] * 2, [int(how[-1])]
    keys = config.linear_num_key_heads
    group = config.linear_num_value_heads // keys
    if how == "ba":  # [b | a] a key head -> a | b
        return keys, [group, group], [1, 0]
    dk, dv = config.linear_key_head_dim, group * config.linear_value_head_dim
    return keys, [dk, dk, dv, dv], [0, 1, 2, 3]


def _from_heads(a, config: LlamaConfig, how: str):
    """[in, heads x parts] as the checkpoint has it (turned) -> the tree's
    [in, part | part | ...]."""
    heads, widths, order = _by_head(config, how)
    cuts = np.cumsum(widths)[:-1]
    parts = jnp.split(a.reshape(a.shape[0], heads, sum(widths)), cuts, axis=-1)
    return jnp.concatenate([parts[p].reshape(a.shape[0], -1) for p in order], axis=-1)


def _to_heads(parts: list[np.ndarray], config: LlamaConfig, how: str) -> np.ndarray:
    """The inverse: the tree's parts [in, heads * width] in the CHECKPOINT's
    order -> [in, heads x parts]."""
    heads, widths, _ = _by_head(config, how)
    rows = parts[0].shape[0]
    return np.concatenate(
        [p.reshape(rows, heads, w) for p, w in zip(parts, widths)], axis=-1
    ).reshape(rows, -1)


def _hybrid_table(config: LlamaConfig, kind: str, ff: str) -> dict:
    """A run's table: its mixer's and, where the model type's tables say the
    feed-forward apart, its feed-forward's (without a selection bias or a
    shared expert the config leaves out)."""
    tables = _HYBRID_TABLES[config.model_type][0]
    table = {**tables[kind], **tables.get(ff, {})}
    if not config.router_bias:
        table.pop("router_bias", None)
    if not config.shared_expert_intermediate_size:
        for key in ("sh_gate", "sh_up", "sh_down", "se_gate"):
            table.pop(key, None)
    return table


def _hybrid_read(reader: SafetensorsReader, names, how, dtype, config=None):
    if isinstance(names, tuple):
        return jnp.concatenate(
            [_hybrid_read(reader, n, how, dtype) for n in names], axis=-1
        )
    if how == "conv":
        return reader.jax(names, dtype)[:, 0, :].T
    if how in _HEAD_MAJOR:
        return _from_heads(reader.jax(names, dtype, transpose=True), config, how)
    if how == "experts":
        first = config.expert_offset
        return jnp.stack([
            reader.jax(names.format(e=e), dtype, transpose=True)
            for e in range(first, first + config.num_local_experts)
        ])
    return reader.jax(names, dtype, transpose=how == "T")


def load_hybrid_layers(
    reader: SafetensorsReader, config: LlamaConfig, dtype
) -> list[Params]:
    """One stacked tree a run of layers alike in mixer and feed-forward, in
    the model's order (``config.layer_runs``), from the model type's HF
    names."""
    at = lambda i, names: (
        tuple(f"model.layers.{i}.{n}" for n in names)
        if isinstance(names, tuple) else f"model.layers.{i}.{names}"
    )
    return [
        {
            key: jnp.stack([
                _hybrid_read(reader, at(i, names), how, dtype, config)
                for i in config.layers_of(kind)[lo:hi]
            ])
            for key, (names, how) in _hybrid_table(config, kind, ff).items()
        }
        for (kind, lo, hi), ff in zip(config.layer_runs, config.run_ff_kinds)
    ]


def _head_major(run: Params, k: int, key: str, how: str, config: LlamaConfig, dtype):
    """One layer's tensor in the checkpoint's head-at-a-time layout from the
    tree's (``hybrid_tensor_dict``): [out, in], or None for the key whose
    tensor another key writes (``wg`` rides in ``wq``'s ``q_proj``)."""
    if how == "q|gate:1":
        return None
    if how == "q|gate:0":
        parts = [np.asarray(run[n][k].astype(dtype)) for n in ("wq", "wg")]
    else:
        heads, widths, order = _by_head(config, how)
        whole = np.asarray(run[key][k].astype(dtype))
        joined = np.split(whole, np.cumsum([heads * widths[p] for p in order])[:-1], axis=-1)
        parts = [joined[order.index(p)] for p in range(len(widths))]
    return _to_heads(parts, config, how).T


def _joined_widths(config: LlamaConfig, key: str) -> list[int]:
    """Widths of the checkpoint's tensors that one joined key holds."""
    heads = config.linear_num_value_heads
    n_k = config.linear_num_key_heads * config.linear_key_head_dim
    n_v = heads * config.linear_value_head_dim
    return {
        "in_proj": [n_k, n_k, n_v, n_v], "ab_proj": [heads, heads],
        "conv_w": [n_k, n_k, n_v],
    }[key]


def hybrid_tensor_dict(
    params: Params, config: LlamaConfig, dtype
) -> dict[str, np.ndarray]:
    """THE inverse of ``load_hybrid_layers`` (fixtures and round trips)."""
    _, final_norm = _HYBRID_TABLES[config.model_type]
    tensors = {
        "model.embed_tokens.weight": np.asarray(params["embed"].astype(dtype)),
        final_norm: np.asarray(params["ln_f"].astype(dtype)),
    }
    if not config.tie_word_embeddings:
        _emit_tensor(tensors, "lm_head.weight", params["lm_head"], True, dtype)
    for run, (kind, lo, hi), ff in zip(
        params["layers"], config.layer_runs, config.run_ff_kinds
    ):
        for key, (names, how) in _hybrid_table(config, kind, ff).items():
            for k, i in enumerate(config.layers_of(kind)[lo:hi]):
                whole = np.asarray(run[key][k].astype(dtype))
                if how in _HEAD_MAJOR:
                    a = _head_major(run, k, key, how, config, dtype)
                    if a is not None:
                        tensors[f"model.layers.{i}.{names}"] = a.copy()
                    continue
                if isinstance(names, tuple):
                    cuts = np.cumsum(_joined_widths(config, key))[:-1]
                    parts = zip(names, np.split(whole, cuts, axis=-1))
                elif how == "experts":  # a tensor a held expert, turned back
                    parts = [
                        (names.format(e=config.expert_offset + j), a.T)
                        for j, a in enumerate(whole)
                    ]
                else:
                    parts = [(names, whole)]
                for name, a in parts:
                    if how == "conv":
                        a = a.T[:, None, :]
                    elif how == "T":
                        a = a.T
                    tensors[f"model.layers.{i}.{name}"] = a.copy()
    return tensors


# Latent stacks (models/llama/latent.py, ``model_type: pangu_ultra_moe``): HF
# names as the DeepSeek-V3 family writes them -> key in the run's tree. Every
# matrix is [out, in] on disk and [in, out] here; ``kv_b_proj`` is held as its
# two per-head halves; a sparse layer's experts are stacked over the experts
# HELD (``config.expert_offset`` on: their own numbers on disk).
_LATENT_MATRICES = {
    "wq_a": "self_attn.q_a_proj.weight", "wq_b": "self_attn.q_b_proj.weight",
    "wkv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "wo": "self_attn.o_proj.weight",
}
_LATENT_VECTORS = {
    "q_a_ln": "self_attn.q_a_layernorm.weight",
    "kv_a_ln": "self_attn.kv_a_layernorm.weight",
    "ln_attn": "input_layernorm.weight",
    "ln_post_attn": "post_attention_layernorm.weight",
    "ln_mlp": "pre_mlp_layernorm.weight",
    "ln_post_mlp": "post_mlp_layernorm.weight",
}
# ``model_type: deepseek_v32``: one norm on each branch's INPUT (HF's
# ``post_attention_layernorm`` is the feed-forward's), the learned index's
# tensors under ``self_attn.indexer`` and the router's correction bias.
_LATENT_VECTORS_PRE_NORM = {
    "q_a_ln": "self_attn.q_a_layernorm.weight",
    "kv_a_ln": "self_attn.kv_a_layernorm.weight",
    "ln_attn": "input_layernorm.weight",
    "ln_mlp": "post_attention_layernorm.weight",
}
_INDEX_MATRICES = {
    "wi_q": "self_attn.indexer.wq_b.weight", "wi_k": "self_attn.indexer.wk.weight",
    "wi_w": "self_attn.indexer.weights_proj.weight",
}
_INDEX_VECTORS = {
    "i_k_ln": "self_attn.indexer.k_norm.weight",
    "i_k_ln_b": "self_attn.indexer.k_norm.bias",
}
_ROUTER_BIAS = "mlp.gate.e_score_correction_bias"
_LATENT_KV_B = "self_attn.kv_b_proj.weight"
_SWIGLU = {"gate": "gate_proj.weight", "up": "up_proj.weight", "down": "down_proj.weight"}


def _latent_names(config: LlamaConfig) -> tuple[dict[str, str], dict[str, str]]:
    """(matrices, vectors) of a latent layer's attention, by the config."""
    matrices = dict(_LATENT_MATRICES)
    vectors = dict(_LATENT_VECTORS if config.post_block_norms else _LATENT_VECTORS_PRE_NORM)
    if config.index_topk:
        matrices.update(_INDEX_MATRICES)
        vectors.update(_INDEX_VECTORS)
    return matrices, vectors


def _read_feed_forward(reader: SafetensorsReader, config: LlamaConfig, p: str, sparse: bool, dtype, shared: str) -> Params:
    """A layer's feed-forward under prefix ``p``: a dense SwiGLU, or the
    router, the experts HELD stacked (their own numbers on disk:
    ``config.expert_offset`` on) and the shared expert under ``mlp.<shared>``
    (the by-run loaders of a latent model and of a pool a kind share it)."""
    if not sparse:
        return {f"w_{k}": reader.jax(f"{p}mlp.{name}", dtype, transpose=True)
                for k, name in _SWIGLU.items()}
    out = {"router": reader.jax(p + "mlp.gate.weight", dtype, transpose=True)}
    if config.router_bias:
        out["router_bias"] = reader.jax(p + _ROUTER_BIAS, dtype)
    held = range(config.expert_offset, config.expert_offset + config.num_local_experts)
    for k, name in _SWIGLU.items():
        out[f"w_{k}"] = jnp.stack([
            reader.jax(f"{p}mlp.experts.{e}.{name}", dtype, transpose=True) for e in held
        ])
        if config.shared_expert_intermediate_size:
            out[f"sh_{k}"] = reader.jax(f"{p}mlp.{shared}.{name}", dtype, transpose=True)
    return out


def _put_feed_forward(put, run: Params, k: int, config: LlamaConfig, p: str, sparse: bool, shared: str) -> None:
    """THE inverse of ``_read_feed_forward`` for layer ``k`` of a run."""
    for key, name in _SWIGLU.items():
        if not sparse:
            put(f"{p}mlp.{name}", run[f"w_{key}"][k], True)
            continue
        for j in range(config.num_local_experts):
            put(f"{p}mlp.experts.{config.expert_offset + j}.{name}", run[f"w_{key}"][k, j], True)
        if f"sh_{key}" in run:
            put(f"{p}mlp.{shared}.{name}", run[f"sh_{key}"][k], True)
    if sparse:
        put(p + "mlp.gate.weight", run["router"][k], True)
        if "router_bias" in run:
            put(p + _ROUTER_BIAS, run["router_bias"][k])


def _latent_layer(reader: SafetensorsReader, config: LlamaConfig, i: int, sparse: bool, dtype) -> Params:
    p = f"model.layers.{i}."
    matrices, vectors = _latent_names(config)
    out = {k: reader.jax(p + n, dtype, transpose=True) for k, n in matrices.items()}
    out.update({k: reader.jax(p + n, dtype) for k, n in vectors.items()})
    n, nope = config.num_attention_heads, config.qk_nope_head_dim
    kv_b = reader.jax(p + _LATENT_KV_B, dtype).reshape(n, -1, config.kv_lora_rank)
    out["w_uk"] = jnp.swapaxes(kv_b[:, :nope], 1, 2)  # [heads, rank, nope]
    out["w_uv"] = jnp.swapaxes(kv_b[:, nope:], 1, 2)
    out.update(_read_feed_forward(reader, config, p, sparse, dtype, "shared_experts"))
    return out


def load_latent_layers(reader: SafetensorsReader, config: LlamaConfig, dtype) -> list[Params]:
    """One stacked tree a run of layers of one feed-forward kind
    (``config.ff_runs``), a key at a time so that a run's largest stack and
    its parts are all that is held twice."""
    from cake_tpu.models.llama.config import SPARSE

    runs = []
    for kind, lo, hi in config.ff_runs:
        layers = [_latent_layer(reader, config, i, kind == SPARSE, dtype) for i in range(lo, hi)]
        run = {}
        for key in list(layers[0]):
            run[key] = jnp.stack([layer.pop(key) for layer in layers])
        runs.append(run)
    return runs


def latent_tensor_dict(params: Params, config: LlamaConfig, dtype) -> dict[str, np.ndarray]:
    """THE inverse of ``load_latent_layers`` (fixtures and round trips)."""
    from cake_tpu.models.llama.config import SPARSE

    tensors = head_tensor_dict(params, config, dtype)

    def put(name, a, transpose=False):
        a = np.asarray(a.astype(dtype))
        tensors[name] = (a.T if transpose else a).copy()

    matrices, vectors = _latent_names(config)
    for run, (kind, lo, hi) in zip(params["layers"], config.ff_runs):
        for k, i in enumerate(range(lo, hi)):
            p = f"model.layers.{i}."
            for key, name in matrices.items():
                put(p + name, run[key][k], True)
            for key, name in vectors.items():
                put(p + name, run[key][k])
            kv_b = jnp.concatenate([run["w_uk"][k], run["w_uv"][k]], axis=-1)  # [n, rank, nope+v]
            put(p + _LATENT_KV_B, jnp.swapaxes(kv_b, 1, 2).reshape(-1, config.kv_lora_rank))
            _put_feed_forward(put, run, k, config, p, kind == SPARSE, "shared_experts")
    return tensors


# Stacks by attention kind and feed-forward (models/llama/kinds.py,
# ``model_type: laguna``): HF names (ASSUMED: the catalog row carries the
# config alone) -> key in the run's tree. The gate a head is ``g_proj``; a
# sparse layer's experts are stacked over the experts HELD, under their own
# numbers on disk, the shared one under ``shared_expert``.
_KINDS_MATRICES = {
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "wg": "self_attn.g_proj.weight",
}
_KINDS_VECTORS = {
    "ln_attn": "input_layernorm.weight",
    "ln_mlp": "post_attention_layernorm.weight",
}


def _kinds_matrices(config: LlamaConfig) -> dict[str, str]:
    return {k: n for k, n in _KINDS_MATRICES.items() if k != "wg" or config.attn_gate}


def _kinds_vectors(config: LlamaConfig) -> dict[str, str]:
    qk = {"q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight"}
    return {**_KINDS_VECTORS, **(qk if config.qk_norm else {})}


def _kinds_layer(reader: SafetensorsReader, config: LlamaConfig, i: int, sparse: bool, dtype) -> Params:
    p = f"model.layers.{i}."
    out = {k: reader.jax(p + n, dtype, transpose=True) for k, n in _kinds_matrices(config).items()}
    out.update({k: reader.jax(p + n, dtype) for k, n in _kinds_vectors(config).items()})
    out.update(_read_feed_forward(reader, config, p, sparse, dtype, "shared_expert"))
    return out


def load_kinds_layers(reader: SafetensorsReader, config: LlamaConfig, dtype) -> list[Params]:
    """One stacked tree a run of layers alike in attention kind and
    feed-forward (``config.stack_runs``), a key at a time."""
    from cake_tpu.models.llama.config import SPARSE

    runs = []
    for _, ff, lo, hi, _ in config.stack_runs:
        layers = [_kinds_layer(reader, config, i, ff == SPARSE, dtype) for i in range(lo, hi)]
        run = {}
        for key in list(layers[0]):
            run[key] = jnp.stack([layer.pop(key) for layer in layers])
        runs.append(run)
    return runs


def kinds_tensor_dict(params: Params, config: LlamaConfig, dtype) -> dict[str, np.ndarray]:
    """THE inverse of ``load_kinds_layers`` (fixtures and round trips)."""
    from cake_tpu.models.llama.config import SPARSE

    tensors = head_tensor_dict(params, config, dtype)

    def put(name, a, transpose=False):
        a = np.asarray(a.astype(dtype))
        tensors[name] = (a.T if transpose else a).copy()

    for run, (_, ff, lo, hi, _) in zip(params["layers"], config.stack_runs):
        for k, i in enumerate(range(lo, hi)):
            p = f"model.layers.{i}."
            for key, name in _kinds_matrices(config).items():
                put(p + name, run[key][k], True)
            for key, name in _kinds_vectors(config).items():
                put(p + name, run[key][k])
            _put_feed_forward(put, run, k, config, p, ff == SPARSE, "shared_expert")
    return tensors


def _model_norm(config: LlamaConfig) -> str:
    return "model.norm.weight"


# A cache kind whose layers stack by run, not as one tree: (its layers'
# reader, its tree's inverse, the final norm's tensor). Plain K and V is the
# one-tree reader below.
_BY_KIND = {
    CACHE_KV_STATE: (
        load_hybrid_layers, hybrid_tensor_dict,
        lambda config: _HYBRID_TABLES[config.model_type][1],
    ),
    CACHE_LATENT: (load_latent_layers, latent_tensor_dict, _model_norm),
    CACHE_LATENT_INDEX: (load_latent_layers, latent_tensor_dict, _model_norm),
    CACHE_KV_KINDS: (load_kinds_layers, kinds_tensor_dict, _model_norm),
}


def load_params(
    model_dir: str | Path,
    config: LlamaConfig,
    dtype: jnp.dtype = jnp.bfloat16,
    layer_range: tuple[int, int] | None = None,
    times: dict | None = None,
) -> Params:
    """Load a full param pytree (or, for a worker, just a block range's layers).

    With ``layer_range`` set, only the stacked layer shard is returned — embedding,
    final norm, and lm_head stay on the master (llama.rs:178-196 vs worker.rs:95-108).

    ``times``, when given, receives ``read_s`` (opening the checkpoint and
    paging its tensors in) and ``put_s`` (moving them to the device in the
    model's layout: transfer, transpose, cast, stack), the second ended by
    ``block_until_ready`` on the whole tree.
    """
    t0 = time.perf_counter()
    reader = open_checkpoint(model_dir)
    refuse_unsupported(config, layer_range=layer_range is not None)
    if config.cache_kind in _BY_KIND:
        by_run, _, ln_f = _BY_KIND[config.cache_kind]
        params = {
            "embed": reader.jax("model.embed_tokens.weight", dtype),
            "layers": by_run(reader, config, dtype),
            "ln_f": reader.jax(ln_f(config), dtype),
        }
    elif layer_range is not None:
        lo, hi = layer_range
        return {"layers": load_layer_params(reader, lo, hi, dtype, config)}
    else:
        params = _load_llama_params(reader, config, dtype)
    if not config.tie_word_embeddings:
        params["lm_head"] = read_weight(reader, "lm_head.weight", dtype, True)
    if times is not None:
        jax.block_until_ready(params)
        times["read_s"] = round(reader.read_s, 3)
        times["put_s"] = round(
            time.perf_counter() - t0 - reader.read_s, 3
        )
    return params


def _load_llama_params(reader, config: LlamaConfig, dtype) -> Params:
    return {
        "embed": reader.jax("model.embed_tokens.weight", dtype),
        "layers": load_layer_params(
            reader, 0, config.num_hidden_layers, dtype, config
        ),
        "ln_f": reader.jax("model.norm.weight", dtype),
    }


def hf_tensor_dict(
    params: Params, config: LlamaConfig, dtype: jnp.dtype = jnp.float32
) -> dict[str, np.ndarray]:
    """Flatten a param tree into HF-named checkpoint tensors ([out, in] rows).

    THE inverse of load_layer_params' name mapping, shared by both fixture
    writers (single-file and sharded) so writer and reader naming cannot
    drift. (The splitter never rebuilds names — it filters the reader's raw
    tensors by ownership, io/splitter.py.) ``dtype`` is the STORAGE dtype
    (bf16 for realistic full-size checkpoints; the reader handles
    BF16/F16/F32).

    QUANTIZED leaves (ops/quant.py, e.g. a tree from quantize_params — the
    io/quantizer.py tool's path) store under suffixed names in COMPUTE
    orientation (no [out, in] transpose: the packed int4 in-axis and the
    scale layouts are meaningful as stored):

        {hf name}.q8     int8 [..., in, out]        (int8 weights)
        {hf name}.q4     int8 [..., in//2, out]     (packed int4 nibbles)
        {hf name}.scale  f32  [..., 1|G, out]

    load_layer_params reconstructs the exact QuantWeight/Quant4Weight leaves
    (bit-identical round trip, tests/test_quantized_checkpoint.py)."""
    if config.cache_kind in _BY_KIND:
        return _BY_KIND[config.cache_kind][1](params, config, dtype)
    tensors = head_tensor_dict(params, config, dtype)
    tensors.update(
        layer_tensor_dict(
            params["layers"], config, dtype, 0, config.num_hidden_layers
        )
    )
    return tensors


def head_tensor_dict(
    params: Params, config: LlamaConfig, dtype: jnp.dtype = jnp.float32
) -> dict[str, np.ndarray]:
    """HF-named tensors for the non-layer leaves (embed, final norm, and —
    when untied — lm_head, plain or quantized). The head half of
    hf_tensor_dict, shared with the streaming quantizer so the name/transpose
    contract lives in one place."""
    tensors: dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"].astype(dtype)),
        "model.norm.weight": np.asarray(params["ln_f"].astype(dtype)),
    }
    if not config.tie_word_embeddings:
        _emit_tensor(tensors, "lm_head.weight", params["lm_head"], True, dtype)
    return tensors


def _emit_tensor(
    tensors: dict, name: str, leaf, transpose: bool, dtype
) -> None:
    from cake_tpu.ops.quant import Quant4Weight, QuantWeight

    if isinstance(leaf, QuantWeight):
        tensors[name + ".q8"] = np.asarray(leaf.w)
        tensors[name + ".scale"] = np.asarray(leaf.scale, np.float32)
    elif isinstance(leaf, Quant4Weight):
        tensors[name + ".q4"] = np.asarray(leaf.w)
        tensors[name + ".scale"] = np.asarray(leaf.scale, np.float32)
    else:
        a = np.asarray(leaf.astype(dtype))
        tensors[name] = a.T.copy() if transpose else a


def layer_tensor_dict(
    layers: Params,
    config: LlamaConfig,
    dtype: jnp.dtype,
    lo: int,
    hi: int,
) -> dict[str, np.ndarray]:
    """HF-named tensors for a stacked layer tree covering ABSOLUTE layers
    [lo, hi) — names carry lo..hi-1, the stack axis indexes 0..hi-lo-1.

    The per-range half of hf_tensor_dict, split out so the offline quantizer
    can stream one block range at a time instead of materializing the whole
    tree (io/quantizer.py)."""
    from cake_tpu.ops.quant import Quant4Weight, QuantWeight

    tensors: dict[str, np.ndarray] = {}

    def emit(name: str, leaf, transpose: bool) -> None:
        _emit_tensor(tensors, name, leaf, transpose, dtype)

    def leaf_slice(leaf, *idx):
        if isinstance(leaf, (QuantWeight, Quant4Weight)):
            w, s = leaf.w, leaf.scale
            for i in idx:
                w, s = w[i], s[i]
            return type(leaf)(w=w, scale=s)
        a = leaf
        for i in idx:
            a = a[i]
        return a

    moe = "router" in layers
    all_templates = {**_LAYER_TEMPLATES, **_LAYER_BIAS_TEMPLATES}
    if "q_norm" in layers:
        all_templates.update(_QK_NORM_TEMPLATES)
    if "ln_post_attn" in layers:
        all_templates.update(_GEMMA2_NORM_TEMPLATES)
    n_range = hi - lo
    # win_flag is positional metadata synthesized at load, never a tensor.
    if moe:
        # Layout by declared family, not params-key sniffing: a qwen2_moe
        # model with the shared expert disabled has no sh_gate but must still
        # write qwen2_moe tensor names to match its own config.json.
        layout = _MOE_LAYOUTS[
            "qwen2_moe"
            if config.model_type in ("qwen2_moe", "qwen3_moe", "sdar_moe")
            else "mixtral"
        ]
        for key in layout["experts"]:
            del all_templates[key]
        routers = np.asarray(layers["router"].astype(dtype))
        for i in range(routers.shape[0]):
            tensors[layout["router"].format(i=lo + i)] = routers[i].T.copy()
        for key, tmpl in layout["experts"].items():
            leaf = layers[key]
            n_experts = (
                leaf.w.shape[1]
                if isinstance(leaf, (QuantWeight, Quant4Weight))
                else leaf.shape[1]
            )
            for i in range(n_range):
                for e in range(n_experts):
                    emit(tmpl.format(i=lo + i, e=e), leaf_slice(leaf, i, e), True)
        for key, tmpl in layout["shared"].items():
            if key not in layers:
                continue  # shared expert disabled
            leaf = layers[key]
            for i in range(n_range):
                emit(tmpl.format(i=lo + i), leaf_slice(leaf, i), True)
    for key, (tmpl, transpose) in all_templates.items():
        if key not in layers:
            continue
        leaf = layers[key]
        for i in range(n_range):
            emit(tmpl.format(i=lo + i), leaf_slice(leaf, i), transpose)
    return tensors


_NP_TO_ST = {
    np.dtype(np.float32): "F32",
    np.dtype(np.float16): "F16",
    np.dtype(np.int8): "I8",  # quantized weights (plain or nibble-packed)
}


def _st_dtype(arr: np.ndarray) -> str:
    if arr.dtype in _NP_TO_ST:
        return _NP_TO_ST[arr.dtype]
    if "bfloat16" in str(arr.dtype):
        return "BF16"
    raise ValueError(f"unsupported checkpoint dtype {arr.dtype}")


def write_safetensors(path: Path, tensors: dict[str, np.ndarray]) -> int:
    """Write one .safetensors file; returns its payload byte count."""
    import struct

    header: dict[str, dict] = {}
    offset = 0
    blobs: list[bytes] = []
    for name, arr in tensors.items():
        blob = arr.tobytes()
        header[name] = {
            "dtype": _st_dtype(arr),
            "shape": list(arr.shape),
            "data_offsets": [offset, offset + len(blob)],
        }
        offset += len(blob)
        blobs.append(blob)
    header_bytes = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(header_bytes)))
        f.write(header_bytes)
        for blob in blobs:
            f.write(blob)
    return offset


class ShardedCheckpointWriter:
    """Incremental HF-style multi-file checkpoint writer.

    ``add()`` tensors in any order, in as many calls as you like; shards are
    greedily packed to ``max_shard_bytes`` and FLUSHED TO DISK as they fill,
    so peak memory is one shard regardless of checkpoint size — the seam the
    offline quantizer streams 70B-scale checkpoints through (io/quantizer.py).
    Shards are written under temporary names (the final ``i-of-N`` names need
    the total count) and renamed at ``finish()``, which also writes the
    weight_map index and returns the shard paths. On failure mid-stream call
    ``abort()`` (or use the writer as a context manager, which aborts on
    exception) — it deletes the flushed .tmp shards so a died run doesn't
    strand gigabytes of hidden partial output."""

    def __init__(self, model_dir: str | Path, max_shard_bytes: int = 1 << 30):
        self.dir = Path(model_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.max_shard_bytes = max_shard_bytes
        self._cur: dict[str, np.ndarray] = {}
        self._cur_bytes = 0
        self._tmp_paths: list[Path] = []
        self._shard_names: list[list[str]] = []
        self._total = 0
        # Stale tmp shards from a previously-died run would otherwise survive
        # next to a smaller successful retry.
        for stale in self.dir.glob(".model-part-*.tmp"):
            stale.unlink()

    def __enter__(self) -> "ShardedCheckpointWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.abort()

    def abort(self) -> None:
        """Delete all flushed tmp shards and drop the buffered one."""
        for tmp in self._tmp_paths:
            tmp.unlink(missing_ok=True)
        self._tmp_paths = []
        self._shard_names = []
        self._cur = {}
        self._cur_bytes = 0

    def add(self, tensors: dict[str, np.ndarray]) -> None:
        for name, arr in tensors.items():
            nbytes = arr.size * arr.dtype.itemsize
            if self._cur_bytes and self._cur_bytes + nbytes > self.max_shard_bytes:
                self._flush()
            self._cur[name] = arr
            self._cur_bytes += nbytes

    def _flush(self) -> None:
        if not self._cur:
            return
        path = self.dir / f".model-part-{len(self._tmp_paths):05d}.tmp"
        self._total += write_safetensors(path, self._cur)
        self._tmp_paths.append(path)
        self._shard_names.append(list(self._cur))
        self._cur = {}
        self._cur_bytes = 0

    def finish(self) -> list[Path]:
        self._flush()
        n = len(self._tmp_paths)
        weight_map: dict[str, str] = {}
        paths = []
        for i, (tmp, names) in enumerate(
            zip(self._tmp_paths, self._shard_names), start=1
        ):
            fname = f"model-{i:05d}-of-{n:05d}.safetensors"
            tmp.rename(self.dir / fname)
            for name in names:
                weight_map[name] = fname
            paths.append(self.dir / fname)
        with open(self.dir / INDEX_FILE, "w") as f:
            json.dump(
                {
                    "metadata": {"total_size": self._total},
                    "weight_map": weight_map,
                },
                f,
                indent=2,
            )
        return paths


def save_tiny_checkpoint(
    model_dir: str | Path, params: Params, config: LlamaConfig
) -> None:
    """Write a random-init model as a real safetensors checkpoint (test fixture)."""
    model_dir = Path(model_dir)
    model_dir.mkdir(parents=True, exist_ok=True)
    with open(model_dir / "config.json", "w") as f:
        json.dump(config.to_hf_dict(), f, indent=2)

    tensors = hf_tensor_dict(params, config)
    total = write_safetensors(model_dir / SINGLE_FILE, tensors)

    # An index file too, so the weight_map path (splitter, workers) is exercised.
    with open(model_dir / INDEX_FILE, "w") as f:
        json.dump(
            {
                "metadata": {"total_size": total},
                "weight_map": {name: SINGLE_FILE for name in tensors},
            },
            f,
            indent=2,
        )


def save_sharded_checkpoint(
    model_dir: str | Path,
    params: Params,
    config: LlamaConfig,
    *,
    max_shard_bytes: int = 1 << 30,
    dtype: jnp.dtype = jnp.float32,
) -> list[Path]:
    """Write an HF-style MULTI-FILE checkpoint: model-0000i-of-0000N shards
    packed greedily to ``max_shard_bytes``, plus the weight_map index.

    This is the layout real multi-GB checkpoints ship in (file boundaries
    cut across layers, a worker's block range spans several files) — the
    full-size IO smoke (tests/test_checkpoint_smoke.py, the
    checkpoint_smoke CLI) runs resolve -> mmap -> split -> serve against it.
    Returns the shard paths."""
    model_dir = Path(model_dir)
    model_dir.mkdir(parents=True, exist_ok=True)
    with open(model_dir / "config.json", "w") as f:
        json.dump(config.to_hf_dict(), f, indent=2)

    writer = ShardedCheckpointWriter(model_dir, max_shard_bytes)
    writer.add(hf_tensor_dict(params, config, dtype=dtype))
    return writer.finish()
