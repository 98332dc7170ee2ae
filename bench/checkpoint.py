"""A seeded random checkpoint in the format a user's has: ``config.json``,
``tokenizer.json``, sharded safetensors under HF tensor names, an index.

The program loads only from a checkpoint directory, so the weights have to
be on disk. They are drawn on the device, one jitted call a layer, in the
type they are served in, and written once per checkout and configuration
(``READY`` marks a finished directory). The reader is the reference's: it
maps the files and hands out one tensor at a time. Independent of
``cake_tpu/io``; imports JAX only inside ``write_checkpoint``.
"""

from __future__ import annotations

import json
import shutil
import struct
import time
from pathlib import Path

import numpy as np

READY = "READY"
_ST_DTYPE = {"bfloat16": "BF16", "float32": "F32"}


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


def _write_safetensors(path: Path, tensors: dict[str, np.ndarray]) -> None:
    header, offset = {}, 0
    for name, arr in tensors.items():
        header[name] = {
            "dtype": _ST_DTYPE[str(arr.dtype)], "shape": list(arr.shape),
            "data_offsets": [offset, offset + arr.nbytes],
        }
        offset += arr.nbytes
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(blob)) + blob)
        for arr in tensors.values():
            f.write(np.ascontiguousarray(arr).view(np.uint8).data)


def write_checkpoint(model_dir: Path, cfg: dict, dtype: str, seed: int) -> dict:
    """Draw and write the weights; returns {bytes, seconds}. Runs in the
    process that holds the chip (or on the CPU in a rehearsal)."""
    import jax
    import jax.numpy as jnp

    from bench.tokens import FIRST_WORD_ID, write_tokenizer

    t0 = time.perf_counter()
    shutil.rmtree(model_dir, ignore_errors=True)
    model_dir.mkdir(parents=True)
    jdtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    std = cfg.get("initializer_range", 0.02)
    shapes = layer_shapes(cfg)
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]

    def normal(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(jdtype)

    @jax.jit
    def draw_layer(key):
        keys = jax.random.split(key, len(shapes))
        return {n: normal(k, s) for k, (n, s) in zip(keys, shapes.items())}

    draw_table = jax.jit(lambda key: normal(key, (vocab, h)))
    # The head's rows of the special ids are zero: their logits are 0 and
    # never the largest of a random model's, so no answer stops on an
    # end-of-sequence token that the words of one seed happened to draw.
    # Every answer then runs to its ``max_tokens``, whatever the seed.
    draw_head = jax.jit(lambda key: normal(key, (vocab, h)).at[:FIRST_WORD_ID].set(0))
    root = jax.random.key(seed)
    ones = np.ones((h,), np.dtype(jdtype))
    n_layers = cfg["num_hidden_layers"]
    weight_map, total = {}, 0

    def emit(index: int, tensors: dict) -> None:
        nonlocal total
        fname = f"model-{index + 1:05d}-of-{n_layers + 1:05d}.safetensors"
        _write_safetensors(model_dir / fname, tensors)
        weight_map.update(dict.fromkeys(tensors, fname))
        total += sum(a.nbytes for a in tensors.values())

    emit(0, {
        "model.embed_tokens.weight": np.asarray(draw_table(jax.random.fold_in(root, 0))),
        "model.norm.weight": ones,
        "lm_head.weight": np.asarray(draw_head(jax.random.fold_in(root, 1))),
    })
    for i in range(n_layers):
        drawn = jax.device_get(draw_layer(jax.random.fold_in(root, 2 + i)))
        layer = {f"model.layers.{i}.{n}": a for n, a in drawn.items()}
        layer.update({f"model.layers.{i}.{n}": ones for n in NORMS})
        emit(i + 1, layer)
    with open(model_dir / "model.safetensors.index.json", "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    with open(model_dir / "config.json", "w") as f:
        json.dump(cfg, f, indent=1)
    write_tokenizer(model_dir / "tokenizer.json", vocab)
    (model_dir / READY).write_text(f"{seed}\n")
    return {"bytes": total, "seconds": time.perf_counter() - t0}


class Reader:
    """Tensors of a checkpoint directory by HF name, mapped from the files."""

    def __init__(self, model_dir: Path):
        import ml_dtypes

        self._np = {"BF16": ml_dtypes.bfloat16, "F32": np.float32}
        self._dir = Path(model_dir)
        with open(self._dir / "model.safetensors.index.json") as f:
            self._files = json.load(f)["weight_map"]
        self._headers: dict[str, tuple[dict, int]] = {}

    def __call__(self, name: str) -> np.ndarray:
        fname = self._files[name]
        if fname not in self._headers:
            with open(self._dir / fname, "rb") as f:
                (n,) = struct.unpack("<Q", f.read(8))
                self._headers[fname] = (json.loads(f.read(n)), 8 + n)
        header, base = self._headers[fname]
        meta = header[name]
        lo, hi = meta["data_offsets"]
        raw = np.memmap(self._dir / fname, np.uint8, "r", base + lo, (hi - lo,))
        return raw.view(self._np[meta["dtype"]]).reshape(meta["shape"])
