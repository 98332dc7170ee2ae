"""A seeded random checkpoint in the format a user's has: ``config.json``,
``tokenizer.json``, sharded safetensors under HF tensor names, an index.

The program loads only from a checkpoint directory, so the weights have to
be on disk. They are drawn on the device, one jitted call a layer, in the
type they are served in, and written once per checkout and configuration
(``READY`` marks a finished directory). Which tensors there are is the
architecture's to say (``bench/architectures``): this file keeps the format,
the split into files, the seeded draw and the reader. The reader is the reference's: it
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


# How an architecture's tensor table says a tensor is drawn: normal at the
# configuration's ``initializer_range``; constant; or ``head``, normal with
# the rows of the special ids zero: their logits are 0 and never the largest
# of a random model's, so no answer stops on an end-of-sequence token that
# the words of one seed happened to draw. Every answer then runs to its
# ``max_tokens``, whatever the seed.
DRAWS = ("normal", "ones", "zeros", "head")


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


def write_checkpoint(model_dir: Path, cfg: dict, dtype: str, seed: int, arch) -> dict:
    """Draw and write the tensors of ``arch``'s table (see
    ``bench/architectures``); returns {bytes, seconds}. Runs in the process
    that holds the chip (or on the CPU in a rehearsal). The top's drawn
    tensors take the seed's keys 0, 1, ... in the table's order, one call
    each, and its file keeps that order. Layer ``i`` splits the next key but
    ``i`` among its drawn tensors in the table's order, in one call; its file
    holds them by name (as JAX hands back a dict), then its constants."""
    import functools

    import jax
    import jax.numpy as jnp

    from bench.tokens import Vocabulary

    t0 = time.perf_counter()
    shutil.rmtree(model_dir, ignore_errors=True)
    model_dir.mkdir(parents=True)
    jdtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    std = cfg.get("initializer_range", 0.02)
    vocab = Vocabulary(arch, cfg)
    special_ids = np.asarray(vocab.special_ids)

    def drawn(key, shape, draw):
        x = (jax.random.normal(key, shape, jnp.float32) * std).astype(jdtype)
        return x.at[special_ids].set(0) if draw == "head" else x

    @functools.cache
    def draw_together(spec):  # one jitted call for all the drawn tensors of a layer
        return jax.jit(lambda key: [
            drawn(k, shape, draw) for k, (shape, draw) in zip(jax.random.split(key, len(spec)), spec)
        ])

    def constants(table: dict) -> dict:
        for name, (_, draw) in table.items():
            if draw not in DRAWS:
                raise ValueError(f"tensor {name!r}: unknown draw {draw!r} (has: {DRAWS})")
        return {name: (np.ones if draw == "ones" else np.zeros)(shape, np.dtype(jdtype))
                for name, (shape, draw) in table.items() if draw in ("ones", "zeros")}

    root = jax.random.key(seed)
    n_layers = cfg["num_hidden_layers"]
    weight_map, total = {}, 0

    def emit(index: int, tensors: dict) -> None:
        nonlocal total
        fname = f"model-{index + 1:05d}-of-{n_layers + 1:05d}.safetensors"
        _write_safetensors(model_dir / fname, tensors)
        weight_map.update(dict.fromkeys(tensors, fname))
        total += sum(a.nbytes for a in tensors.values())

    top = arch.top_tensors(cfg)
    tensors = constants(top)
    top_drawn = [n for n in top if n not in tensors]
    for k, name in enumerate(top_drawn):
        one = jax.jit(functools.partial(drawn, shape=top[name][0], draw=top[name][1]))
        tensors[name] = np.asarray(one(jax.random.fold_in(root, k)))
    emit(0, {name: tensors[name] for name in top})
    for i in range(n_layers):
        table = arch.layer_tensors(cfg, i)
        fixed = constants(table)
        names = [n for n in table if n not in fixed]
        arrays = draw_together(tuple(table[n] for n in names))(
            jax.random.fold_in(root, len(top_drawn) + i))
        by_name = sorted(zip(names, jax.device_get(arrays)), key=lambda kv: kv[0])
        emit(i + 1, {**dict(by_name), **fixed})
    with open(model_dir / "model.safetensors.index.json", "w") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f)
    with open(model_dir / "config.json", "w") as f:
        json.dump(cfg, f, indent=1)
    vocab.write_tokenizer(model_dir / "tokenizer.json")
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
