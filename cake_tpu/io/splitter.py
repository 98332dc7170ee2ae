"""Model splitter: carve a checkpoint into per-worker bundles.

Covers the reference's ``cake-split-model`` tool (cake-split-model/src/main.rs):
for each topology worker, filter the safetensors weight_map by layer ownership
(main.rs:80-106), copy only the owned tensors into a reduced checkpoint
(main.rs:108-142), and emit ``{worker}-node/model/`` with a rewritten index, the
reduced safetensors, a single-entry topology.yml, and the model config
(main.rs:161-224), then validate the bundle round-trips (main.rs:202-208).

Design notes vs the reference:
  * Output is written as ONE ``reduced.safetensors`` per worker with a fresh
    contiguous layout (the reference also rewrites data, main.rs:120-137).
  * ``config.json`` and (if present) ``tokenizer.json`` are copied into each
    bundle so a worker dir is self-sufficient.
  * Pure-Python safetensors writer (io.safetensors_io) — no framework dep.
"""

from __future__ import annotations

import json
import logging
import shutil
import struct
from pathlib import Path

import numpy as np

from cake_tpu.io.safetensors_io import (
    INDEX_FILE,
    SafetensorsReader,
    open_checkpoint,
)
from cake_tpu.parallel.topology import Topology

log = logging.getLogger("cake_tpu.splitter")

REDUCED_FILE = "reduced.safetensors"


def _write_safetensors(path: Path, tensors: dict[str, tuple[np.ndarray, str]]) -> int:
    """Write {name: (raw_array, safetensors_dtype)} preserving raw dtypes."""
    header: dict[str, dict] = {}
    offset = 0
    for name, (arr, st_dtype) in tensors.items():
        nbytes = arr.nbytes
        header[name] = {
            "dtype": st_dtype,
            "shape": list(arr.shape),
            "data_offsets": [offset, offset + nbytes],
        }
        offset += nbytes
    header_bytes = json.dumps(header).encode()
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(header_bytes)))
        f.write(header_bytes)
        for arr, _ in tensors.values():
            f.write(arr.tobytes())
    return offset


def split_model(
    model_dir: str | Path,
    topology_path: str | Path,
    output_dir: str | Path,
) -> list[Path]:
    """Produce ``{worker}-node/model`` bundles; returns the bundle paths."""
    model_dir = Path(model_dir)
    output_dir = Path(output_dir)
    topology = Topology.from_path(topology_path)
    if (model_dir / "config.json").exists():
        from cake_tpu.models.llama.config import LlamaConfig
        from cake_tpu.models.llama.capability import refuse_unsupported

        refuse_unsupported(
            LlamaConfig.from_model_dir(model_dir), split_model=True
        )
    reader = open_checkpoint(model_dir)

    bundles: list[Path] = []
    for name, node in topology.nodes.items():
        owned = sorted(t for t in reader.names() if node.is_layer_owner(t))
        if not owned:
            log.warning("worker %s owns no tensors, skipping", name)
            continue
        bundle = output_dir / f"{name}-node"
        bundle_model = bundle / "model"
        bundle_model.mkdir(parents=True, exist_ok=True)

        tensors: dict[str, tuple[np.ndarray, str]] = {
            t: (reader.numpy(t), reader.st_dtype(t)) for t in owned
        }
        total = _write_safetensors(bundle_model / REDUCED_FILE, tensors)

        with open(bundle_model / INDEX_FILE, "w") as f:
            json.dump(
                {
                    "metadata": {"total_size": total},
                    "weight_map": {t: REDUCED_FILE for t in tensors},
                },
                f,
                indent=2,
            )
        # Self-sufficient bundle: config + tokenizer + single-node topology
        # (split-model main.rs:176-223 writes the reduced topology the same way).
        shutil.copy(model_dir / "config.json", bundle_model / "config.json")
        tok = model_dir / "tokenizer.json"
        if tok.exists():
            shutil.copy(tok, bundle_model / "tokenizer.json")
        Topology({name: node}).save(bundle / "topology.yml")

        _validate_bundle(bundle_model, list(tensors))
        log.info(
            "wrote %s: %d tensors, %.1f MiB", bundle, len(tensors), total / 2**20
        )
        bundles.append(bundle)
    return bundles


def _validate_bundle(bundle_model: Path, expected: list[str]) -> None:
    """Round-trip validation (split-model main.rs:202-208)."""
    r = SafetensorsReader([bundle_model / REDUCED_FILE])
    names = set(r.names())
    missing = set(expected) - names
    if missing:
        raise RuntimeError(f"bundle {bundle_model} missing tensors: {missing}")
    for t in expected:
        r.numpy(t)  # decodable


def main(argv: list[str] | None = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="cake-tpu-split-model",
        description="split a checkpoint into per-worker bundles by topology",
    )
    p.add_argument("--model", required=True, help="source checkpoint directory")
    p.add_argument("--topology", required=True, help="topology YAML")
    p.add_argument("--output", required=True, help="output directory")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    bundles = split_model(args.model, args.topology, args.output)
    print(f"wrote {len(bundles)} worker bundles under {args.output}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
