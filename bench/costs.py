"""The table of peaks that a step's bytes and operations are held against.
What a step has to move is counted from the configuration's shapes by its
architecture's cost functions (``bench/architectures``). Stdlib only."""

from __future__ import annotations

import json
from pathlib import Path

from bench.manifest import architecture


def peaks(device_kind: str) -> dict:
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(
            f"no peaks known for device_kind {device_kind!r}: add it to "
            "bench/peaks.json with its source"
        )
    return table[device_kind]


def decode_weight_bytes(cfg: dict, dtype: str) -> int:
    """Bytes of weights a chip must read to decode one token for any batch,
    as the configuration's architecture counts them."""
    root = Path(__file__).resolve().parents[1]
    return architecture(root, cfg).decode_weight_bytes(cfg, dtype)
