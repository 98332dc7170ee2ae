"""What a step has to move, from the configuration's shapes alone, and the
table of peaks it is held against. Stdlib only."""

from __future__ import annotations

import json
from pathlib import Path

from bench.checkpoint import layer_shapes

ITEMSIZE = {"bf16": 2, "f32": 4}


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
    """Bytes of weights a chip that holds the whole model must read to decode
    one token for any batch: every layer's seven matrices and two norms, the
    final norm and the output head. The embedding is a lookup of one row a
    lane and the KV cache depends on the contexts: neither is counted, so
    the share of peak bandwidth made from this is a floor on the traffic."""
    h = cfg["hidden_size"]
    per_layer = sum(o * i for o, i in layer_shapes(cfg).values()) + 2 * h
    total = cfg["num_hidden_layers"] * per_layer + h + cfg["vocab_size"] * h
    return total * ITEMSIZE[dtype]
