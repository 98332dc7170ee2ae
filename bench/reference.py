"""The comparison that decides ``correct``, over the plain reference.

The reference's forward pass is the architecture's
(``forward_logits`` in ``bench/architectures/<model_type>.py``): the decoder
as published, straight ``jax.numpy`` in float32 with matmul precision
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

import numpy as np


def judge(arch, reader, cfg: dict, tol: float, probes: list[dict]) -> dict:
    """``probes``: [{"context": ids the server saw, "served": ids it sent}];
    ``arch`` is the configuration's architecture module.
    Returns {"correct", "worst", "tolerance", "positions", "per_probe"}."""
    timing: dict = {}
    logits = arch.forward_logits(
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


def greedy(arch, reader, cfg: dict, context: list[int], n_new: int) -> list[int]:
    """Greedy continuation by repeated full forward passes; for tests."""
    ids = list(context)
    for _ in range(n_new):
        ids.append(int(arch.forward_logits(reader, cfg, [ids])[0][-1].argmax()))
    return ids[len(context):]
