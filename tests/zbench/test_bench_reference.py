"""The plain reference and its tolerance, at a tiny size on the CPU: it
passes its own greedy stream and fails a model with a layer missing and one
that ignores the sliding window. It does not see weights rounded to eight
bits a channel: served tokens alone cannot tell that from bf16's rounding."""

from __future__ import annotations

import random

import numpy as np
import pytest

from bench import reference
from bench.checkpoint import Reader, layer_shapes, write_checkpoint
from bench.tokens import chat_ids

from conftest import TINY_MODEL

MODEL = {**TINY_MODEL, "sliding_window": 16}
CONTEXT = 120  # most keys lie outside the window of 16
NEW = 4


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_model")
    wrote = write_checkpoint(path, TINY_MODEL, "f32", seed=3)
    reader = Reader(path)
    shapes = layer_shapes(TINY_MODEL)
    assert reader("model.layers.3.mlp.down_proj.weight").shape == shapes["mlp.down_proj.weight"]
    assert wrote["bytes"] == sum(
        reader(n).nbytes for n in reader._files  # every tensor of the index
    )
    rng = random.Random(0)
    context = chat_ids([rng.randrange(5, 512) for _ in range(CONTEXT)])
    return reader, context


TOLERANCE = {"bf16": 0.25, "f32": 0.005}  # as the committed configuration writes them


def int8_a_channel(w: np.ndarray) -> np.ndarray:
    if w.ndim < 2:
        return w
    scale = np.maximum(np.abs(w).max(-1, keepdims=True), 1e-12) / 127.0
    return (np.round(w / scale) * scale).astype(w.dtype)


FAULTS = {
    "as_published": ({}, None, True),
    "last_layer_skipped": ({"num_hidden_layers": MODEL["num_hidden_layers"] - 1}, None, False),
    "window_ignored": ({"sliding_window": None}, None, False),
    # the judge's blind spot, kept in sight: PERF.md section 7
    "int8_weights_pass_unseen": ({}, int8_a_channel, True),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_judge_passes_the_model_and_fails_a_faulty_one(model, fault):
    reader, context = model
    change, rounding, want = FAULTS[fault]
    serving = (lambda name: rounding(reader(name))) if rounding else reader
    served = reference.greedy(serving, {**MODEL, **change}, context, NEW)
    probes = [{"context": context, "served": served}]
    verdict = reference.judge(reader, MODEL, TOLERANCE["f32"], probes)
    assert verdict["positions"] == NEW and verdict["tolerance"] == TOLERANCE["f32"]
    if rounding:
        assert reference.judge(reader, MODEL, TOLERANCE["bf16"], probes)["correct"] is True
        assert verdict["worst"] < 0.05
    elif want:
        assert verdict["correct"] is True and verdict["worst"] == 0.0
    else:
        # far outside the tolerance of the type the cells are served in, too
        assert verdict["correct"] is False
        assert verdict["worst"] > 2 * TOLERANCE["bf16"], verdict


def test_checkpoint_is_seeded(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    write_checkpoint(a, TINY_MODEL, "f32", seed=3)
    write_checkpoint(b, TINY_MODEL, "f32", seed=4)
    name = "model.layers.0.self_attn.q_proj.weight"
    assert (Reader(a)(name) != Reader(b)(name)).any()
    assert abs(float(Reader(a)(name).std()) - 0.02) < 0.002
    assert (a / "READY").exists() and (a / "tokenizer.json").exists()
    head = Reader(a)("lm_head.weight")
    assert not head[:5].any() and head[5:].all(axis=-1).any()  # no special id is ever served
