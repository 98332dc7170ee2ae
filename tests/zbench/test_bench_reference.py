"""The plain reference and its tolerance, at a tiny size on the CPU: it
passes its own greedy stream and fails a model with a layer missing and one
that ignores the sliding window. It does not see weights rounded to eight
bits a channel: served tokens alone cannot tell that from bf16's rounding."""

from __future__ import annotations

import random

import numpy as np
import pytest

import hashlib

from bench import reference
from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import architecture

from conftest import REPO, TINY_MODEL, vocabulary

ARCH = architecture(REPO, TINY_MODEL)

MODEL = {**TINY_MODEL, "sliding_window": 16}
CONTEXT = 120  # most keys lie outside the window of 16
NEW = 4


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_model")
    wrote = write_checkpoint(path, TINY_MODEL, "f32", 3, ARCH)
    reader = Reader(path)
    shapes = ARCH.layer_shapes(TINY_MODEL)
    assert reader("model.layers.3.mlp.down_proj.weight").shape == shapes["mlp.down_proj.weight"]
    assert wrote["bytes"] == sum(
        reader(n).nbytes for n in reader._files  # every tensor of the index
    )
    rng = random.Random(0)
    context = vocabulary(TINY_MODEL).chat_ids([rng.randrange(5, 512) for _ in range(CONTEXT)])
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
    served = reference.greedy(ARCH, serving, {**MODEL, **change}, context, NEW)
    probes = [{"context": context, "served": served}]
    verdict = reference.judge(ARCH, reader, MODEL, TOLERANCE["f32"], probes)
    assert verdict["positions"] == NEW and verdict["tolerance"] == TOLERANCE["f32"]
    if rounding:
        assert reference.judge(ARCH, reader, MODEL, TOLERANCE["bf16"], probes)["correct"] is True
        assert verdict["worst"] < 0.05
    elif want:
        assert verdict["correct"] is True and verdict["worst"] == 0.0
    else:
        # far outside the tolerance of the type the cells are served in, too
        assert verdict["correct"] is False
        assert verdict["worst"] > 2 * TOLERANCE["bf16"], verdict


def test_checkpoint_is_seeded(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    write_checkpoint(a, TINY_MODEL, "f32", 3, ARCH)
    write_checkpoint(b, TINY_MODEL, "f32", 4, ARCH)
    name = "model.layers.0.self_attn.q_proj.weight"
    assert (Reader(a)(name) != Reader(b)(name)).any()
    assert abs(float(Reader(a)(name).std()) - 0.02) < 0.002
    assert (a / "READY").exists() and (a / "tokenizer.json").exists()
    head = Reader(a)("lm_head.weight")
    assert not head[:5].any() and head[5:].all(axis=-1).any()  # no special id is ever served


# Every file of the tiny checkpoint (TINY_MODEL, f32, seed 3) as the code before
# the architecture seam wrote it (commit 5e06de7): the same tensors from the
# same keys in the same files, byte for byte, and the same tokenizer.
GOLDEN = {
    "READY": "1121cfccd5913f0a63fec40a6ffd44ea64f9dc135c66634ba001d10bcf4302a2",
    "config.json": "226d957ddb3c05ebeefb51e69eae624058d3e31f2d39073e47445b70caf8c374",
    "model-00001-of-00005.safetensors":
        "8da1ec1f541e999937b641c9d2b01185c974bde61b88557ffad09bd87776ee32",
    "model-00002-of-00005.safetensors":
        "7177a359469ea87cfea82fa1e049a7c2f329cd7f7ae264e8af0fc336b161257a",
    "model-00003-of-00005.safetensors":
        "9828ec37e195e59660a043d3be769e8d4ec0a99ad664923103bdeb427efa0c5b",
    "model-00004-of-00005.safetensors":
        "d60d8583453a322fe83da6b59d849284fb8d44fc59e9c941653f4241676c7092",
    "model-00005-of-00005.safetensors":
        "c882ee18c273a295d2a19e24975419730b7ad5cf68534abdb2e6c059aabc61d1",
    "model.safetensors.index.json":
        "db25bf6c8d676a9d1111742d52957ede72ac650bb6500789680b771cf3e4e841",
    "tokenizer.json": "f701d167b117b0efe7b7e5d07d5ab5a858137e48f3809231951f5afe44d3229f",
}


@pytest.mark.parametrize("name", GOLDEN)
def test_checkpoint_file_is_byte_for_byte_what_it_was_before_the_seam(model, name):
    reader, _ = model
    assert sorted(p.name for p in reader._dir.iterdir()) == sorted(GOLDEN)
    assert hashlib.sha256((reader._dir / name).read_bytes()).hexdigest() == GOLDEN[name]


def test_writer_refuses_a_draw_it_does_not_know(tmp_path):
    class Odd:
        UNKNOWN_WORD = ARCH.UNKNOWN_WORD
        special_words, chat_text, chat_ids = ARCH.special_words, ARCH.chat_text, ARCH.chat_ids
        layer_tensors = ARCH.layer_tensors

        @staticmethod
        def top_tensors(cfg):
            return {**ARCH.top_tensors(cfg), "model.extra.weight": ((4,), "uniform")}

    with pytest.raises(ValueError, match="unknown draw 'uniform'"):
        write_checkpoint(tmp_path / "m", TINY_MODEL, "f32", 3, Odd)
