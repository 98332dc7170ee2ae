"""Files are enough for an architecture that is not the committed one.

A tiny ``qwen2_moe`` (per-expert tensor names, a router, a shared expert and
its gate, q/k/v biases, ChatML, ``norm_topk_prob`` false, special ids at 480
and up) enters a temporary copy of the benchmark as a later PR would bring
it: its architecture file, a configuration, a mix, a cell and entries in
BENCHMARK.json. No file of the copy changes. ``bench.run --rehearse-cpu``
serves it through ``cake_tpu.cli.main`` and the plain reference agrees at
the float32 tolerance; the same reference with one fault does not.
"""

from __future__ import annotations

import json
import random

import pytest

from bench import reference
from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import Manifest, architecture

from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, REPO, TINY_MOE_MODEL, add_architecture,
                      add_cell, copy_benchmark, file_hashes, last_json, run_bench, tiny_config, tiny_mix,
                      vocabulary)

FAULTS = ["topk_off_by_one", "no_shared_expert"]
TOLERANCE = {"bf16": 0.25, "f32": 0.005}  # as the committed configuration writes them
NEW = 16


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """The copy before and after the addition, with every file's hash."""
    root = copy_benchmark(tmp_path_factory.mktemp("bench_moe"))
    before = file_hashes(root)
    add_architecture(root, "qwen2_moe")
    add_cell(root, "tiny-moe-closed", "tiny-moe", tiny_config(1, ONE_CHIP_FLAGS, TINY_MOE_MODEL),
             "tiny-moe-closed", tiny_mix(CLOSED_LOOP))
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    return root, before


def test_the_addition_changes_no_file_that_was_there(moe_root):
    root, before = moe_root
    after = file_hashes(root)
    entries = before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/architectures/qwen2_moe.py", "bench/configs/tiny-moe.json",
        "bench/traffic/tiny-moe-closed.json", "bench/workloads/tiny-moe-closed.json"]
    assert entries[0] != entries[1]  # entries only: see conftest.add_cell
    Manifest(root).check()
    cell = Manifest(root).cell("tiny-moe-closed")
    # the committed cell's per-layer metrics are the new cell's too, all of them
    committed = Manifest(root).cell("mistral7b-chat-closed")["per_layer"]
    assert cell["per_layer"] == committed and len(committed) == 16


@pytest.mark.parametrize("fault", [None, "no_shared_expert"])
def test_served_through_the_program_and_judged(moe_root, fault, monkeypatch):
    """The program's MoE block, paged cache and batch engine against the plain
    reference. With the shared expert left out of the reference the same
    served tokens are not the reference's: the run says ``correct`` false."""
    root, _ = moe_root
    if fault:
        monkeypatch.setenv("ZBENCH_REFERENCE_FAULT", fault)
    r = run_bench(root, "--workload", "tiny-moe-closed", "--seed", str(2**31 + 23),
                  "--seconds", "3", "--trace", "0", "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    assert out["rehearsal"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["correct"] is (fault is None), r.stdout[-2000:]
    assert f"of tolerance {TOLERANCE['f32']} " in r.stdout
    assert f"finished_length={out['attempted']} " in r.stdout  # no answer stops on <|im_end|>
    assert set(out["metrics"]) == {"gap_p95_ms", "setup_s"}
    checkpoint = root / ".bench_work/models/tiny-moe"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_MOE_MODEL
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in index["weight_map"]
    assert "model.layers.0.self_attn.k_proj.bias" in index["weight_map"]


# --------------------------------------------- the reference against itself


@pytest.fixture(scope="module")
def moe_model(moe_root, tmp_path_factory):
    root, _ = moe_root
    arch = architecture(root, TINY_MOE_MODEL)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_moe_model")
    wrote = write_checkpoint(path, TINY_MOE_MODEL, "f32", 3, arch)
    reader = Reader(path)
    assert wrote["bytes"] == sum(reader(n).nbytes for n in reader._files)
    vocab = vocabulary(TINY_MOE_MODEL, root)
    head = reader("lm_head.weight")
    assert not head[vocab.special_ids].any()  # the template's words are never served
    assert head[:480].all(axis=-1).any() and head[481].any()
    assert reader("model.layers.0.mlp.gate.weight").shape == (8, 128)
    assert reader("model.layers.0.self_attn.q_proj.bias").std() > 0.01  # drawn, not zero
    rng = random.Random(0)
    probes = []
    for n in (12, 60):  # the rehearsal's probe lengths
        context = vocab.chat_ids(vocab.draw(rng, n))
        served = reference.greedy(arch, reader, TINY_MOE_MODEL, context, NEW)
        probes.append({"context": context, "served": served})
    return arch, reader, probes


def test_moe_reference_passes_its_own_stream(moe_model):
    arch, reader, probes = moe_model
    verdict = reference.judge(arch, reader, TINY_MOE_MODEL, TOLERANCE["f32"], probes)
    assert verdict["correct"] is True and verdict["worst"] == 0.0
    assert verdict["positions"] == 2 * NEW


@pytest.mark.parametrize("fault", FAULTS)
def test_moe_reference_with_one_fault_fails_it(moe_model, fault):
    arch, reader, probes = moe_model
    arch.FAULT = fault
    try:
        verdict = reference.judge(arch, reader, TINY_MOE_MODEL, TOLERANCE["f32"], probes)
    finally:
        arch.FAULT = None
    # far outside the tolerance of the type a cell would be served in, too
    assert verdict["correct"] is False and verdict["worst"] > 2 * TOLERANCE["bf16"], verdict


def test_moe_weight_bytes_count_the_experts_a_token_meets(moe_root):
    root, _ = moe_root
    arch = architecture(root, TINY_MOE_MODEL)
    attn = 128 * (128 + 64 + 64) + (128 + 64 + 64) + 128 * 128
    sparse = 8 * 128 + 2 * 3 * 128 * 64 + 3 * 128 * 96 + 128
    want = 2 * (attn + sparse + 2 * 128) + 128 + 512 * 128
    assert arch.decode_weight_bytes(TINY_MOE_MODEL, "bf16") == 2 * want
    # a dense count from ``intermediate_size`` would be another number
    assert want != 2 * (attn + 3 * 128 * 256 + 2 * 128) + 128 + 512 * 128
