"""``model_type: pangu_ultra_moe`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``openpangu-ultra-moe-718b-ep16``, the mix ``chat-closed-128``, the cell
``pangu-ultra-ep16-chat-closed`` and three metrics of its own. Here a tiny
look-alike of the configuration (same keys: latent attention, a leading dense
layer, a share of 2 of 16 ranked experts beside a shared one, a sliced
vocabulary) enters a temporary copy of the benchmark as a configuration, a
mix and a cell, is served by ``bench.run --rehearse-cpu`` through
``cake_tpu.cli.main`` (latent page pool, continuous scheduler, look-ahead)
and judged by the plain reference; the same reference with one fault says
``correct`` false of the same program. Nothing here pins how many cells the
benchmark has or what another cell reports.
"""

from __future__ import annotations

import importlib.util
import json
import random

import pytest

from bench import reference
from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import Manifest, architecture, model_config

from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, REPO, add_cell, copy_benchmark, file_hashes,
                      last_json, run_bench, tiny_config, tiny_mix, vocabulary)

CELL = "pangu-ultra-ep16-chat-closed"
CATALOG_CONFIG = {  # the catalog row's ``config``, key for key
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600,
}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
           "vocab_size": 19200}
# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY_PANGU = {
    **CATALOG_CONFIG, "architectures": ["PanguUltraMoEForCausalLM"], "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 8, "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "n_routed_experts": 2, "n_routed_experts_total": 16,
    "first_routed_expert": 6, "num_experts_per_tok": 4, "vocab_size": 512,
    "max_position_embeddings": 512, "bos_token_id": 1, "eos_token_id": 2,
    "initializer_range": 0.1,
}
FLAGS = [("off" if prev == "--prefix-cache" else f)
         for prev, f in zip([None, *ONE_CHIP_FLAGS], ONE_CHIP_FLAGS)]
NEW = 16
# As the tiny Jamba's: prompts inside one window width and three times the
# shared tiny mix's time, for a server that is one thread beside the other
# workers' tests (and two layers, one of each kind: under six workers' load a
# third made a start-up that outlasted the harness's wait for an idle engine).
MIX = {**tiny_mix(CLOSED_LOOP), "lead_in_s": 10.0,
       "prompt_tokens": {"dist": "lognormal", "mu": 3.0, "sigma": 0.5, "min": 8, "max": 50}}
SECONDS = "10"


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell = Manifest(REPO).cell(CELL)
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in CATALOG_CONFIG} == {**CATALOG_CONFIG, **REDUCED}
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    assert cfg["source"].endswith("openPangu-Ultra-MoE-718B/blob/main/config.json")
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: CATALOG_CONFIG[k] for k in REDUCED}
    assert (deployment["chips"], deployment["chips_sharing_a_layer"], deployment["rank"]) == (1, 16, 0)
    assert (model["n_routed_experts_total"], model["first_routed_expert"]) == (256, 0)
    assert model["n_routed_experts"] * deployment["chips_sharing_a_layer"] == 256
    assert model["vocab_size"] * 8 == 153600
    assert any("multi-token-prediction" in a and "NOT served" in a for a in cfg["assumed"])
    assert cell["entry"]["chips"] == 1 and cell["mix"]["clients"] == 128
    # between the largest sound reading and the smallest of the nearest control it refuses (int4; judge.why)
    assert 0.559 < cfg["judge"]["tolerance"] < 1.494
    assert "float8" in cfg["judge"]["why"] and "DOES NOT REFUSE" in cfg["judge"]["why"]
    sixty_four = json.loads((REPO / "bench/traffic/chat-closed-64.json").read_text())
    assert {**cell["mix"], "clients": 64, "pool": 64} == sixty_four  # the committed mix, 128 callers
    flags = cfg["server_flags"]
    assert flags[flags.index("--api-batch") + 1] == "64"
    assert flags[flags.index("--prefix-cache") + 1] == "off"
    for m in Manifest(REPO).bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "gap_p95_ms"
    assert {"decode_expert_stream_pct", "latent_decode_attention_roofline_pct",
            "moe_held_assignments_per_step"} <= {m["name"] for m in cell["per_layer"]}


def test_the_parameter_count_is_the_issues():
    """ISSUE 32's count, tensor by tensor from the architecture's table."""
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    import numpy as np
    attention = sum(int(np.prod(s)) for s in arch.attention_shapes(model).values())
    assert attention + 1536 + 512 == 196_577_280  # with MLA's two inner norms
    assert arch.expert_parameters(model) == 47_185_920
    assert arch.layer_parameters(model, 0) == 621_281_280  # the dense layer
    assert arch.layer_parameters(model, 1) == 1_000_734_720  # a sparse layer, 16 experts
    router, norms = 256 * 7680, 4 * 7680
    assert 196_577_280 + norms + router + 17 * 47_185_920 == 1_000_734_720
    total = 621_281_280 + 4 * 1_000_734_720 + 2 * 19200 * 7680 + 7680
    assert arch.parameters(model) == total == 4_919_139_840
    assert total == Manifest(REPO).cell(CELL)["config"]["deployment"]["parameters"]
    # a decode step's weights whatever the routing: no routed expert, no embedding
    fixed = total - 4 * 16 * 47_185_920 - 19200 * 7680
    assert arch.decode_weight_bytes(model, "bf16") == 2 * fixed == 3_503_569_920
    assert arch.expert_bytes(model, "bf16") == 94_371_840 and arch.sparse_layers(model) == 4
    assert arch.latent_bytes_per_token(model, "bf16") == 1152
    ops, moved = arch.latent_decode_attention_cost(model, 0, 1.0, "bf16")
    assert (ops, moved) == (2 * 128 * (576 + 512), 1152)  # 242 operations a byte: the ridge


def test_the_program_counts_the_cache_as_the_architecture_file_does():
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.latent import cache_bytes_per_token, run_shapes

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.cache_kind == "latent" and config.latent_width == 640
    per = cache_bytes_per_token(config, "bfloat16")
    assert per == {"needed": 5 * arch.latent_bytes_per_token(model, "bf16"), "stored": 5 * 1280}
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (16, 256, 0)
    assert config.ff_runs == (("dense", 0, 1), ("sparse", 1, 5))
    import numpy as np
    for kind, lo, _ in config.ff_runs:  # the program's trees hold what the table draws
        held = sum(int(np.prod(s)) for s in run_shapes(config, kind).values())
        assert held == arch.layer_parameters(model, lo)


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(dispatches, held, touched, seconds=0.0, live=0.0, count=0, cached=0):
    return {"engine": {
        "moe": {"dispatches": dispatches, "routed": 0, "held": held, "touched": touched,
                "max_load": 0},
        "period": {"seconds": seconds, "count": count, "cached_tokens": cached,
                   "lane_seconds": {"live": live}}}}


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    return {
        "config": {**model, "server_flags": ["--decode-chunk", "8"], "served_dtype": "bf16"},
        "architecture": architecture(REPO, model), "device": {"device_kind": "TPU v5 lite"},
        "stats_before": _stats(1000, 30_000, 13_000, 10.0, 600.0, 100, 2_000_000),
        "stats_after": _stats(5000, 158_000, 69_000, 60.0, 3800.0, 500, 13_520_000),
        "trace": {"programs": {"decode_expert_stream_pct": [0.096, 0.096]},
                  "ops": {"latent_decode_attention_roofline_pct": {"seconds": 0.02, "count": 200}}},
    }


def test_readers_on_recorded_facts(facts):
    held = _reader("moe_held_assignments_per_step")({**facts, "metric": "x"}, {})
    assert held == pytest.approx(32.0)  # 128,000 over 4,000 dispatches: all 64 lanes live
    stream = _reader("decode_expert_stream_pct")
    # 14 touched a dispatch x 4 layers x 94,371,840 B over 819 GB/s = 6.453 ms of a 12 ms step
    assert stream({**facts, "metric": "decode_expert_stream_pct"}, {}) == pytest.approx(53.77, rel=1e-3)
    roof = _reader("latent_decode_attention_roofline_pct")
    # 64 lanes, 28,800 cached tokens a call: 8.02 GFLOP -> 40.7 us; 33.2 + 17.8 MB -> 62.3 us:
    # the bytes bound it (queries in, sums out beside the latents), of a 100 us call
    got = roof({**facts, "metric": "latent_decode_attention_roofline_pct"}, {})
    assert got == pytest.approx(62.3, rel=5e-3) and got < 100


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit's ``/stats`` has no ``engine.moe`` and no
    ``cached_tokens``, an architecture of the benchmark's other cells no
    routed experts: every new reader returns None and does not raise."""
    for side in ("stats_before", "stats_after"):
        del facts[side]["engine"]["moe"]
        del facts[side]["engine"]["period"]["cached_tokens"]
    for name in ("moe_held_assignments_per_step", "decode_expert_stream_pct",
                 "latent_decode_attention_roofline_pct"):
        assert _reader(name)({**facts, "metric": name}, {}) is None
        assert _reader(name)({**facts, "metric": name, "trace": None}, {}) is None
    mistral = Manifest(REPO).cell("mistral7b-chat-closed")
    other = {**facts, "architecture": mistral["architecture"], "config": mistral["config"]}
    for name in ("decode_expert_stream_pct", "latent_decode_attention_roofline_pct"):
        assert _reader(name)({**other, "metric": name}, {}) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def pangu_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_pangu"))
    before = file_hashes(root)
    add_cell(root, "tiny-pangu-closed", "tiny-pangu", tiny_config(1, FLAGS, TINY_PANGU),
             "tiny-pangu-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    return root, before


def test_a_pangu_cell_is_files_and_entries_only(pangu_root):
    root, before = pangu_root
    after = file_hashes(root)
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-pangu.json", "bench/traffic/tiny-pangu-closed.json",
        "bench/workloads/tiny-pangu-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "softmax_scores", "no_shared_expert", "k_rope_unrotated"])
def test_served_through_the_program_and_judged(pangu_root, fault):
    """The program's latent attention over the latent pool, joins, dead
    lanes and its share of the experts against the plain reference given the
    same share; a reference with one fault says ``correct`` false of it. The
    fault is planted in the COPY's architecture file for the one run."""
    root, _ = pangu_root
    arch_file = root / "bench/architectures/pangu_ultra_moe.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-pangu-closed", "--seed", str(2**31 + 32),
                      "--seconds", SECONDS, "--trace", "0", "--rehearse-cpu")
    finally:
        arch_file.write_text(sound)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    assert out["rehearsal"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["correct"] is (fault is None), r.stdout[-2000:]
    assert f"finished_length={out['attempted']} " in r.stdout  # no answer stops early
    assert set(out["metrics"]) == {"gap_p95_ms", "setup_s"}
    checkpoint = root / ".bench_work/models/tiny-pangu"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_PANGU
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in index
    assert "model.layers.1.mlp.experts.8.down_proj.weight" not in index  # not held
    assert "model.layers.0.mlp.down_proj.weight" in index and "lm_head.weight" in index
    assert not any("nextn" in n or "mtp" in n for n in index)  # the MTP module is not drawn


# --------------------------------------------- the reference against itself


@pytest.fixture(scope="module")
def pangu_model(tmp_path_factory):
    arch = architecture(REPO, TINY_PANGU)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_pangu_model")
    write_checkpoint(path, TINY_PANGU, "f32", 3, arch)
    reader = Reader(path)
    vocab = vocabulary(TINY_PANGU)
    assert vocab.special_ids == list(range(7))
    assert not reader("lm_head.weight")[vocab.special_ids].any()
    assert reader("model.layers.1.mlp.gate.weight").shape == (16, 128)  # every ranked expert
    assert reader("model.layers.0.self_attn.kv_a_proj_with_mqa.weight").shape == (40, 128)
    assert (reader("model.layers.1.pre_mlp_layernorm.weight") == 1).all()
    rng = random.Random(0)
    probes = []
    for n in (12, 60):
        context = vocab.chat_ids(vocab.draw(rng, n))
        served = reference.greedy(arch, reader, TINY_PANGU, context, NEW)
        probes.append({"context": context, "served": served})
    return arch, reader, probes


def test_pangu_reference_passes_its_own_stream(pangu_model):
    arch, reader, probes = pangu_model
    verdict = reference.judge(arch, reader, TINY_PANGU, 0.005, probes)
    assert verdict["correct"] is True and verdict["worst"] == 0.0


@pytest.mark.parametrize("fault", ["softmax_scores", "no_shared_expert", "k_rope_unrotated"])
def test_pangu_reference_with_one_fault_fails_it(pangu_model, fault):
    arch, reader, probes = pangu_model
    assert fault in arch.FAULTS
    arch.FAULT = fault
    try:
        verdict = reference.judge(arch, reader, TINY_PANGU, 0.005, probes)
    finally:
        arch.FAULT = None
    assert verdict["correct"] is False and verdict["worst"] > 0.05, verdict
