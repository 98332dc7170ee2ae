"""``model_type: laguna`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``laguna-s-2.1-ep8-d9``, the mix ``code-closed-64``, the cell
``laguna-s-ep8-code-closed`` and three metrics of its own. Here a tiny
look-alike of the configuration (same keys: window and full attention layers
of different head counts, two ropes, the gate a head, a leading dense layer,
a share of 4 of 16 ranked experts beside a shared one, a sliced vocabulary)
enters a temporary copy of the benchmark as a configuration, a mix and a
cell, is served by ``bench.run --rehearse-cpu`` through ``cake_tpu.cli.main``
(a pool a kind, pages freed behind the window, continuous scheduler,
look-ahead) and judged by the plain reference; the same reference with one
fault says ``correct`` false of the same program. Nothing here pins how many
cells the benchmark has or what another cell reports.
"""

from __future__ import annotations

import importlib.util
import json
import random

import pytest

from bench import reference
from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import Manifest, architecture, model_config

from conftest import (CLOSED_LOOP, REPO, add_cell, copy_benchmark, file_hashes, last_json,
                      run_bench, tiny_config, tiny_mix, vocabulary)

CELL = "laguna-s-ep8-code-closed"
# ``mixed_decode_attention_roofline_pct`` (ISSUE 41's fourth) is NOT here: on the chip it read 102,
# 109 and 155% (PERF.md section 6, PR 41), a share of a roofline may not pass 100, and the cause
# was not found in the session; its cost function stays in the architecture file, tested below.
NEW_METRICS = ("kv_bytes_per_cached_token", "window_pages_freed_per_s", "laguna_expert_stream_pct")


def catalog_config() -> dict:
    """The catalog row's ``config``, key for key (the guide's own file)."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Laguna-S-2.1")["config"]


REDUCED = {"num_hidden_layers": 9, "num_experts": 32, "vocab_size": 12544}
LISTS = ("layer_types", "mlp_layer_types", "gating_types", "num_attention_heads_per_layer")
# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY_LAGUNA = {
    "architectures": ["LagunaForCausalLM"], "model_type": "laguna", "vocab_size": 512,
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 3,
    "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False, "rms_norm_eps": 1e-06,
    "num_experts": 4, "num_experts_total": 16, "first_expert": 8, "num_experts_per_tok": 6,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 32,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                           "original_max_position_embeddings": 32, "beta_slow": 1,
                           "beta_fast": 4, "attention_factor": 1.2079,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "gating_types": ["per_head"] * 3, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [12, 18, 12], "moe_router_logit_softcapping": 0,
    "bos_token_id": 1, "eos_token_id": 2, "initializer_range": 0.1,
}
# Pages of 16 slots under a window of 32: a lane of 100 tokens has freed
# pages behind it, which a page of 128 would not show at this size.
FLAGS = ["--api-batch", "4", "--max-seq-len", "512", "--kv-mode", "paged", "--page-size", "16",
         "--scheduler", "continuous", "--prefix-cache", "off", "--attention-impl", "pallas",
         "--temperature", "0", "--repeat-penalty", "1.0", "--decode-chunk", "8"]
NEW = 16
MIX = {**tiny_mix(CLOSED_LOOP), "lead_in_s": 10.0,
       "prompt_tokens": {"dist": "lognormal", "mu": 3.6, "sigma": 0.5, "min": 8, "max": 100}}
SECONDS = "10"


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell, catalog = Manifest(REPO).cell(CELL), catalog_config()
    cfg, model = cell["config"], model_config(cell["config"])
    cut = {**catalog, **REDUCED, **{k: catalog[k][:9] for k in LISTS}}
    assert {k: model[k] for k in catalog} == cut
    assert sorted(cfg["reduced"]) == sorted([*REDUCED, *LISTS])
    assert cfg["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: catalog[k] for k in (*REDUCED, *LISTS)}
    assert (deployment["chips"], deployment["chips_sharing_a_layer"], deployment["rank"]) == (1, 8, 0)
    assert (model["num_experts_total"], model["first_expert"]) == (256, 0)
    assert model["num_experts"] * deployment["chips_sharing_a_layer"] == 256
    assert model["vocab_size"] * 8 == 100352
    # the leading dense layer and two whole periods behind it
    assert model["layer_types"] == (["full_attention"] + ["sliding_attention"] * 3) * 2 + ["full_attention"]
    assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 8
    assert model["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48, 72, 72, 72, 48]
    assumed = " ".join(cfg["assumed"])
    for word in ("the gate", "router scores", "no norm on q or k", "YaRN", "tensor names"):
        assert word in assumed
    # between the largest sound reading and the smaller reading of the nearest precision below (judge.why)
    assert 0.254 < cfg["judge"]["tolerance"] < 0.933 and "float8" in cfg["judge"]["why"]
    flags = cfg["server_flags"]
    assert flags[flags.index("--step-prefill") + 1] == "16384"  # without it no prompt of this mix joins
    assert flags[flags.index("--api-batch") + 1] == "32"
    assert flags[flags.index("--prefix-cache") + 1] == "off"
    assert flags[flags.index("--max-seq-len") + 1] in ("16384", "24576")
    assert cell["entry"]["chips"] == 1 and cell["file"]["probe_prompt_tokens"] == [64, 300, 1200, 3000]
    assert cell["file"]["trace_seconds"] == 12.0
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "gap_p95_ms"
    assert set(NEW_METRICS) <= {m["name"] for m in cell["per_layer"]}


def test_the_mix_is_the_issues_letter_for_letter():
    mix = Manifest(REPO).cell(CELL)["mix"]
    assert {k: mix[k] for k in ("loop", "clients", "pool", "lead_in_s", "min_send_gap_s",
                                "order_seed", "sharing")} == {
        "loop": "closed", "clients": 64, "pool": 64, "lead_in_s": 20.0,
        "min_send_gap_s": 0.02, "order_seed": 24, "sharing": None}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "mu": 8.0, "sigma": 0.7, "min": 256, "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal", "mu": 6.9, "sigma": 0.5, "min": 128, "max": 4096}
    from bench import traffic
    prompts = traffic.length_set(mix["prompt_tokens"], 64)
    answers = traffic.length_set(mix["output_tokens"], 64)
    assert (prompts[0], prompts[32], prompts[-1]) == (549, 3022, 12288)
    assert (answers[0], answers[-1]) == (296, 3324)
    p, o = traffic._lengths_in_order(mix, 64, random.Random(mix["order_seed"]))
    assert max(a + b for a, b in zip(p, o)) + 3 == 13783 < 16384  # the longest lane fits its table


def test_the_parameter_count_is_the_issues():
    """ISSUE 41's count, tensor by tensor from the architecture's table."""
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    import numpy as np
    attention = lambda i: sum(int(np.prod(s)) for s in arch.attention_shapes(model, i).values())  # noqa: E731
    assert attention(0) == 2 * 48 * 128 * 3072 + 2 * 8 * 128 * 3072 + 48 * 3072 == 44_187_648
    assert attention(1) == 2 * 72 * 128 * 3072 + 2 * 8 * 128 * 3072 + 72 * 3072 == 63_135_744
    assert arch.expert_parameters(model) == 3 * 3072 * 1024 == 9_437_184
    assert arch.layer_parameters(model, 0) == 44_187_648 + 3 * 3072 * 12288 + 2 * 3072 == 157_440_000
    router, norms = 256 * 3072, 2 * 3072
    assert arch.layer_parameters(model, 1) == 63_135_744 + router + norms + 33 * 9_437_184 == 375_355_392
    assert arch.layer_parameters(model, 4) == 44_187_648 + router + norms + 33 * 9_437_184 == 356_407_296
    total = 157_440_000 + 6 * 375_355_392 + 2 * 356_407_296 + 2 * 12544 * 3072 + 3072
    assert arch.parameters(model) == total == 3_199_460_352
    assert total == Manifest(REPO).cell(CELL)["config"]["deployment"]["parameters"]
    fixed = total - 8 * 32 * 9_437_184 - 12544 * 3072
    assert arch.decode_weight_bytes(model, "bf16") == 2 * fixed == 1_490_012_160
    assert arch.expert_bytes(model, "bf16") == 18_874_368 and arch.sparse_layers(model) == 8
    assert arch.kv_bytes_per_token_layer(model, "bf16") == 4096
    # 16 lanes of 4,400 tokens: the full layers read every token, the sliding ones 640 a lane
    ops, moved = arch.mixed_decode_attention_cost(model, 16, 16 * 4400, 128, "bf16")
    kv = 3 * 16 * 4400 * 4096 + 6 * 16 * 640 * 4096
    io = 2 * 16 * (3 * 48 + 6 * 72) * 128 * 2
    assert moved == kv + io and kv == 1_116_733_440
    assert ops == 2 * 2 * 128 * (3 * 48 * 16 * 4400 + 6 * 72 * 16 * 640)
    assert ops / 197e12 < moved / 819e9  # bytes bound it: 1.37 ms a step


def test_the_program_counts_the_cache_as_the_architecture_file_does():
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.kinds import bytes_per_page, run_shapes

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.cache_kind == "kv+kinds" and config.attention_kinds == ("full", "sliding")
    per = bytes_per_page(config, 128, "bfloat16")
    assert per == {"full": 3 * 128 * arch.kv_bytes_per_token_layer(model, "bf16"),
                   "sliding": 6 * 128 * 4096}
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (32, 256, 0)
    assert [(k, f, hi - lo) for k, f, lo, hi, _ in config.stack_runs] == [
        ("full", "dense", 1), ("sliding", "sparse", 3), ("full", "sparse", 1),
        ("sliding", "sparse", 3), ("full", "sparse", 1)]
    import numpy as np
    for _, ff, lo, _, _ in config.stack_runs:  # the program's trees hold what the table draws
        held = sum(int(np.prod(s)) for s in run_shapes(config, config.heads_per_layer[lo], ff).values())
        assert held == arch.layer_parameters(model, lo)


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(mapped, freed, cached_now, dispatches, touched, seconds, live, count, cached, joined):
    kind = lambda pages, per, f: {"window": None, "pages_total": 9999, "pages_mapped": pages,  # noqa: E731
                                  "bytes_per_page": per, "freed_behind_window": f}
    return {"engine": {
        "cache": {"kinds": {"full": kind(mapped[0], 1_572_864, 0),
                            "sliding": kind(mapped[1], 3_145_728, freed)},
                  "cached_tokens": cached_now},
        "moe": {"dispatches": dispatches, "routed": 0, "held": 0, "touched": touched, "max_load": 0},
        "period": {"seconds": seconds, "count": count, "cached_tokens": cached,
                   "lane_seconds": {"live": live},
                   "with_join": {"count": joined[0], "seconds": joined[1]}}}}


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    flags = ["--decode-chunk", "8", "--page-size", "128"]
    return {
        "config": {**model, "server_flags": flags, "served_dtype": "bf16"},
        "architecture": architecture(REPO, model),
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite"},
        "stats_before": _stats((560, 96), 1000, 70_400, 8000, 80_000, 10.0, 160.0, 100, 7_040_000,
                               (10, 1.2)),
        "stats_after": _stats((600, 96), 1600, 76_800, 40_000, 400_000, 60.0, 960.0, 500, 35_200_000,
                              (50, 8.0)),
        # a traced 12 s that lay wholly inside an epoch's prefill: no decode chunk, no join
        # (the driver's first check of PR 41, seed 1849007888: the line lacked the third metric)
        "trace": {"programs": {}, "ops": {}},
    }


def test_readers_on_recorded_facts(facts):
    per_token = _reader("kv_bytes_per_cached_token")({**facts, "metric": "x"}, {})
    before = (560 * 1_572_864 + 96 * 3_145_728) / 70_400
    after = (600 * 1_572_864 + 96 * 3_145_728) / 76_800
    assert per_token == pytest.approx((before + after) / 2) and 16_000 < per_token < 17_000 < 36_864
    freed = _reader("window_pages_freed_per_s")({**facts, "metric": "x"}, {})
    assert freed == pytest.approx(600 / 50.0)
    # 10 touched a dispatch x 8 sparse layers x 18,874,368 B over 819 GB/s = 1.844 ms of a 15 ms step:
    # 360 periods without a join took 43.2 of the window's 50 s, 120 ms a chunk of 8 steps
    stream = _reader("laguna_expert_stream_pct")({**facts, "metric": "laguna_expert_stream_pct"}, {})
    assert stream == pytest.approx(12.29, rel=1e-3)
    assert _reader("laguna_expert_stream_pct")({**facts, "metric": "x", "trace": None}, {}) == stream
    cpu = {**facts, "metric": "x", "device": {"platform": "cpu", "device_kind": "cpu"}}
    assert _reader("laguna_expert_stream_pct")(cpu, {}) is None  # a rehearsal: no device's peak
    # the counters it reads are the ones the engine's account keeps
    from cake_tpu.obs.period import PeriodAccount
    kept = PeriodAccount(4).snapshot()["period"]
    assert {"seconds", "count"} <= set(kept) and {"seconds", "count"} <= set(kept["with_join"])


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit's ``/stats`` has no ``engine.cache.kinds`` and no
    ``cached_tokens`` beside them, an architecture of the benchmark's other
    cells neither cost function: every new reader returns None and does not
    raise."""
    for side in ("stats_before", "stats_after"):
        facts[side]["engine"]["cache"] = {"kind": "kv", "pages": 320}
        del facts[side]["engine"]["moe"]
    for name in NEW_METRICS:
        assert _reader(name)({**facts, "metric": name}, {}) is None
    for name in NEW_METRICS:
        bare = {**facts, "metric": name, "trace": None,
                "stats_before": {"engine": {}}, "stats_after": {"engine": {}}}
        assert _reader(name)(bare, {}) is None
    mistral = Manifest(REPO).cell("mistral7b-chat-closed")
    other = {**facts, "architecture": mistral["architecture"], "config": mistral["config"]}
    assert _reader(NEW_METRICS[2])({**other, "metric": NEW_METRICS[2]}, {}) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def laguna_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_laguna"))
    before = file_hashes(root)
    add_cell(root, "tiny-laguna-closed", "tiny-laguna", tiny_config(1, FLAGS, TINY_LAGUNA),
             "tiny-laguna-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    after = file_hashes(root)
    # the three metrics list the committed cell alone: in the copy the look-alike joins their lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-laguna-closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, after


def test_a_laguna_cell_is_files_and_entries_only(laguna_root):
    root, before, after = laguna_root
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-laguna.json", "bench/traffic/tiny-laguna-closed.json",
        "bench/workloads/tiny-laguna-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "no_gate", "no_window"])
def test_served_through_the_program_and_judged(laguna_root, fault):
    """The program's two kinds of attention over a pool each, joins past the
    window, pages freed behind it, the gate and its share of the experts
    against the plain reference given the same share; a reference with one
    fault says ``correct`` false of it. The fault is planted in the COPY's
    architecture file for the one run. The sound run is a TRACED one: its
    line carries the three metrics of the cell, each from ``GET /stats``
    alone (no device trace on the CPU, and none needed: the driver's first
    check of PR 41 met a traced window without a decode chunk)."""
    root, *_ = laguna_root
    arch_file = root / "bench/architectures/laguna.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-laguna-closed", "--seed", str(2**31 + 41),
                      "--seconds", SECONDS, "--trace", "0" if fault else "1", "--rehearse-cpu")
    finally:
        arch_file.write_text(sound)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    assert out["rehearsal"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["correct"] is (fault is None), r.stdout[-2000:]
    assert f"finished_length={out['attempted']} " in r.stdout  # no answer stops early
    if fault:
        assert set(out["metrics"]) == {"gap_p95_ms", "setup_s"}
    else:
        metrics = {k: v["value"] for k, v in out["metrics"].items()}
        # the third is a share of a device's peak: a rehearsal on the CPU leaves it out
        assert set(NEW_METRICS[:2]) <= set(metrics) and NEW_METRICS[2] not in metrics, sorted(metrics)
        # two full-attention layers and one sliding one of 2 KV heads x 16: 256 B a token a layer
        assert 0 < metrics["kv_bytes_per_cached_token"] < 3 * 256 * 2
        assert metrics["window_pages_freed_per_s"] > 0
    checkpoint = root / ".bench_work/models/tiny-laguna"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_LAGUNA
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.1.mlp.experts.11.down_proj.weight" in index
    assert "model.layers.1.mlp.experts.12.down_proj.weight" not in index  # not held
    assert "model.layers.0.mlp.down_proj.weight" in index and "lm_head.weight" in index
    assert "model.layers.1.self_attn.g_proj.weight" in index


# --------------------------------------------- the reference against itself


@pytest.fixture(scope="module")
def laguna_model(tmp_path_factory):
    arch = architecture(REPO, TINY_LAGUNA)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_laguna_model")
    write_checkpoint(path, TINY_LAGUNA, "f32", 3, arch)
    reader = Reader(path)
    vocab = vocabulary(TINY_LAGUNA)
    assert vocab.special_ids == list(range(5))
    assert not reader("lm_head.weight")[vocab.special_ids].any()
    assert reader("model.layers.1.mlp.gate.weight").shape == (16, 128)  # every ranked expert
    assert reader("model.layers.1.self_attn.q_proj.weight").shape == (18 * 16, 128)
    assert reader("model.layers.2.self_attn.q_proj.weight").shape == (12 * 16, 128)
    assert reader("model.layers.1.self_attn.g_proj.weight").shape == (18, 128)
    assert (reader("model.layers.1.post_attention_layernorm.weight") == 1).all()
    rng = random.Random(0)
    probes = []
    for n in (12, 60):
        context = vocab.chat_ids(vocab.draw(rng, n))
        served = reference.greedy(arch, reader, TINY_LAGUNA, context, NEW)
        probes.append({"context": context, "served": served})
    return arch, reader, probes


def test_laguna_reference_passes_its_own_stream(laguna_model):
    arch, reader, probes = laguna_model
    verdict = reference.judge(arch, reader, TINY_LAGUNA, 0.005, probes)
    assert verdict["correct"] is True and verdict["worst"] == 0.0


@pytest.mark.parametrize("fault", ["no_gate", "no_window", "plain_rope", "full_rotary",
                                   "no_shared_expert", "softmax_scores"])
def test_laguna_reference_with_one_fault_fails_it(laguna_model, fault):
    arch, reader, probes = laguna_model
    assert fault in arch.FAULTS
    arch.FAULT = fault
    try:
        verdict = reference.judge(arch, reader, TINY_LAGUNA, 0.005, probes)
    finally:
        arch.FAULT = None
    assert verdict["correct"] is False and verdict["worst"] > 0.05, verdict
