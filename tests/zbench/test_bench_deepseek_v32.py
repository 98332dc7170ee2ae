"""``model_type: deepseek_v32`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``deepseek-v3.2-exp-ep16-d5``, the mix ``longdoc-closed-32``, the cell
``deepseek-v32-ep16-longdoc-closed`` and four metrics of its own. Here a tiny
look-alike of the configuration (same keys: MLA behind a learned index whose
budget is SMALLER than the prompts, YaRN, group-limited routing with a
correction bias, a leading dense layer, a share of 4 of 16 ranked experts
beside a shared one, a sliced vocabulary) enters a temporary copy of the
benchmark as a configuration, a mix and a cell, is served by ``bench.run
--rehearse-cpu`` through ``cake_tpu.cli.main`` (both pools behind one table,
continuous scheduler, look-ahead) and judged by the plain reference; the same
reference with one fault says ``correct`` false of the same program. Nothing
here pins how many cells the benchmark has or what another cell reports.
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

CELL = "deepseek-v32-ep16-longdoc-closed"
NEW_METRICS = ("sparse_attended_share_pct", "index_select_dev_ms", "index_scores_roofline_pct",
               "sparse_attention_roofline_pct")
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1, "n_routed_experts": 16,
           "vocab_size": 16160}


def catalog_config() -> dict:
    """The catalog row's ``config``, key for key (the guide's own file)."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "DeepSeek-V3.2-Exp")["config"]


# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY_DEEPSEEK = {
    "architectures": ["DeepseekV32ForCausalLM"], "model_type": "deepseek_v32",
    "hidden_size": 128, "intermediate_size": 256, "vocab_size": 512, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "attention_bias": False,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "n_routed_experts_total": 16,
    "first_routed_expert": 8, "n_shared_experts": 1, "moe_intermediate_size": 64,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "hidden_act": "silu", "ep_size": 1, "moe_layer_freq": 1,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "max_position_embeddings": 4096, "num_nextn_predict_layers": 1,
    "tie_word_embeddings": False, "bos_token_id": 0, "eos_token_id": 1, "initializer_range": 0.1,
}
FLAGS = ["--api-batch", "4", "--max-seq-len", "512", "--kv-mode", "paged", "--page-size", "16",
         "--scheduler", "continuous", "--prefix-cache", "off", "--attention-impl", "pallas",
         "--temperature", "0", "--repeat-penalty", "1.0", "--step-prefill", "512",
         "--decode-chunk", "8"]
NEW = 16
MIX = {**tiny_mix(CLOSED_LOOP), "lead_in_s": 10.0,
       "prompt_tokens": {"dist": "lognormal", "mu": 3.6, "sigma": 0.5, "min": 12, "max": 100}}
SECONDS = "10"


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell, catalog = Manifest(REPO).cell(CELL), catalog_config()
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in catalog} == {**catalog, **REDUCED}
    assert cfg["reduced"] == list(REDUCED)
    assert cfg["source"] == "https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json"
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: catalog[k] for k in REDUCED}
    assert (deployment["chips"], deployment["chips_sharing_a_layer"], deployment["rank"]) == (1, 16, 0)
    assert (model["n_routed_experts_total"], model["first_routed_expert"]) == (256, 0)
    assert model["n_routed_experts"] * 16 == 256 and model["vocab_size"] * 8 == 129280
    # every width as published: the index, the budget, the groups, the rope's scaling
    assert (model["index_n_heads"], model["index_head_dim"], model["index_topk"]) == (64, 128, 2048)
    assert (model["n_group"], model["topk_group"], model["num_experts_per_tok"]) == (8, 4, 8)
    assert model["rope_scaling"] == catalog["rope_scaling"] and model["num_nextn_predict_layers"] == 1
    assumed = " ".join(cfg["assumed"])
    for word in ("bf16 for FP8", "Hadamard", "rope pairing", "tie-breaking", "NOT served"):
        assert word in assumed
    assert "4,635.5 M" in deployment["layout"] and "9.27 GB" in deployment["layout"]
    # between the largest sound reading and the smallest reading of the nearest precision below
    assert 0.594 < cfg["judge"]["tolerance"] < 1.325 and "float8" in cfg["judge"]["why"]
    assert "NOT refused" in cfg["judge"]["why"]  # the group limit: the CPU tests guard it
    flags = cfg["server_flags"]
    value = lambda flag: flags[flags.index(flag) + 1]  # noqa: E731
    assert int(value("--step-prefill")) >= 16387  # without it no prompt of this mix joins
    assert (value("--api-batch"), value("--prefix-cache"), value("--decode-chunk")) == ("16", "off", "8")
    assert (value("--max-seq-len"), value("--max-pages"), value("--page-size")) == ("21504", "2688", "128")
    assert cell["entry"]["chips"] == 1 and cell["file"]["probe_prompt_tokens"] == [300, 3000, 8000]
    assert 4.0 < cell["file"]["trace_seconds"] <= 25.0
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "gap_p95_ms"
    assert [m["name"] for m in Manifest(REPO).bench["per_layer"]][-4:] == list(NEW_METRICS)
    assert set(NEW_METRICS) <= {m["name"] for m in cell["per_layer"]}


def test_the_mix_is_the_issues_letter_for_letter():
    mix = Manifest(REPO).cell(CELL)["mix"]
    assert {k: mix[k] for k in ("loop", "clients", "pool", "lead_in_s", "sharing",
                                "lengths_source")} == {
        "loop": "closed", "clients": 32, "pool": 64, "lead_in_s": 20.0, "sharing": None,
        "lengths_source": "assumed: ISSUE 43"}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "mu": 8.7, "sigma": 0.6, "min": 2048, "max": 16384}
    assert mix["output_tokens"] == {"dist": "lognormal", "mu": 7.3, "sigma": 0.5, "min": 256, "max": 5120}
    with open(REPO / "bench/traffic/code-closed-64.json") as f:
        assert sorted(json.load(f)) == sorted(mix)  # in the keys of Laguna's mix
        assert mix["order_seed"] not in (24, 1)  # an order of its own
    from bench import traffic
    prompts = traffic.length_set(mix["prompt_tokens"], 64)
    answers = traffic.length_set(mix["output_tokens"], 64)
    assert (prompts[0], prompts[-1]) == (2048, 16384) and 5900 < prompts[32] < 6100
    assert (answers[0], answers[-1]) == (442, 4958) and 1470 < answers[32] < 1500
    assert min(prompts) > 2048 - 1  # every lane holds more than index_topk tokens all its life
    p, o = traffic._lengths_in_order(mix, 64, random.Random(mix["order_seed"]))
    assert max(a + b for a, b in zip(p, o)) + 3 == 18487 < 21504  # the longest lane fits its table


def test_the_parameter_count_is_the_issues():
    """ISSUE 43's count, tensor by tensor from the architecture's table."""
    cfg = Manifest(REPO).cell(CELL)["config"]
    model = model_config(cfg)
    arch = architecture(REPO, model)
    groups = arch.parameter_groups(model)
    mla = 7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 128 * 128 * 7168
    assert groups["mla"] == mla == 187_105_280
    assert groups["index"] == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 == 13_959_168
    assert groups["dense_ff"] == 3 * 7168 * 18432 == 396_361_728
    assert groups["shared"] == arch.expert_parameters(model) == 3 * 7168 * 2048 == 44_040_192
    assert groups["router"] == 256 * 7168 and groups["held_experts"] == 16 * 44_040_192
    norms = 2 * 7168 + 1536 + 512 + 2 * 128
    dense_layer = mla + 13_959_168 + 396_361_728 + norms
    sparse_layer = mla + 13_959_168 + 17 * 44_040_192 + 256 * 7168 + 256 + norms
    assert arch.layer_parameters(model, 0) == dense_layer == 597_442_816
    assert arch.layer_parameters(model, 1) == sparse_layer == 951_599_616
    total = dense_layer + 4 * sparse_layer + 2 * 16160 * 7168 + 7168
    assert arch.parameters(model) == total == groups["all"] == cfg["deployment"]["parameters"]
    assert round(total / 1e6, 1) == 4635.5 and round(2 * total / 1e9, 2) == 9.27
    fixed = total - 4 * 16 * 44_040_192 - 16160 * 7168
    assert arch.decode_weight_bytes(model, "bf16") == 2 * fixed == 3_402_222_080
    assert arch.cache_bytes_per_token(model, "bf16") == {
        "latent_needed": 1152, "latent_stored": 1280, "index": 256}
    # 16 rows that hold 7,000 tokens each: the index scans them all, attention reads 2,048 a row
    ops, moved = arch.index_scores_cost(model, 16, 16 * 7000, "bf16")
    assert ops == 2 * 64 * 128 * 112_000 and moved == 112_000 * 256 + 16 * (16384 + 256) + 112_000 * 4
    ops, moved = arch.sparse_attention_cost(model, 16, 16 * 2048, "bf16")
    assert ops == 2 * 128 * (576 + 512) * 32768 and moved == 32768 * 1280 + 16 * 128 * 1088 * 2
    assert ops / 197e12 < moved / 819e9  # bytes bound both


def test_the_program_counts_the_cache_and_the_weights_as_the_architecture_file_does():
    import numpy as np

    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.latent import run_shapes
    from cake_tpu.models.llama.latent_index import cache_bytes_per_token

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.cache_kind == "latent+index" and config.ff_runs == (("dense", 0, 1), ("sparse", 1, 5))
    per = arch.cache_bytes_per_token(model, "bf16")
    assert cache_bytes_per_token(config, "bfloat16") == {
        "latent": 5 * per["latent_stored"], "latent_needed": 5 * per["latent_needed"],
        "index": 5 * per["index"]}
    assert 5 * (per["latent_stored"] + per["index"]) == 7680
    for kind, lo, _ in config.ff_runs:  # the program's trees hold what the table draws
        held = sum(int(np.prod(s)) for s in run_shapes(config, kind).values())
        assert held == arch.layer_parameters(model, lo)


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(dispatches, rows, scanned, chosen, traced):
    counts = lambda d, r, s, c: {"index_topk": 2048, "dispatches": d, "rows": r,  # noqa: E731
                                 "scanned": s, "chosen": c}
    return {"engine": {"sparse": {**counts(dispatches, rows, scanned, chosen),
                                  "traced": counts(*traced), "join": {}}}}


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    return {
        "config": {**model, "server_flags": ["--decode-chunk", "8"], "served_dtype": "bf16"},
        "architecture": architecture(REPO, model),
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite"},
        "stats_before": _stats(4000, 60_000, 400_000_000, 120_000_000, (0, 0, 0, 0)),
        "stats_after": _stats(24_000, 380_000, 2_900_000_000, 775_000_000,
                              (4000, 64_000, 448_000_000, 131_072_000)),
        # a traced slice that held no whole decode chunk (Pangu's cell since PR 39, row 19)
        "trace": {"programs": {}, "ops": {}},
    }


def test_readers_on_recorded_facts(facts):
    share = _reader(NEW_METRICS[0])({**facts, "metric": NEW_METRICS[0]}, {})
    assert share == pytest.approx(100 * 655_000_000 / 2_500_000_000) == pytest.approx(26.2)
    assert _reader(NEW_METRICS[0])({**facts, "metric": "x", "trace": None}, {}) == share
    # no trace file here: the three device readers find nothing and do not raise
    spec = {"pattern": {"module": "^jit_decode_chunk"}}
    for name in NEW_METRICS[1:]:
        assert _reader(name)({**facts, "metric": name}, spec) is None
        assert _reader(name)({**facts, "metric": name, "trace": None}, spec) is None


def test_the_roofline_share_is_the_cost_at_the_traced_counts(facts, monkeypatch):
    """100 whole decode chunks in the trace (8 steps x 5 layers each: the 4,000
    layer-steps the engine counted under the profiler), the scope's own time
    2.0 s: 448 M scanned tokens at 256 B + the scores out + the queries in
    over 819 GB/s is 0.1508 s, 7.5% of it."""
    from bench import sparse_scopes

    monkeypatch.setattr(sparse_scopes, "scope_seconds", lambda facts, spec: {
        "runs": 100, "own_s": {"index_scores": 2.0, "index_select": 1.0, "sparse_attention": 4.0}})
    got = sparse_scopes.roofline_pct(facts, {}, "index_scores", "index_scores_cost", "scanned")
    moved = 448_000_000 * (256 + 4) + 64_000 * (16384 + 256)
    assert got == pytest.approx(100 * moved / 819e9 / 2.0) == pytest.approx(7.18, abs=0.01)
    got = sparse_scopes.roofline_pct(facts, {}, "sparse_attention", "sparse_attention_cost", "chosen")
    moved = 131_072_000 * 1280 + 64_000 * 128 * 1088 * 2
    assert got == pytest.approx(100 * moved / 819e9 / 4.0) == pytest.approx(5.66, abs=0.01)
    # half the counted dispatches' chunks were cut by the trace's edges: the cost follows the runs
    monkeypatch.setattr(sparse_scopes, "scope_seconds", lambda facts, spec: {
        "runs": 50, "own_s": {"index_scores": 1.0}})
    half = sparse_scopes.roofline_pct(facts, {}, "index_scores", "index_scores_cost", "scanned")
    assert half == pytest.approx(7.18, abs=0.01)
    cpu = {**facts, "device": {"platform": "cpu", "device_kind": "cpu"}}
    assert sparse_scopes.roofline_pct(cpu, {}, "index_scores", "index_scores_cost", "scanned") is None


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit's ``/stats`` has no ``engine.sparse`` and its programs
    no such scope; an architecture of the benchmark's other cells has neither
    cost function: every new reader returns None and does not raise."""
    spec = {"pattern": {"module": "^jit_decode_chunk"}}
    for side in ("stats_before", "stats_after"):
        facts[side]["engine"] = {"cache": {"kind": "latent"}}
    for name in NEW_METRICS:
        assert _reader(name)({**facts, "metric": name}, spec) is None
    pangu = Manifest(REPO).cell("pangu-ultra-ep16-chat-closed")
    other = {**facts, "architecture": pangu["architecture"], "config": pangu["config"]}
    for name in NEW_METRICS:
        assert _reader(name)({**other, "metric": name}, spec) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def deepseek_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_deepseek"))
    before = file_hashes(root)
    add_cell(root, "tiny-deepseek-closed", "tiny-deepseek", tiny_config(1, FLAGS, TINY_DEEPSEEK),
             "tiny-deepseek-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    after = file_hashes(root)
    # the four metrics list the committed cell alone: in the copy the look-alike joins their lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny-deepseek-closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, after


def test_a_deepseek_cell_is_files_and_entries_only(deepseek_root):
    root, before, after = deepseek_root
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-deepseek.json", "bench/traffic/tiny-deepseek-closed.json",
        "bench/workloads/tiny-deepseek-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "dense_attention", "no_group_limit"])
def test_served_through_the_program_and_judged(deepseek_root, fault):
    """The program's index, its choice with a budget of 8 under prompts of 12
    to 100 tokens, attention over the chosen through both pools, joins, the
    group-limited router and its share of the experts against the plain
    reference given the same share; a reference with one fault says
    ``correct`` false of it. The sound run is a TRACED one: its line carries
    the counter metric of the cell (no device trace on the CPU)."""
    root, *_ = deepseek_root
    arch_file = root / "bench/architectures/deepseek_v32.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-deepseek-closed", "--seed", str(2**31 + 43),
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
        assert NEW_METRICS[0] in metrics and not set(NEW_METRICS[1:]) & set(metrics), sorted(metrics)
        assert 5 < metrics["sparse_attended_share_pct"] < 60  # 8 of some dozens of tokens a lane
    checkpoint = root / ".bench_work/models/tiny-deepseek"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_DEEPSEEK
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.1.mlp.experts.11.down_proj.weight" in index
    assert "model.layers.1.mlp.experts.12.down_proj.weight" not in index  # not held
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in index
    assert "model.layers.0.self_attn.indexer.wk.weight" in index and "lm_head.weight" in index


# --------------------------------------------- the reference against itself


@pytest.fixture(scope="module")
def deepseek_model(tmp_path_factory):
    arch = architecture(REPO, TINY_DEEPSEEK)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_deepseek_model")
    write_checkpoint(path, TINY_DEEPSEEK, "f32", 3, arch)
    reader = Reader(path)
    vocab = vocabulary(TINY_DEEPSEEK)
    assert vocab.special_ids == list(range(4))
    assert not reader("lm_head.weight")[vocab.special_ids].any()
    assert reader("model.layers.1.mlp.gate.weight").shape == (16, 128)  # every ranked expert
    assert reader("model.layers.1.mlp.gate.e_score_correction_bias").shape == (16,)
    assert reader("model.layers.0.self_attn.indexer.wq_b.weight").shape == (4 * 16, 48)
    assert (reader("model.layers.1.self_attn.indexer.k_norm.bias") == 0).all()
    rng = random.Random(0)
    probes = []
    for n in (12, 60):
        context = vocab.chat_ids(vocab.draw(rng, n))
        served = reference.greedy(arch, reader, TINY_DEEPSEEK, context, NEW)
        probes.append({"context": context, "served": served})
    return arch, reader, probes


def test_deepseek_reference_passes_its_own_stream(deepseek_model):
    arch, reader, probes = deepseek_model
    verdict = reference.judge(arch, reader, TINY_DEEPSEEK, 0.005, probes)
    assert verdict["correct"] is True and verdict["worst"] == 0.0


@pytest.mark.parametrize("fault", ["dense_attention", "random_selection", "index_key_unrotated",
                                   "softmax_scores", "no_group_limit", "no_shared_expert"])
def test_deepseek_reference_with_one_fault_fails_it(deepseek_model, fault):
    arch, reader, probes = deepseek_model
    assert fault in arch.FAULTS
    arch.FAULT = fault
    try:
        verdict = reference.judge(arch, reader, TINY_DEEPSEEK, 0.005, probes)
    finally:
        arch.FAULT = None
    assert verdict["correct"] is False and verdict["worst"] > 0.02, verdict
