"""``model_type: sdar_moe`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``sdar-30b-a3b-chat-pp8-d6``, the cell ``sdar-30b-a3b-chat-closed`` over the
mix the benchmark already had (``chat-closed-128``) and seventeen metrics under
its own names (thirteen of them an accepted reader, whole). Here a tiny look-alike of the configuration (same keys: blocks of
4 slots, grouped heads with q/k norms, 8 softmax-routed experts all held, two
a token) enters a temporary copy of the benchmark as a configuration, a mix
and a cell, is served by ``bench.run --rehearse-cpu`` through
``cake_tpu.cli.main`` (the plain K-and-V record's block programs, continuous
scheduler, more lanes than callers, ``--denoise-steps`` and ``--remask``) and
judged by the plain reference; the same reference with one fault says
``correct`` false of the same program. Nothing here pins how many cells the
benchmark has or what another cell reports.
"""

from __future__ import annotations

import importlib.util
import json
import random

import pytest

from bench.manifest import Manifest, architecture, model_config

from conftest import (CLOSED_LOOP, REPO, add_cell, copy_benchmark, file_hashes, last_json,
                      run_bench, tiny_config, tiny_mix)

CELL = "sdar-30b-a3b-chat-closed"
# The readers that are an accepted reader, whole, under the cell's name (the
# older entries list older cells and are not a later PR's to edit): every part
# of a dispatch and of a join that the cell runs, and the traced slice's two
# counters (what says at how many lanes the slice's dispatches were made).
ALIASES = {
    "sdar_decode_feed_forward_dev_ms": "decode_feed_forward_dev_ms",
    "sdar_decode_mixer_dev_ms": "decode_mixer_dev_ms",
    "sdar_decode_head_dev_ms": "decode_head_dev_ms",
    "sdar_decode_sample_dev_ms": "decode_sample_dev_ms",
    "sdar_decode_projections_dev_ms": "decode_projections_dev_ms",
    "sdar_decode_cache_write_dev_ms": "decode_cache_write_dev_ms",
    "sdar_decode_unscoped_pct": "decode_unscoped_pct",
    "sdar_join_mixer_dev_ms": "join_mixer_dev_ms",
    "sdar_join_cache_write_dev_ms": "join_cache_write_dev_ms",
    "sdar_slice_decode_share_pct": "slice_decode_share_pct",
    "sdar_slice_lanes_live_mean": "slice_lanes_live_mean",
}
# (a specification alone: ``kind: program_mean_ms`` has no reader of its own)
SPEC_ALIASES = {"sdar_join_prefill_dev_ms": "join_prefill_dev_ms"}
SLICE = ("slice_decode_share_pct", "slice_lanes_live_mean")
NEW = ("sdar_decode_dispatch_dev_ms", *SPEC_ALIASES, *ALIASES, "sdar_expert_stream_pct",
       "sdar_block_attention_roofline_pct", "sdar_passes_per_block",
       "sdar_rows_per_expert_pass")
# The catalog row's ``config`` (SDAR-30B-A3B-Chat of
# /opt/skills/guides/model-configs/architectures.jsonl), key for key: the
# test machine may not have the guide.
ROW = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 6}

# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY = {
    "architectures": ["SDARMoeForCausalLM"], "model_type": "sdar_moe",
    "hidden_size": 128, "intermediate_size": 256, "vocab_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 1000000,
    "rope_scaling": None, "attention_bias": False, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "use_sliding_window": False, "sliding_window": None, "max_position_embeddings": 4096,
    "block_length": 4, "mask_token_id": 300, "bos_token_id": 3, "eos_token_id": 4,
    "pad_token_id": 3, "initializer_range": 0.1,
}
FLAGS = ["--api-batch", "8", "--max-seq-len", "512", "--kv-mode", "paged", "--page-size", "16",
         "--scheduler", "continuous", "--prefix-cache", "off", "--attention-impl", "pallas",
         "--temperature", "0", "--repeat-penalty", "1.0", "--step-prefill", "512",
         "--decode-chunk", "8", "--denoise-steps", "4", "--remask", "sequential"]
MIX = tiny_mix(CLOSED_LOOP)
SECONDS = "15"  # a window that holds finished requests under a whole run's other workers too


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell = Manifest(REPO).cell(CELL)
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in ROW} == {**ROW, **REDUCED}
    assert cfg["reduced"] == list(REDUCED)
    assert cfg["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert (model["block_length"], model["mask_token_id"]) == (4, 151669)
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: ROW[k] for k in REDUCED}
    assert (deployment["chips"], deployment["chips_sharing_a_layer"]) == (1, 1)
    assert (deployment["stage"], deployment["stages"], deployment["layers"]) == (0, 8, "0-5 of 48")
    assert deployment["parameters"] == 4_361_055_744
    # every width, the experts, the eight a token, the heads and the vocabulary
    # as published; six layers are a stage of eight: the floors are kept
    assert model["num_hidden_layers"] * 8 == ROW["num_hidden_layers"]
    assumed = " ".join(cfg["assumed"])
    for word in ("block_length 4", "denoising_steps 4", "mask_token_id 151669", "sequential",
                 "low_confidence_static", "low_confidence_dynamic", "no shift", "the commit",
                 "P mod 4", "block-causal", "BEFORE the rotary", "softmax over all 128",
                 "tensor names", "template", "initializer_range"):
        assert word in assumed, word
    for word in ("4,361,055,744", "8.72 GB", "12,288", "eight pipeline stages"):
        assert word in deployment["layout"], word
    flags = cfg["server_flags"]
    value = lambda flag: flags[flags.index(flag) + 1]  # noqa: E731
    assert (value("--api-batch"), value("--prefix-cache"), value("--decode-chunk")) == ("64", "off", "8")
    assert (value("--max-seq-len"), value("--page-size"), value("--kv-mode")) == ("4096", "128", "paged")
    assert (value("--denoise-steps"), value("--remask"), value("--repeat-penalty")) == ("4", "sequential", "1.0")
    assert f"--step-prefill {value('--step-prefill')}" in deployment["layout"]
    assert f"--max-pages {value('--max-pages')}" in deployment["layout"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == "chat-closed-128"
    assert "five passes a block" in cell["entry"]["why"] and "6 of 48 layers" in cell["entry"]["why"]
    assert cell["file"]["probe_prompt_tokens"] == [64, 300, 1200]
    assert "float8" in cfg["judge"]["why"] and cfg["served_dtype"] == "bf16"
    arch = cell["architecture"]
    for fault in arch.FAULTS:
        assert fault in cfg["judge"]["why"]
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    assert len(NEW) == 17
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "gap_p95_ms"
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}
    # the thirteen every-cell metrics are the cell's too
    assert len([m for m in cell["per_layer"] if "workloads" not in m]) == 13


def test_the_mix_is_the_issues_letter_for_letter():
    """The file the benchmark had (Pangu's and LFM2's cells'): no traffic is added."""
    mix = Manifest(REPO).cell(CELL)["mix"]
    assert {k: mix[k] for k in ("loop", "clients", "pool", "lead_in_s", "min_send_gap_s",
                                "order_seed", "sharing")} == {
        "loop": "closed", "clients": 128, "pool": 128, "lead_in_s": 10.0,
        "min_send_gap_s": 0.02, "order_seed": 24, "sharing": None}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "mu": 5.7, "sigma": 0.8, "min": 16, "max": 3000}
    assert mix["output_tokens"] == {"dist": "lognormal", "mu": 4.6, "sigma": 0.6, "min": 16, "max": 512}
    from bench import traffic
    p, o = traffic._lengths_in_order(mix, 128, random.Random(mix["order_seed"]))
    # the longest lane fits its table with its template, a whole last block and a known tail
    assert max(a + b for a, b in zip(p, o)) + 6 + 8 < 4096
    assert Manifest(REPO).cell("lfm2-8b-a1b-chat-closed")["mix"] == mix


def test_the_parameter_count_is_the_issues():
    """ISSUE 57's count, tensor by tensor from the architecture's table."""
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    expert = 3 * 2048 * 768
    attention = 2 * 4096 * 2048 + 2 * 512 * 2048 + 2 * 128
    layer = attention + 128 * 2048 + 128 * expert + 2 * 2048
    assert (expert, attention, layer) == (4_718_592, 18_874_624, 623_120_640)
    assert arch.layer_parameters(model, 0) == layer
    top = 2 * 151936 * 2048 + 2048
    assert arch.parameters(model) == 6 * layer + top == 4_361_055_744
    assert round(2 * arch.parameters(model) / 1e9, 2) == 8.72
    assert 30.5e9 < arch.parameters({**model, "num_hidden_layers": 48}) < 30.6e9  # the published 30B
    assert arch.expert_bytes(model, "bf16") == 2 * expert == 9_437_184
    assert arch.sparse_layers(model) == 6
    # what EVERY denoising pass reads: no routed expert; the head, not the embedding
    every = arch.parameters(model) - 6 * 128 * expert - 151936 * 2048
    assert arch.decode_weight_bytes(model, "bf16") == 2 * every == 852_024_320
    assert arch.kv_bytes_per_token(model, "bf16") == 12_288
    ops, moved = arch.block_attention_cost(model, 44, 44 * 450, "bf16")
    assert ops == 4 * 4 * 44 * 450 * 32 * 128
    assert moved == 2 * 44 * 450 * 4 * 128 * 2 + 2 * 44 * 4 * 32 * 128 * 2


def test_the_program_counts_the_cache_and_the_weights_as_the_architecture_file_does():
    import jax
    import numpy as np

    from cake_tpu.models.llama import model as M
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.programs import kind_of

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    config = LlamaConfig.from_hf_dict(model)
    shapes = jax.eval_shape(lambda: M.init_params(config, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == arch.parameters(model)
    import jax.numpy as jnp

    assert kind_of(config).token_bytes(config, jnp.bfloat16) == (12_288, 12_288)
    assert (config.generation, config.block_length, config.mask_token_id) == ("block_diffusion", 4, 151669)
    words = arch.special_words(model)
    assert words[151669] == "<|MASK|>" and words[151645] == "<|im_end|>" and len(words) == 6


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _engine(dispatches, live, seconds, count, cached):
    """An engine's accounts after ``dispatches`` decode dispatches of two
    blocks at ``live`` live lanes: ten passes of six layers a dispatch."""
    passes = 10 * dispatches
    return {
        "moe": {"dispatches": 6 * passes, "routed": 6 * passes * live * 4 * 8,
                "held": 6 * passes * live * 4 * 8, "touched": 6 * passes * 128, "max_load": 30},
        "diffusion": {"dispatches": dispatches, "blocks": 2 * dispatches * live, "passes": passes,
                      "commit_passes": 2 * dispatches, "lane_passes": passes * live,
                      "revealed": 8 * dispatches * live, "emitted": 8 * dispatches * live, "known": 0},
        "period": {"seconds": seconds, "count": count, "steps": 8 * count,
                   "cached_tokens": cached * count, "lane_seconds": {"live": live * seconds}},
    }


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    opened, closed = _engine(100, 44, 20.0, 100, 19_800), _engine(130, 44, 24.0, 130, 19_800)
    return {
        "config": {**model, "server_flags": ["--decode-chunk", "8"], "served_dtype": "bf16"},
        "architecture": architecture(REPO, model),
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite"},
        "stats_before": {"engine": {**_engine(50, 44, 10.0, 50, 19_800), "profiled": {"sessions": 0}}},
        "stats_after": {"engine": {**_engine(450, 44, 61.0, 450, 19_800), "profiled": {
            "sessions": 1, "open": {"mono": 1.0, "engine": opened},
            "close": {"mono": 5.0, "engine": closed}}}},
        "trace": {"programs": {}, "ops": {}},
    }


def test_readers_on_recorded_facts(facts, monkeypatch):
    name = "sdar_passes_per_block"
    assert _reader(name)({**facts, "metric": name}, {}) == pytest.approx(5.0)
    name = "sdar_rows_per_expert_pass"
    assert _reader(name)({**facts, "metric": name}, {}) == pytest.approx(0.25 * 44)
    # a pass's attention at 4 queries a lane: K and V of 19,800 cached tokens once
    # (40.6 MB) and the block's queries and sums (2.9 MB) over 819 GB/s = 53 us, of
    # a call of 100 us
    name = "sdar_block_attention_roofline_pct"
    traced = {**facts, "metric": name,
              "trace": {"programs": {}, "ops": {name: {"seconds": 0.006, "count": 60}}}}
    floor_us = (2 * 19_800 * 4 * 128 * 2 + 2 * 44 * 4 * 32 * 128 * 2) / 819e9 * 1e6
    assert _reader(name)(traced, {}) == pytest.approx(100 * floor_us / 100.0)
    assert 50 < _reader(name)(traced, {}) < 60
    assert _reader(name)({**traced, "trace": None}, {}) is None
    # the experts' stream of a pass: 6 x 128 x 9.4 MB over 819 GB/s = 8.85 ms, of
    # a pass's feed-forward time of 11 ms (110 ms a dispatch of ten passes)
    name = "sdar_expert_stream_pct"
    from bench import parts
    monkeypatch.setattr(parts, "dispatch_ms", lambda facts, spec: 110.0)
    spec = {"pattern": {"module": "^jit_decode_chunk"}, "parts": ["feed_forward"]}
    share = _reader(name)({**facts, "metric": name}, spec)
    assert share == pytest.approx(100 * 6 * 128 * 9_437_184 / 819e9 / 0.011)
    assert 80 < share < 81
    cpu = {**facts, "device": {"platform": "cpu", "device_kind": "cpu"}}
    assert _reader(name)({**cpu, "metric": name}, spec) is None
    monkeypatch.undo()
    # no trace file here: the part readers find nothing and do not raise
    for name in (*(a for a in ALIASES if ALIASES[a] not in SLICE), "sdar_expert_stream_pct"):
        assert _reader(name)({**facts, "metric": name}, spec) is None
        assert _reader(name)({**facts, "metric": name, "trace": None}, spec) is None


@pytest.mark.parametrize("alias", sorted({**ALIASES, **SPEC_ALIASES}))
def test_a_reader_under_the_cells_name_is_the_accepted_one(alias, facts):
    """Same specification, same function, same entry but for the name and the
    cell: the number on this cell's line is computed as the older cells' is."""
    metrics = REPO / "bench/layer_metrics"
    accepted = {**ALIASES, **SPEC_ALIASES}[alias]
    spec = json.loads((metrics / f"{alias}.json").read_text())
    assert spec == json.loads((metrics / f"{accepted}.json").read_text())
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    differ = {k for k in per_layer[alias] if per_layer[alias][k] != per_layer[accepted][k]}
    assert differ == {"name", "workloads"}
    if alias in SPEC_ALIASES:
        assert spec["kind"] == "program_mean_ms" and not (metrics / f"{alias}.py").exists()
        return
    assert _reader(alias).__module__ == f"bench.layer_metrics.{accepted}"
    if accepted in SLICE:  # the slice's counters need no trace, only ``engine.profiled``
        assert _reader(alias)({**facts, "metric": alias}, spec) == pytest.approx(
            _reader(accepted)({**facts, "metric": accepted}, spec))
        facts["stats_after"]["engine"].pop("profiled")
    assert _reader(alias)({**facts, "metric": alias, "trace": None}, spec) is None


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit cannot parse the configuration at all; a program
    that could and had no ``engine.diffusion``, ``engine.moe`` or
    ``engine.profiled`` (Mistral's), or an architecture without
    ``expert_bytes`` or ``block_attention_cost``, gives every new reader
    nothing to read, and none raises."""
    spec = {"pattern": {"module": "^jit_decode_chunk"}, "parts": ["mixer"]}
    name = "sdar_block_attention_roofline_pct"
    ops = {"programs": {}, "ops": {name: {"seconds": 0.01, "count": 60}}}
    for side in ("stats_before", "stats_after"):
        del facts[side]["engine"]["moe"], facts[side]["engine"]["diffusion"]
    for reader in NEW:
        if (REPO / f"bench/layer_metrics/{reader}.py").exists() and "_slice_" not in reader:
            assert _reader(reader)({**facts, "metric": reader}, spec) is None
    facts["stats_after"]["engine"].pop("profiled")
    for reader in ("sdar_slice_decode_share_pct", "sdar_slice_lanes_live_mean"):
        assert _reader(reader)({**facts, "metric": reader}, spec) is None
    assert _reader(name)({**facts, "metric": name, "trace": ops}, spec) is None
    mistral = Manifest(REPO).cell("mistral7b-chat-closed")
    other = {**facts, "architecture": mistral["architecture"], "config": mistral["config"]}
    for reader in NEW:
        if (REPO / f"bench/layer_metrics/{reader}.py").exists():
            assert _reader(reader)({**other, "metric": reader, "trace": ops}, spec) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def sdar_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_sdar"))
    before = file_hashes(root)
    add_cell(root, "tiny-sdar-closed", "tiny-sdar", tiny_config(1, FLAGS, TINY),
             "tiny-sdar-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    after = file_hashes(root)
    # the new metrics list the committed cell alone: in the copy the look-alike joins their lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # (and PR 55's two slice counters': ``test_bench_profiled.py``'s traced
    # rehearsal reaches them as the LAST four entries, which they no longer are)
    for m in bench["per_layer"]:
        if m["name"] in (*NEW, *SLICE):
            m["workloads"].append("tiny-sdar-closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, after


def test_an_sdar_cell_is_files_and_entries_only(sdar_root):
    root, before, after = sdar_root
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-sdar.json", "bench/traffic/tiny-sdar-closed.json",
        "bench/workloads/tiny-sdar-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "causal_inside_block", "logits_shifted",
                                   "block_uncommitted", "weights_not_renormalised"])
def test_served_through_the_program_and_judged(sdar_root, fault):
    """The program's prefill under the block-causal mask, blocks of passes
    through the pool, joins, and eight softmax-routed experts all held
    against the plain reference's states; a reference with one fault says
    ``correct`` false of it. The sound run is a TRACED one: its line carries
    the counter metrics of the cell (no device trace, and no peak to hold a
    pass against, on the CPU)."""
    root, *_ = sdar_root
    arch_file = root / "bench/architectures/sdar_moe.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-sdar-closed", "--seed", str(2**31 + 57),
                      "--seconds", SECONDS if fault is None else "10",
                      "--trace", "0" if fault else "1", "--rehearse-cpu")
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
        assert metrics["sdar_passes_per_block"] == pytest.approx(5.0)
        # 2 assignments a row x 4 rows a live lane / 8 experts: a row an expert a
        # live lane (4 callers on 8 lanes)
        assert 0.9 <= metrics["sdar_rows_per_expert_pass"] <= 8
        assert "sdar_expert_stream_pct" not in metrics
        assert "sdar_block_attention_roofline_pct" not in metrics
        # ``engine.profiled`` reaches the line: the engine noticed the
        # harness's profiler start and stop and its accounts were copied at
        # both edges of THAT session (``test_bench_profiled.py``'s traced
        # rehearsal, carried here: PERF.md section 7, row 12)
        assert 0 < metrics["slice_decode_share_pct"] <= 100.0
        assert 0 < metrics["slice_lanes_live_mean"] <= 8 and 0 < metrics["lanes_live_mean"] <= 8
        for name in SLICE:
            assert metrics[f"sdar_{name}"] == metrics[name]
    checkpoint = root / ".bench_work/models/tiny-sdar"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in index
    assert "model.layers.0.self_attn.q_norm.weight" in index and "lm_head.weight" in index
