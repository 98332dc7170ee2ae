"""``model_type: qwen3_next`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``qwen3-next-80b-a3b-ep4-d12``, the cell ``qwen3-next-ep4-chat-closed`` over
the mix the benchmark already had (``chat-closed-128``) and six metrics under
its own names. Here a tiny look-alike of the configuration (same keys: gated
delta-rule layers three to one with gated attention, value heads in groups of
two on the key heads (one period of layers), a rotary term over a quarter of a head, 4 experts HELD
of the 16 the router ranks beside a gated shared one, (1 + w) norms drawn
about 0) enters a temporary copy of the benchmark as a configuration, a mix
and a cell, is served by ``bench.run --rehearse-cpu`` through
``cake_tpu.cli.main`` (the ``kv+state`` record's programs, continuous
scheduler, more lanes than callers) and judged by the plain reference; the
same reference with one fault says ``correct`` false of the same program.
Nothing here pins how many cells the benchmark has or what another cell
reports.
"""

from __future__ import annotations

import importlib.util
import json
import random

import pytest

from bench.manifest import Manifest, architecture, model_config

from conftest import (CLOSED_LOOP, REPO, add_cell, copy_benchmark, file_hashes, last_json,
                      run_bench, tiny_config, tiny_mix)

CELL = "qwen3-next-ep4-chat-closed"
# The six readers under the cell's names, and the accepted reader each is,
# whole (the older entries list older cells and are not a later PR's to edit).
ALIASES = {
    "qwen3next_decode_dispatch_dev_ms": "decode_dispatch_dev_ms",
    "qwen3next_decode_feed_forward_dev_ms": "decode_feed_forward_dev_ms",
    "qwen3next_decode_mixer_dev_ms": "decode_mixer_dev_ms",
    "qwen3next_delta_step_roofline_pct": "delta_rule_step_roofline_pct",
    "qwen3next_expert_stream_pct": "laguna_expert_stream_pct",
    "qwen3next_held_assignments_per_step": "moe_held_assignments_per_step",
}
# The catalog row's ``config`` (Qwen3-Next-80B-A3B-Instruct of
# /opt/skills/guides/model-configs/architectures.jsonl), key for key: the
# test machine may not have the guide.
ROW = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 12, "num_experts": 128, "vocab_size": 37984}

# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
# One period of layers: a program's code, and its compile under the other
# workers of a whole run of the tests, is by the run of layers.
TINY = {
    "architectures": ["Qwen3NextForCausalLM"], "model_type": "qwen3_next",
    "hidden_size": 128, "intermediate_size": 256, "vocab_size": 512, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 128, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 32, "linear_value_head_dim": 32, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_total": 16, "first_expert": 0, "num_experts_per_tok": 4,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "use_sliding_window": False,
    "max_position_embeddings": 4096, "bos_token_id": 0, "eos_token_id": 1, "pad_token_id": 0,
    "initializer_range": 0.1,
}
FLAGS = ["--api-batch", "8", "--max-seq-len", "512", "--kv-mode", "paged", "--page-size", "16",
         "--scheduler", "continuous", "--prefix-cache", "off", "--attention-impl", "pallas",
         "--temperature", "0", "--repeat-penalty", "1.0", "--step-prefill", "512",
         "--decode-chunk", "8"]
MIX = tiny_mix(CLOSED_LOOP)
SECONDS = "15"  # a window that holds finished requests under a whole run's other workers too


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell = Manifest(REPO).cell(CELL)
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in ROW} == {**ROW, **REDUCED}
    assert cfg["reduced"] == list(REDUCED)
    assert cfg["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert (model["num_experts_total"], model["first_expert"]) == (512, 0)
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: ROW[k] for k in REDUCED}
    assert (deployment["chips"], deployment["chips_sharing_a_layer"], deployment["rank"]) == (1, 4, 0)
    assert deployment["stage"] == 0 and deployment["layers"] == "0-11 of 48"
    assert deployment["parameters"] == 5_423_084_736
    # three whole periods, three to one as published; a quarter of the experts
    # and of the vocabulary: the floors of the guide's section 4 are kept
    arch = cell["architecture"]
    kinds = arch.layer_types(model)
    assert kinds == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert model["num_experts"] * 4 == ROW["num_experts"] and model["vocab_size"] * 4 == ROW["vocab_size"]
    assumed = " ".join(cfg["assumed"])
    for word in ("(1 + w)", "KEY head", "[q 256 | gate 256]", "j // 2", "sigmoid on EVERY number",
                 "BEFORE the rotary", "FIRST 64", "1e-6", "without a factor 2", "softmax over all 512",
                 "tensor names", "template", "initializer_range", "multi-token-prediction"):
        assert word in assumed, word
    for word in ("5,423,084,736", "10.85 GB", "6,144", "19,316,736", "four pipeline stages"):
        assert word in deployment["layout"], word
    flags = cfg["server_flags"]
    value = lambda flag: flags[flags.index(flag) + 1]  # noqa: E731
    assert (value("--api-batch"), value("--prefix-cache"), value("--decode-chunk")) == ("64", "off", "8")
    assert (value("--max-seq-len"), value("--page-size"), value("--kv-mode")) == ("4096", "128", "paged")
    assert f"--step-prefill {value('--step-prefill')}" in deployment["layout"]
    assert f"--max-pages {value('--max-pages')}" in deployment["layout"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == "chat-closed-128"
    assert "1/6" in cell["entry"]["why"] and "4x" in cell["entry"]["why"]
    assert cell["file"]["probe_prompt_tokens"] == [64, 300, 1200]
    assert "float8" in cfg["judge"]["why"] and cfg["served_dtype"] == "bf16"
    for fault in ("norm_without_one", "keys_not_grouped", "rope_over_whole_head",
                  "shared_gate_dropped"):
        assert fault in cfg["judge"]["why"] and fault in arch.FAULTS
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    for name in ALIASES:
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "gap_p95_ms"
    assert set(ALIASES) <= {m["name"] for m in cell["per_layer"]}
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
    prompts = traffic.length_set(mix["prompt_tokens"], 128)
    answers = traffic.length_set(mix["output_tokens"], 128)
    assert 290 < prompts[64] < 310 and 95 < answers[64] < 105
    p, o = traffic._lengths_in_order(mix, 128, random.Random(mix["order_seed"]))
    assert max(a + b for a, b in zip(p, o)) + 6 < 4096  # the longest lane fits its table
    assert Manifest(REPO).cell("lfm2-8b-a1b-chat-closed")["mix"] == mix


def test_the_parameter_count_is_the_issues():
    """ISSUE 53's count, tensor by tensor from the architecture's table."""
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    expert = 3 * 2048 * 512
    delta = 12288 * 2048 + 64 * 2048 + 8192 * 4 + 32 + 32 + 128 + 2048 * 4096
    attention = 8192 * 2048 + 2 * 512 * 2048 + 2048 * 4096 + 2 * 256
    fixed = 512 * 2048 + expert + 2048  # router, shared expert, its gate
    assert (expert, delta, attention, fixed) == (3_145_728, 33_718_464, 27_263_488, 4_196_352)
    assert arch.layer_parameters(model, 0) == delta + fixed + 128 * expert + 2 * 2048 == 440_572_096
    assert arch.layer_parameters(model, 3) == attention + fixed + 128 * expert + 2 * 2048 == 434_117_120
    top = 2 * 37984 * 2048 + 2048
    assert arch.parameters(model) == 9 * 440_572_096 + 3 * 434_117_120 + top == 5_423_084_736
    assert round(2 * arch.parameters(model) / 1e9, 2) == 10.85
    whole = {**ROW, "bos_token_id": 0, "eos_token_id": 1, "pad_token_id": 0}
    assert 79e9 < arch.parameters(whole) < 80e9  # the published 80 B, less its MTP module
    assert arch.expert_bytes(model, "bf16") == 2 * expert == 6_291_456
    assert arch.sparse_layers(model) == 12
    # what EVERY step reads: no routed expert; the head, not the embedding
    every = arch.parameters(model) - 12 * 128 * expert - 37984 * 2048
    assert arch.decode_weight_bytes(model, "bf16") == 2 * every == 1_026_910_592
    assert arch.kv_bytes_per_token(model, "bf16") == 6144
    assert arch.state_bytes_per_lane(model) == 19_316_736
    ops, moved = arch.gated_delta_step_cost(model, 64, "bf16")
    assert (ops, moved) == (64 * 32 * 7 * 128 * 128, 64 * 4 * 32 * (2 * 128 * 128 + 514))
    ops, moved = arch.gated_delta_rule_cost(model, 1, 512, "bf16")
    assert moved == 512 * 32 * 514 * 4 + 2 * 4 * 32 * 128 * 128 and ops > 0


def test_the_program_counts_the_cache_and_the_weights_as_the_architecture_file_does():
    import numpy as np

    from cake_tpu.models.llama import programs
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.hybrid import run_shapes

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.cache_kind == "kv+state" and len(config.layer_runs) == 6
    assert config.state_bytes_per_lane == arch.state_bytes_per_lane(model)
    kind = programs.KINDS[config.cache_kind]
    assert kind.token_bytes(config, "bfloat16") == (6144, 6144)
    assert kind.accounts_of(config) and not config.tie_word_embeddings
    first = {"state": config.layers_of("state"), "attention": config.layers_of("attention")}
    for (mixer, lo, _), ff in zip(config.layer_runs, config.run_ff_kinds):
        held = sum(int(np.prod(s)) for s in run_shapes(config, mixer, ff).values())
        assert held == arch.layer_parameters(model, first[mixer][lo])  # what the table draws


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(dispatches, held, touched, seconds, count, join_seconds, join_count, rows, chunks):
    return {"engine": {
        "moe": {"dispatches": dispatches, "routed": 4 * held, "held": held, "touched": touched,
                "max_load": 9, "join": {}},
        "state": {"decode_rows": rows, "decode_dispatches": chunks},
        "period": {"seconds": seconds, "count": count,
                   "with_join": {"seconds": join_seconds, "count": join_count}}}}


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    return {
        "config": {**model, "server_flags": ["--decode-chunk", "8"], "served_dtype": "bf16"},
        "architecture": architecture(REPO, model),
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite"},
        # 400 periods in the window, 300 of them with a join; 100 join-free
        # periods of 0.12 s: a step of 15 ms. 8 steps x 12 layers a period.
        "stats_before": _stats(9_600, 1_056_000, 710_400, 10.0, 100, 8.0, 70, 6_400, 100),
        "stats_after": _stats(48_000, 5_280_000, 3_552_000, 72.0, 500, 58.0, 370, 32_000, 500),
        "trace": {"programs": {}, "ops": {}},
    }


def test_readers_on_recorded_facts(facts):
    name = "qwen3next_held_assignments_per_step"
    held = _reader(name)({**facts, "metric": name}, {})
    assert held == pytest.approx(110.0)  # 2.5 x 44 live lanes
    touched = (3_552_000 - 710_400) / 38_400
    assert touched == pytest.approx(74.0)
    name = "qwen3next_expert_stream_pct"
    share = _reader(name)({**facts, "metric": name}, {})
    step_s = (62.0 - 50.0) / (400 - 300) / 8
    assert share == pytest.approx(100 * touched * 12 * 6_291_456 / 819e9 / step_s)
    assert share == pytest.approx(45.5, abs=0.1) and share < 100
    # both are on a line whatever the traced slice holds
    assert _reader(name)({**facts, "metric": "x", "trace": None}, {}) == share
    cpu = {**facts, "device": {"platform": "cpu", "device_kind": "cpu"}}
    assert _reader(name)({**cpu, "metric": "x"}, {}) is None
    # the kernel's share of ITS roofline: 64 rows of 32 heads' 128 x 128 states
    # read and written, 273 MB over 819 GB/s = 333 us, of a call of 500 us
    name = "qwen3next_delta_step_roofline_pct"
    traced = {**facts, "metric": name,
              "trace": {"programs": {}, "ops": {name: {"seconds": 0.036, "count": 72}}}}
    floor_us = 64 * 4 * 32 * (2 * 128 * 128 + 514) / 819e9 * 1e6
    assert _reader(name)(traced, {}) == pytest.approx(100 * floor_us / 500.0)
    assert 60 < _reader(name)(traced, {}) < 70
    # no trace file here: the part readers find nothing and do not raise
    spec = {"pattern": {"module": "^jit_decode_chunk"}, "parts": ["mixer"]}
    for name in ("qwen3next_decode_mixer_dev_ms", "qwen3next_decode_feed_forward_dev_ms"):
        assert _reader(name)({**facts, "metric": name}, spec) is None
        assert _reader(name)({**facts, "metric": name, "trace": None}, spec) is None


@pytest.mark.parametrize("alias", sorted(ALIASES))
def test_a_reader_under_the_cells_name_is_the_accepted_one(alias, facts):
    """Same specification, same function, same entry but for the name and the
    cell: the number on this cell's line is computed as the older cells'
    is; with no trace file a device reader finds nothing and does not raise."""
    metrics = REPO / "bench/layer_metrics"
    accepted = ALIASES[alias]
    spec = json.loads((metrics / f"{alias}.json").read_text())
    assert spec == json.loads((metrics / f"{accepted}.json").read_text())
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    differ = {k for k in per_layer[alias] if per_layer[alias][k] != per_layer[accepted][k]}
    assert differ == {"name", "workloads"}
    if (metrics / f"{accepted}.py").exists():
        assert _reader(alias).__module__ == f"bench.layer_metrics.{accepted}"
        if per_layer[alias]["source"] == "device_trace":
            assert _reader(alias)({**facts, "metric": alias, "trace": None}, spec) is None
    else:
        assert not (metrics / f"{alias}.py").exists()  # a specification alone


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit cannot parse the configuration at all; a program
    that could and had no ``engine.moe`` or ``engine.state`` (Mistral's), or
    an architecture without ``expert_bytes`` or ``gated_delta_step_cost``,
    gives every new reader nothing to read, and none raises."""
    spec = {"pattern": {"module": "^jit_decode_chunk"}, "parts": ["mixer"]}
    for side in ("stats_before", "stats_after"):
        del facts[side]["engine"]["moe"], facts[side]["engine"]["state"]
    for name in ALIASES:
        if (REPO / f"bench/layer_metrics/{name}.py").exists():
            assert _reader(name)({**facts, "metric": name}, spec) is None
    mistral = Manifest(REPO).cell("mistral7b-chat-closed")
    other = {**facts, "architecture": mistral["architecture"], "config": mistral["config"]}
    for name in ALIASES:
        if (REPO / f"bench/layer_metrics/{name}.py").exists():
            assert _reader(name)({**other, "metric": name}, spec) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def qwen3_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_qwen3_next"))
    before = file_hashes(root)
    add_cell(root, "tiny-qwen3next-closed", "tiny-qwen3next", tiny_config(1, FLAGS, TINY),
             "tiny-qwen3next-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    after = file_hashes(root)
    # the new metrics list the committed cell alone: in the copy the look-alike joins their lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in ALIASES:
            m["workloads"].append("tiny-qwen3next-closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, after


def test_a_qwen3_next_cell_is_files_and_entries_only(qwen3_root):
    root, before, after = qwen3_root
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-qwen3next.json", "bench/traffic/tiny-qwen3next-closed.json",
        "bench/workloads/tiny-qwen3next-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "norm_without_one", "keys_not_grouped",
                                   "rope_over_whole_head", "shared_gate_dropped"])
def test_served_through_the_program_and_judged(qwen3_root, fault):
    """The program's grouped delta rule through the lane cache, gated
    attention with its partial rotary term through the pool, joins, and the
    share of softmax-routed experts beside the gated shared one against the
    plain reference; a reference with one fault says ``correct`` false of it.
    The sound run is a TRACED one: its line carries the counter metric of the
    cell (no device trace, and no peak to hold a step against, on the CPU)."""
    root, *_ = qwen3_root
    arch_file = root / "bench/architectures/qwen3_next.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-qwen3next-closed", "--seed", str(2**31 + 53),
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
        name = "qwen3next_held_assignments_per_step"
        assert name in metrics and "qwen3next_expert_stream_pct" not in metrics
        # a quarter of the ranked experts is held: 4 a token x 4 / 16 = 1 a live
        # lane of a step (4 callers on 8 lanes), as the router happens to deal
        assert 0.25 <= metrics[name] <= 2 * 4
    checkpoint = root / ".bench_work/models/tiny-qwen3next"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.3.mlp.experts.3.down_proj.weight" in index
    assert "model.layers.3.mlp.experts.4.down_proj.weight" not in index  # held: 0..3
    assert "model.layers.0.linear_attn.in_proj_qkvz.weight" in index
    assert "model.layers.3.self_attn.q_norm.weight" in index and "lm_head.weight" in index
    assert "model.layers.0.mlp.shared_expert_gate.weight" in index
