"""``model_type: lfm2_moe`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``lfm2-8b-a1b-d16``, the cell ``lfm2-8b-a1b-chat-closed`` over the mix the
benchmark already had (``chat-closed-128``) and three metrics of its own.
Here a tiny look-alike of the configuration (same keys: gated short
convolutions three to one with rotary attention behind a norm a head, heads
of 64, two dense layers then 8 experts of which 2 a token behind a selection
bias, ALL held, a tied head) enters a temporary copy of the benchmark as a
configuration, a mix and a cell, is served by ``bench.run --rehearse-cpu``
through ``cake_tpu.cli.main`` (the ``kv+state`` record's programs, continuous
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

CELL = "lfm2-8b-a1b-chat-closed"
NEW_METRICS = ("lfm2_expert_stream_pct", "lfm2_held_assignments_per_step",
               "short_conv_decode_dev_ms")
# Two part readers of the accepted benchmark under this cell's names: their
# own entries list older cells (not a later PR's to edit), and the expert
# layer's device time is this cell's reason.
PART_ALIASES = {"lfm2_decode_dispatch_dev_ms": "decode_dispatch_dev_ms",
                "lfm2_decode_feed_forward_dev_ms": "decode_feed_forward_dev_ms"}
PERIOD = ["conv", "conv", "full_attention", "conv"]
# The catalog row's ``config`` (LFM2-8B-A1B of
# /opt/skills/guides/model-configs/architectures.jsonl), key for key: the
# test machine may not have the guide.
ROW = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": [*PERIOD * 4, "conv", "conv", "full_attention", "conv", "conv",
                    "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
REDUCED = {"num_hidden_layers": 16, "layer_types": PERIOD * 4}

# Weights of 0.1 and not 0.02: at this width a branch of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY_LFM2 = {
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe",
    "hidden_size": 128, "intermediate_size": 256, "vocab_size": 512, "num_hidden_layers": 8,
    "layer_types": PERIOD * 2, "conv_L_cache": 3, "conv_bias": False,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64, "norm_eps": 1e-05,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "use_expert_bias": True, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "rope_theta": 1000000, "max_position_embeddings": 4096,
    "bos_token_id": 1, "eos_token_id": 7, "pad_token_id": 0, "tie_embedding": True,
    "initializer_range": 0.1,
}
FLAGS = ["--api-batch", "8", "--max-seq-len", "512", "--kv-mode", "paged", "--page-size", "16",
         "--scheduler", "continuous", "--prefix-cache", "off", "--attention-impl", "pallas",
         "--temperature", "0", "--repeat-penalty", "1.0", "--step-prefill", "512",
         "--decode-chunk", "8"]
MIX = tiny_mix(CLOSED_LOOP)
SECONDS = "10"


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell = Manifest(REPO).cell(CELL)
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in ROW} == {**ROW, **REDUCED}
    assert cfg["reduced"] == list(REDUCED)
    assert cfg["source"] == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    deployment = cfg["deployment"]
    assert deployment["published"] == {k: ROW[k] for k in REDUCED}
    assert deployment["chips"] == 1 and deployment["parameters"] == 5_399_129_024
    # the cut is the row's first 16 layers: four whole periods, three to one
    # as published, both dense layers and 14 of the 22 sparse ones; every
    # width, every expert and the whole vocabulary as published
    assert model["layer_types"] == ROW["layer_types"][:16]
    assert model["layer_types"].count("full_attention") * 3 == model["layer_types"].count("conv")
    assumed = " ".join(cfg["assumed"])
    for word in ("tied", "B | C | u", "no activation", "1e-6", "s + b", "tensor names",
                 "template", "initializer_range"):
        assert word in assumed, word
    assert "5,399,129,024" in deployment["layout"] and "10.80 GB" in deployment["layout"]
    assert "second stage" in deployment["layout"]
    flags = cfg["server_flags"]
    value = lambda flag: flags[flags.index(flag) + 1]  # noqa: E731
    assert (value("--api-batch"), value("--prefix-cache"), value("--decode-chunk")) == ("64", "off", "8")
    # (the join work a step is granted: 1,024 tokens, which binds and steadies
    # the judged gap; ISSUE 48's 4,096 spread by 1.7% over a set of six: layout)
    assert (value("--max-seq-len"), value("--page-size"), value("--step-prefill")) == (
        "4096", "128", "1024")
    assert "--step-prefill 1024" in deployment["layout"]
    assert int(value("--max-pages")) <= 2048
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == "chat-closed-128"
    assert cell["file"]["probe_prompt_tokens"] == [64, 300, 1200]
    assert cell["file"]["trace_seconds"] == 4.0
    assert "float8" in cfg["judge"]["why"] and cfg["served_dtype"] == "bf16"
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    for name in (*NEW_METRICS, *PART_ALIASES):
        assert per_layer[name]["workloads"] == [CELL] and per_layer[name]["moves"] == "gap_p95_ms"
    assert [m["name"] for m in Manifest(REPO).bench["per_layer"]][-5:] == [
        *NEW_METRICS, *PART_ALIASES]
    assert {*NEW_METRICS, *PART_ALIASES} <= {m["name"] for m in cell["per_layer"]}
    # the thirteen every-cell metrics are the cell's too
    assert len([m for m in cell["per_layer"] if "workloads" not in m]) == 13


def test_the_mix_is_the_issues_letter_for_letter():
    """The file the benchmark had (Pangu's cell's): this PR adds no traffic."""
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
    assert max(a + b for a, b in zip(p, o)) + 7 < 4096  # the longest lane fits its table
    assert Manifest(REPO).cell("pangu-ultra-ep16-chat-closed")["mix"] == mix


def test_the_parameter_count_is_the_issues():
    """ISSUE 48's count, tensor by tensor from the architecture's table."""
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    arch = architecture(REPO, model)
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2048 * 2048 * 2 + 2048 * 512 * 2 + 2 * 64
    dense, expert = 3 * 2048 * 7168, 3 * 2048 * 1792
    sparse = 32 * expert + 32 * 2048 + 32
    assert (conv, attention, dense, sparse) == (16_783_360, 10_485_888, 44_040_192, 352_387_104)
    assert arch.layer_parameters(model, 0) == conv + dense + 2 * 2048
    assert arch.layer_parameters(model, 2) == attention + sparse + 2 * 2048
    assert arch.layer_parameters(model, 3) == conv + sparse + 2 * 2048
    total = 12 * conv + 4 * attention + 2 * dense + 14 * sparse + 32 * 2048 + 65536 * 2048 + 2048
    assert arch.parameters(model) == total == 5_399_129_024
    assert round(2 * total / 1e9, 2) == 10.80
    whole = {**ROW, "bos_token_id": 1, "eos_token_id": 7, "pad_token_id": 0}
    assert arch.parameters(whole) == 8_339_930_560  # the published 8.3 B, tied
    assert arch.expert_bytes(model, "bf16") == 2 * expert == 22_020_096
    assert arch.sparse_layers(model) == 14
    # what EVERY step reads: no routed expert; the tied matrix once, as the head
    fixed = total - 14 * 32 * expert
    assert arch.decode_weight_bytes(model, "bf16") == 2 * fixed == 933_255_040
    assert arch.kv_bytes_per_token(model, "bf16") == 8192
    assert arch.state_bytes_per_lane(model) == 98_304


def test_the_program_counts_the_cache_and_the_weights_as_the_architecture_file_does():
    import numpy as np

    from cake_tpu.models.llama import programs
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.hybrid import run_shapes

    model = model_config(Manifest(REPO).cell(CELL)["config"])
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.cache_kind == "kv+state" and len(config.layer_runs) == 9
    assert config.state_bytes_per_lane == arch.state_bytes_per_lane(model)
    kind = programs.KINDS[config.cache_kind]
    assert kind.token_bytes(config, "bfloat16") == (8192, 8192)
    assert kind.accounts_of(config) and config.tie_word_embeddings
    first = {"state": config.layers_of("state"), "attention": config.layers_of("attention")}
    for (mixer, lo, _), ff in zip(config.layer_runs, config.run_ff_kinds):
        held = sum(int(np.prod(s)) for s in run_shapes(config, mixer, ff).values())
        assert held == arch.layer_parameters(model, first[mixer][lo])  # what the table draws


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(dispatches, held, touched, seconds, count, join_seconds, join_count):
    return {"engine": {
        "moe": {"dispatches": dispatches, "routed": held, "held": held, "touched": touched,
                "max_load": 9, "join": {}},
        "period": {"seconds": seconds, "count": count,
                   "with_join": {"seconds": join_seconds, "count": join_count}}}}


@pytest.fixture()
def facts():
    model = model_config(Manifest(REPO).cell(CELL)["config"])
    return {
        "config": {**model, "server_flags": ["--decode-chunk", "8"], "served_dtype": "bf16"},
        "architecture": architecture(REPO, model),
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite"},
        # 400 periods in the window, 100 of them with a join; 300 join-free
        # periods of 0.1 s: a step of 12.5 ms. 8 steps x 14 layers a period.
        "stats_before": _stats(11_200, 1_075_200, 343_840, 10.0, 100, 4.0, 30),
        "stats_after": _stats(56_000, 5_376_000, 1_719_200, 60.0, 500, 24.0, 130),
        "trace": {"programs": {}, "ops": {}},
    }


def test_readers_on_recorded_facts(facts):
    held = _reader(NEW_METRICS[1])({**facts, "metric": NEW_METRICS[1]}, {})
    assert held == pytest.approx(96.0)  # 4 a token x 24 live lanes
    touched = (1_719_200 - 343_840) / 44_800
    assert touched == pytest.approx(30.7)
    share = _reader(NEW_METRICS[0])({**facts, "metric": NEW_METRICS[0]}, {})
    step_s = (50.0 - 20.0) / (400 - 100) / 8
    assert share == pytest.approx(100 * touched * 14 * 22_020_096 / 819e9 / step_s)
    assert share == pytest.approx(92.4, abs=0.1) and share < 100
    # both are on a line whatever the traced slice holds
    assert _reader(NEW_METRICS[0])({**facts, "metric": "x", "trace": None}, {}) == share
    cpu = {**facts, "device": {"platform": "cpu", "device_kind": "cpu"}}
    assert _reader(NEW_METRICS[0])({**cpu, "metric": "x"}, {}) is None
    assert _reader(NEW_METRICS[1])({**cpu, "metric": "x"}, {}) == held
    # no trace file here: the device reader finds nothing and does not raise
    spec = {"pattern": {"module": "^jit_decode_chunk"}}
    assert _reader(NEW_METRICS[2])({**facts, "metric": NEW_METRICS[2]}, spec) is None
    assert _reader(NEW_METRICS[2])({**facts, "metric": NEW_METRICS[2], "trace": None}, spec) is None


@pytest.mark.parametrize("alias", sorted(PART_ALIASES))
def test_a_part_reader_under_the_cells_name_is_the_accepted_one(alias, facts):
    """Same specification, same function, same entry but for the name and the
    cell: the number on this cell's line is the one the older cells report;
    with no trace file it finds nothing and does not raise."""
    metrics = REPO / "bench/layer_metrics"
    accepted = PART_ALIASES[alias]
    spec = json.loads((metrics / f"{alias}.json").read_text())
    assert spec == json.loads((metrics / f"{accepted}.json").read_text())
    per_layer = {m["name"]: m for m in Manifest(REPO).bench["per_layer"]}
    differ = {k for k in per_layer[alias] if per_layer[alias][k] != per_layer[accepted][k]}
    assert differ == {"name", "workloads"}
    if (metrics / f"{accepted}.py").exists():
        assert _reader(alias).__module__ == f"bench.layer_metrics.{accepted}"
        assert _reader(alias)({**facts, "metric": alias, "trace": None}, spec) is None
    else:
        assert not (metrics / f"{alias}.py").exists()  # a specification alone


def test_the_convolutions_time_is_the_scopes_own_over_the_whole_runs(facts, monkeypatch):
    """40 whole decode chunks in the trace, 0.12 s under ``short_conv``: 3 ms
    a dispatch; the vocabulary handed to the walk is the one scope's name."""
    from bench import scope_times

    seen = {}

    def scope_seconds(facts, module, vocabulary):
        seen.update(module=module, vocabulary=vocabulary)
        return {"runs": 40, "own_s": {"short_conv": 0.12}}

    monkeypatch.setattr(scope_times, "scope_seconds", scope_seconds)
    spec = {"pattern": {"module": "^jit_decode_chunk"}}
    got = _reader(NEW_METRICS[2])({**facts, "metric": NEW_METRICS[2]}, spec)
    assert got == pytest.approx(3.0)
    assert seen == {"module": "^jit_decode_chunk", "vocabulary": ("short_conv",)}


def test_readers_find_nothing_on_a_program_without_the_counters(facts):
    """The parent commit's ``kv+state`` programs count no expert
    (``engine.moe`` is absent from Jamba's ``/stats``) and have no such scope;
    an architecture of the benchmark's other cells has no ``expert_bytes``
    for a hybrid: every new reader returns None and does not raise."""
    spec = {"pattern": {"module": "^jit_decode_chunk"}}
    for side in ("stats_before", "stats_after"):
        del facts[side]["engine"]["moe"]
    for name in NEW_METRICS:
        assert _reader(name)({**facts, "metric": name}, spec) is None
    jamba = Manifest(REPO).cell("jamba2-3b-chat-closed")
    other = {**facts, "architecture": jamba["architecture"], "config": jamba["config"]}
    for name in NEW_METRICS:
        assert _reader(name)({**other, "metric": name}, spec) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_lfm2"))
    before = file_hashes(root)
    add_cell(root, "tiny-lfm2-closed", "tiny-lfm2", tiny_config(1, FLAGS, TINY_LFM2),
             "tiny-lfm2-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    after = file_hashes(root)
    # the new metrics list the committed cell alone: in the copy the look-alike joins their lists
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in (*NEW_METRICS, *PART_ALIASES):
            m["workloads"].append("tiny-lfm2-closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before, after


def test_an_lfm2_cell_is_files_and_entries_only(lfm2_root):
    root, before, after = lfm2_root
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-lfm2.json", "bench/traffic/tiny-lfm2-closed.json",
        "bench/workloads/tiny-lfm2-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "no_gate_c", "taps_dropped", "bias_in_weights",
                                   "no_qk_norm"])
def test_served_through_the_program_and_judged(lfm2_root, fault):
    """The program's short convolutions through the lane cache, rotary
    attention through the pool, joins, and the routed experts behind their
    selection bias against the plain reference; a reference with one fault
    says ``correct`` false of it. The sound run is a TRACED one: its line
    carries the counter metric of the cell (no device trace, and no peak to
    hold a step against, on the CPU)."""
    root, *_ = lfm2_root
    arch_file = root / "bench/architectures/lfm2_moe.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-lfm2-closed", "--seed", str(2**31 + 48),
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
        assert NEW_METRICS[1] in metrics and not {NEW_METRICS[0], NEW_METRICS[2]} & set(metrics)
        # every expert is held: 2 a token x the live lanes of a step (4 callers on 8 lanes)
        assert 2 <= metrics["lfm2_held_assignments_per_step"] <= 2 * 4
    checkpoint = root / ".bench_work/models/tiny-lfm2"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_LFM2
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.3.feed_forward.experts.7.w2.weight" in index
    assert "model.layers.3.feed_forward.expert_bias" in index
    assert "model.layers.0.conv.conv.weight" in index and "lm_head.weight" not in index
    assert "model.layers.2.self_attn.q_layernorm.weight" in index
