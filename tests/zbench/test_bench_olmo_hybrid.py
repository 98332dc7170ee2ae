"""``model_type: olmo_hybrid`` in the benchmark: files and entries only.

The committed tree has the architecture file, the configuration
``olmo-hybrid-7b-d16``, the cell ``olmo-hybrid-7b-chat-closed`` (on the mix
Jamba's cell uses, ``chat-closed-64``) and three metrics of its own. Here a
tiny look-alike of the configuration (same keys: gated-delta-rule layers
beside full attention, heads whose ``dk != dv``, ``H`` no power of two, untied
head) enters a temporary copy of the benchmark as a configuration, a mix and
a cell, is served by ``bench.run --rehearse-cpu`` through
``cake_tpu.cli.main`` (paged pool, lane state, continuous scheduler) and
judged by the plain reference; the same reference with ``beta = sigmoid(b)``
or with the decay ``alpha`` left out says ``correct`` false of the same
program. Nothing here pins how many cells the benchmark has or what another
cell reports.
"""

from __future__ import annotations

import importlib.util
import json
import random

import numpy as np
import pytest

from bench import reference
from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import Manifest, architecture, model_config

from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, REPO, add_cell, copy_benchmark, file_hashes,
                      last_json, run_bench, tiny_config, tiny_mix, vocabulary)

CELL = "olmo-hybrid-7b-chat-closed"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
CATALOG_CONFIG = {  # the catalog row's ``config``, key for key
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}
REDUCED = {"num_hidden_layers": 16, "layer_types": PERIOD * 4}
# Weights of 0.1 and not 0.02: at this width a mixer of 0.02 adds little to
# the residual, and a faulty reference would move few of the largest logits.
TINY_OLMO = {
    **CATALOG_CONFIG, "architectures": ["OlmoHybridForCausalLM"], "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 4, "layer_types": PERIOD,
    "num_attention_heads": 4, "num_key_value_heads": 4, "linear_num_key_heads": 3,
    "linear_num_value_heads": 3, "linear_key_head_dim": 8, "linear_value_head_dim": 24,
    "vocab_size": 512, "max_position_embeddings": 512, "bos_token_id": 2, "eos_token_id": 2,
    "pad_token_id": 0, "initializer_range": 0.1,
}
FLAGS = [("off" if prev == "--prefix-cache" else f)
         for prev, f in zip([None, *ONE_CHIP_FLAGS], ONE_CHIP_FLAGS)]
NEW = 16
# As the tiny Jamba's: prompts inside one window width and three times the
# shared tiny mix's time, for a server that is one thread beside the other
# workers' tests.
MIX = {**tiny_mix(CLOSED_LOOP), "lead_in_s": 10.0,
       "prompt_tokens": {"dist": "lognormal", "mu": 3.0, "sigma": 0.5, "min": 8, "max": 50}}
SECONDS = "10"


def test_the_committed_configuration_is_the_catalog_row_cut_as_it_says():
    cell = Manifest(REPO).cell(CELL)
    cfg, model = cell["config"], model_config(cell["config"])
    assert {k: model[k] for k in CATALOG_CONFIG} == {**CATALOG_CONFIG, **REDUCED}
    assert sorted(cfg["reduced"]) == sorted(REDUCED)  # exactly these keys changed
    assert cfg["source"].endswith("allenai/Olmo-Hybrid-7B/blob/main/config.json")
    deployment = cfg["deployment"]
    assert deployment["chips"] == 1 and deployment["published"]["num_hidden_layers"] == 32
    assert "second pipeline stage" in deployment["layout"]
    assert cell["entry"]["chips"] == 1 and cell["entry"]["traffic"] == "chat-closed-64"
    assert cell["mix"]["clients"] == 64
    jamba = json.loads((REPO / "bench/configs/ai21-jamba2-3b.json").read_text())
    assert cfg["server_flags"] == [*jamba["server_flags"], "--max-pages", "320"]
    assert cfg["served_dtype"] == "bf16" and len(cfg["assumed"]) >= 8
    entry = next(c for c in Manifest(REPO).bench["configs"] if c["name"] == cell["config_name"])
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    ours = [m for m in Manifest(REPO).bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in ours} >= {"delta_rule_step_roofline_pct", "delta_state_stream_pct"}
    for m in ours:
        assert (m["moves"], m["layer"]) == ("gap_p95_ms", "kernels")
    Manifest(REPO).check()


def test_the_parameter_count_is_the_issues_to_the_unit():
    """ISSUE 34's sums, tensor by tensor from the architecture's table: 12
    linear layers of 215.5M-class, 4 full layers of 185.8M-class, embedding,
    head, final norm."""
    cfg = Manifest(REPO).cell(CELL)["config"]
    model = model_config(cfg)
    arch = architecture(REPO, model)
    h, inter, vocab = 3840, 11008, 100352
    swiglu = 3 * h * inter
    assert swiglu == 126_812_160
    linear = (2 * h * 2880 + 3 * h * 5760 + 2 * h * 30 + 4 * 11520  # q k | v z o | a b | conv
              + 30 + 30 + 192 + swiglu + 2 * h)  # A_log, dt_bias, o_norm, two norms
    full = 4 * h * h + 2 * h + swiglu + 2 * h  # q k v o, q_norm k_norm, two norms
    assert (linear, full) == (215_570_172, 185_809_920)
    assert [arch.layer_parameters(model, i) for i in range(4)] == [linear] * 3 + [full]
    total = 12 * linear + 4 * full + 2 * vocab * h + h
    assert arch.parameters(model) == total == 4_100_788_944 == cfg["deployment"]["parameters"]
    published = {**model, "num_hidden_layers": 32, "layer_types": PERIOD * 8}
    assert arch.parameters(published) == 24 * linear + 8 * full + 2 * vocab * h + h == 7_430_870_688
    # a decode step reads every layer, the final norm and the head; not the embedding
    assert arch.decode_weight_bytes(model, "bf16") == 2 * (total - vocab * h) == 7_430_874_528
    ops, moved = arch.gated_delta_step_cost(model, 32, "bf16")
    assert moved == 32 * 4 * 30 * (2 * 96 * 192 + 2 * 96 + 2 * 192 + 2)  # the state twice, q k v o, gates
    assert ops == 32 * 30 * 7 * 96 * 192
    ops, moved = arch.gated_delta_rule_cost(model, 1, 512, "bf16")
    assert moved == 512 * 30 * (2 * 96 + 2 * 192 + 2) * 4 + 2 * 4 * 30 * 96 * 192
    assert 2.9e9 < ops < 3.2e9  # 6 MFLOP a token a layer beside the layer's 431 of weights


def test_the_program_counts_the_state_as_the_architecture_file_does():
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.hybrid import run_shapes

    cfg = Manifest(REPO).cell(CELL)["config"]
    model = model_config(cfg)
    config, arch = LlamaConfig.from_hf_dict(model), architecture(REPO, model)
    assert config.state_bytes_per_lane == arch.state_bytes_per_lane(model) == 27_371_520
    assert config.state_bytes_per_lane == cfg["deployment"]["state_bytes_per_lane"]
    assert (config.state_shape, config.conv_window) == ((96, 5760), (3, 11520))
    assert config.state_bytes_per_lane == 12 * (2_211_840 + 69_120)
    assert config.cache_kind == "kv+state" and config.state_mixer == "gated_delta"
    assert [i for i, k in enumerate(config.layer_kinds) if k == "attention"] == [3, 7, 11, 15]
    assert [i for i in range(16) if arch.is_attention(model, i)] == [3, 7, 11, 15]
    assert len(config.layer_runs) == 8  # eight scans where Jamba has five
    kv = 2 * 2 * config.num_key_value_heads * config.head_dim * 4  # K and V, bf16, 4 layers
    assert kv == cfg["deployment"]["kv_bytes_per_token"] == 61_440
    for kind, lo, _ in config.layer_runs:  # the program's trees hold what the table draws
        held = sum(int(np.prod(s)) for s in run_shapes(config, kind).values())
        layer = config.layers_of(kind)[lo]
        assert held == arch.layer_parameters(model, layer)


def _reader(name):
    spec = importlib.util.spec_from_file_location("m", REPO / f"bench/layer_metrics/{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _stats(seconds, live, dispatches=0, rows=0):
    return {"engine": {
        "period": {"seconds": seconds, "lane_seconds": {"live": live}},
        "state": {"layers": 12, "mixer": "gated_delta", "bytes_per_lane": 27_371_520,
                  "bytes": 0, "lane_writes": 0, "decode_dispatches": dispatches,
                  "decode_rows": rows}}}


def _facts(metric, trace):
    cfg = Manifest(REPO).cell(CELL)["config"]
    return {
        "metric": metric, "config": cfg, "architecture": architecture(REPO, cfg),
        "device": {"device_kind": "TPU v5 lite"},
        "stats_before": _stats(10.0, 100.0, 100, 3200),
        "stats_after": _stats(60.0, 850.0, 600, 19200), "trace": trace,
    }


def test_the_state_stream_reader_on_recorded_facts():
    read = _reader("delta_state_stream_pct")
    facts = _facts("delta_state_stream_pct",
                   {"programs": {"delta_state_stream_pct": [0.096, 0.096]}})
    # 15 live lanes x 2 x 27,371,520 B over 819 GB/s = 1.0026 ms of a 12 ms step
    assert read(facts, {}) == pytest.approx(8.355, rel=1e-3)
    assert read({**facts, "trace": None}, {}) is None
    for side in ("stats_before", "stats_after"):  # the parent: no ``engine.state``
        del facts[side]["engine"]["state"]
    assert read(facts, {}) is None


def test_the_step_roofline_reader_on_recorded_facts():
    read = _reader("delta_rule_step_roofline_pct")
    facts = _facts("delta_rule_step_roofline_pct",
                   {"ops": {"delta_rule_step_roofline_pct": {"seconds": 0.0288, "count": 96}}})
    # 32 rows a dispatch: 143,777,280 B over 819 GB/s = 175.6 us of a 300 us call
    assert read(facts, {}) == pytest.approx(58.52, rel=1e-3)
    assert read({**facts, "trace": {"ops": {}}}, {}) is None
    assert read({**facts, "trace": None}, {}) is None
    for side in ("stats_before", "stats_after"):  # no counters: nothing to read
        del facts[side]["engine"]["state"]
    assert read(facts, {}) is None


def test_the_prefill_roofline_reader_sums_the_scope_of_a_recorded_trace(tmp_path, monkeypatch):
    """``bench/testdata/v5e_scope_probe.xplane.pb``: three runs on a v5e of a
    program ``jit_f`` whose products, triangular solve and scan run under the
    scope ``gated_delta_rule`` and whose last product does not. The names of
    its operations hold no scope; their metadata's ``tf_op`` does."""
    import shutil
    from types import SimpleNamespace

    name = "delta_rule_prefill_roofline_pct"
    module = _reader(name).__globals__
    held = tmp_path / "plugins" / "profile" / "run"
    held.mkdir(parents=True)
    monkeypatch.setitem(module, "TRACES", tmp_path)
    spec = {"pattern": {"module": "^jit_f"}}
    one = SimpleNamespace(arrivals=[0.5], request=SimpleNamespace(prompt_ids=list(range(64))),
                          vocab=SimpleNamespace(chat_ids=lambda ids: ids))
    late = SimpleNamespace(arrivals=[1.5], request=one.request, vocab=one.vocab)
    facts = {**_facts(name, {"programs": {name: [26.2e-6]}, "t_start": 0.0, "t_stop": 1.0}),
             "outcomes": [one, late]}
    assert module["read"](facts, spec) is None  # no trace file
    shutil.copy(REPO / "bench/testdata/v5e_scope_probe.xplane.pb", held / "host.xplane.pb")
    ops = module["scoped"](str(held / "host.xplane.pb"), "gated_delta_rule")["/device:TPU:0"]
    assert len(ops["XLA Modules"]) == 3 and len(ops["XLA Ops"]) == 105
    assert {n for n, _, _ in ops["XLA Ops"]} == {"", "gated_delta_rule"}
    # One whole run (the window's edges cut the other two): 27 of its 35
    # operations in the scope, 25.089 of 26.202 us. One call of 64 tokens a
    # layer moves 8,862,720 B (10.82 us at 819 GB/s; its 0.383 GFLOP 1.94 us
    # at 197 TFLOP/s), twelve layers 129.9 us: a share of a probe's time
    # only, and what the arithmetic is held to.
    assert module["read"](facts, spec) == pytest.approx(517.6, rel=2e-3)
    assert module["read"]({**facts, "trace": None}, spec) is None
    assert module["read"]({**facts, "outcomes": [late]}, spec) is None
    monkeypatch.setitem(module, "SCOPE", "a_scope_no_program_has")  # the parent
    assert module["read"](facts, spec) is None


# ------------------------------------------------- a tiny look-alike, served


@pytest.fixture(scope="module")
def olmo_root(tmp_path_factory):
    root = copy_benchmark(tmp_path_factory.mktemp("bench_olmo"))
    before = file_hashes(root)
    add_cell(root, "tiny-olmo-closed", "tiny-olmo", tiny_config(1, FLAGS, TINY_OLMO),
             "tiny-olmo-closed", MIX)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    return root, before


def test_an_olmo_hybrid_cell_is_files_and_entries_only(olmo_root):
    root, before = olmo_root
    after = file_hashes(root)
    before.pop("BENCHMARK.json"), after.pop("BENCHMARK.json")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "bench/configs/tiny-olmo.json", "bench/traffic/tiny-olmo-closed.json",
        "bench/workloads/tiny-olmo-closed.json"]
    Manifest(root).check()


@pytest.mark.parametrize("fault", [None, "beta_sigmoid", "no_decay"])
def test_served_through_the_program_and_judged(olmo_root, fault):
    """The program's delta-rule layers, lane state beside the paged pool,
    joins and dead lanes against the plain reference's rule one position at a
    time over the whole sequence; a reference with ``beta = sigmoid(b)`` or
    without the decay says ``correct`` false of it. The fault is planted in
    the COPY's architecture file for the one run (the committed reference
    reads no switch from its environment)."""
    root, _ = olmo_root
    arch_file = root / "bench/architectures/olmo_hybrid.py"
    sound = arch_file.read_text()
    assert sound.count("\nFAULT = None\n") == 1
    if fault:
        arch_file.write_text(sound.replace("\nFAULT = None\n", f"\nFAULT = {fault!r}\n"))
    try:
        r = run_bench(root, "--workload", "tiny-olmo-closed", "--seed", str(2**31 + 34),
                      "--seconds", SECONDS, "--trace", "0", "--rehearse-cpu")
    finally:
        arch_file.write_text(sound)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    assert out["rehearsal"] is True and out["failed"] == 0 and out["attempted"] >= 1
    assert out["correct"] is (fault is None), r.stdout[-2000:]
    assert f"finished_length={out['attempted']} " in r.stdout  # no answer stops early
    assert set(out["metrics"]) == {"gap_p95_ms", "setup_s"}
    checkpoint = root / ".bench_work/models/tiny-olmo"
    assert json.loads((checkpoint / "config.json").read_text()) == TINY_OLMO
    index = json.loads((checkpoint / "model.safetensors.index.json").read_text())["weight_map"]
    assert "model.layers.0.linear_attn.A_log" in index
    assert "model.layers.3.self_attn.k_norm.weight" in index
    assert "lm_head.weight" in index and "model.norm.weight" in index


# --------------------------------------------- the reference against itself


@pytest.fixture(scope="module")
def olmo_model(tmp_path_factory):
    arch = architecture(REPO, TINY_OLMO)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_olmo_model")
    write_checkpoint(path, TINY_OLMO, "f32", 3, arch)
    reader = Reader(path)
    vocab = vocabulary(TINY_OLMO)
    assert vocab.special_ids == list(range(6))  # pad, eos and four markers
    assert not reader("lm_head.weight")[vocab.special_ids].any()
    assert reader("model.embed_tokens.weight")[vocab.special_ids].any()  # untied: only the head
    assert reader("model.layers.0.linear_attn.q_conv1d.weight").shape == (24, 1, 4)
    assert reader("model.layers.0.linear_attn.v_conv1d.weight").shape == (72, 1, 4)
    assert reader("model.layers.0.linear_attn.A_log").std() > 0.01  # drawn, not constant
    assert (reader("model.layers.0.linear_attn.o_norm.weight") == 1).all()
    assert reader("model.layers.3.self_attn.q_norm.weight").shape == (64,)
    rng = random.Random(0)
    probes = []
    for n in (12, 60):
        context = vocab.chat_ids(vocab.draw(rng, n))
        served = reference.greedy(arch, reader, TINY_OLMO, context, NEW)
        probes.append({"context": context, "served": served})
    return arch, reader, probes


def test_the_reference_passes_its_own_stream(olmo_model):
    arch, reader, probes = olmo_model
    verdict = reference.judge(arch, reader, TINY_OLMO, 0.005, probes)
    assert verdict["correct"] is True and verdict["worst"] == 0.0


@pytest.mark.parametrize("fault", ["beta_sigmoid", "no_decay", "no_recurrence"])
def test_the_reference_with_one_fault_fails_it(olmo_model, fault):
    arch, reader, probes = olmo_model
    assert fault in arch.FAULTS
    arch.FAULT = fault
    try:
        verdict = reference.judge(arch, reader, TINY_OLMO, 0.005, probes)
    finally:
        arch.FAULT = None
    assert verdict["correct"] is False and verdict["worst"] > 0.3, verdict


def test_the_template_is_the_programs():
    from cake_tpu.models.llama.chat import Message, encode_dialog

    arch = architecture(REPO, TINY_OLMO)
    assert encode_dialog([Message.user("w9 w10")], "olmo_hybrid") == arch.chat_text("w9 w10")
    # <|endoftext|> is id 2 (eos), the markers take the free ids 1, 3, 4, 5
    assert arch.chat_ids(TINY_OLMO, [9, 10]) == [2, 4, 9, 10, 5]
