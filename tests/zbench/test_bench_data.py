"""The benchmark's data and arithmetic: manifest, generator, percentiles,
bytes, readers, trace reduction. No server, no JAX."""

from __future__ import annotations

import hashlib
import json
import random
import re

import pytest

from bench import costs, readers, stats, traffic, xplane
from bench.client import Outcome
from bench.manifest import BENCH_KEYS, Manifest, ManifestError, architecture, model_config

from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, OPEN_LOOP, REPO, TINY_MODEL, TINY_MOE_MODEL,
                      add_architecture, add_cell, copy_benchmark, tiny_config, tiny_mix,
                      vocabulary)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
MIXES = sorted(p.stem for p in (REPO / "bench/traffic").glob("*.json"))
COMMITTED = json.loads((REPO / "bench/configs/mistral-7b-v0.1-d16.json").read_text())
VOCAB = vocabulary(COMMITTED)  # 32000 words, the committed configuration's template
TINY_VOCAB = vocabulary(TINY_MODEL)


def test_manifest_cross_references():
    Manifest(REPO).check()
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200 for e in BENCH["workloads"] + BENCH["configs"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads(cell):
    c = Manifest(REPO).cell(cell)
    cfg = c["config"]
    assert set(BENCH_KEYS) <= set(cfg)
    assert c["entry"]["chips"] == cfg["deployment"]["chips"]
    assert "--decode-chunk" in cfg["server_flags"]
    # only depth may differ from the published model
    published = {"hidden_size": 4096, "intermediate_size": 14336, "vocab_size": 32000,
                 "num_attention_heads": 32, "num_key_value_heads": 8,
                 "sliding_window": 4096, "rope_theta": 10000.0}
    assert {k: model_config(cfg)[k] for k in published} == published
    declared = next(x for x in BENCH["configs"] if x["name"] == c["config_name"])
    assert (cfg["num_hidden_layers"] != 32) == ("num_hidden_layers" in declared["reduced"])
    assert cfg["reduced"] == declared["reduced"]


def test_manifest_refuses_a_dangling_cell(tmp_path):
    root = copy_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "no-such-mix"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ManifestError):
        Manifest(root).check()


def test_added_files_are_enough_for_the_manifest(tmp_path):
    root = copy_benchmark(tmp_path)
    add_cell(root, "tiny-open", "tiny", tiny_config(1, ONE_CHIP_FLAGS), "tiny-open",
             tiny_mix(OPEN_LOOP))
    Manifest(root).check()
    assert Manifest(root).cell("tiny-open")["config"]["hidden_size"] == 128


def test_an_unknown_model_type_names_the_file_to_add(tmp_path):
    root = copy_benchmark(tmp_path)
    config = tiny_config(1, ONE_CHIP_FLAGS, TINY_MOE_MODEL)
    add_cell(root, "tiny-moe", "tiny-moe", config, "tiny-open", tiny_mix(OPEN_LOOP))
    with pytest.raises(ManifestError, match=r"add bench/architectures/qwen2_moe\.py"):
        Manifest(root).cell("tiny-moe")
    with pytest.raises(ManifestError, match="model_type 'qwen2_moe'"):
        Manifest(root).check()
    Manifest(root).cell("mistral7b-chat-closed")  # the other cells still load
    add_architecture(root, "qwen2_moe")  # a new file, and the cell loads
    Manifest(root).check()
    cell = Manifest(root).cell("tiny-moe")
    assert cell["architecture"].__file__ == str(root / "bench/architectures/qwen2_moe.py")


# ------------------------------------------------------------------ generator


def _mix(name: str) -> dict:
    if name in ("tiny-open", "tiny-closed"):
        return tiny_mix(OPEN_LOOP if name == "tiny-open" else CLOSED_LOOP)
    return json.loads((REPO / f"bench/traffic/{name}.json").read_text())


def _requests(mix: dict, seed: int) -> list:
    if mix["loop"] == "open":
        return traffic.open_requests(mix, seed, 30.0, VOCAB)
    stream = traffic.closed_requests(mix, seed, VOCAB)
    return [next(stream) for _ in range(2 * mix["pool"])]


@pytest.mark.parametrize("mix_name", MIXES + ["tiny-open", "tiny-closed"])
def test_same_work_in_the_same_order_every_seed(mix_name):
    mix = _mix(mix_name)
    a, b, again = _requests(mix, 7), _requests(mix, 2**31 + 11), _requests(mix, 7)
    assert a == again
    assert [len(r.prompt_ids) for r in a] == [len(r.prompt_ids) for r in b]
    assert [(r.due_s, r.max_tokens) for r in a] == [(r.due_s, r.max_tokens) for r in b]
    assert [r.prompt_ids for r in a] != [r.prompt_ids for r in b]
    assert all(0 <= r.due_s < 30.0 for r in a)
    if mix["loop"] == "open":
        assert len(a) == int(mix["arrivals"]["rate_per_s"] * 30.0)
        assert [r.due_s for r in a] == sorted(r.due_s for r in a)
    else:
        # the pool's lengths come round again, its words do not
        n = mix["pool"]
        assert [len(r.prompt_ids) for r in a[:n]] == [len(r.prompt_ids) for r in a[n:]]
        assert [r.index for r in a] == list(range(2 * n))
    # unique text: no prompt is a prefix of another
    assert len({r.prompt_ids[:8] for r in a}) == len(a)
    lengths = {len(r.prompt_ids) for r in a}
    assert len(lengths) > 0.5 * min(len(a), mix.get("pool", len(a)))  # continuous, no levels


def test_lengths_keep_the_tail_and_sharing_shares():
    spec = {"dist": "lognormal", "mu": 5.7, "sigma": 0.8, "min": 16, "max": 3000}
    drawn = traffic.length_set(spec, 500)
    assert min(drawn) >= 16 and max(drawn) == 3000 and len(set(drawn)) > 200
    assert drawn == sorted(drawn) and 290 <= drawn[250] <= 310
    mix = tiny_mix(CLOSED_LOOP)
    mix["sharing"] = {"prefix_tokens": 20, "groups": 2}
    stream = traffic.closed_requests(mix, 1, TINY_VOCAB)
    reqs = [next(stream) for _ in range(12)]
    assert len({r.prompt_ids[:20] for r in reqs}) == 2
    assert len({r.prompt_ids for r in reqs}) == len(reqs)
    warm = traffic.warmup_requests(tiny_mix(OPEN_LOOP), TINY_VOCAB)
    assert len(warm) == 3 and traffic.warmup_requests(mix, TINY_VOCAB) == []


def test_bursty_train_is_the_mixes_own():
    spec = {"process": "bursty", "on_rate_per_s": 20.0, "off_rate_per_s": 0.0,
            "mean_on_s": 1.0, "mean_off_s": 2.0, "pattern_seed": 5}
    a = traffic.arrival_offsets(spec, 30.0, random.Random(1))
    b = traffic.arrival_offsets(spec, 30.0, random.Random(2))
    assert a == b and a == sorted(a) and 0 < len(a) and a[-1] < 30.0
    gaps = [y - x for x, y in zip(a, a[1:])]
    assert max(gaps) > 10 * sorted(gaps)[len(gaps) // 2]  # silences between bursts


def test_words_round_trip():
    ids = [5, 31999, 77]
    assert VOCAB.ids_from_text(VOCAB.prompt_text(ids)) == ids
    assert VOCAB.chat_ids(ids) == [1, 3, 5, 31999, 77, 4]
    with pytest.raises(ValueError):
        VOCAB.ids_from_text("w5 cake")


def test_the_seam_gives_what_the_code_before_it_gave():
    """Values of commit 5e06de7, where the template, the ids traffic never
    draws (``FIRST_WORD_ID`` 5) and the bytes were written into bench/*.py."""
    arch = architecture(REPO, COMMITTED)
    assert arch.special_words(COMMITTED) == {
        0: "<unk>", 1: "<s>", 2: "</s>", 3: "[INST]", 4: "[/INST]"}
    assert VOCAB.special_ids == [0, 1, 2, 3, 4] and len(VOCAB.chat_ids([])) == 3
    assert VOCAB.chat_text("w7 w9") == "<s>[INST] w7 w9 [/INST]"
    assert [VOCAB.word(i) for i in (0, 2, 4, 5)] == ["<unk>", "</s>", "[/INST]", "w5"]
    assert arch.decode_weight_bytes(COMMITTED, "bf16") == 7241736192
    assert costs.decode_weight_bytes(COMMITTED, "bf16") == 7241736192
    # the same words for the same seed: randrange(5, vocab) then, the
    # randrange(vocab - 5)-th ordinary id now
    mix = _mix("chat-closed-16")
    stream = traffic.closed_requests(mix, 2**31 + 11, VOCAB)
    reqs = [next(stream) for _ in range(64)]
    assert reqs[0].prompt_ids[:5] == (19379, 21195, 7272, 15655, 26698)
    assert min(min(r.prompt_ids) for r in reqs) == 5
    listed = [[r.index, r.due_s, list(r.prompt_ids), r.max_tokens] for r in reqs]
    assert hashlib.sha256(json.dumps(listed).encode()).hexdigest() == (
        "6e60db48ecd622f0f345f69bc705e4afec4c716ce6c9dce4197e81bdb4676fc2")
    probes = traffic.probe_requests([12, 60], 32, 77, TINY_VOCAB)
    assert probes[0].prompt_ids[:4] == (57, 238, 499, 196)


def test_special_ids_anywhere_are_never_drawn(tmp_path):
    """A template's words at the configuration's own ids (bos 480, eos 482),
    not below a first word: traffic draws every other id and none of them."""
    root = copy_benchmark(tmp_path)
    add_architecture(root, "qwen2_moe")
    vocab = vocabulary(TINY_MOE_MODEL, root)
    assert vocab.specials[480] == "<|endoftext|>" and vocab.specials[482] == "<|im_end|>"
    assert vocab.special_ids == [480, 482, 483, 484, 485, 486, 487]

    class Counting:  # every k once, in order
        def __init__(self):
            self.k = -1

        def randrange(self, n):
            assert n == 512 - 7
            self.k += 1
            return self.k

    assert vocab.draw(Counting(), 505) == [i for i in range(512) if i not in vocab.specials]
    ids = vocab.chat_ids([7, 481])
    assert ids == [483, 484, 487, 482, 483, 485, 7, 481, 482, 483, 486]
    assert vocab.ids_from_text("w7 <|im_end|> w481") == [7, 482, 481]
    vocab.write_tokenizer(tmp_path / "tokenizer.json")  # raises unless it encodes chat_ids
    from tokenizers import Tokenizer

    tok = Tokenizer.from_file(str(tmp_path / "tokenizer.json"))
    assert tok.encode(vocab.chat_text("w7 w481"), add_special_tokens=False).ids == ids
    assert tok.decode([7, 481], skip_special_tokens=False) == "w7 w481"


# ---------------------------------------------------------------- percentiles


@pytest.mark.parametrize("samples,q,misses,want", [
    (list(range(1, 101)), 50, 0, 50),
    (list(range(1, 101)), 95, 0, 95),
    (list(range(1, 101)), 95, 5, 100),   # rank 100 of 105: the largest sample
    (list(range(1, 11)), 95, 0, 10),     # under 20 samples a p95 is the maximum
    ([3.0], 50, 0, 3.0),
    ([], 50, 3, None),
])
def test_percentile_counts_misses_above_every_sample(samples, q, misses, want):
    assert stats.percentile(samples, q, misses) == want


def _outcome(i, due, arrivals, **kw):
    req = traffic.Request(i, 0.0, (5, 6, 7), 4)
    usage = {"prompt_tokens": 6, "completion_tokens": len(arrivals)}
    base = dict(sent=due + 0.001, status=200, finish="length", usage=usage, done=True,
                ended=arrivals[-1] + 0.01)
    return Outcome(req, due, VOCAB, arrivals=arrivals, **{**base, **kw})


def test_end_to_end_times_from_due_and_counts_failures():
    outs = [
        _outcome(0, 10.0, [10.5, 10.6, 10.9]),
        _outcome(1, 11.0, [11.2, 11.3]),
        _outcome(2, 12.0, [12.1], done=False),             # cut at the drain's end
        _outcome(3, 12.5, [12.9], status=503),
        _outcome(4, 13.0, [13.4, 13.5], usage={"prompt_tokens": 9, "completion_tokens": 2}),
    ]
    e = stats.end_to_end(outs, t0=10.0, seconds=3.0, loop="open")
    assert e["attempted"] == 5 and e["failed"] == 3
    assert e["samples"] == {"requests": 5, "ttft": 2, "gaps": 4, "tokens": 7}
    # two samples and three misses above them: rank 3 of 5 is already a miss,
    # so both percentiles report the largest sample, which is a floor
    assert e["values"]["ttft_p50_ms"] == pytest.approx(500.0)
    assert e["values"]["ttft_p95_ms"] == pytest.approx(500.0)
    assert stats.percentile([0.2, 0.5], 20, misses=3) == 0.2
    assert e["values"]["tokens_per_s"] == pytest.approx(7 / 3.0)  # 13.4 and 13.5 are late
    assert sorted(e["failures"]) == [2, 3, 4]


def test_a_closed_loop_counts_what_ended_in_the_window():
    outs = [
        _outcome(0, 8.0, [9.0, 9.9, 10.2]),                   # begun in the lead-in
        _outcome(1, 10.5, [11.0, 11.4]),
        _outcome(2, 11.0, [11.5], status=503),
        _outcome(3, 12.0, [12.5, 13.2], done=False),          # cut at the window's end
        _outcome(4, 9.0, [9.5], done=False),                  # never ended: not counted
        _outcome(5, 8.0, [8.5, 9.5]),                         # ended in the lead-in
    ]
    e = stats.end_to_end(outs, t0=10.0, seconds=3.0, loop="closed")
    assert [o.request.index for o in e["counted"]] == [0, 1, 2]
    assert e["attempted"] == 3 and e["failed"] == 1 and sorted(e["failures"]) == [2]
    # tokens and gaps are the window's, whichever request they belong to
    assert e["samples"] == {"requests": 3, "ttft": 2, "gaps": 2, "tokens": 5}
    assert e["values"]["tokens_per_s"] == pytest.approx(5 / 3.0)
    assert e["values"]["gap_p95_ms"] == pytest.approx(400.0)
    assert e["values"]["ttft_p50_ms"] == pytest.approx(1000.0)


# ---------------------------------------------------------------------- costs


def test_weight_bytes_against_the_shapes():
    cfg = json.loads((REPO / "bench/configs/mistral-7b-v0.1-d16.json").read_text())
    layer = 4096 * (4096 + 1024 + 1024 + 4096) + 3 * 4096 * 14336 + 2 * 4096
    head = 32000 * 4096 + 4096
    assert costs.decode_weight_bytes(cfg, "bf16") == 2 * (16 * layer + head)
    assert costs.peaks("TPU v5 lite")["hbm_gb_per_s"] == 819.0
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        costs.peaks("source")


# -------------------------------------------------------------------- readers


def test_declarative_readers_and_a_reader_of_its_own(tmp_path):
    root = copy_benchmark(tmp_path)
    metrics = root / "bench/layer_metrics"
    (metrics / "joins_in_window.json").write_text(
        json.dumps({"kind": "stats_delta", "path": "engine.joins"}))
    (metrics / "twice.json").write_text(json.dumps({"kind": "python"}))
    (metrics / "twice.py").write_text("def read(facts, spec):\n    return 2 * facts['x']\n")
    facts = {
        "stats_before": {"engine": {"joins": 3}, "compile": {"count": 10}},
        "stats_after": {"engine": {"joins": 8}, "compile": {"count": 10}},
        "gauges": {"cake_batch_occupancy": [2.0, 4.0]}, "x": 21,
        "requests": {"a": {"queue_s": 0.1}, "b": {"queue_s": 0.3}, "c": {"queue_s": None}},
        "trace": None,
    }
    assert readers.read_metric(root, "joins_in_window", facts) == 5
    assert readers.read_metric(root, "twice", facts) == 42
    assert readers.read_metric(root, "compiles_in_window", facts) == 0
    assert readers.read_metric(root, "batch_occupancy_mean", facts) == 3.0
    (metrics / "queue_wait_p95_ms.json").write_text(json.dumps(
        {"kind": "requestlog_percentile", "field": "queue_s", "q": 95, "scale": 1000.0}))
    assert readers.read_metric(root, "queue_wait_p95_ms", facts) == pytest.approx(300.0)
    # nothing to read: nothing reported
    assert readers.read_metric(root, "decode_dispatch_dev_ms", facts) is None
    assert readers.read_metric(root, "device_idle_pct", facts) is None


# ---------------------------------------------------------------------- trace


@pytest.fixture(scope="module")
def recorded():
    return json.loads((REPO / "bench/testdata/v5e_decode_trace.json").read_text())["planes"]


def test_interval_arithmetic():
    assert xplane.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.gaps([(1, 2), (4, 5)], 0, 6) == [(2, 4), (0, 1), (5, 6)]
    own = xplane.self_times([("loop", 0, 10), ("a", 1, 4), ("b", 5, 6), ("a", 11, 12)])
    assert own == {"loop": 6, "a": 4, "b": 1}


def test_reduction_of_the_recorded_trace(recorded):
    s = xplane.summary(recorded)
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(0.2963, abs=1e-3)
    assert s["busy_s_per_chip"][0] == pytest.approx(0.2792, abs=1e-3)
    assert s["busy_s_per_chip"][0] < s["window_s"]
    # own time, not the loops that contain everything
    assert s["device_ops"][0][0].startswith("%fusion.150")
    assert sum(t for _, t in s["device_ops"]) < s["busy_s_per_chip"][0]
    assert len(s["idle_gaps"]) == 5 and "PjitFunction" in s["idle_gaps"][0][0]
    decode = json.loads((REPO / "bench/layer_metrics/decode_dispatch_dev_ms.json").read_text())
    runs = xplane.programs(recorded, decode["pattern"])
    assert runs == [pytest.approx(0.26232, abs=1e-4)]
    assert xplane.programs(recorded, {"module": "^jit_no_such"}) == []


def test_one_named_kernels_time_from_the_recorded_trace(recorded, tmp_path):
    """130 ``paged_decode_attention.8`` calls are in the trace; 2 lie in the
    decode program the window's edge cut, 128 (16 layers x 8 steps) in the
    whole one."""
    ops = recorded["/device:TPU:0"][xplane.OPS]
    assert sum("decode_attention.8" in name for name, _, _ in ops) == 130
    got = xplane.op_times(recorded, {"op": r"decode_attention\.8"})
    assert got["count"] == 128
    assert got["seconds"] == pytest.approx(2.775e-3, rel=1e-3)
    assert got == xplane.op_times(recorded, {"op": "decode_attention", "module": "^jit_run"})
    # a kernel has no children: its own time is its events' time
    whole = [(a, b) for name, a, b in ops if "decode_attention" in name and a > 0.0686]
    assert got["seconds"] == pytest.approx(sum(b - a for a, b in whole))
    # a loop's own time is what its children leave of it
    loops = xplane.op_times(recorded, {"op": r"^%while"})
    assert 0 < loops["seconds"] < 0.01 * xplane.programs(recorded, {"module": "^jit_run"})[0]
    assert xplane.op_times(recorded, {"op": "decode_attention", "module": "^jit_less"}) == {
        "seconds": 0.0, "count": 0}
    assert xplane.op_times({}, {"op": "x"}) == {"seconds": 0.0, "count": 0}
    # as data: a declarative reader a later PR adds, mean microseconds a call
    root = copy_benchmark(tmp_path)
    (root / "bench/layer_metrics/decode_attention_us.json").write_text(json.dumps(
        {"kind": "op_mean_us", "pattern": {"op": "decode_attention"}}))
    facts = {"trace": {"ops": {"decode_attention_us": got}}}
    assert readers.read_metric(root, "decode_attention_us", facts) == pytest.approx(21.68, abs=0.01)
    assert readers.read_metric(root, "decode_attention_us", {"trace": None}) is None
    none = {"trace": {"ops": {"decode_attention_us": {"seconds": 0.0, "count": 0}}}}
    assert readers.read_metric(root, "decode_attention_us", none) is None


def test_device_readers_on_the_recorded_trace(recorded):
    cfg = json.loads((REPO / "bench/configs/mistral-7b-v0.1-d16.json").read_text())
    decode = json.loads((REPO / "bench/layer_metrics/decode_dispatch_dev_ms.json").read_text())
    runs = xplane.programs(recorded, decode["pattern"])
    facts = {
        "config": cfg, "device": {"device_kind": "TPU v5 lite"}, "outcomes": [],
        "trace": {"summary": xplane.summary(recorded), "t_start": 0.0, "t_stop": 1.0,
                  "programs": {"decode_dispatch_dev_ms": runs,
                               "decode_weight_stream_pct": runs}},
    }
    assert readers.read_metric(REPO, "decode_dispatch_dev_ms", facts) == pytest.approx(262.3, abs=0.1)
    share = readers.read_metric(REPO, "decode_weight_stream_pct", facts)
    assert share == pytest.approx(100 * (7503880192 - 262144000) / 819e9 / (0.26232 / 8), rel=1e-3)
    assert 0 < share < 100
    assert readers.read_metric(REPO, "device_idle_pct", facts) == pytest.approx(5.76, abs=0.05)
    assert readers.read_metric(REPO, "prefill_dev_tokens_per_s", facts) is None
