"""The per-layer metrics whose two sides are of the same dispatches (ISSUE 55):
``bench/profiled.py`` cuts a run's facts to the traced slice from the
program's ``engine.profiled`` (its accounts at the recorder's start and
stop), and four readers take their counts there. Each on made facts: the
arithmetic, the 0.0 cases, every None case, and the four entries as data."""

from __future__ import annotations

import copy
import json

import pytest

from bench import period_stats, profiled, readers
from bench.manifest import Manifest
from conftest import REPO

MISTRAL = "mistral7b-chat-closed"
OLMO, QWEN = "olmo-hybrid-7b-chat-closed", "qwen3-next-ep4-chat-closed"
LAGUNA, LFM2 = "laguna-s-ep8-code-closed", "lfm2-8b-a1b-chat-closed"
NEW = {
    "slice_decode_share_pct": ("%", "program_counter", "engine", None),
    "slice_lanes_live_mean": ("lanes", "program_counter", "engine", None),
    "delta_step_slice_roofline_pct": ("%", "device_trace", "kernels", [OLMO, QWEN]),
    "expert_stream_slice_pct": ("%", "device_trace", "kernels", [LAGUNA, LFM2, QWEN]),
}
V5E = {"platform": "tpu", "device_kind": "TPU v5 lite"}
CPU = {"platform": "cpu", "device_kind": "cpu"}
HBM = 819e9


def engine(*, periods=0, seconds=0.0, live=0.0, steps=0, joins=0, dispatches=0, rows=0,
           moe_dispatches=0, touched=0, open_seconds=0.0) -> dict:
    """The engine's accounts after ``periods`` dispatching periods, read
    ``open_seconds`` into the next."""
    return {
        "period": {
            "count": periods, "seconds": seconds, "steps": steps, "open_seconds": open_seconds,
            "with_join": {"count": joins, "seconds": 0.5 * seconds * bool(joins)},
            "lane_seconds": {"live": live, "offered": 64 * seconds, "idle_queued": 0.0},
        },
        "state": {"decode_dispatches": dispatches, "decode_rows": rows, "decode_lanes": 64 * dispatches},
        "moe": {"dispatches": moe_dispatches, "touched": touched},
    }


def stats(accounts: dict, sessions: int, opened=None, closed=None) -> dict:
    return {"engine": {**accounts, "profiled": {"sessions": sessions, "open": opened, "close": closed}}}


# The window: 400 periods at 20 live lanes, 16 rows a dispatch, 50 experts
# touched a layer-step. The slice inside it (4.5 s from the engine's first
# notice to its second): 16 periods of 0.25 s at 60 live lanes, 15 chunks of
# 8 steps and a tail of 3, 62 rows a dispatch, 74 experts touched.
BEFORE = engine(periods=100, seconds=20.0, live=400.0, steps=800, joins=90, dispatches=9600,
                rows=9600 * 16, moe_dispatches=9600, touched=9600 * 50)
OPEN = engine(periods=220, seconds=44.0, live=880.0, steps=1760, joins=200, dispatches=21120,
              rows=21120 * 16, moe_dispatches=21120, touched=21120 * 50)
CLOSE = engine(periods=236, seconds=48.0, live=880.0 + 240.0, steps=1760 + 123, joins=216,
               dispatches=21120 + 1476, rows=21120 * 16 + 1476 * 62,
               moe_dispatches=21120 + 1476, touched=21120 * 50 + 1476 * 74)
AFTER = engine(periods=500, seconds=100.0, live=2000.0, steps=4000, joins=450, dispatches=48000,
               rows=48000 * 16, moe_dispatches=48000, touched=48000 * 50)
KEPT = ({"mono": 1000.0, "engine": OPEN}, {"mono": 1004.5, "engine": CLOSE})


def facts(cell: str = QWEN, trace: dict | None = None, device: dict = V5E, **kept) -> dict:
    found = Manifest(REPO).cell(cell)
    after = stats(AFTER, 1, *KEPT)
    after["engine"]["profiled"].update(kept)
    return {
        "cell": cell, "config": found["config"], "architecture": found["architecture"],
        "device": device, "stats_before": stats(BEFORE, 0), "stats_after": after,
        "trace": trace,
    }


def read(name: str, made: dict):
    return readers.read_metric(REPO, name, made)


# ------------------------------------------------------------ slice_facts


def test_slice_facts_hands_every_helper_the_slices_two_edges():
    made = facts()
    cut = profiled.slice_facts(made)
    assert cut["stats_before"] == {"engine": OPEN} and cut["stats_after"] == {"engine": CLOSE}
    assert {k: v for k, v in cut.items() if not k.startswith("stats_")} == {
        k: v for k, v in made.items() if not k.startswith("stats_")}
    assert profiled.slice_seconds(made) == 4.5
    assert period_stats.delta(cut, "engine.period.count") == 16
    assert period_stats.delta(made, "engine.period.count") == 400
    assert period_stats.ratio(cut, "engine.state.decode_rows", "engine.state.decode_dispatches") == 62.0
    assert period_stats.ratio(made, "engine.state.decode_rows", "engine.state.decode_dispatches") == 16.0
    assert period_stats.ratio(cut, "engine.period.steps", "engine.period.count") == 123 / 16
    assert made["stats_after"]["engine"]["profiled"]["open"] is KEPT[0]  # nothing was changed


def none_cases() -> dict:
    made = {
        "the session never closed": facts(close=None),
        "no session at all": facts(open=None, close=None, sessions=0),
        "sessions did not grow": facts(sessions=0),
        "another session besides the run's": facts(sessions=2),
    }
    parent = facts()  # a program from before ``engine.profiled``
    for side in ("stats_before", "stats_after"):
        del parent[side]["engine"]["profiled"]
    made["no engine.profiled"] = parent
    no_engine = facts()
    no_engine["stats_before"] = no_engine["stats_after"] = {"compile": {"count": 0}}
    made["no engine"] = no_engine
    return made


NONE_CASES = none_cases()
TRACE = {
    "programs": {"expert_stream_slice_pct": [0.16] * 7 + [0.06]},
    "ops": {"delta_step_slice_roofline_pct": {"seconds": 0.0415 * 96, "count": 96 * 100}},
}


@pytest.mark.parametrize("why", sorted(NONE_CASES))
def test_no_slice_where_the_program_kept_none_or_not_the_runs_own(why):
    made = {**NONE_CASES[why], "trace": TRACE}
    assert profiled.slice_facts(made) is None and profiled.slice_seconds(made) is None
    for name in NEW:
        assert read(name, made) is None, name


# ------------------------------------------------- the engine's two metrics


def test_the_share_of_the_slice_in_dispatching_periods_and_its_live_lanes():
    made = facts()
    assert read("slice_decode_share_pct", made) == pytest.approx(100 * 4.0 / 4.5, rel=1e-12)
    assert read("slice_lanes_live_mean", made) == pytest.approx(60.0, rel=1e-12)
    assert read("lanes_live_mean", made) == pytest.approx(20.0, rel=1e-12)  # the window's, beside it
    # no trace is needed: the program's counters alone
    assert read("slice_lanes_live_mean", {**made, "trace": None}) == pytest.approx(60.0)


def edge(mono: float, accounts: dict, open_seconds: float) -> dict:
    kept = copy.deepcopy(accounts)
    kept["period"]["open_seconds"] = open_seconds
    return {"mono": mono, "engine": kept}


@pytest.mark.parametrize("at_open, at_close, share", [
    # the period open at the first notice had run 0.125 s before it: not the slice's
    (0.125, 0.0, 100 * 3.875 / 4.5),
    # the one open at the second notice had run 0.5 s by then: the slice's
    (0.0, 0.5, 100 * 4.5 / 4.5),
    (0.125, 0.25, 100 * 4.125 / 4.5),
])
def test_a_period_across_an_edge_is_counted_for_its_part_inside_the_slice(at_open, at_close, share):
    made = facts(open=edge(1000.0, OPEN, at_open), close=edge(1004.5, CLOSE, at_close))
    assert read("slice_decode_share_pct", made) == pytest.approx(share, rel=1e-12)
    assert read("slice_lanes_live_mean", made) == pytest.approx(60.0, rel=1e-12)  # of the periods that ENDED


def test_a_period_that_began_long_before_the_slice_cannot_carry_the_share_past_100():
    """My chip call 1 (PR 55): a window that compiled for 20 s. A period that
    began 8 s before the first notice ended in a 10 s slice, and ``seconds``
    alone read 177: all of it is in ``seconds`` and 8 s of it were not the
    slice's."""
    long = copy.deepcopy(CLOSE)
    long["period"]["seconds"] = OPEN["period"]["seconds"] + 17.7
    made = facts(open=edge(1000.0, OPEN, 8.0), close=edge(1010.0, long, 0.25))
    assert read("slice_decode_share_pct", made) == pytest.approx(99.5, rel=1e-12)
    # one period under the whole slice: none ended in it, and all of it was one
    under = facts(open=edge(1000.0, OPEN, 3.0), close=edge(1004.5, OPEN, 7.5))
    assert read("slice_decode_share_pct", under) == pytest.approx(100.0, rel=1e-12)
    assert read("slice_lanes_live_mean", under) == 0.0


def test_a_slice_no_period_ended_in_reads_zero_and_not_nothing():
    """An epoch's prefill under the whole slice: the line says so."""
    blind = facts(close={"mono": 1004.5, "engine": copy.deepcopy(OPEN)})
    assert read("slice_decode_share_pct", blind) == 0.0
    assert read("slice_lanes_live_mean", blind) == 0.0
    for name in ("delta_step_slice_roofline_pct", "expert_stream_slice_pct"):
        assert read(name, {**blind, "trace": TRACE}) is None


def test_a_slice_of_no_length_gives_nothing():
    assert read("slice_decode_share_pct", facts(close={"mono": 1000.0, "engine": CLOSE})) is None


@pytest.mark.parametrize("name", ["slice_decode_share_pct", "slice_lanes_live_mean"])
def test_an_account_without_the_counters_gives_nothing(name):
    bare = ({"mono": 1.0, "engine": {"joins": 3}}, {"mono": 2.0, "engine": {"joins": 4}})
    assert read(name, facts(open=bare[0], close=bare[1])) is None


# --------------------------------------------------- the delta step's share


def test_the_delta_steps_share_at_the_slices_rows_in_both_cells():
    """Each architecture's own cost at the SLICE's 62 rows a dispatch under
    the slice's 415 us a call, where the old name of the same line takes the
    window's 16 rows under that time."""
    for cell in (QWEN, OLMO):
        made = facts(cell, TRACE)
        cfg, arch = made["config"], made["architecture"]
        ops, moved = arch.gated_delta_step_cost(cfg, 62.0, "bf16")
        floor_s = max(ops / 197e12, moved / HBM)
        got = read("delta_step_slice_roofline_pct", made)
        assert got == pytest.approx(100 * floor_s / 415e-6, rel=1e-9)
        # the window's rows under the slice's time: what the old name reads
        old = {**made, "trace": {"ops": {"x": TRACE["ops"]["delta_step_slice_roofline_pct"]}}}
        name = "qwen3next_delta_step_roofline_pct" if cell == QWEN else "delta_rule_step_roofline_pct"
        spec = readers.load_spec(REPO, name)
        assert spec["pattern"] == readers.load_spec(REPO, "delta_step_slice_roofline_pct")["pattern"]
        old["trace"]["ops"][name] = old["trace"]["ops"].pop("x")
        assert read(name, old) == pytest.approx(got * 16 / 62, rel=1e-9)
    # Qwen3-Next's at 64 rows: 272,646,144 B, 332.9 us at 819 GB/s
    qwen = facts(QWEN)
    assert qwen["architecture"].gated_delta_step_cost(qwen["config"], 64, "bf16")[1] == 272_646_144
    assert read("delta_step_slice_roofline_pct", facts(QWEN, TRACE)) == pytest.approx(
        100 * (272_646_144 * 62 / 64 / HBM) / 415e-6, rel=1e-9)


@pytest.mark.parametrize("why", ["the CPU", "no trace", "no such kernel", "no whole call",
                                 "no state account", "an architecture without the rule"])
def test_the_delta_steps_share_finds_nothing(why):
    made = facts(QWEN, TRACE)
    if why == "the CPU":
        made["device"] = CPU
    elif why == "no trace":
        made["trace"] = None
    elif why == "no such kernel":
        made["trace"] = {"ops": {}, "programs": {}}
    elif why == "no whole call":
        made["trace"] = {"ops": {"delta_step_slice_roofline_pct": {"seconds": 0.0, "count": 0}}}
    elif why == "no state account":
        for edge in ("open", "close"):
            kept = copy.deepcopy(made["stats_after"]["engine"]["profiled"][edge])
            del kept["engine"]["state"]
            made["stats_after"]["engine"]["profiled"][edge] = kept
    else:
        made = facts(LAGUNA, TRACE)
    assert read("delta_step_slice_roofline_pct", made) is None


# ------------------------------------------------ the experts' share of a step


@pytest.mark.parametrize("cell, layers, expert", [
    (QWEN, 12, 6_291_456), (LAGUNA, 8, 18_874_368), (LFM2, 14, 22_020_096)])
def test_the_experts_share_of_a_steps_device_time_by_the_steps_really_made(cell, layers, expert):
    """Eight whole runs in the trace, 1.18 s of the device together, a tail
    among them; the slice's 16 periods were dispatched for 123 steps, 7.6875
    a dispatch: a step is 1.18 s over 8 x 7.6875, not over 8 x 8."""
    made = facts(cell, TRACE)
    arch, cfg = made["architecture"], made["config"]
    assert (arch.sparse_layers(cfg), arch.expert_bytes(cfg, "bf16")) == (layers, expert)
    step_s = 1.18 / (8 * (123 / 16))
    want = 100 * (74 * layers * expert / HBM) / step_s
    assert read("expert_stream_slice_pct", made) == pytest.approx(want, rel=1e-9)
    flags = cfg["server_flags"]
    chunk = int(flags[flags.index("--decode-chunk") + 1])
    by_the_flag = 100 * (74 * layers * expert / HBM) / (1.18 / (8 * chunk))
    assert chunk == 8 and want == pytest.approx(by_the_flag * (123 / 16) / 8, rel=1e-9)
    assert want < by_the_flag  # a tail among the runs: the flag's steps read high


@pytest.mark.parametrize("why", ["the CPU", "no trace", "no whole run", "no moe account",
                                 "no steps counted", "an architecture without experts"])
def test_the_experts_share_finds_nothing(why):
    made = facts(QWEN, TRACE)
    if why == "the CPU":
        made["device"] = CPU
    elif why == "no trace":
        made["trace"] = None
    elif why == "no whole run":
        made["trace"] = {"programs": {"expert_stream_slice_pct": []}, "ops": {}}
    elif why in ("no moe account", "no steps counted"):
        for edge in ("open", "close"):
            kept = copy.deepcopy(made["stats_after"]["engine"]["profiled"][edge])
            if why == "no moe account":
                del kept["engine"]["moe"]
            else:
                del kept["engine"]["period"]["steps"]
            made["stats_after"]["engine"]["profiled"][edge] = kept
    else:
        made = facts(OLMO, TRACE)
    assert read("expert_stream_slice_pct", made) is None


# ---------------------------------------------------- the entries, as data


def test_the_four_entries_as_data():
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW)
    for entry in bench["per_layer"][-4:]:
        unit, source, layer, where = NEW[entry["name"]]
        assert entry == {
            "name": entry["name"], "unit": unit, "better": "higher", "source": source,
            "layer": layer, "moves": "gap_p95_ms",
            "workloads": where or [c for c in cells if c != MISTRAL]}
        assert MISTRAL not in entry["workloads"] and set(entry["workloads"]) <= set(cells)
        spec = readers.load_spec(REPO, entry["name"])
        assert spec["kind"] == "python"
        assert (REPO / "bench/layer_metrics" / f"{entry['name']}.py").exists()
    pattern = lambda name: readers.load_spec(REPO, name).get("pattern")  # noqa: E731
    assert pattern("slice_decode_share_pct") is None and pattern("slice_lanes_live_mean") is None
    assert pattern("delta_step_slice_roofline_pct") == {"op": "gated_delta_step", "module": "^jit_decode_chunk"}
    assert pattern("expert_stream_slice_pct") == {"module": "^jit_decode_chunk"}
    manifest = Manifest(REPO)
    manifest.check()
    for cell in cells:
        listed = {m["name"] for m in manifest.cell(cell)["per_layer"]}
        assert listed & set(NEW) == {n for n, (_, _, _, where) in NEW.items()
                                     if cell != MISTRAL and (where is None or cell in where)}


def test_the_program_keeps_what_the_readers_read():
    """The keys the readers dig for are the ones the engine's account keeps."""
    from cake_tpu.obs.period import PeriodAccount

    kept = PeriodAccount(4).snapshot()["period"]
    assert {"seconds", "open_seconds", "count", "steps", "lane_seconds"} <= set(kept)
    assert kept["open_seconds"] == 0.0 and "live" in kept["lane_seconds"]


# ------------------------------------------- a real server's slice, rehearsed


def test_a_traced_rehearsal_prints_the_slices_two_counters(tmp_path):
    """A tiny closed cell added as files and entries, and listed by the two
    engine metrics as a later PR would list its own: the served path on the
    CPU under the harness's own profiler window keeps ``engine.profiled`` of
    THAT session and the line carries both; the two device shares find no
    device whose peak to hold a time against."""
    from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, add_cell, copy_benchmark, last_json,
                          run_bench, tiny_config, tiny_mix)

    root = copy_benchmark(tmp_path)
    (root / "cake_tpu").symlink_to(REPO / "cake_tpu")
    add_cell(root, "tiny-slice", "tiny-s", tiny_config(1, ONE_CHIP_FLAGS),
             "tiny-slice", tiny_mix(CLOSED_LOOP))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for entry in bench["per_layer"][-4:]:
        entry["workloads"].append("tiny-slice")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / ".bench_work/cold_pass").mkdir(parents=True)
    (root / ".bench_work/cold_pass/tiny-slice.3").touch()
    r = run_bench(root, "--workload", "tiny-slice", "--seed", "2147483659", "--seconds", "3",
                  "--trace", "1", "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    metrics = {k: v["value"] for k, v in last_json(r.stdout)["metrics"].items()}
    assert 0 < metrics["slice_decode_share_pct"] <= 100.0
    assert 0 < metrics["slice_lanes_live_mean"] <= 4 and 0 < metrics["lanes_live_mean"] <= 4
    assert "delta_step_slice_roofline_pct" not in metrics
    assert "expert_stream_slice_pct" not in metrics
