"""The per-layer metrics that read the engine's own account of its step loop
(``engine.period``, ``compile``, ``startup`` in ``GET /stats``): each reader
on synthetic facts, the manifest with their entries, and one traced
closed-loop rehearsal that prints them from a real server on the CPU."""

from __future__ import annotations

import copy
import math

import pytest

from bench import period_stats, readers
from bench.manifest import Manifest
from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, REPO, add_cell, last_json, run_bench,
                      tiny_config, tiny_mix)

COUNTERS = ["period_p90_ms", "host_ms_per_period", "join_ms_per_join",
            "join_period_share_pct", "lanes_live_mean", "lanes_idle_queued_pct",
            "compile_stall_s_in_window", "load_s"]
EDGES = [0.001 * 1.02 ** i for i in range(400)]


def stats(periods: list[float], *, joins: int = 0, stall: float = 0.0) -> dict:
    """``GET /stats`` after ``periods`` (seconds each; 4 live of 8 lanes, a
    request queued throughout, 10 ms of every period the host's own)."""
    counts = [0] * (len(EDGES) + 1)
    for p in periods:
        counts[1 + max(i for i, e in enumerate(EDGES) if e <= p)] += 1
    seconds = sum(periods)
    host = 0.010 * len(periods)
    return {
        "engine": {"period": {
            "count": len(periods), "seconds": seconds,
            "with_join": {"count": joins, "seconds": 0.4 * joins},
            "phase_seconds": {"sweep": 0.2 * host, "admit": 0.1 * host, "join": 0.0,
                              "pages": 0.0, "dispatch": 0.3 * host,
                              "readback": seconds - host, "emit": 0.4 * host, "other": 0.0},
            "joins": joins, "join_seconds": 0.05 * joins, "join_readback_seconds": 0.04 * joins,
            "lane_seconds": {"live": 4 * seconds, "offered": 8 * seconds,
                             "idle_queued": 4 * seconds},
            "hist": {"edges_s": EDGES, "counts": counts},
        }},
        "compile": {"count": 3, "seconds": 1.0, "stall_seconds": stall},
        "startup": {"load_s": 3.25, "main_to_ready_s": 9.0},
    }


# Before the window: 50 periods of 0.1 s. In it: 90 of 0.3 s and 10 of 0.5 s.
BEFORE = stats([0.1] * 50, joins=5, stall=2.0)
AFTER = stats([0.1] * 50 + [0.3] * 90 + [0.5] * 10, joins=25, stall=2.75)
FACTS = {"stats_before": BEFORE, "stats_after": AFTER}
WANT = {
    "period_p90_ms": 300.0,  # the 90th of 100 is the last 0.3 s period
    "host_ms_per_period": 10.0,
    "join_ms_per_join": 50.0,
    "join_period_share_pct": 20.0,
    "lanes_live_mean": 4.0,
    "lanes_idle_queued_pct": 50.0,
    "compile_stall_s_in_window": 0.75,
    "load_s": 3.25,
}


@pytest.mark.parametrize("name", COUNTERS)
def test_reader_on_synthetic_counters(name):
    value = readers.read_metric(REPO, name, FACTS)
    # the histogram's buckets hold a percentile to 1%; the rest is exact
    assert value == pytest.approx(WANT[name], rel=0.011 if name == "period_p90_ms" else 1e-9)


@pytest.mark.parametrize("name", COUNTERS)
def test_reader_gives_none_where_the_program_has_no_such_block(name):
    """The parent commit's ``/stats``: no ``engine.period``, a ``compile``
    block of two keys, no ``startup``."""
    old = {"engine": {"joins": 3}, "compile": {"count": 3, "seconds": 1.0}}
    assert readers.read_metric(REPO, name, {"stats_before": old, "stats_after": old}) is None
    no_engine = {"compile": {"count": 0, "seconds": 0.0}}
    facts = {"stats_before": no_engine, "stats_after": no_engine}
    assert readers.read_metric(REPO, name, facts) is None


@pytest.mark.parametrize("name", COUNTERS[:6])
def test_reader_gives_none_for_a_window_without_periods(name):
    assert readers.read_metric(REPO, name, {"stats_before": AFTER, "stats_after": AFTER}) is None


def test_histogram_percentile_at_the_edges():
    def facts(counts):
        after = copy.deepcopy(BEFORE)
        after["engine"]["period"]["hist"]["counts"] = [
            a + b for a, b in zip(BEFORE["engine"]["period"]["hist"]["counts"], counts)]
        return {"stats_before": BEFORE, "stats_after": after}

    n = len(EDGES) + 1
    under, over = [3] + [0] * (n - 1), [0] * (n - 1) + [3]
    path = f"{period_stats.PERIOD}.hist"
    assert period_stats.hist_percentile(facts(under), path, 90) == EDGES[0]
    assert period_stats.hist_percentile(facts(over), path, 90) == EDGES[-1]
    one = [0] * n
    one[7] = 1
    assert period_stats.hist_percentile(facts(one), path, 50) == pytest.approx(
        math.sqrt(EDGES[6] * EDGES[7]))


def test_join_prefill_pattern_picks_joins_only():
    """``join_prefill_dev_ms`` reads the programs whose module is a join's;
    the older prefill metric keeps selecting joins and prefills both."""
    from bench import xplane

    ops = [("fusion.1", t + 0.001, t + 0.009) for t in (1.0, 2.0, 3.0, 4.0)]
    ops += [("edge", 0.0, 0.1), ("edge", 9.0, 9.1)]
    planes = {"/device:TPU:0": {
        xplane.OPS: ops,
        xplane.MODULES: [
            ("jit_prefill_join_paged_suffix(1)", 1.0, 1.010),
            ("jit_prefill_paged_suffix(2)", 2.0, 2.020),
            ("jit_prefill_join_paged(3)", 3.0, 3.030),
            ("jit_decode_chunk_paged(4)", 4.0, 4.040),
        ],
    }}
    join = readers.load_spec(REPO, "join_prefill_dev_ms")["pattern"]
    assert xplane.programs(planes, join) == pytest.approx([0.010, 0.030])
    prefill = readers.load_spec(REPO, "prefill_dev_tokens_per_s")["pattern"]
    assert xplane.programs(planes, prefill) == pytest.approx([0.010, 0.020, 0.030])
    facts = {"trace": {"programs": {"join_prefill_dev_ms": [0.010, 0.030]}}}
    assert readers.read_metric(REPO, "join_prefill_dev_ms", facts) == pytest.approx(20.0)
    assert readers.read_metric(REPO, "join_prefill_dev_ms", {"trace": None}) is None


def test_manifest_holds_the_new_entries():
    manifest = Manifest(REPO)
    manifest.check()
    cell = manifest.cell("mistral7b-chat-closed")
    by_name = {m["name"]: m for m in cell["per_layer"]}
    for name in COUNTERS + ["join_prefill_dev_ms"]:
        # every cell's: they read what every --api-batch server has
        assert "workloads" not in by_name[name]
        assert (REPO / f"bench/layer_metrics/{name}.json").exists()
    assert by_name["load_s"]["moves"] == "setup_s"
    assert {by_name[n]["moves"] for n in COUNTERS[:7]} == {"gap_p95_ms"}


def test_traced_rehearsal_prints_the_counters(tiny_root):
    """A tiny closed cell, added as files and entries: the period metrics
    are every cell's, so also its own. The served path on the CPU fills
    every counter the readers need, and the phases close on the periods'
    seconds."""
    add_cell(tiny_root, "tiny-period", "tiny-p", tiny_config(1, ONE_CHIP_FLAGS),
             "tiny-period", tiny_mix(CLOSED_LOOP))
    (tiny_root / ".bench_work/cold_pass").mkdir(parents=True, exist_ok=True)
    (tiny_root / ".bench_work/cold_pass/tiny-period.3").touch()

    r = run_bench(tiny_root, "--workload", "tiny-period", "--seed", "77", "--seconds", "3",
                  "--trace", "1", "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(COUNTERS) <= set(metrics)
    assert "join_prefill_dev_ms" not in metrics  # no device trace on the CPU
    assert metrics["period_p90_ms"] > 0 and metrics["host_ms_per_period"] > 0
    assert 0 < metrics["lanes_live_mean"] <= 4
    assert 0 <= metrics["lanes_idle_queued_pct"] <= 100
    assert 0 <= metrics["join_period_share_pct"] <= 100
    assert metrics["load_s"] > 0 and metrics["compile_stall_s_in_window"] >= 0
