"""``bench.run`` end to end on the CPU, from a temporary copy to which a
configuration, two mixes, two cells and three per-layer metrics were added as
files and entries only. Also: what it does with no TPU, and with no program."""

from __future__ import annotations

import json

import pytest

from conftest import (CLOSED_LOOP, ONE_CHIP_FLAGS, OPEN_LOOP, add_cell, copy_benchmark,
                      last_json, run_bench, tiny_config, tiny_mix)

# A reader of a later PR's own, for its open cell alone.
GEN_LATE = '''\
from bench.stats import percentile


def read(facts, spec):
    p = percentile(facts["late_s"], 95)
    return None if p is None else p * 1e3
'''


@pytest.fixture(scope="module")
def root(tiny_root):
    config = tiny_config(1, ONE_CHIP_FLAGS)
    add_cell(tiny_root, "tiny-open", "tiny", config, "tiny-open", tiny_mix(OPEN_LOOP))
    add_cell(tiny_root, "tiny-closed", "tiny-b", config, "tiny-closed", tiny_mix(CLOSED_LOOP))
    metrics = tiny_root / "bench/layer_metrics"
    (metrics / "joins_in_window.json").write_text(
        json.dumps({"kind": "stats_delta", "path": "engine.joins"}))
    # one named kernel's device time, as data; a CPU has no device trace to give it
    (metrics / "decode_attention_us.json").write_text(json.dumps(
        {"kind": "op_mean_us", "pattern": {"op": "decode_attention", "module": "^jit_"}}))
    (metrics / "gen_late_p95_ms.json").write_text(json.dumps({"kind": "python"}))
    (metrics / "gen_late_p95_ms.py").write_text(GEN_LATE)
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [
        {"name": "joins_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "engine", "moves": "gap_p95_ms"},
        {"name": "decode_attention_us", "unit": "us", "better": "lower",
         "source": "device_trace", "layer": "kernels", "moves": "gap_p95_ms"},
        {"name": "gen_late_p95_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "load generator", "moves": "gap_p95_ms",
         "workloads": ["tiny-open"]},
    ]
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    # as if tiny-open had run here before: only tiny-closed makes its cold pass
    (tiny_root / ".bench_work/cold_pass").mkdir(parents=True)
    (tiny_root / ".bench_work/cold_pass/tiny-open.2").touch()
    return tiny_root


# The committed cell's loop with and without the trace; an open loop once (each
# run is a server on this machine's CPU beside the repo's other tests).
@pytest.mark.parametrize("loop,trace", [("open", 1), ("closed", 0), ("closed", 1)])
def test_rehearsal_through_the_served_path(root, loop, trace):
    seconds = 2 if loop == "open" else 3  # time for some requests to end inside it
    first_here = not (root / f".bench_work/cold_pass/tiny-{loop}.{seconds}").exists()
    r = run_bench(root, "--workload", f"tiny-{loop}", "--seed", str(2**31 + 17),
                  "--seconds", str(seconds), "--trace", str(trace), "--rehearse-cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    out = last_json(r.stdout)
    assert out["rehearsal"] is True and out["device"]["platform"] == "cpu"
    assert out["correct"] is True and out["failed"] == 0
    assert ("cold pass:" in r.stdout) is first_here is (loop == "closed" and trace == 0)
    assert f"finished_length={out['attempted']} " in r.stdout  # no answer stops early
    if loop == "open":
        assert out["attempted"] == 6  # every request due in the window
    else:
        assert out["attempted"] >= 1  # those that ended inside it
    samples = out["client"]["samples"]
    assert samples["requests"] == out["attempted"] == samples["ttft"] > 0
    assert samples["tokens"] > 0
    assert out["client"]["tokens_per_s"] * seconds == pytest.approx(samples["tokens"])
    assert out["client"]["ttft_p50_ms"] > 0 and samples["gaps"] > 0
    assert "dropped_flags=['--an-option-a-later-pr-deleted']" in r.stdout
    assert all("platform=cpu-rehearsal" in line for line in r.stdout.splitlines()[:-1])
    names = set(out["metrics"])
    if trace == 0:
        assert names == {"gap_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in out["metrics"].values())
    else:
        # host metrics and counts, the added ones and the engine's own account of
        # its periods among them (every cell's); nothing of a device
        want = {"tpot_p50_ms", "batch_occupancy_mean", "compiles_in_window",
                "joins_in_window", "period_p90_ms", "host_ms_per_period",
                "join_ms_per_join", "join_period_share_pct", "lanes_live_mean",
                "lanes_idle_queued_pct", "compile_stall_s_in_window", "load_s",
                } | ({"gen_late_p95_ms"} if loop == "open" else set())
        # a window in which no request happened to join has no join to time
        assert want - {"join_ms_per_join"} <= names <= want
        assert "busy_s" not in out["device"] and "breakdown" not in out


def test_no_tpu_means_no_result(root):
    r = run_bench(root, "--workload", "tiny-open", "--seed", "1", "--seconds", "1",
                  "--trace", "0")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_nothing_without_the_program(tmp_path):
    bare = copy_benchmark(tmp_path)
    r = run_bench(bare, "--workload", "mistral7b-chat-closed", "--seed", "1",
                  "--seconds", "1", "--trace", "0", timeout=60)
    # the repo is still importable here through PYTHONPATH; the check is on
    # what lies beside bench/, which is what the driver's bare directory lacks
    assert r.returncode != 0 and "no cake_tpu package" in r.stderr
    assert not r.stdout.strip()
