"""One run of one cell.

    python3 -m bench.run --workload NAME --seed N --seconds S --trace 0|1

Starts the server as a user would (``cake_tpu.cli.main`` with ``--model DIR
--api HOST:PORT --api-batch N``, in ``bench.child``), warms up, offers the
cell's traffic for S seconds, checks a fixed set of probes against the plain
reference, and prints one JSON object as the last line. A checkout's first
run of a cell first offers the traffic once unmeasured (``cold_pass``). Without a TPU it exits non-zero and prints no
result; ``--rehearse-cpu`` walks the same path on the CPU and says so in
every line it prints. This process never imports JAX.
"""

from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import threading
import time
from pathlib import Path

T_START = time.perf_counter()

from bench import readers, stats, traffic  # noqa: E402
from bench.client import Load  # noqa: E402
from bench.manifest import Manifest, ManifestError  # noqa: E402
from bench.server import Server, ServerFailed  # noqa: E402
from bench.tokens import Vocabulary  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
WORK = ROOT / ".bench_work"  # listed in .gitignore; checkpoints, logs, traces
TRACE_AT = 0.3  # the profiler's window opens this far into the measured one
TRACE_SECONDS = 4.0  # unless the cell's file says ``trace_seconds``
PROBE_NEW_TOKENS = 32
GAUGE_PERIOD_S = 0.5
WARMUP_CUT = 17  # tokens: the prefill's and two decode dispatches'


def say(platform: str, msg: str) -> None:
    print(f"[bench platform={platform}] {msg}", flush=True)


def build_native() -> list[str]:
    """``cake_tpu/native/*.so`` are not in git: build them when absent, as
    ``chip_smoke.py`` does. Without them the Python codec serves."""
    native = ROOT / "cake_tpu" / "native"
    if not list(native.glob("*.so")):
        subprocess.run([sys.executable, "-m", "cake_tpu.native.build"], cwd=ROOT,
                       capture_output=True, timeout=300, check=False)
    return sorted(p.name for p in native.glob("*.so"))


def wait_idle(server: Server, timeout_s: float = 60.0) -> None:
    """Until the engine holds no live lane and ends no more requests: cut
    warm-up requests take a step or two to leave."""
    deadline = time.monotonic() + timeout_s
    quiet, last = 0, None
    while time.monotonic() < deadline:
        seq = server.get("/requests?limit=1")["last_seq"]
        lanes = _gauge(server.get_text("/metrics"), "cake_batch_occupancy")
        quiet = quiet + 1 if (not lanes and seq == last) else 0
        if quiet >= 3:
            return
        last = seq
        time.sleep(0.1)
    raise ServerFailed("the engine did not go idle")


def _gauge(metrics_text: str, name: str) -> float | None:
    for line in metrics_text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


class GaugeSampler(threading.Thread):
    """Samples gauges of ``GET /metrics`` through the window (traced runs
    only: the program keeps no time average of its batch occupancy)."""

    def __init__(self, server: Server, names: list[str]):
        super().__init__(daemon=True)
        self.server, self.names = server, names
        self.samples: dict[str, list[float]] = {n: [] for n in names}
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(GAUGE_PERIOD_S):
            try:
                text = self.server.get_text("/metrics", timeout=2)
            except OSError:
                continue
            for n in self.names:
                v = _gauge(text, n)
                if v is not None:
                    self.samples[n].append(v)

    def stop(self) -> dict:
        self._halt.set()
        self.join(5)
        return self.samples


def offer(load: Load, mix: dict, seed: int, seconds: float, vocab: Vocabulary,
          at_t0=lambda: None) -> float:
    """Offer the mix for ``seconds``; returns the window's start, t0. An
    open loop starts at t0. A closed loop starts ``lead_in_s`` before it, so
    that the window sees the loop under way and not its first admission,
    and runs on to the window's end. ``at_t0`` is called at t0."""
    if mix["loop"] == "open":
        at_t0()
        t0 = time.perf_counter()
        load.run_open(traffic.open_requests(mix, seed, seconds, vocab), t0)
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        return t0
    t0 = time.perf_counter() + mix["lead_in_s"]
    callers = threading.Thread(
        target=load.run_closed, daemon=True,
        args=(traffic.closed_requests(mix, seed, vocab), mix["clients"], t0 + seconds,
              mix["min_send_gap_s"]),
    )
    callers.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    at_t0()
    callers.join()
    return t0


def memory_line(server: Server) -> str:
    """In use / peak per device in GB, as the program's memwatch reads them."""
    devices = server.get("/stats")["memwatch"]["devices"]
    return " ".join(
        f"{(d.get('bytes_in_use') or 0) / 1e9:.2f}/{(d.get('peak_bytes_in_use') or 0) / 1e9:.2f}"
        for d in devices
    ) or "none reported"


def device_bytes(stats_body: dict, field: str) -> int:
    """``field`` of the program's memwatch on the fullest chip; the CPU
    reports none."""
    return max((d.get(field) or 0 for d in stats_body["memwatch"]["devices"]), default=0)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-events", action="store_true",
                    help="with --trace 1, also write the trace's events as JSON "
                    "(how bench/testdata's recorded trace was made)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the run on the CPU: a rehearsal of the harness, "
                    "never a result; no device metric is printed")
    args = ap.parse_args(argv)
    platform = "cpu-rehearsal" if args.rehearse_cpu else "tpu"
    try:
        cell = Manifest(ROOT).cell(args.workload)
    except ManifestError as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    cell["vocab"] = Vocabulary(cell["architecture"], cell["config"])
    if not (ROOT / "cake_tpu" / "cli.py").exists():
        print("bench.run: no cake_tpu package beside bench/: the benchmark "
              "measures that program and is nothing without it", file=sys.stderr)
        return 2
    say(platform, f"cell={cell['name']} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} native={build_native()}")

    def start() -> Server:
        return Server(
            ROOT, ROOT / cell["config_file"], WORK / "models" / cell["config_name"],
            WORK / "logs" / f"{cell['name']}.log",
            rehearse_cpu=args.rehearse_cpu, chips=cell["entry"]["chips"],
        )

    server = start()
    try:
        passed = WORK / "cold_pass" / f"{cell['name']}.{args.seconds:g}"
        if not passed.exists():
            cold_pass(args, cell, server, platform)
            server.stop()
            server = start()
            passed.parent.mkdir(parents=True, exist_ok=True)
            passed.touch()
        return measure(args, cell, server, platform)
    except ServerFailed as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 1
    finally:
        server.stop()


def cold_pass(args, cell: dict, server: Server, platform: str) -> None:
    """A checkout's first run of a cell: offer the window's traffic once
    before anything is measured, so that every program it leads the engine
    through is in the persistent compile cache, and start the server anew. A
    window that compiles runs at half the speed and so through half the
    requests; the run after it would meet the other half cold. A closed loop
    is offered ``cold_pass_factor`` times as long, since compiling slows it."""
    mix, vocab = cell["mix"], cell["vocab"]
    server.wait_started(timeout_s=900)
    server.wait_health(timeout_s=900)
    t = time.perf_counter()
    load = Load(server.base, vocab)
    if mix["loop"] == "open":
        offer(load, mix, args.seed, args.seconds, vocab)
        load.finish(mix["drain_s"])
    else:
        offer(load, mix, args.seed, args.seconds * mix["cold_pass_factor"], vocab)
        load.finish(0.0)
    compiled = server.get("/stats")["compile"]
    say(platform, f"cold pass: {len(load.outcomes)} requests in "
        f"{time.perf_counter() - t:.1f} s, {compiled['count']} programs compiled in "
        f"{compiled['seconds']} s; the server starts anew")


def measure(args, cell: dict, server: Server, platform: str) -> int:
    config, mix, vocab = cell["config"], cell["mix"], cell["vocab"]
    started = server.wait_started(timeout_s=900)
    t_written = time.perf_counter()
    health = server.wait_health(timeout_s=900)
    t_serving = time.perf_counter()
    if not args.rehearse_cpu and health["platform"] != "tpu":
        raise ServerFailed(f"/health names platform {health['platform']!r}, not a TPU")
    if health["device_count"] < cell["entry"]["chips"]:
        raise ServerFailed(
            f"the cell needs {cell['entry']['chips']} chip(s), JAX sees "
            f"{health['device_count']}"
        )
    say(platform, f"device={health['device_kind']} x{health['device_count']} "
        f"attention_impl={health.get('attention_impl')} "
        f"checkpoint={started['checkpoint']} dropped_flags={started['dropped_flags']}")

    mem_loaded = memory_line(server)
    # Warm-up, the same in every run: quantiles of the prompt lengths alone,
    # then (open loops) the mix itself for a few seconds, cut short. A closed
    # loop's lead-in is its warm-up.
    load = Load(server.base, vocab)
    alone = load.run_each(traffic.warmup_requests(mix, vocab), cut_after=WARMUP_CUT)
    bad = [o for o in alone if o.status != 200 or not o.arrivals]
    if bad:
        raise ServerFailed(f"warm-up request failed: status {bad[0].status} {bad[0].error}")
    if mix["loop"] == "open" and mix["warmup"].get("mix_seconds"):
        offer(load, mix, mix["order_seed"], mix["warmup"]["mix_seconds"], vocab)
        load.finish(drain_s=0.0)
    wait_idle(server)
    t_warm = time.perf_counter()
    mem_warm = memory_line(server)

    sampler = GaugeSampler(server, ["cake_batch_occupancy"]) if args.trace else None
    trace = {} if args.trace else None
    before = {}

    def at_t0() -> None:
        before["stats"] = server.get("/stats")
        before["seq"] = server.get("/requests?limit=1")["last_seq"]
        if args.trace:
            sampler.start()
            tracer.start()

    if args.trace:
        seconds = cell["file"].get("trace_seconds", TRACE_SECONDS)
        tracer = threading.Thread(
            target=take_trace, daemon=True,
            args=(server, TRACE_AT * args.seconds, min(seconds, 0.5 * args.seconds), trace),
        )
    load = Load(server.base, vocab)
    t0 = offer(load, mix, args.seed, args.seconds, vocab, at_t0)
    setup_s = t0 - T_START
    drained = load.finish(mix["drain_s"] if mix["loop"] == "open" else 0.0)
    gauges = sampler.stop() if sampler else {}
    if args.trace:
        tracer.join()  # the control socket carries one call at a time
    stats_before, stats_after = before["stats"], server.get("/stats")
    log = server.get(f"/requests?since={before['seq']}")["requests"]
    mem_window = memory_line(server)
    e2e = stats.end_to_end(load.outcomes, t0, args.seconds, mix["loop"])
    if not e2e["attempted"]:
        raise ServerFailed("no request of the window came to an end")

    # Probes: a fixed set from the seed, greedy, one at a time on the idle
    # engine after the window, judged by the plain reference.
    wait_idle(server)
    t_probe = time.perf_counter()
    probe_reqs = traffic.probe_requests(cell["file"]["probe_prompt_tokens"],
                                        PROBE_NEW_TOKENS, args.seed, vocab)
    probes = Load(server.base, vocab).run_each(probe_reqs)
    bad = [o.failure() for o in probes if o.failure()]
    if bad:
        raise ServerFailed(f"probe request failed: {bad[0]}")
    verdict = server.call("judge", rehearsal=args.rehearse_cpu, probes=[
        {"context": vocab.chat_ids(o.request.prompt_ids), "served": o.served_ids()}
        for o in probes
    ])
    stats_end = server.get("/stats")
    say(platform, f"set-up {setup_s:.1f} s: checkpoint+start {t_written - T_START:.1f}, "
        f"load+compile {t_serving - t_written:.1f}, warm-up {t_warm - t_serving:.1f}, "
        f"lead-in {t0 - t_warm:.1f}; compiles before the window "
        f"{stats_before['compile']['count']} in {stats_before['compile']['seconds']} s; "
        f"after it: probes and reference {time.perf_counter() - t_probe:.1f} s")
    say(platform, f"window: attempted={e2e['attempted']} failed={e2e['failed']} "
        f"finished_length={e2e['finished_length']} samples={e2e['samples']} "
        f"drain={drained:.1f} s compiles_in_window="
        f"{stats_after['compile']['count'] - stats_before['compile']['count']} "
        f"client={ {k: v and round(v, 1) for k, v in e2e['values'].items()} }")
    say(platform, f"memory GB in use/peak per device: loaded {mem_loaded}; warmed up "
        f"{mem_warm}; at the window's end {mem_window}")
    for index, why in list(e2e["failures"].items())[:5]:
        say(platform, f"  request {index} failed: {why}")
    say(platform, f"reference: correct={verdict['correct']} worst deficit "
        f"{verdict['worst']:.4f} of tolerance {verdict['tolerance']} over "
        f"{verdict['positions']} positions in {verdict['seconds']:.1f} s (weights to the "
        f"device {verdict['load_s']:.1f}, first layer {verdict['first_layer_s']:.1f}, "
        f"other layers {verdict['other_layers_s']:.1f}) "
        f"per probe {[round(x, 4) for x in verdict['per_probe']]}")

    device = {
        "platform": health["platform"], "kind": health["device_kind"],
        "count": health["device_count"],
        "memory_peak_bytes": device_bytes(stats_end, "peak_bytes_in_use"),
        # what the deployment holds while it serves, beside the load's peak
        "memory_in_use_bytes": device_bytes(stats_after, "bytes_in_use"),
    }
    # ``client``: everything the client's clock gave, with the counts behind
    # each percentile, in traced and untraced runs alike; no bound is on it.
    out = {"correct": verdict["correct"], "attempted": e2e["attempted"],
           "failed": e2e["failed"], "metrics": {}, "device": device,
           "client": {**e2e["values"], "samples": e2e["samples"]}}
    if args.rehearse_cpu:
        out["rehearsal"] = True
    if not args.trace:
        values = dict(e2e["values"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                out["metrics"][m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        reduced = reduce_trace(server, cell, trace, args.keep_events)
        facts = {
            "cell": cell["name"], "config": config, "architecture": cell["architecture"],
            "device": health,
            "outcomes": load.outcomes, "late_s": e2e["late_s"],
            "requests": {r["request_id"]: r for r in log},
            "stats_before": stats_before, "stats_after": stats_after,
            "gauges": gauges, "trace": reduced,
        }
        for m in cell["per_layer"]:
            value = readers.read_metric(ROOT, m["name"], facts)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        summary = (reduced or {}).get("summary")
        if summary and summary["chips"]:
            busy = summary["busy_s_per_chip"]
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = summary["window_s"]
            out["breakdown"] = {
                "device_ops": [[n[:120], t] for n, t in summary["device_ops"]],
                "idle_gaps": summary["idle_gaps"],
            }
            say(platform, f"trace: window {summary['window_s']:.3f} s, busy per chip "
                f"{[round(b, 3) for b in busy]}, closing it took "
                f"{trace['stop_took_s']:.1f} s")
    print(json.dumps(out), flush=True)
    return 0


def take_trace(server: Server, at: float, seconds: float, trace: dict) -> None:
    """Started at t0: the profiler's window opens ``at`` seconds later."""
    time.sleep(at)
    trace["dir"] = str(WORK / "trace")
    shutil.rmtree(trace["dir"], ignore_errors=True)
    trace["t_start"] = server.call("trace_start", dir=trace["dir"])["started"]
    time.sleep(seconds)
    t = time.perf_counter()
    trace["t_stop"] = server.call("trace_stop")["stopped"]
    trace["stop_took_s"] = trace["t_stop"] - t


def reduce_trace(server: Server, cell: dict, trace: dict, keep_events: bool) -> dict | None:
    if "t_stop" not in trace:
        return None
    patterns = {}
    for m in cell["per_layer"]:
        spec = readers.load_spec(ROOT, m["name"])
        if "pattern" in spec:
            patterns[m["name"]] = spec["pattern"]
    reduced = server.call(
        "trace_reduce", patterns=patterns, timeout_s=300,
        keep_events=str(WORK / "trace" / "events.json") if keep_events else None,
    )
    with open(WORK / "trace" / "inventory.json", "w") as f:
        json.dump(reduced.pop("inventory"), f, indent=1)
    return {**reduced, "t_start": trace["t_start"], "t_stop": trace["t_stop"]}


if __name__ == "__main__":
    sys.exit(main())
