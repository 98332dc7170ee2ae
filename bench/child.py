"""The process that holds the chip: writes the checkpoint if it is missing,
then serves through the program's own entry point, ``cake_tpu.cli.main``,
on the main thread. A second thread answers the benchmark's few requests
that only this process can meet: open and close the profiler's window,
reduce the trace, run the reference beside the served weights.

    python -m bench.child --control PORT --config FILE --model-dir DIR \
        --api HOST:PORT [--rehearse-cpu]

Commands and replies are JSON lines over a loopback socket the parent opened.
"""

from __future__ import annotations

import argparse
import glob
import json
import socket
import sys
import threading
import time
import traceback
from pathlib import Path


def known_flags(flags: list[str]) -> tuple[list[str], list[str]]:
    """The configuration's server flags without those the program's parser
    no longer has (a later PR may delete an option whose path won; a
    configuration file that is there may not be edited). A dropped flag takes
    its values with it."""
    from cake_tpu.cli import build_parser

    known = {s for a in build_parser()._actions for s in a.option_strings}
    kept, dropped, keep = [], [], True
    for tok in flags:
        if tok.startswith("--"):
            keep = tok in known
            if not keep:
                dropped.append(tok)
        if keep:
            kept.append(tok)
    return kept, dropped


class Control:
    def __init__(self, port: int, config: dict, arch, model_dir: Path):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.lines = self.sock.makefile("r")
        self.config, self.arch, self.model_dir = config, arch, model_dir
        self.trace_dir: str | None = None

    def send(self, obj: dict) -> None:
        self.sock.sendall((json.dumps(obj) + "\n").encode())

    def serve(self) -> None:
        for line in self.lines:
            msg = json.loads(line)
            try:
                reply = getattr(self, "do_" + msg["cmd"])(msg)
            except Exception:  # noqa: BLE001 - reported to the parent, which fails the run
                reply = {"error": traceback.format_exc()}
            self.send(reply)

    def do_trace_start(self, msg: dict) -> dict:
        import jax

        opts = jax.profiler.ProfileOptions()
        # Python's own tracer slows the host it is meant to watch; level 1
        # of the host tracer keeps jit dispatch spans.
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.trace_dir = msg["dir"]
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        return {"started": time.perf_counter()}

    def do_trace_stop(self, msg: dict) -> dict:
        import jax

        jax.profiler.stop_trace()
        return {"stopped": time.perf_counter()}

    def do_trace_reduce(self, msg: dict) -> dict:
        from bench import xplane

        files = glob.glob(f"{self.trace_dir}/plugins/profile/*/*.xplane.pb")
        if not files:
            return {"error": f"no .xplane.pb under {self.trace_dir}"}
        planes = xplane.load(files[0])
        # a pattern with ``op`` asks for one named kernel, else for whole programs
        by_op = {k: p for k, p in msg["patterns"].items() if "op" in p}
        out = {
            "summary": xplane.summary(planes),
            "programs": {k: xplane.programs(planes, p) for k, p in msg["patterns"].items()
                         if k not in by_op},
            "ops": {k: xplane.op_times(planes, p) for k, p in by_op.items()},
            "inventory": xplane.inventory(planes),
        }
        if msg.get("keep_events"):
            with open(msg["keep_events"], "w") as f:
                json.dump(planes, f)
        return out

    def do_judge(self, msg: dict) -> dict:
        from bench import reference
        from bench.checkpoint import Reader
        from bench.manifest import model_config

        t0 = time.perf_counter()
        # A rehearsal serves float32, which differs from the reference only
        # by the order of sums.
        tolerance = self.config["judge"]["rehearsal_tolerance" if msg["rehearsal"]
                                         else "tolerance"]
        out = reference.judge(
            self.arch, Reader(self.model_dir), model_config(self.config), tolerance,
            msg["probes"],
        )
        out["seconds"] = time.perf_counter() - t0
        return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", type=int, required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--api", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    with open(args.config) as f:
        config = json.load(f)
    model_dir = Path(args.model_dir)
    from bench.manifest import architecture, model_config

    # the one place this process learns the architecture: checkpoint and judge
    arch = architecture(Path(__file__).resolve().parents[1], config)
    control = Control(args.control, config, arch, model_dir)

    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()  # the program's own cache, before anything compiles
    device = describe_devices()
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"bench.child: no TPU, JAX is on {device}", file=sys.stderr)
        return 3
    from bench.checkpoint import READY, write_checkpoint

    wrote = None
    if not (model_dir / READY).exists():
        dtype = "f32" if args.rehearse_cpu else config["served_dtype"]
        wrote = write_checkpoint(model_dir, model_config(config), dtype,
                                 config["weights_seed"], arch)
    flags, dropped = known_flags(config["server_flags"])
    if args.rehearse_cpu:
        flags += ["--cpu", "--dtype", "f32"]
    control.send({"event": "starting", "checkpoint": wrote, "dropped_flags": dropped,
                  "device": device})
    threading.Thread(target=control.serve, daemon=True).start()

    from cake_tpu.cli import main as serve

    return serve(["--model", str(model_dir), "--api", args.api, *flags])


if __name__ == "__main__":
    sys.exit(main())
