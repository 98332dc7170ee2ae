"""The parent's side of the server: start ``bench.child``, wait for it to
serve, talk to it, stop it. Stdlib only; the parent never imports JAX, so
the chip stays the child's."""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from pathlib import Path


class ServerFailed(RuntimeError):
    pass


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, root: Path, config_file: Path, model_dir: Path,
                 log_path: Path, *, rehearse_cpu: bool, chips: int):
        self.root, self.log_path = root, log_path
        self.base = f"http://127.0.0.1:{_free_port()}"
        self._listener = socket.create_server(("127.0.0.1", 0))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(root)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        )
        cmd = []
        if rehearse_cpu:
            # A rehearsal shares its machine with whatever else runs there
            # (the repo's tests, under several workers): one thread, low
            # priority. Nothing of this reaches a measured run.
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={chips} "
                "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
            )
            cmd = ["nice", "-n", "10"]
        cmd += [
            sys.executable, "-m", "bench.child",
            "--control", str(self._listener.getsockname()[1]),
            "--config", str(config_file), "--model-dir", str(model_dir),
            "--api", self.base.removeprefix("http://"),
        ] + (["--rehearse-cpu"] if rehearse_cpu else [])
        log_path.parent.mkdir(parents=True, exist_ok=True)
        with open(log_path, "w") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=root, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        self._conn = None
        self._lines = None

    def _fail(self, what: str) -> ServerFailed:
        try:
            with open(self.log_path, errors="replace") as f:
                tail = "".join(f.readlines()[-40:])
        except OSError:
            tail = "(no log)"
        return ServerFailed(f"{what}\n--- end of {self.log_path} ---\n{tail}")

    def _alive(self, what: str) -> None:
        if self.proc.poll() is not None:
            raise self._fail(f"server exited with code {self.proc.returncode} {what}")

    def wait_started(self, timeout_s: float) -> dict:
        """The child's first message: what it wrote and on which device."""
        deadline = time.monotonic() + timeout_s
        self._listener.settimeout(1.0)
        while self._conn is None:
            self._alive("before it reported")
            if time.monotonic() > deadline:
                raise self._fail(f"server did not report in {timeout_s:.0f} s")
            try:
                self._conn, _ = self._listener.accept()
            except TimeoutError:
                pass
        self._conn.settimeout(1.0)
        self._lines = self._read_lines()
        while True:
            self._alive("while writing the checkpoint")
            if time.monotonic() > deadline:
                raise self._fail(f"server did not start in {timeout_s:.0f} s")
            msg = next(self._lines)
            if msg is not None:
                return msg

    def _read_lines(self):
        buf = b""
        while True:
            while b"\n" not in buf:
                try:
                    got = self._conn.recv(1 << 20)
                except TimeoutError:
                    yield None
                    continue
                if not got:
                    raise self._fail("the server closed its control socket")
                buf += got
            line, buf = buf.split(b"\n", 1)
            yield json.loads(line)

    def wait_health(self, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        while True:
            self._alive("before /health answered")
            if time.monotonic() > deadline:
                raise self._fail(f"/health did not answer in {timeout_s:.0f} s")
            try:
                return self.get("/health", timeout=2)
            except (OSError, ValueError):
                time.sleep(0.25)

    def get_text(self, route: str, timeout: float = 30.0) -> str:
        with urllib.request.urlopen(self.base + route, timeout=timeout) as r:
            return r.read().decode("utf-8", "replace")

    def get(self, route: str, timeout: float = 30.0) -> dict:
        return json.loads(self.get_text(route, timeout))

    def call(self, cmd: str, timeout_s: float = 300.0, **fields) -> dict:
        self._conn.sendall((json.dumps({"cmd": cmd, **fields}) + "\n").encode())
        deadline = time.monotonic() + timeout_s
        while True:
            self._alive(f"during {cmd}")
            if time.monotonic() > deadline:
                raise self._fail(f"{cmd} did not answer in {timeout_s:.0f} s")
            msg = next(self._lines)
            if msg is not None:
                if "error" in msg:
                    raise ServerFailed(f"{cmd} failed in the server:\n{msg['error']}")
                return msg

    def stop(self) -> None:
        """End the child and everything in its session; wait until it has."""
        for sig in (signal.SIGTERM, signal.SIGKILL):
            if self.proc.poll() is None:
                try:
                    os.killpg(self.proc.pid, sig)
                    self.proc.wait(timeout=20)
                except (ProcessLookupError, subprocess.TimeoutExpired):
                    pass
        for s in (self._conn, self._listener):
            if s is not None:
                s.close()
