"""Tracing, profiling, and memory observability.

The reference's observability is ad-hoc timers and log lines: worker ops/s and
wire B/s every 5 ops (worker.rs:19, 253-264), master tokens/s with first-token
exclusion (master.rs:67-73, 86-94), handshake latency echoed in WorkerInfo
(worker.rs:165-177), and resident memory printed at load/run via memory_stats
(cake/mod.rs:69-75). This module is the structured superset (SURVEY.md §5):

  * ``span(name)`` — thread-safe accumulating timers (count/total/min/max/last)
    with a process-global registry; ``snapshot()`` for machine consumption
    (the API's /stats endpoint), ``report()`` for logs.
  * ``jax_profile(dir)`` — context manager around ``jax.profiler`` traces: one
    xplane dump per entry, viewable in TensorBoard/XProf. This is the TPU-first
    answer to "no spans, no profiler hooks" in the reference.
  * ``memory_report()`` — host RSS plus per-device HBM stats (bytes_in_use /
    peak_bytes_in_use) where the backend exposes them.

Everything is dependency-free and safe to call on any backend (missing device
stats simply yield fewer fields).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field

log = logging.getLogger("cake_tpu.trace")


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    min_s: float = field(default=float("inf"))
    max_s: float = 0.0
    last_s: float = 0.0

    def add(self, dt: float) -> None:
        self.count += 1
        self.total_s += dt
        self.min_s = min(self.min_s, dt)
        self.max_s = max(self.max_s, dt)
        self.last_s = dt

    def to_dict(self) -> dict:
        mean = self.total_s / self.count if self.count else 0.0
        return {
            "count": self.count,
            "total_s": round(self.total_s, 6),
            "mean_s": round(mean, 6),
            "min_s": round(self.min_s, 6) if self.count else 0.0,
            "max_s": round(self.max_s, 6),
            "last_s": round(self.last_s, 6),
        }


class SpanRegistry:
    """Process-global named timers. One instance (``spans``) serves the whole
    runtime; tests may build private ones.

    With ``timeline=True`` (the global instance) every span ALSO lands as a
    structured event on the obs timeline (cake_tpu/obs/timeline.py) with both
    wall and monotonic timestamps — so the accumulated per-hop/stage timers
    and the Perfetto view are the same instrumentation, merged without clock
    skew. Private registries stay pure accumulators.
    """

    def __init__(self, timeline: bool = False) -> None:
        self._lock = threading.Lock()
        self._stats: dict[str, SpanStats] = {}
        self._timeline = timeline

    @contextlib.contextmanager
    def span(self, name: str, timeline: bool | None = None, **attrs):
        """``timeline=False`` keeps a call out of the obs timeline while
        still accumulating — for sites whose round trip is ALREADY a
        structured span one frame deeper (master hop vs client wire span),
        where bridging both would record the same latency twice."""
        bridge = self._timeline if timeline is None else timeline
        with contextlib.ExitStack() as stack:
            if bridge:
                from cake_tpu.obs.timeline import timeline as _tl

                stack.enter_context(
                    _tl.span(name, rid=attrs.pop("rid", None), args=attrs or None)
                )
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def add(self, name: str, dt: float) -> None:
        with self._lock:
            s = self._stats.get(name)
            if s is None:
                s = self._stats[name] = SpanStats()
            s.add(dt)

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            return {k: v.to_dict() for k, v in self._stats.items()}

    def report(self) -> str:
        lines = []
        for name, d in sorted(self.snapshot().items()):
            lines.append(
                f"{name}: n={d['count']} mean={d['mean_s'] * 1e3:.2f}ms "
                f"last={d['last_s'] * 1e3:.2f}ms total={d['total_s']:.2f}s"
            )
        return "\n".join(lines)

    def clear(self) -> None:
        with self._lock:
            self._stats.clear()


spans = SpanRegistry(timeline=True)
span = spans.span  # module-level convenience: `with trace.span("hop.w0"): ...`


@contextlib.contextmanager
def jax_profile(trace_dir: str | None, host_python: bool = True):
    """Capture a JAX/XLA profiler trace (xplane) into ``trace_dir``.

    No-op when trace_dir is falsy, so callers can thread a CLI flag straight
    through. View with TensorBoard's profile plugin or xprof. With
    ``host_python`` off, Python's own tracer stays off (it slows the host it
    watches): the host plane keeps jit dispatches and the engine's spans
    (obs/timeline.py) — a window of a running server.
    """
    if not trace_dir:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    if not host_python:
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", trace_dir)


def memory_report() -> dict:
    """Host RSS + per-device memory stats (where the backend exposes them)."""
    out: dict = {}
    try:
        import resource

        # ru_maxrss is KiB on Linux.
        out["host_peak_rss_bytes"] = (
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        )
    except (ImportError, AttributeError, OSError):  # pragma: no cover - non-POSIX
        log.debug("host RSS unavailable (no POSIX resource module)")
    try:
        import jax

        devices = []
        for d in jax.local_devices():
            entry: dict = {"device": str(d)}
            stats = getattr(d, "memory_stats", None)
            if callable(stats):
                try:
                    s = stats() or {}
                    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                        if k in s:
                            entry[k] = int(s[k])
                except Exception as e:  # backend-specific failure modes
                    log.debug("memory_stats failed for %s: %s", d, e)
            devices.append(entry)
        out["devices"] = devices
    except (ImportError, RuntimeError) as e:  # pragma: no cover - no jax/backend
        log.debug("device memory stats unavailable: %s", e)
    return out


def log_memory(tag: str) -> None:
    """Log a one-line memory summary (parity with the reference's resident-
    memory printouts at load/run, cake/mod.rs:69-75, worker.rs:112-116)."""
    m = memory_report()
    rss = m.get("host_peak_rss_bytes")
    parts = [f"host_peak_rss={rss / 1e9:.2f}GB"] if rss else []
    for d in m.get("devices", []):
        if "bytes_in_use" in d:
            parts.append(f"{d['device']}={d['bytes_in_use'] / 1e9:.2f}GB")
    log.info("[mem:%s] %s", tag, " ".join(parts) or "n/a")
