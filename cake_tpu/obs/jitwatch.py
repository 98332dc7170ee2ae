"""Jit retrace/compile watchdog: make "it never retraces" a runtime invariant.

The paged serving path's core perf promise — block tables are traced operands,
so join/growth/release never retrace (PR 4) — and the static lint rules (PR 2/3)
both assert jit DISCIPLINE, but nothing at runtime counted what jax actually
did. This module wraps the project's jit families in a tracker:

  * ``tracked_jit(fn, name=..., **jit_kwargs)`` — a drop-in ``jax.jit`` whose
    wrapped body bumps a per-name trace counter AT TRACE TIME (the body only
    runs while jax is tracing, so the count is exact, with zero steady-state
    overhead: a cache hit never enters Python).
  * Traces land in ``cake_jit_traces_total{fn}``; the wall time of each
    tracing call (trace + lower + backend compile, the thing that stalls a
    serving epoch) lands in ``cake_jit_compile_seconds``.
  * A RETRACE — tracing a (name, abstract-signature) pair that was already
    traced in this process (an evicted-and-rebuilt wrapper recompiling the
    same program), or ANY trace while the watchdog is armed — increments
    ``cake_jit_retraces_total{fn}``, records a ``jit-retrace`` flight event,
    and (opt-in ``CAKE_RETRACE_FATAL=1``, for tests) raises RetraceError.
  * ``arm()`` declares warmup over: steady state must not trace at all.
    Tests warm the decode path, arm in fatal mode, and pin zero retraces.
  * The XLA module of a tracked function is named for its FAMILY (the label
    without its bracketed key, or ``module=``): a device trace then tells a
    decode chunk from a join by name. The call that traced records a
    ``compile`` span (label and argument shapes) on the timeline's engine
    track, so it also lands in an open profiler window.
  * ``install_compile_listener()`` taps ``jax.monitoring`` for process-wide
    XLA backend-compile seconds — ``GET /stats`` reports the total and
    ``chip_smoke.py`` prints it per phase, cold beside warm-cache.

Importing this module does NOT import jax; ``tracked_jit`` does (its callers
already have).
"""

from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
import time

from cake_tpu.obs.timeline import PROFILED_TRACK, timeline
from cake_tpu.utils import metrics


class RetraceError(RuntimeError):
    """A tracked jit function retraced while the watchdog was armed (or
    recompiled an already-compiled signature) under CAKE_RETRACE_FATAL=1."""


class JitWatch:
    """Process-global trace/compile bookkeeping for tracked jit families."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._traces: dict[str, int] = {}
        self._retraces: dict[str, int] = {}
        self._compile_s: dict[str, float] = {}
        self._sigs: dict[str, set] = {}
        self._armed = False

    # ------------------------------------------------------------- arming

    def arm(self) -> None:
        """Warmup is over: any tracked trace from now on is a retrace."""
        with self._lock:
            self._armed = True

    def disarm(self) -> None:
        with self._lock:
            self._armed = False

    @property
    def armed(self) -> bool:
        with self._lock:
            return self._armed

    @contextlib.contextmanager
    def expect_no_retrace(self):
        """Armed for the duration (tests: steady state must not trace)."""
        self.arm()
        try:
            yield
        finally:
            self.disarm()

    # ------------------------------------------------------------- recording

    def note_trace(self, name: str, sig) -> None:
        """Called from INSIDE the traced body — i.e. exactly once per trace."""
        with self._lock:
            self._traces[name] = self._traces.get(name, 0) + 1
            seen = self._sigs.setdefault(name, set())
            duplicate = sig in seen
            seen.add(sig)
            armed = self._armed
        metrics.registry.counter(
            "cake_jit_traces_total",
            "Times jax traced a tracked function (one compile each).",
        ).inc(fn=name)
        if duplicate or armed:
            why = "armed" if armed and not duplicate else "duplicate-signature"
            with self._lock:
                self._retraces[name] = self._retraces.get(name, 0) + 1
            metrics.registry.counter(
                "cake_jit_retraces_total",
                "Traces of a tracked function after warmup (armed watchdog) "
                "or of an already-compiled signature (rebuilt wrapper).",
            ).inc(fn=name)
            metrics.flight.record("jit-retrace", fn=name, reason=why)
            if os.environ.get("CAKE_RETRACE_FATAL") == "1":
                raise RetraceError(
                    f"jit retrace of {name!r} ({why}); steady state must not "
                    "trace — see cake_jit_traces_total{fn} for the history"
                )

    def note_compile(self, name: str, seconds: float) -> None:
        with self._lock:
            self._compile_s[name] = self._compile_s.get(name, 0.0) + seconds
        metrics.registry.histogram(
            "cake_jit_compile_seconds",
            "Wall time of each tracing call (trace + lower + XLA compile).",
        ).observe(seconds, fn=name)

    def trace_count(self, name: str) -> int:
        with self._lock:
            return self._traces.get(name, 0)

    def retrace_total(self) -> int:
        with self._lock:
            return sum(self._retraces.values())

    def snapshot(self) -> dict[str, dict]:
        with self._lock:
            names = set(self._traces) | set(self._compile_s)
            return {
                n: {
                    "traces": self._traces.get(n, 0),
                    "retraces": self._retraces.get(n, 0),
                    "compile_s": round(self._compile_s.get(n, 0.0), 6),
                }
                for n in sorted(names)
            }

    def by_family(self) -> dict[str, dict]:
        """{family: {count, seconds}}: traces and the wall of the calls that
        traced, summed over a family's keys (``batch.paged_join[w=64]`` and
        ``[w=128]`` are one family)."""
        out: dict[str, dict] = {}
        with self._lock:
            for label in set(self._traces) | set(self._compile_s):
                fam = out.setdefault(
                    family(label), {"count": 0, "seconds": 0.0}
                )
                fam["count"] += self._traces.get(label, 0)
                fam["seconds"] += self._compile_s.get(label, 0.0)
        return {
            name: {"count": f["count"], "seconds": round(f["seconds"], 6)}
            for name, f in sorted(out.items())
        }

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self._retraces.clear()
            self._compile_s.clear()
            self._sigs.clear()
            self._armed = False


watch = JitWatch()
arm = watch.arm
disarm = watch.disarm
expect_no_retrace = watch.expect_no_retrace
snapshot = watch.snapshot
retrace_total = watch.retrace_total


def _abstract_sig(args: tuple, kwargs: dict):
    """Hashable abstraction of a call: array leaves -> (shape, dtype), other
    leaves (statics: python scalars, strings, configs) -> their repr. Two
    calls sharing it would hit the same executable, so tracing it twice IS a
    recompile of an already-compiled program."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, tuple(sorted(
        kwargs.items()
    ))))
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            parts.append((tuple(shape), str(getattr(leaf, "dtype", "?"))))
        else:
            parts.append(repr(leaf)[:80])
    return (str(treedef), tuple(parts))


def family(label: str) -> str:
    """A label without its bracketed key: ``batch.join[w=64]`` -> ``batch.join``."""
    return label.split("[", 1)[0]


def _array_shapes(args: tuple) -> list[str]:
    """``dtype[shape]`` of a call's array arguments; pytree arguments (the
    weights, the cache) are left out: their shapes never tell two compiles
    of one family apart."""
    return [
        f"{a.dtype}{list(a.shape)}" for a in args
        if hasattr(a, "shape") and hasattr(a, "dtype")
    ]


# One slot per tracked call in flight on this thread (a tracked function
# traced inside another nests): None until the call's trace opens its
# ``compile`` span. A trace outside any call (``_jitted.lower``) opens none.
_in_flight = threading.local()


def _slots() -> list:
    try:
        return _in_flight.slots
    except AttributeError:
        _in_flight.slots = []
        return _in_flight.slots


def tracked_jit(
    fn, *, name: str | None = None, module: str | None = None, **jit_kwargs
):
    """``jax.jit`` with the watchdog attached; same call surface/donation.

    ``name`` labels the metrics series — include the builder's cache key for
    per-cached-entry functions (``batch.decode[n=8,t=0.0,...]``) so a rebuilt
    lru entry retracing its old signature is flagged, while two entries that
    legitimately share shapes are not. ``module`` names the XLA module
    (``jit_<module>``, what a device trace shows); by default the label's
    family as an identifier. A module name carries no shape.
    """
    import jax

    label = name or getattr(fn, "__name__", "jit")

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        # Runs ONLY while jax traces (a compile-cache hit never enters
        # Python), so this is the exact trace count.
        slots = _slots()
        if slots and slots[-1] is None:
            # Entered here, exited by ``call``: only a trace knows that the
            # call compiles, and only the call knows when that is over.
            slots[-1] = timeline.span(
                "compile", track=PROFILED_TRACK,
                args={"fn": label, "shapes": _array_shapes(args)},
            )
            slots[-1].__enter__()
        watch.note_trace(label, _abstract_sig(args, kwargs))
        return fn(*args, **kwargs)

    traced.__name__ = traced.__qualname__ = module or re.sub(
        r"\W", "_", family(label)
    )
    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        slots = _slots()
        slots.append(None)
        t0 = time.perf_counter()
        try:
            return jitted(*args, **kwargs)
        finally:
            span = slots.pop()
            if span is not None:
                # This call traced: the wall delta is trace+lower+compile
                # plus one async dispatch — compile dominates, and that is
                # the number a serving operator needs ("what stalled the
                # epoch").
                # cake-lint: disable-next-line=unblocked-timing
                watch.note_compile(label, time.perf_counter() - t0)
                span.__exit__(None, None, None)

    call._jitted = jitted  # escape hatch (lower/compile introspection)
    call._watch_name = label
    return call


# ------------------------------------------------- process-wide compile tap

_listener_lock = threading.Lock()
_listener_installed = False
_compile_events = 0
_compile_total_s = 0.0


def install_compile_listener() -> bool:
    """Tap jax.monitoring for EVERY backend compile in the process (tracked
    or not). Idempotent; returns False when the monitoring API is absent."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            import jax.monitoring as monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
        except (ImportError, AttributeError):
            return False
        _listener_installed = True
        return True


def _on_duration(name: str, seconds: float, **kw) -> None:
    global _compile_events, _compile_total_s
    if "backend_compile" in name:
        with _listener_lock:
            _compile_events += 1
            _compile_total_s += seconds


def compile_totals() -> tuple[int, float]:
    """(backend compiles seen, total seconds) since the listener went in."""
    with _listener_lock:
        return _compile_events, _compile_total_s


def compile_stats() -> dict:
    """The ``compile`` block of ``GET /stats``: every backend compile in the
    process (``count``, ``seconds``; with a warm persistent cache the time to
    fetch them), the wall the tracked calls that traced spent doing so
    (``stall_seconds``: trace + lower + compile or fetch, what the calling
    thread waited), the same by family, and ``untracked``: compiles no
    tracked family accounts for (eager operations, plain ``jax.jit``)."""
    count, seconds = compile_totals()
    families = watch.by_family()
    return {
        "count": count,
        "seconds": round(seconds, 3),
        "stall_seconds": round(
            sum(f["seconds"] for f in families.values()), 6
        ),
        "by_family": families,
        "untracked": count - sum(f["count"] for f in families.values()),
    }
