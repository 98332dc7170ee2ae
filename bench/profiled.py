"""The traced slice's own side of a per-layer share.

A device trace's times are of the dispatches the profiler recorded; a counter
of ``GET /stats`` differenced over the whole window is of every dispatch of
the window. The program keeps its accounts as they stood where the recorder
started and where it stopped (``engine.profiled`` of ``GET /stats``:
``{"sessions", "open": {"mono", "engine"}, "close": {...}}``, of the LAST
profiler session; cake_tpu/runtime/serving.py), so a reader can take both
sides of a share from the same dispatches: hand ``slice_facts(facts)`` to any
helper of ``bench/period_stats.py`` (``ratio``, ``delta``,
``hist_percentile``) and it works on the slice unchanged. The accounts are
cumulative and a period's numbers are committed at its end, so the slice is
of the periods that ENDED between the two notices.

Both give None where the program has no ``engine.profiled`` (a commit from
before it), where the session never closed, and where it is not the run's
own: ``sessions`` did not grow by one over the window.
"""

from __future__ import annotations

from bench.period_stats import dig


def _edges(facts: dict):
    before = dig(facts["stats_before"], "engine.profiled.sessions")
    kept = dig(facts["stats_after"], "engine.profiled")
    if before is None or not kept or kept["sessions"] != before + 1:
        return None
    if not kept["open"] or not kept["close"]:
        return None
    return kept["open"], kept["close"]


def slice_facts(facts: dict):
    """``facts`` with ``stats_before`` / ``stats_after`` replaced by the
    engine's accounts at the recorder's start and stop."""
    edges = _edges(facts)
    if edges is None:
        return None
    opened, closed = edges
    return {**facts, "stats_before": {"engine": opened["engine"]},
            "stats_after": {"engine": closed["engine"]}}


def slice_seconds(facts: dict):
    """From the engine's notice of the recorder's start to its notice of the
    stop, on the clock the control socket's ``trace_start`` / ``trace_stop``
    answer with (``time.perf_counter()`` of the serving process)."""
    edges = _edges(facts)
    return None if edges is None else edges[1]["mono"] - edges[0]["mono"]
