"""Seconds the engine's thread spent inside tracked calls that traced
(trace, lower, compile or fetch from the cache) during the window. Python
and not ``stats_delta``: that kind raises where the counter is missing."""

from bench.period_stats import delta


def read(facts, spec):
    return delta(facts, "compile.stall_seconds")
