"""Share of the window's periods that held at least one join."""

from bench.period_stats import PERIOD, ratio


def read(facts, spec):
    return ratio(facts, f"{PERIOD}.with_join.count", f"{PERIOD}.count", 100.0)
