"""90th percentile of the dispatch periods that ended inside the window, as
the engine timed them (``engine.period.hist``): the gap between two bursts of
tokens seen from inside. The 90th and not the 95th because a window holds
about 170 periods, which leaves 17 beyond it."""

from bench.period_stats import PERIOD, hist_percentile


def read(facts, spec):
    p = hist_percentile(facts, f"{PERIOD}.hist", 90)
    return None if p is None else p * 1e3
