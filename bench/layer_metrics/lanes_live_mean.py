"""Live lanes, time-weighted over the window's periods (the engine's own
count at each dispatch, where ``batch_occupancy_mean`` samples a gauge)."""

from bench.period_stats import PERIOD, ratio


def read(facts, spec):
    return ratio(facts, f"{PERIOD}.lane_seconds.live", f"{PERIOD}.seconds")
