"""Share of the lane-seconds the window offered (``--api-batch`` x time) in
which a lane stood empty while a request was queued: what the admission rule
costs."""

from bench.period_stats import PERIOD, ratio


def read(facts, spec):
    return ratio(facts, f"{PERIOD}.lane_seconds.idle_queued",
                 f"{PERIOD}.lane_seconds.offered", 100.0)
