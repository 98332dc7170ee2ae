"""Median over requests of the mean gap between a request's tokens."""

from bench.stats import percentile


def read(facts, spec):
    means = [
        (o.arrivals[-1] - o.arrivals[0]) / (len(o.arrivals) - 1)
        for o in facts["outcomes"] if len(o.arrivals) > 1
    ]
    p = percentile(means, 50)
    return None if p is None else p * 1e3
