"""What one join adds to the period it falls in: the ``join`` span whole
(fork, prefill dispatch, first-token wait, lane state), mean over the joins
of the window."""

from bench.period_stats import PERIOD, ratio


def read(facts, spec):
    return ratio(facts, f"{PERIOD}.join_seconds", f"{PERIOD}.joins", 1e3)
