"""The share of the traced slice that the engine spent in dispatching
periods: 100 x the seconds in periods BETWEEN the engine's two notices of
the recorder (``bench/profiled.py``) over the seconds between the notices.
A period's wall enters ``engine.period.seconds`` whole at its END, so each
copy also holds how long the iteration open at its notice had run
(``engine.period.open_seconds``): the slice's ``seconds`` less what the
period open at the first notice had run before it, plus what the one open at
the second had run by then. Both sides cover the same span, so the share
cannot pass 100. The rest is an epoch's prefill, a segment's start, waiting:
time in which no decode chunk ran and every ``*_dev_ms`` metric of a decode
program had nothing to time. 0.0 where the slice met no period (the line
then says why its decode metrics are absent). A program without
``engine.profiled`` gives nothing to read."""

from bench.period_stats import PERIOD, delta
from bench.profiled import slice_facts, slice_seconds


def read(facts, spec):
    cut, seconds = slice_facts(facts), slice_seconds(facts)
    if cut is None or seconds <= 0:
        return None
    ended, still_open = delta(cut, f"{PERIOD}.seconds"), delta(cut, f"{PERIOD}.open_seconds")
    if ended is None or still_open is None:
        return None
    return 100.0 * (ended + still_open) / seconds
