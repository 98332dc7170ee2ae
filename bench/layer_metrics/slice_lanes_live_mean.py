"""``lanes_live_mean`` of the traced slice: the live lane-seconds of the
periods that ended between the engine's two notices of the recorder over
their seconds (``bench/profiled.py``): how many lanes the dispatches that
every ``*_dev_ms`` metric timed were made at, to read beside the window's
``lanes_live_mean`` (a fixed slice behind an epoch's start holds nearly every
lane, the window's mean two thirds of them). 0.0 where no period ended in the
slice. A program without ``engine.profiled`` gives nothing to read."""

from bench.period_stats import PERIOD, delta
from bench.profiled import slice_facts


def read(facts, spec):
    cut = slice_facts(facts)
    if cut is None:
        return None
    live, seconds = delta(cut, f"{PERIOD}.lane_seconds.live"), delta(cut, f"{PERIOD}.seconds")
    if live is None or seconds is None:
        return None
    return live / seconds if seconds else 0.0
