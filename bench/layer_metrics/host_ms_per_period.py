"""The host's own share of a period: its wall less ``readback`` (the wait
for the device), that is sweep, admission, joins' host work, pages,
dispatch, emit and whatever no span covers; mean over the window's periods."""

from bench.period_stats import PERIOD, delta


def read(facts, spec):
    seconds = delta(facts, f"{PERIOD}.seconds")
    readback = delta(facts, f"{PERIOD}.phase_seconds.readback")
    count = delta(facts, f"{PERIOD}.count")
    if seconds is None or readback is None or not count:
        return None
    return 1e3 * (seconds - readback) / count
