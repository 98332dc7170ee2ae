"""Share of the traced window in which no operation ran on a chip, mean
over the chips (the run's log line gives each chip)."""

from statistics import fmean


def read(facts, spec):
    s = (facts["trace"] or {}).get("summary")
    if not s or not s["chips"] or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - fmean(s["busy_s_per_chip"]) / s["window_s"])
