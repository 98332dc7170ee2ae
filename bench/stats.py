"""From outcomes to the end-to-end metrics. Stdlib only.

A percentile is the nearest rank over the window's samples; a request that
failed or was never finished is a miss and sorts above every sample, so a
run cannot improve its tail by dropping requests. With fewer than ten
samples beyond it a percentile is close to a maximum: the number of samples
goes out with every result line (``samples``).
"""

from __future__ import annotations

import math

from bench.client import Outcome


def percentile(samples: list[float], q: float, misses: int = 0) -> float | None:
    """Nearest-rank percentile ``q`` (0-100] of ``samples`` plus ``misses``
    values above all of them. A rank that falls among the misses has no
    finite value: it reports the largest sample, which is a floor."""
    n = len(samples) + misses
    if not samples:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))
    ordered = sorted(samples)
    return ordered[min(rank, len(ordered)) - 1]


def end_to_end(outcomes: list[Outcome], t0: float, seconds: float, loop: str) -> dict:
    """Values of every end-to-end metric this window can give, by name, and
    the counts behind them. The cell's list in BENCHMARK.json picks.

    Tokens per second are the window's: every token that reached the client
    in [t0, t0 + seconds), whichever request it belongs to. An open loop
    counts the requests that were due in the window, with all their gaps
    (what the drain did not finish has failed). A closed loop is under way
    before the window and is cut at its end: it counts the requests that
    ended inside the window, and every gap that ended there. A caller whose request never ends sends no more:
    the loop's tokens per second show it, its count of failures does not."""
    t_end = t0 + seconds
    if loop == "open":
        counted = outcomes
    else:
        counted = [o for o in outcomes if o.done and t0 <= o.ended < t_end]
    ttft, failures = [], {}
    for out in counted:
        why = out.failure()
        if why:
            failures[out.request.index] = why
        elif out.arrivals:
            ttft.append(out.arrivals[0] - out.due)
    gaps, tokens_in_window = [], 0
    for out in outcomes:
        gaps.extend(b - a for a, b in zip(out.arrivals, out.arrivals[1:])
                    if loop == "open" or t0 <= b < t_end)
        tokens_in_window += sum(t0 <= t < t_end for t in out.arrivals)
    misses = len(failures)
    ms = lambda v: None if v is None else v * 1e3
    return {
        "values": {
            "ttft_p50_ms": ms(percentile(ttft, 50, misses)),
            "ttft_p95_ms": ms(percentile(ttft, 95, misses)),
            "gap_p95_ms": ms(percentile(gaps, 95)),
            "tokens_per_s": tokens_in_window / seconds,
        },
        "attempted": len(counted),
        "failed": misses,
        "failures": failures,
        "samples": {"requests": len(counted), "ttft": len(ttft), "gaps": len(gaps),
                    "tokens": tokens_in_window},
        "counted": counted,
        "late_s": [o.sent - o.due for o in counted if o.sent],
        "finished_length": sum(o.finish == "length" for o in counted),
    }
