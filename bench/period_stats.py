"""What the engine's own account of its step loop gives the per-layer
metrics: differences over the window of the cumulative counters the program
keeps in ``GET /stats`` (``engine.period``, ``compile``; cake_tpu/obs/
period.py and obs/jitwatch.py say what each counts). Every function here
gives None where the program has no such counter (a commit from before they
existed, a server without an engine), and the metric is then left out.
"""

from __future__ import annotations

import math

PERIOD = "engine.period"


def dig(obj, path: str):
    """``obj[a][b]...`` for ``path`` "a.b...", or None where a key is missing."""
    for key in path.split("."):
        if not isinstance(obj, dict) or key not in obj:
            return None
        obj = obj[key]
    return obj


def delta(facts: dict, path: str):
    """A counter of ``GET /stats`` after the window less before it."""
    after, before = dig(facts["stats_after"], path), dig(facts["stats_before"], path)
    return None if after is None or before is None else after - before


def ratio(facts: dict, num: str, den: str, scale: float = 1.0):
    """``scale`` x the window's ``num`` over the window's ``den``."""
    n, d = delta(facts, num), delta(facts, den)
    return None if n is None or not d else scale * n / d


def hist_percentile(facts: dict, path: str, q: float):
    """Nearest-rank percentile ``q`` (0-100] of the durations that entered
    the histogram at ``path`` inside the window, in seconds: the geometric
    middle of the bucket the rank falls in (the buckets' ratio bounds the
    error). ``counts[0]`` is what fell under the first edge and ``counts[-1]``
    what reached the last; a rank there reports that edge."""
    after, before = dig(facts["stats_after"], path), dig(facts["stats_before"], path)
    if after is None or before is None:
        return None
    edges = after["edges_s"]
    counts = [a - b for a, b in zip(after["counts"], before["counts"])]
    n = sum(counts)
    if n <= 0:
        return None
    rank, seen = max(1, math.ceil(q / 100.0 * n)), 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= rank:
            if i == 0:
                return edges[0]
            if i == len(counts) - 1:
                return edges[-1]
            return math.sqrt(edges[i - 1] * edges[i])
    return edges[-1]
