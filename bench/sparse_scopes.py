"""Device time of the learned index's three scopes inside whole decode chunks,
for the readers of ``deepseek-v32-ep16-longdoc-closed``'s four metrics.

The program (``cake_tpu/ops/sparse_index.py``) runs a decode step's index
scores, its choice and the attention over the chosen tokens under three
``jax.named_scope``s nested inside the part ``mixer``: ``index_scores``,
``index_select``, ``sparse_attention``. ``bench/parts.py`` walks a trace
file's metadata for a vocabulary of scope names (``labelled``); here the
vocabulary is these three, and the arithmetic is ``parts.part_seconds``'s:
own time inside the WHOLE runs of the programs ``module`` picks. Beside the
seconds it gives the count of those runs, so that a reader can hold the
program's own counters of the dispatches it made under the profiler
(``engine.sparse.traced``) to the dispatches the time is of. A program
without the scopes (the parent of the PR that brought them), no trace file
or no whole run gives None.
"""

from __future__ import annotations

import bisect
import glob

from bench import parts, xplane
from bench.costs import peaks
from bench.period_stats import delta

SCOPES = ("index_scores", "index_select", "sparse_attention")
TRACED = "engine.sparse.traced"


def scope_seconds(facts: dict, spec: dict) -> dict | None:
    """``{"runs", "own_s": {scope: seconds}}`` of the first chip."""
    files = sorted(glob.glob(f"{parts.TRACES}/plugins/profile/*/*.xplane.pb"))
    if not facts["trace"] or not files:
        return None
    planes = parts.labelled(files[0], SCOPES)
    chips = xplane.device_planes(planes)
    if not chips:
        return None
    ops, runs = xplane._whole_runs(planes[chips[0]], spec["pattern"]["module"])
    runs.sort(key=lambda r: r[1])
    starts = [a for _, a, _ in runs]
    own_s: dict = {}
    for label, a, b, own in xplane.own_events(ops):
        k = bisect.bisect_right(starts, a) - 1
        if label and k >= 0 and b <= runs[k][2]:
            own_s[label] = own_s.get(label, 0.0) + own
    return {"runs": len(runs), "own_s": own_s} if runs and own_s else None


def roofline_pct(facts: dict, spec: dict, scope: str, cost_name: str, counter: str):
    """The share of its roofline that ``scope`` reached inside the traced
    window's whole decode chunks: the larger of the cost's operations over
    the peak bf16 rate and its bytes over the peak HBM bandwidth, the cost
    (the architecture's ``cost_name``, of the WORK: rows and tokens) taken at
    ``engine.sparse.traced``'s counts (the decode dispatches the engine made
    while the profiler was open) and brought to the whole runs the time is of
    by their count of layer-steps, over the scope's own device time."""
    got = scope_seconds(facts, spec)
    arch, cfg = facts["architecture"], facts["config"]
    counts = {k: delta(facts, f"{TRACED}.{k}") for k in ("dispatches", "rows", counter)}
    if got is None or not hasattr(arch, cost_name) or not all(counts.values()):
        return None
    if not got["own_s"].get(scope) or facts["device"].get("platform") == "cpu":
        return None
    flags = cfg["server_flags"]
    steps = int(flags[flags.index("--decode-chunk") + 1]) * cfg["num_hidden_layers"]
    share = got["runs"] * steps / counts["dispatches"]  # of the counted dispatches
    ops, moved = getattr(arch, cost_name)(
        cfg, counts["rows"] * share, counts[counter] * share, cfg["served_dtype"]
    )
    peak = peaks(facts["device"]["device_kind"])
    floor_s = max(ops / (peak["bf16_tflops"] * 1e12), moved / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * floor_s / got["own_s"][scope]
