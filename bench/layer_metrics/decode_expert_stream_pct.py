"""Share of a decode step's device time that streaming the TOUCHED routed
experts accounts for: held experts with at least one assignment, a decode
step and sparse layer (``engine.moe.touched`` over ``engine.moe.dispatches``
of ``GET /stats``, after less before: the program's own account, read back
with each chunk's tokens), times the sparse layers, times one expert's bytes
(the architecture's ``expert_bytes``), over the peak HBM bandwidth, over the
measured device time of one step (a ``jit_decode_chunk_*`` program's mean
time in the traced window over the tokens it makes, ``--decode-chunk``).
``decode_weight_stream_pct`` counts no routed expert (its architecture's
``decode_weight_bytes``); the two add up to the step's share of the peak
that weights explain. What the program READ may be more (a dense combine
reads every held expert whatever the routing). **The two sides cover
different spans**: the step's time is of the traced seconds (4 of 51), the
touched experts a step are a mean over the whole window (a reader is handed
``GET /stats`` at the window's two ends only), so a traced slice with more
or fewer live lanes than the window's mean is off by that much. A program
without
``engine.moe``, or an architecture without routed experts, gives nothing to
read."""

from statistics import fmean

from bench.costs import peaks
from bench.period_stats import ratio


def read(facts, spec):
    runs = (facts["trace"] or {}).get("programs", {}).get(facts["metric"])
    touched = ratio(facts, "engine.moe.touched", "engine.moe.dispatches")
    arch = facts["architecture"]
    if not runs or touched is None or not hasattr(arch, "expert_bytes"):
        return None
    cfg = facts["config"]
    flags = cfg["server_flags"]
    step_s = fmean(runs) / int(flags[flags.index("--decode-chunk") + 1])
    moved = touched * arch.sparse_layers(cfg) * arch.expert_bytes(cfg, cfg["served_dtype"])
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / step_s
