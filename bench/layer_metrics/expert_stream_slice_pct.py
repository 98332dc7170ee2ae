"""The routed experts' share of a decode step's DEVICE time, both sides of
the same dispatches: held experts with at least one assignment a decode step
and sparse layer in the traced slice (``engine.moe.touched`` over
``engine.moe.dispatches`` between the engine's two notices of the recorder,
``bench/profiled.py``), times the architecture's ``sparse_layers``, times one
expert's bytes (``expert_bytes``), over the peak HBM bandwidth, over the
device's time of a step: the summed device time of the slice's whole
``^jit_decode_chunk`` runs over (their count x the steps a dispatch of the
slice made, ``engine.period.steps`` over ``engine.period.count``: a
segment's last chunk is a program of fewer steps whose name matches too).
What ``laguna_``, ``lfm2_`` and ``qwen3next_expert_stream_pct`` estimate from
the host's clock over the window's join-free periods and ``--decode-chunk``.
A program without ``engine.profiled`` or ``engine.moe`` gives nothing to
read, nor does a slice that holds no whole run or no period, nor a rehearsal
on the CPU."""

from bench.costs import peaks
from bench.period_stats import PERIOD, ratio
from bench.profiled import slice_facts


def read(facts, spec):
    cut = slice_facts(facts)
    runs = (facts["trace"] or {}).get("programs", {}).get(facts["metric"])
    arch = facts["architecture"]
    if cut is None or not runs or not hasattr(arch, "expert_bytes"):
        return None
    if facts["device"].get("platform") == "cpu":
        return None
    touched = ratio(cut, "engine.moe.touched", "engine.moe.dispatches")
    steps = ratio(cut, f"{PERIOD}.steps", f"{PERIOD}.count")
    if touched is None or not steps:
        return None
    cfg = facts["config"]
    step_s = sum(runs) / (len(runs) * steps)
    moved = touched * arch.sparse_layers(cfg) * arch.expert_bytes(cfg, cfg["served_dtype"])
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / step_s
