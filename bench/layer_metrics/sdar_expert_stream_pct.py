"""The routed experts' share of a PASS's feed-forward device time in SDAR's
cell, both sides of the same dispatches: ``expert_stream_slice_pct``'s
arithmetic with a pass for a step. Experts with at least one assignment a
pass and sparse layer in the traced slice (``engine.moe.touched`` over
``engine.moe.dispatches`` between the engine's two notices of the recorder,
``bench/profiled.py``: a dispatch of the experts is a pass and layer), times
the architecture's ``sparse_layers``, times one expert's bytes
(``expert_bytes``: 9,437,184), over the peak HBM bandwidth: the least time the
experts' stream of one pass could take. Over it the slice's own device time
under the part ``feed_forward`` a pass: a whole ``^jit_decode_chunk`` run's
(``bench/parts.py dispatch_ms``) over the passes a dispatch of the slice made
(``engine.diffusion.passes`` over ``engine.diffusion.dispatches``). With 128
of 128 held and 8 chosen a token, 60 live lanes x 4 rows touch every expert
of a layer: this is the grouped products' share of their roofline at about
15 rows an expert. A program without ``engine.profiled``, ``engine.moe`` or
``engine.diffusion`` gives nothing to read, nor does a slice that holds no
whole run, nor a rehearsal on the CPU."""

from bench import parts
from bench.costs import peaks
from bench.period_stats import ratio
from bench.profiled import slice_facts


def read(facts, spec):
    cut = slice_facts(facts)
    arch = facts["architecture"]
    if cut is None or not hasattr(arch, "expert_bytes"):
        return None
    if facts["device"].get("platform") == "cpu":
        return None
    ff_ms = parts.dispatch_ms(facts, spec)
    touched = ratio(cut, "engine.moe.touched", "engine.moe.dispatches")
    passes = ratio(cut, "engine.diffusion.passes", "engine.diffusion.dispatches")
    if not ff_ms or touched is None or not passes:
        return None
    cfg = facts["config"]
    moved = touched * arch.sparse_layers(cfg) * arch.expert_bytes(cfg, cfg["served_dtype"])
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / (ff_ms * 1e-3 / passes)
