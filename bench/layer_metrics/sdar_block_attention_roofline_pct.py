"""A pass's attention's share of its roofline AT THE SHAPE A PASS RUNS IT: one
block's 4 queries a lane, all of which see the same keys (bidirectional inside
the block, every earlier block whole), so the program folds them into 4 x 8 =
32 rows a KV head of the paged DECODE kernel (``paged_decode_attention``
inside ``^jit_decode_chunk``: ``models/llama/batch.block_pass_attention``),
sixty calls a dispatch of two blocks. (The issue named the chunk kernel at 4
queries a lane: on the chip that call took 24.7 ms, its grid walking lanes x
query heads x table pages for 4 rows of a 16-row tile, and the builder gave
the pass this call in its place: PERF.md, PR 57.) The least time the chip
could take for one call, the larger of its operations over the peak bf16 rate
and its bytes over the peak HBM bandwidth (the architecture's
``block_attention_cost``), over the kernel's own mean device time a call
inside whole ``^jit_decode_chunk`` runs of the traced slice (``op_mean_us``'s
facts). Both sides of the same dispatches: the live lanes
(``engine.period.lane_seconds.live`` over ``seconds``) and the tokens they
hold together (``engine.period.cached_tokens`` over ``count``) are the
SLICE's (``bench/profiled.slice_facts``). A floor over LIVE lanes (the kernel
walks a dead lane's row too, one page of it). A program without the kernel in
its decode program, ``engine.profiled`` or the counters gives nothing to
read, nor does a rehearsal on the CPU."""

from bench.costs import peaks
from bench.period_stats import PERIOD, ratio
from bench.profiled import slice_facts


def read(facts, spec):
    got = (facts["trace"] or {}).get("ops", {}).get(facts["metric"])
    cut = slice_facts(facts)
    arch = facts["architecture"]
    if (not got or not got["count"] or cut is None
            or not hasattr(arch, "block_attention_cost")):
        return None
    if facts["device"].get("platform") == "cpu":
        return None
    lanes = ratio(cut, f"{PERIOD}.lane_seconds.live", f"{PERIOD}.seconds")
    cached = ratio(cut, f"{PERIOD}.cached_tokens", f"{PERIOD}.count")
    if lanes is None or cached is None:
        return None
    cfg = facts["config"]
    ops, moved = arch.block_attention_cost(cfg, lanes, cached, cfg["served_dtype"])
    peak = peaks(facts["device"]["device_kind"])
    floor_s = max(ops / (peak["bf16_tflops"] * 1e12), moved / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * floor_s / (got["seconds"] / got["count"])
