"""The absorbed latent decode kernel's share of its roofline: the least
time the chip could take for one call, the larger of its operations over the
peak bf16 rate and its bytes over the peak HBM bandwidth, over the kernel's
own mean device time a call in the traced window (``op_mean_us``'s facts).
Operations and bytes are the architecture's
(``latent_decode_attention_cost``) for the window's mean call: the live
lanes (``engine.period.lane_seconds.live`` over ``seconds``) and the tokens
they hold in the cache together (``engine.period.cached_tokens`` over
``count``), both ``GET /stats`` after less before. At 128 heads the kernel
sits at the chip's ridge (242 operations a byte against 240), so which peak
bounds it is decided here, per call, not assumed. A floor over LIVE lanes:
the kernel also walks a dead lane's row. **The two sides cover different
spans**: the kernel's time is of the traced seconds (``trace_seconds`` of the
cell, 4 of 51), the lanes and cached tokens are means over the whole window
(the harness hands a reader ``GET /stats`` at the window's two ends and
nothing at the trace's): where the traced slice holds more or fewer cached
tokens than the window's mean call, the share is off by that ratio. A
program without the kernel or the counters gives nothing to read."""

from bench.costs import peaks
from bench.period_stats import PERIOD, ratio


def read(facts, spec):
    got = (facts["trace"] or {}).get("ops", {}).get(facts["metric"])
    lanes = ratio(facts, f"{PERIOD}.lane_seconds.live", f"{PERIOD}.seconds")
    cached = ratio(facts, f"{PERIOD}.cached_tokens", f"{PERIOD}.count")
    arch = facts["architecture"]
    if (not got or not got["count"] or lanes is None or cached is None
            or not hasattr(arch, "latent_decode_attention_cost")):
        return None
    cfg = facts["config"]
    ops, moved = arch.latent_decode_attention_cost(cfg, lanes, cached, cfg["served_dtype"])
    peak = peaks(facts["device"]["device_kind"])
    floor_s = max(ops / (peak["bf16_tflops"] * 1e12), moved / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * floor_s / (got["seconds"] / got["count"])
