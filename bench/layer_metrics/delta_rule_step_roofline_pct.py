"""The gated delta rule's one-token update's share of ITS roofline: the least
time the chip could take for one call (one layer, every row of the dispatch),
the larger of its operations over the peak bf16 rate and its bytes over the
peak HBM bandwidth, over the operation's own mean device time a call in the
traced window (``op_mean_us``'s facts). ``gated_delta_step`` is ONE operation
in the trace, the Pallas kernel of that name (``ops/pallas/delta_step.py``),
inside ``jit_decode_chunk_*`` programs; were the update plain XLA it would
have no operation of that name and this reader would find nothing. Operations
and bytes are the architecture's (``gated_delta_step_cost``: every row's
float32 state read and written once, q, k, v and the gates in, o out) at the
window's mean rows a dispatch: ``engine.state.decode_rows`` over
``decode_dispatches``, ``GET /stats`` after less before (the program steps
every row of a dispatch, live or not, so rows and not live lanes are what
the kernel's time is of). **The two sides cover different spans**, as
``latent_decode_attention_roofline_pct``'s: the kernel's time is of the traced
seconds, the rows a dispatch a mean over the whole window. A program without
the kernel or the counters (the parent of the PR that brought them) gives
nothing to read and the metric is left out."""

from bench.costs import peaks
from bench.period_stats import ratio


def read(facts, spec):
    got = (facts["trace"] or {}).get("ops", {}).get(facts["metric"])
    rows = ratio(facts, "engine.state.decode_rows", "engine.state.decode_dispatches")
    arch = facts["architecture"]
    if (not got or not got["count"] or rows is None
            or not hasattr(arch, "gated_delta_step_cost")):
        return None
    cfg = facts["config"]
    ops, moved = arch.gated_delta_step_cost(cfg, rows, cfg["served_dtype"])
    peak = peaks(facts["device"]["device_kind"])
    floor_s = max(ops / (peak["bf16_tflops"] * 1e12), moved / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * floor_s / (got["seconds"] / got["count"])
