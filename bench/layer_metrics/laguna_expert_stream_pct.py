"""``decode_expert_stream_pct``'s quantity in Laguna's cell (that entry's
``workloads`` is not a later PR's to edit), with BOTH sides over the whole
window and from the program's own counters alone: the share of a decode
step's time that streaming the TOUCHED routed experts accounts for. Held
experts with at least one assignment a decode step and sparse layer
(``engine.moe.touched`` over ``engine.moe.dispatches`` of ``GET /stats``,
after less before: the decode program's own account, read back with each
chunk's tokens), times the sparse layers, times one expert's bytes (the
architecture's ``expert_bytes``), over the peak HBM bandwidth, over the time
of one step: the mean wall time of the window's periods that held NO join
(``engine.period``: ``seconds`` and ``count`` less ``with_join``'s) over
``--decode-chunk``. The engine's loop keeps a chunk in flight, so where the
device is the limit (``device_idle_pct`` 0-2.4 in this cell) a join-free
period is a chunk's device time and the host's gap between two chunks: the
step reads a few percent long and the share that much low. The step's time
is NOT taken from the device trace as ``decode_expert_stream_pct`` takes it:
an epoch's prefill here is 11-22 s in which no lane decodes, the traced 12 s
can lie wholly inside one, and the metric then had nothing to read (PERF.md
section 7, row 24); and the touched experts are a mean over the whole
window, which a traced slice of a cell whose load moves with its epochs does
not match. ``decode_weight_stream_pct`` counts no routed expert, and no
metric yet counts the cache (PERF.md row 25). A program without
``engine.moe`` or ``engine.period.with_join`` gives nothing to read, and a
rehearsal on the CPU has no device whose peak a step could be held against."""

from bench.costs import peaks
from bench.period_stats import PERIOD, delta, ratio


def read(facts, spec):
    touched = ratio(facts, "engine.moe.touched", "engine.moe.dispatches")
    arch = facts["architecture"]
    walls = [delta(facts, f"{PERIOD}{part}.{key}")
             for part in ("", ".with_join") for key in ("seconds", "count")]
    if touched is None or None in walls or not hasattr(arch, "expert_bytes"):
        return None
    if facts["device"].get("platform") == "cpu":
        return None
    seconds, count, join_seconds, join_count = walls
    if count <= join_count:
        return None
    cfg = facts["config"]
    flags = cfg["server_flags"]
    chunk_s = (seconds - join_seconds) / (count - join_count)
    step_s = chunk_s / int(flags[flags.index("--decode-chunk") + 1])
    moved = touched * arch.sparse_layers(cfg) * arch.expert_bytes(cfg, cfg["served_dtype"])
    floor_s = moved / (peaks(facts["device"]["device_kind"])["hbm_gb_per_s"] * 1e9)
    return 100.0 * floor_s / step_s
