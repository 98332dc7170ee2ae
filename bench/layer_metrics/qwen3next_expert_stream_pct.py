"""The routed experts' share of a decode step's time in Qwen3-Next's cell:
held experts with at least one assignment a decode step and layer
(``engine.moe.touched`` over ``engine.moe.dispatches`` of ``GET /stats``,
after less before), times the twelve sparse layers, times one expert's bytes
(the architecture's ``expert_bytes``: 6,291,456), over the peak HBM
bandwidth, over the time of one step. ``laguna_expert_stream_pct``'s reader,
whole, and for its reasons (that file's docstring; that entry's ``workloads``
is not a later PR's to edit): both sides over the whole window and from the
program's own counters, the step's time the mean wall time of the window's
join-free periods over ``--decode-chunk``. With 128 of 512 held and 10 chosen
a token, 44 live lanes touch about 74 of the 128 a layer at 1.2 rows each:
this is the grouped product's share of its roofline at that load. Join-free
periods are few in a cell whose lanes turn over every period (PERF.md section
7 row 36): read it beside ``qwen3next_decode_feed_forward_dev_ms``, the
device's own. A program without ``engine.moe`` or ``engine.period.with_join``
gives nothing to read, and a rehearsal on the CPU has no device whose peak a
step could be held against."""

from bench.layer_metrics.laguna_expert_stream_pct import read  # noqa: F401
