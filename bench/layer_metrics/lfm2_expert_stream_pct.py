"""The routed experts' share of a decode step's time in LFM2's cell, the
FIRST cell whose step is mostly their stream: experts with at least one
assignment a decode step and sparse layer (``engine.moe.touched`` over
``engine.moe.dispatches`` of ``GET /stats``, after less before: the decode
program's own account, read back with each chunk's tokens), times the sparse
layers, times one expert's bytes (the architecture's ``expert_bytes``), over
the peak HBM bandwidth, over the time of one step. It is
``laguna_expert_stream_pct``'s reader, whole, and for its reasons (that file's
docstring; that entry's ``workloads`` is not a later PR's to edit): BOTH sides
over the whole window and from the program's own counters, so the number is
on every traced line whatever the traced slice holds; the step's time is the
mean wall time of the window's periods that held NO join over
``--decode-chunk`` (a join-free period is a chunk's device time and the
host's gap between two chunks: the step reads a few percent long and the
share that much low). With every expert held and 4 chosen a token, 24 live
lanes touch 30.7 of 32 experts a layer: this is the grouped product's share
of its roofline at that load, and with ``decode_weight_stream_pct`` (which
counts NO routed expert) the share of peak bandwidth that weights explain. A
program without ``engine.moe`` or ``engine.period.with_join`` gives nothing
to read, and a rehearsal on the CPU has no device whose peak a step could be
held against."""

from bench.layer_metrics.laguna_expert_stream_pct import read  # noqa: F401
