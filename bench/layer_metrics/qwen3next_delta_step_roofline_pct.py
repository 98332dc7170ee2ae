"""The gated delta rule's one-token update's share of ITS roofline in
Qwen3-Next's cell: ``delta_rule_step_roofline_pct``'s reader, whole (that
entry's ``workloads`` is not a later PR's to edit), on the operation
``gated_delta_step`` inside ``^jit_decode_chunk`` with THIS architecture's
``gated_delta_step_cost`` (32 value heads of 128 x 128: the kernel is handed
one q and one k a value head) at the window's mean rows a dispatch
(``engine.state.decode_rows`` over ``decode_dispatches``): the kernel at a
shape it had not run, 64 rows of 2.1 MB beside an expert layer. A program
without the kernel or the counters gives nothing to read."""

from bench.layer_metrics.delta_rule_step_roofline_pct import read  # noqa: F401
