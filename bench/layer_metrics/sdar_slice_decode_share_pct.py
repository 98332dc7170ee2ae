"""The share of the traced slice that the engine spent in dispatching periods,
in SDAR's cell: ``slice_decode_share_pct``'s reader and specification, whole
(that entry's ``workloads`` is not a later PR's to edit). What says whether
the slice that ``sdar_expert_stream_pct`` and
``sdar_block_attention_roofline_pct`` take both sides from held dispatches at
all."""

from bench.layer_metrics.slice_decode_share_pct import read  # noqa: F401
