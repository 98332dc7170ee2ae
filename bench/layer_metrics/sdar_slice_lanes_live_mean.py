"""``lanes_live_mean`` of the traced slice in SDAR's cell:
``slice_lanes_live_mean``'s reader and specification, whole (that entry's
``workloads`` is not a later PR's to edit): how many lanes the dispatches
were made at that ``sdar_expert_stream_pct`` and
``sdar_block_attention_roofline_pct`` timed (a slice behind a switch of trees
holds two)."""

from bench.layer_metrics.slice_lanes_live_mean import read  # noqa: F401
