"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``)
under the part ``mixer`` in SDAR's cell: ``decode_mixer_dev_ms``'s reader and
specification, whole (that entry's ``workloads`` is not a later PR's to
edit). Sixty calls a dispatch of the paged decode kernel at a block's 4
queries a lane folded into 32 rows a KV head (every query of a block sees the
same keys) over the lanes' cached tokens: the call
``sdar_block_attention_roofline_pct`` holds against its floor."""

from bench.layer_metrics.decode_mixer_dev_ms import read  # noqa: F401
