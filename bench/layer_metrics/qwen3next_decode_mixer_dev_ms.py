"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``mixer`` in Qwen3-Next's cell: ``decode_mixer_dev_ms``'s reader and
specification, whole (that entry's ``workloads`` lists older cells and is not
a later PR's to edit). The cell's second reason: 72 calls of
``gated_delta_step`` (nine layers, eight steps: every row's 2.1 MB matrix
state read and written) and 24 of ``paged_decode_attention`` (three layers of
16 query heads on 2 KV heads of 256) a dispatch. It stands beside
``qwen3next_decode_dispatch_dev_ms``, of which it is a part."""

from bench.layer_metrics.decode_mixer_dev_ms import read  # noqa: F401
