"""Own device milliseconds a whole join (``^jit_prefill_join``) under the part
``mixer`` in SDAR's cell: ``join_mixer_dev_ms``'s reader and specification,
whole (that entry's ``workloads`` is not a later PR's to edit). The chunk
kernel over a joiner's window under the block-causal mask."""

from bench.layer_metrics.join_mixer_dev_ms import read  # noqa: F401
