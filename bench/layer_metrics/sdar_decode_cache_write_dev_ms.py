"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``)
under the part ``cache_write`` in SDAR's cell: ``decode_cache_write_dev_ms``'s
reader and specification, whole (that entry's ``workloads`` is not a later
PR's to edit). A dispatch writes each block's K and V into the block's own
slots SIXTY times (ten passes of six layers: a denoising pass's rows are
overwritten by the next pass and by the commit)."""

from bench.layer_metrics.decode_cache_write_dev_ms import read  # noqa: F401
