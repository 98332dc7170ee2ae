"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``)
under the parts ``mixer_in`` and ``mixer_out`` in SDAR's cell:
``decode_projections_dev_ms``'s reader and specification, whole (that entry's
``workloads`` is not a later PR's to edit). Sixty times a dispatch (ten passes
of six layers) the q, k, v and o projections with the q/k norms a head and
the rotary, over lanes x 4 rows."""

from bench.layer_metrics.decode_projections_dev_ms import read  # noqa: F401
