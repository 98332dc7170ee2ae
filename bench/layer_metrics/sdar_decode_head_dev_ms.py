"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``)
under the part ``head`` in SDAR's cell: ``decode_head_dev_ms``'s reader and
specification, whole (that entry's ``workloads`` is not a later PR's to
edit). The final norm and the untied head over the block's rows, lanes x 4 x
151,936, in eight of a dispatch's ten passes (a commit pass runs none)."""

from bench.layer_metrics.decode_head_dev_ms import read  # noqa: F401
