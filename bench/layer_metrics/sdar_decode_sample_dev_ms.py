"""Own device milliseconds a whole decode dispatch (``^jit_decode_chunk``)
under the part ``sample`` in SDAR's cell: ``decode_sample_dev_ms``'s reader
and specification, whole (that entry's ``workloads`` is not a later PR's to
edit). What of a denoising pass's draw is not fused into the head's product:
the softmax over 151,936 a slot for the confidence, and the reveal
(``sample/unmask``: the ranks, the choice of slots, the write of the revealed
tokens)."""

from bench.layer_metrics.decode_sample_dev_ms import read  # noqa: F401
