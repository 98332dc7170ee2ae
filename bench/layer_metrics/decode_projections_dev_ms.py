"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the parts ``mixer_in`` and ``mixer_out`` (``bench/parts.py dispatch_ms``):
the token mixers' input and output projections with their norms (the
weights' stream of the mixers). It stands beside ``decode_dispatch_dev_ms``,
of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
