"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``mixer`` (``bench/parts.py dispatch_ms``): what reads the cache or
runs the recurrence in a decode chunk: the decode attention kernels and the
one-token state updates together. It stands beside
``decode_dispatch_dev_ms``, of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
