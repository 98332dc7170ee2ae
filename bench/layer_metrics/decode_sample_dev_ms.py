"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``sample`` (``bench/parts.py dispatch_ms``): the sampler of a
decode chunk's steps: penalty, key split, arg-max or draw, ring update. It
stands beside ``decode_dispatch_dev_ms``, of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
