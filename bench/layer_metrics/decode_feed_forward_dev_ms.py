"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``feed_forward`` (``bench/parts.py dispatch_ms``): the feed-forward
of a decode chunk: its norms and the dense products, or the router, the
routed experts and the shared expert. It stands beside
``decode_dispatch_dev_ms``, of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
