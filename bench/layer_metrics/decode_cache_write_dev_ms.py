"""Own device milliseconds a whole decode chunk (``^jit_decode_chunk``) under
the part ``cache_write`` (``bench/parts.py dispatch_ms``): what a decode
chunk writes into the caches it keeps: K and V rows or latents into the page
pool, a state layer's state and window into the lane cache. It stands beside
``decode_dispatch_dev_ms``, of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
