"""Own device milliseconds a whole join (``^jit_prefill_join``) under the part
``cache_write`` (``bench/parts.py dispatch_ms``): what a join writes into
the caches: its window's K and V rows or latents into the page pool (the row
scatter), a state layer's state and window into its lane. It stands beside
``join_prefill_dev_ms``, of which it is a part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
