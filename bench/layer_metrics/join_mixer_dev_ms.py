"""Own device milliseconds a whole join (``^jit_prefill_join``) under the part
``mixer`` (``bench/parts.py dispatch_ms``): what reads the cache or runs the
recurrence in a join: chunk attention and the window's selective scan or
gated delta rule. It stands beside ``join_prefill_dev_ms``, of which it is a
part."""

from bench import parts


def read(facts, spec):
    return parts.dispatch_ms(facts, spec)
