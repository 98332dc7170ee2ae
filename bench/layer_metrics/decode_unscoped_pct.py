"""Own device time of a decode chunk under NO part scope, as a share of the
program's device time (``bench/parts.py unscoped_pct``): the scopes' own
health. What is meant to be left outside a part is small: the carries'
selects and copies between the parts, the position grids, the rope rows
gathered once a step. A share that grows says that a later change put work
outside the eight parts, and the other ``decode_*_dev_ms`` stopped adding up
to ``decode_dispatch_dev_ms``."""

from bench import parts


def read(facts, spec):
    return parts.unscoped_pct(facts, spec)
