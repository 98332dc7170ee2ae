"""Assignments to HELD experts a decode step and sparse layer, over the
window: ``engine.moe.held`` over ``engine.moe.dispatches`` (``GET /stats``,
after less before; a dispatch is one sparse layer of one decode step, the
program's own count, read back with each chunk's tokens). With every held
expert 1/16 of the ranked ones and 8 chosen a token it is live lanes / 2: how
near the cell runs to the expert load of the deployment it stands for. A
program without ``engine.moe`` gives nothing to read."""

from bench.period_stats import ratio


def read(facts, spec):
    return ratio(facts, "engine.moe.held", "engine.moe.dispatches")
