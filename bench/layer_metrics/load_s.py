"""Seconds the server's start-up spent loading the weights (read, put to
the device, fuse), as the program timed it: the part of ``setup_s`` that is
the loader's."""

from bench.period_stats import dig


def read(facts, spec):
    return dig(facts["stats_after"], "startup.load_s")
