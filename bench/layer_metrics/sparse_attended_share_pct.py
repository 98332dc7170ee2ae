"""Of the cached tokens the decode steps' index SCORED, the share attention
then READ: ``engine.sparse.chosen`` over ``engine.sparse.scanned`` of ``GET
/stats``, after the window less before it (the decode program's own counts,
summed over live rows, steps and layers and read back with each chunk's
tokens). 100 means the mechanism slept: every lane shorter than
``index_topk``. A program without the counters gives nothing to read."""

from bench.period_stats import ratio


def read(facts, spec):
    return ratio(facts, "engine.sparse.chosen", "engine.sparse.scanned", 100.0)
