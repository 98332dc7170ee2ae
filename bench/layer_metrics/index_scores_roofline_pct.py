"""The index's scores' share of THEIR roofline inside whole decode chunks
(``bench/sparse_scopes.roofline_pct``): the architecture's
``index_scores_cost`` at the cached tokens the traced dispatches scored
(``engine.sparse.traced.scanned``) over the own device time of the scope
``mixer/index_scores``."""

from bench.sparse_scopes import roofline_pct


def read(facts, spec):
    return roofline_pct(facts, spec, "index_scores", "index_scores_cost", "scanned")
