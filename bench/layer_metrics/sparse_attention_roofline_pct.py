"""The share of ITS roofline that attention over the chosen tokens reached
inside whole decode chunks (``bench/sparse_scopes.roofline_pct``): the
architecture's ``sparse_attention_cost`` at the tokens the traced dispatches
chose (``engine.sparse.traced.chosen``) over the own device time of the scope
``mixer/sparse_attention`` (the gather of the chosen rows and the absorbed
attention over them)."""

from bench.sparse_scopes import roofline_pct


def read(facts, spec):
    return roofline_pct(facts, spec, "sparse_attention", "sparse_attention_cost", "chosen")
