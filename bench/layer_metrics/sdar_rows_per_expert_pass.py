"""Rows an expert takes in one pass and sparse layer, over the window:
``engine.moe.held`` (assignments to held experts: all of them here) over
``engine.diffusion.passes`` x the architecture's ``sparse_layers`` x the
configuration's ``num_experts`` (``GET /stats``, after less before). 8
assignments a row x 4 rows a live lane / 128 experts = 0.25 x the live lanes
when sound (about 15 at 60 live lanes): how near the cell runs to the expert
load of the deployment it stands for (a pipeline stage gets every lane's
rows, so the same). A program without ``engine.moe`` or ``engine.diffusion``
gives nothing to read."""

from bench.period_stats import delta


def read(facts, spec):
    held = delta(facts, "engine.moe.held")
    passes = delta(facts, "engine.diffusion.passes")
    if held is None or not passes:
        return None
    cfg = facts["config"]
    return held / (passes * facts["architecture"].sparse_layers(cfg) * cfg["num_experts"])
