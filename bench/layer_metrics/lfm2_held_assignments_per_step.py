"""Assignments to HELD experts a decode step and sparse layer, over the
window: ``engine.moe.held`` over ``engine.moe.dispatches`` (``GET /stats``,
after less before; a dispatch is one sparse layer of one decode step, the
program's own count, read back with each chunk's tokens):
``moe_held_assignments_per_step``'s reader in LFM2's cell (that entry's
``workloads`` is not a later PR's to edit). Every expert is held here, so it
is ``num_experts_per_tok`` x the live lanes of a step: the load the cell
really runs (4 x ``lanes_live_mean`` when sound; less means live lanes'
tokens took no expert's rows). A program without ``engine.moe`` gives nothing
to read."""

from bench.layer_metrics.moe_held_assignments_per_step import read  # noqa: F401
