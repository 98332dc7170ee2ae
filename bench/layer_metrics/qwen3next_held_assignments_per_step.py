"""Assignments to HELD experts a decode step and sparse layer, over the
window: ``engine.moe.held`` over ``engine.moe.dispatches`` (``GET /stats``,
after less before): ``moe_held_assignments_per_step``'s reader in
Qwen3-Next's cell (that entry's ``workloads`` is not a later PR's to edit).
128 of the 512 ranked experts are held and 10 answer a token, so it is 2.5 x
the live lanes of a step when sound (10 x 128 / 512); less means live lanes'
tokens took no expert's rows, more that the share is not the router's
quarter. The deployment's own load is 5 assignments an expert a step (four
chips' lanes feed 512 experts); this cell's 0.86 is a sixth of it. A program
without ``engine.moe`` gives nothing to read."""

from bench.layer_metrics.moe_held_assignments_per_step import read  # noqa: F401
