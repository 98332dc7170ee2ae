"""The gated delta rule's one-token update's share of its roofline with BOTH
sides of the same dispatches: ``delta_rule_step_roofline_pct``'s reader,
whole, handed the traced slice's facts (``bench/profiled.py``): the
architecture's ``gated_delta_step_cost`` at the SLICE's rows a dispatch
(``engine.state.decode_rows`` over ``decode_dispatches`` between the engine's
two notices of the recorder) under the kernel's mean call time in the same
slice. The kernel walks a dispatch's live rows alone, so its time follows the
rows, and the window's mean rows under a slice's time read anything from half
the share to several times it. One reader for every cell with the kernel: the
cost is the architecture's. A program without ``engine.profiled``, the kernel
or the counters gives nothing to read, nor does a rehearsal on the CPU (no
device whose peak a call could be held against)."""

from bench.layer_metrics.delta_rule_step_roofline_pct import read as whole_window
from bench.profiled import slice_facts


def read(facts, spec):
    cut = slice_facts(facts)
    if cut is None or facts["device"].get("platform") == "cpu":
        return None
    return whole_window(cut, spec)
