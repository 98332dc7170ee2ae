"""The joiners of one step as ONE join program (PR 52): ``hybrid.
hybrid_join_rows`` through ``programs.join_rows_program``, ``_PagedBackend.
join_rows`` and the engine's ``_join_group``, for a model whose join re-reads
routed experts the chip holds whole (``shapes._dear_programs``: LFM2's case).

At a tiny LFM2 on the CPU, in float32: a group of R rows with 1, 2 and R live
rows gives every joiner, row for row, what its own one-row join gives it (the
logits to the grouped product's order of sums, the first token, the pages,
the convolution's window in its lane, the experts' account), and a dead row
writes nothing anywhere; the rule that picks group or single from the rows'
widths, case for case at the cell's geometry; and through ``serving.py``'s
loop three requests that join in one step go as one program and stream what
each streams alone.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.runtime.shapes import ProgramShapes

from test_hybrid_jamba import GREEDY, collect, engine, prompts
from test_lfm2_moe import HF, PAGE, backend

ROWS, WIDTH, SLOT = 3, 64, 80
LANES = (2, 0, 3)  # whichever lanes were free: not in order, not adjacent


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.from_hf_dict(HF)
    return config, H.init_params(config, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture(scope="module")
def joiners():
    return prompts(5, 26, 40, 11)  # three prompts of different lengths


def one_a_program(tiny, joiners, live, slot=SLOT):
    """The first ``live`` joiners through ``join``, one row a program: each
    ends at ``slot`` in a window of ``WIDTH`` slots (one the slot cannot hold
    starts at 0 and leaves a dead tail)."""
    be = backend(*tiny)
    cache = be.init_kv(4)
    start = max(0, slot - WIDTH)
    logits, routed = [], 0
    for ids, lane in list(zip(joiners, LANES))[:live]:
        row = np.zeros((1, WIDTH), np.int32)
        row[0, slot - start - len(ids):slot - start] = ids
        be.allocator.map_range(lane, slot - len(ids), slot)
        out, cache = be.join(
            cache, row, jnp.asarray([slot - len(ids)], jnp.int32),
            jnp.asarray([slot], jnp.int32), lane, start=start,
        )
        routed += be.absorb_chunk_counters(be.take_chunk_counters(), decode=False)["routed"]
        logits.append(np.asarray(out[0]))
    return logits, cache, routed, be


def one_program(tiny, joiners, live, slot=SLOT):
    """The same joiners as one program of ``ROWS`` rows, the rest dead."""
    be = backend(*tiny)
    cache = be.init_kv(4)
    start = max(0, slot - WIDTH)
    tokens = np.zeros((ROWS, WIDTH), np.int32)
    pads, lanes = [slot] * ROWS, [-1] * ROWS
    for r, (ids, lane) in enumerate(list(zip(joiners, LANES))[:live]):
        tokens[r, slot - start - len(ids):slot - start] = ids
        pads[r], lanes[r] = slot - len(ids), lane
        be.allocator.map_range(lane, slot - len(ids), slot)
    logits, cache = be.join_rows(cache, tokens, pads, [slot] * ROWS, lanes, start=start)
    said = be.absorb_chunk_counters(be.take_chunk_counters(), decode=False)
    return np.asarray(logits), cache, said["routed"], be


@pytest.mark.parametrize("live,slot", [(1, SLOT), (2, SLOT), (ROWS, SLOT), (ROWS, 48)])
def test_a_group_gives_every_row_what_its_own_join_gives_it(tiny, joiners, live, slot):
    """(``slot`` 48: a window the slot cannot hold starts at 0, its tail dead.)"""
    want, cache_1, routed_1, be_1 = one_a_program(tiny, joiners, live, slot)
    got, cache_r, routed_r, be_r = one_program(tiny, joiners, live, slot)
    assert got.shape == (ROWS, tiny[0].vocab_size)
    for r in range(live):
        # float32 on both sides: only the order of the grouped product's sums
        # differs (2e-7 of a logit spread of 0.15 seen; 2e-5 is the module's)
        np.testing.assert_allclose(got[r], want[r], atol=2e-5)
        assert got[r].argmax() == want[r].argmax()  # the greedy first token
    # the pages and the lanes' windows WHOLE: the live rows' equal, and a dead
    # row wrote nothing (no page, no lane), nor did a live row outside its own
    for a, b in zip(jax.tree.leaves(cache_r), jax.tree.leaves(cache_1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    untouched = [lane for lane in range(4) if lane not in LANES[:live]]
    assert not np.asarray(cache_r.conv)[:, :, untouched].any()
    assert np.asarray(cache_r.conv)[:, :, list(LANES[:live])].any()
    # a dead row takes no expert's rows: the account is the live rows' sum
    assert routed_r == routed_1 == 6 * 2 * sum(len(ids) for ids in joiners[:live])
    # one program where there were ``live``; the lanes written are the live ones
    assert be_r.moe_facts()["join"]["joins"] == 1 and be_1.moe_facts()["join"]["joins"] == live
    assert be_r.state_facts()["lane_writes"] == be_1.state_facts()["lane_writes"] == live
    assert (be_r.allocator.block_tables == be_1.allocator.block_tables).all()


def test_a_group_and_a_row_alone_are_programs_of_one_name(tiny):
    """A device trace, the benchmark's readers and ``engine.moe.join`` know a
    join by its module's name: the group is a join."""
    from cake_tpu.models.llama import programs

    kind = programs.KINDS["kv+state"]
    one = programs.join_program(kind, tiny[0], 64, False)
    group = programs.join_rows_program(kind, tiny[0], 3, 64, False)
    assert one._jitted.__name__ == group._jitted.__name__ == "prefill_join_paged_hybrid"
    assert kind.rows_window is H.hybrid_join_rows
    assert [k.name for k in programs.KINDS.values() if k.rows_window] == ["kv+state"]


def test_every_operation_of_the_group_sits_under_one_part(tiny):
    """``tests/test_program_parts.py``'s rule for the program PR 52 adds: the
    benchmark reads a join's device time by part (``join_*_dev_ms``), and
    the group is a join: every weighty operation under exactly one part, the
    lanes' placement under ``cache_write``."""
    from test_program_parts import GEOMETRY, WEIGHTY, operation_names, parts_of, served_program

    lowered = served_program(tiny[0], "join_rows", join_rows=3, **GEOMETRY).lower()
    names = operation_names(lowered.compiler_ir())
    one = operation_names(served_program(tiny[0], "join", **GEOMETRY).lower().compiler_ir())
    assert {p for _, n in names for p in parts_of(n)} == {p for _, n in one for p in parts_of(n)}
    assert not [n for _, n in names if len(parts_of(n)) > 1]
    assert not [(k, n) for k, n in names if k.split(".")[-1] in WEIGHTY and not parts_of(n)]
    # under no part: what the one-row join leaves (constants aside) and four
    # broadcasts, the rows' pads and ends against the window's grid of slots
    bare = lambda ns: [k for k, n in ns if not parts_of(n) and not k.endswith("constant")]  # noqa: E731
    assert len(bare(names)) <= len(bare(one)) + 4


# ------------------------------------------------------------------ the rule

CELL = ProgramShapes.for_model(
    LlamaConfig.from_hf_dict({**HF, "layer_types": HF["layer_types"]}), 128, 32)


def test_the_cells_group_shapes():
    """``lfm2-8b-a1b-chat-closed``'s geometry (``--max-seq-len 4096 --page-size
    128``): three rows at 512 slots, ONE program more than the twelve."""
    assert (CELL.join_rows, CELL.join_widths, CELL.dead_slots) == (3, (512,), 512)
    assert [p for p in CELL.programs(64) if p[0] == "join" and p[1] > 1] == [("join", 3, 512)]
    assert CELL.join_width([256, 256]) == CELL.join_width([512, 256]) == 512
    assert CELL.join_width([1024]) == 0  # no group program holds it


@pytest.mark.parametrize("widths,programs", [
    ([256], [[0]]),
    ([512], [[0]]),
    ([512, 512], [[0, 1]]),  # 512 slots more than the rows' own: what a program saved pays for
    ([512, 256], [[0], [1]]),  # 768 more: the device runs them faster one by one
    ([256, 512], [[0], [1]]),
    ([256, 256], [[0], [1]]),  # 1,024 more in the 512-wide program
    ([256, 512, 256], [[0, 1, 2]]),  # 512 more, two programs saved
    ([512, 512, 512], [[0, 1, 2]]),  # nothing more
    ([256, 256, 256], [[0, 1, 2]]),  # 768 more, two saved: the narrow rows go in it too
    ([512] * 5, [[0, 1, 2], [3, 4]]),  # more joiners than rows: a group and a remainder
    ([512] * 4, [[0, 1, 2], [3]]),
    # a row wider than the group program goes alone, the others together
    ([1024, 512, 512], [[0], [1, 2]]),
    ([256, 1024], [[0], [1]]),
    ([512, 2048, 256, 256], [[0, 2, 3], [1]]),
    ([4096], [[0]]),
])
def test_group_or_single_by_the_rows_widths(widths, programs):
    assert CELL.join_groups(widths) == programs
    assert sorted(i for p in CELL.join_groups(widths) for i in p) == list(range(len(widths)))


def test_the_rule_counts_the_slots_a_group_adds_against_the_programs_it_saves():
    assert CELL.join_groups([512, 256, 256]) == [[0, 1, 2]]  # 512 slots for two programs
    loose = dataclasses.replace(CELL, dead_slots=1024)
    assert loose.join_groups([512, 256]) == [[0, 1]]  # 768 slots for one
    assert loose.join_groups([256, 256]) == [[0, 1]]  # 1,024 for one
    assert dataclasses.replace(CELL, dead_slots=511).join_groups([512, 512]) == [[0], [1]]
    assert dataclasses.replace(CELL, dead_slots=0).join_groups([512] * 3) == [[0, 1, 2]]
    # with a program at 256 as well a group is as wide as its widest row
    both = dataclasses.replace(CELL, join_widths=(256, 512))
    assert both.join_width([256, 256]) == 256 and both.join_groups([256, 256]) == [[0, 1]]
    # every other kind, and the open instance: one a program, always
    assert ProgramShapes().join_groups([64, 64, 64]) == [[0], [1], [2]]


# ------------------------------------------------------- through the engine


def test_three_joiners_of_one_step_go_as_one_program_and_stream_as_alone(tiny):
    """A long stream runs; three requests wait until all three are queued
    (the gate: a test's, so that they meet ONE step) and join together. One
    group program of three rows, and each stream is the request's alone."""
    config, params = tiny
    texts = ["the first, long-running stream of this test", "late joiner",
             "a second one, longer than the first", "third"]
    alone = []
    for text in texts[1:]:
        eng = engine(config, params)
        alone.append(collect(eng.submit([Message.user(text)], 10, GREEDY)))
        eng.stop()
    eng = engine(config, params, step_prefill_tokens=4096)
    assert eng.shapes.join_rows == 3 and eng.shapes.join_widths == (64,)
    # the chat template alone is 65 tokens here: these prompts' windows are
    # 128 slots wide, so the test's server groups at that width too
    eng.shapes = dataclasses.replace(eng.shapes, join_widths=(64, 128))
    take, gate = eng._take_joins, threading.Event()
    eng._take_joins = lambda *a, **kw: take(*a, **kw) if gate.is_set() else []
    h0 = eng.submit([Message.user(texts[0])], 60, GREEDY)
    deadline = time.time() + 60
    while h0.completion_tokens < 2 and time.time() < deadline:
        time.sleep(0.005)
    late = [eng.submit([Message.user(text)], 10, GREEDY) for text in texts[1:]]
    gate.set()
    got = [collect(h) for h in late]
    collect(h0)
    period = eng.periods.snapshot()["period"]
    facts, state = eng.backend.moe_facts(), eng.backend.state_facts()
    eng.stop()
    assert got == alone
    assert period["join_groups"] == {"programs": 1, "rows": 3, "joiners": 3}
    assert period["joins"] == eng.stats["joins"] == 3
    assert facts["join"]["joins"] == 1  # programs, as it ever counted
    assert state["lane_writes"] >= 4  # the epoch's row and the three joiners'
