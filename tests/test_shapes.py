"""``runtime/shapes.py``: which programs a server compiles, as data.

Plain arithmetic, no device. The OPEN instance is held to the formulas the
engine carried inline before PR 30, written out here; the CLOSED one to the
sets ``jamba2-3b-chat-closed`` was measured with. A change of either is a
change of which programs a cell compiles: make it on purpose (ROADMAP S2),
with both cells measured.
"""

from __future__ import annotations

import dataclasses
import types

import pytest

from cake_tpu.runtime.shapes import ProgramShapes

ATTENTION = types.SimpleNamespace(cache_kind="kv")
STATE = types.SimpleNamespace(cache_kind="kv+state", state_mixer="mamba", ff_kinds=("dense",))
DELTA = types.SimpleNamespace(cache_kind="kv+state", state_mixer="gated_delta",
                              ff_kinds=("dense",))
LATENT = types.SimpleNamespace(cache_kind="latent", state_mixer="mamba")
# state layers beside routed experts, whatever the mixer (PR 48: lfm2_moe's case)
STATE_SPARSE = types.SimpleNamespace(cache_kind="kv+state", state_mixer="mamba",
                                     ff_kinds=("dense", "sparse"))
# (page size, pages of a lane's table, --max-seq-len, --api-batch)
MISTRAL = (128, 32, 4096, 8)
JAMBA = (128, 32, 4096, 32)
TINY = (16, 8, 128, 2)
BOUNDARIES = (1, 15, 16, 17, 63, 64, 65, 127, 128, 129, 255, 256, 257, 300,
              1023, 1024, 1025, 2067, 4000, 4095, 4096)


def ceil_to(x, m):
    return -(-x // m) * m


@pytest.fixture(params=["open", "closed"])
def kind(request):
    return request.param


def instance(kind, geometry):
    page, pages, _, _ = geometry
    return ProgramShapes.for_model(STATE if kind == "closed" else ATTENTION, page, pages)


# ---------------------------------------------------------- who gets which


def test_state_layers_beside_routed_experts_take_the_dear_programs_rule():
    """One rule from the cause (a sparse feed-forward in a ``kv+state`` stack:
    a program is code by the run), not from the mixer: six widths, an epoch's
    rows one a program through the joins' programs, every epoch whole."""
    dear = ProgramShapes.for_model(STATE_SPARSE, 128, 32)
    assert dear.widths == (256, 512, 1024, 2048, 3072, 4096)
    assert dear.prefill_tokens == 1 and dear.one_row_prefill_is_join and dear.whole_batch
    assert dear.lanes(1, 64) == 64
    ops = [op for op, _, _ in dear.programs(64)]
    assert ops.count("join") == 6 + 1 and ops.count("prefill") == 0
    assert ops.count("decode") == ops.count("decode_tail") == 3
    # PR 52: a step's joiners as one program of three rows of 512 slots: one
    # start-up program more than the twelve
    assert (dear.join_rows, dear.join_widths) == (3, (512,))
    assert [p for p in dear.programs(64) if p[0] == "join"] == [
        *(("join", 1, w) for w in dear.widths), ("join", 3, 512)]
    assert len(dear.programs(64)) == 13
    plain = ProgramShapes.for_model(STATE, 128, 32)
    assert not plain.whole_batch and not plain.one_row_prefill_is_join and len(plain.widths) == 11


def test_the_instance_is_picked_from_the_config_alone():
    assert ProgramShapes.for_model(ATTENTION, 128, 32) == ProgramShapes()
    assert ProgramShapes.for_model(ATTENTION) == ProgramShapes()  # dense backends
    closed = ProgramShapes.for_model(STATE, 128, 32)
    # a latent pool takes the same tables; its prefill programs hold less
    latent = ProgramShapes.for_model(LATENT, 128, 32)
    assert dataclasses.replace(latent, prefill_tokens=16384) == closed
    assert latent.prefill_tokens == 4096 and latent.prefill_group(64, 2048) == 2
    assert closed != ProgramShapes() and closed.widths and closed.capacities
    with pytest.raises(AttributeError):  # frozen: a value, not a knob
        closed.widths = ()


def test_every_backend_owns_an_instance_and_dense_ones_the_open_one():
    from cake_tpu.runtime import batch_backend as B

    for cls in (B.LocalBatchBackend, B.TPBatchBackend, B.PipelineBatchBackend,
                B.DistributedBatchBackend):
        assert cls.shapes == ProgramShapes()


# ------------------------------------------------------ (a) the closed sets


def test_the_closed_sets_at_the_measured_geometry():
    s = ProgramShapes.for_model(STATE, 128, 32)
    assert s.widths == (64, 128, 256, 512, 768, 1024, 1536, 2048, 2560, 3072, 4096)
    assert [c // 128 for c in s.capacities] == [8, 16, 32]
    assert s.prefill_tokens == 16384


@pytest.mark.parametrize(
    "page_size,pages", [(16, 8), (64, 64), (128, 2), (256, 8), (128, 1), (128, 64)]
)
def test_the_sets_are_as_closed_at_any_geometry(page_size, pages):
    s = ProgramShapes.for_model(STATE, page_size, pages)
    slots = page_size * pages
    assert 1 <= len(s.widths) <= 11 and s.widths[-1] == slots
    assert all(w % 64 == 0 or w == slots for w in s.widths)
    assert 1 <= len(s.capacities) <= 3 and s.capacities[-1] == slots
    assert all(c % page_size == 0 for c in s.capacities)
    assert list(s.widths) == sorted(set(s.widths))


@pytest.mark.parametrize("n,want", [(1, 16), (16, 16), (17, 32), (100, 128), (500, 500)])
def test_program_width_is_the_narrowest_width_that_holds(n, want):
    s = ProgramShapes(widths=(16, 32, 64, 128))
    assert s.program_width(n) == want
    assert ProgramShapes().program_width(n) == n  # open: the bucket itself


@pytest.mark.parametrize("bucket", BOUNDARIES)
def test_closed_program_width_and_capacity_stay_inside_their_sets(bucket):
    s = ProgramShapes.for_model(STATE, 128, 32)
    assert s.program_width(bucket) in s.widths and s.program_width(bucket) >= bucket
    cap = s.capacity(bucket, 4096)
    # the open bucket first, then the next of the three
    assert cap == next(c for c in s.capacities if c >= min(4096, ceil_to(bucket, 256)))


# ------------------------------------ (b) the open instance, formula by formula


@pytest.mark.parametrize("geometry", [MISTRAL, TINY], ids=["mistral", "tiny"])
@pytest.mark.parametrize("n", BOUNDARIES)
def test_open_widths_and_capacities_are_the_parents_formulas(geometry, n):
    s = instance("open", geometry)
    _, _, max_seq_len, _ = geometry
    n = min(n, max_seq_len)
    assert s.prompt_width(n, max_seq_len) == min(ceil_to(n, 16), max_seq_len)
    assert s.capacity(n, max_seq_len) == min(max_seq_len, ceil_to(n, 256))
    assert s.program_width(n) == n
    assert s.prefill_group(32, n) == 32  # one program, whatever it holds


@pytest.mark.parametrize("slot", [s for s in BOUNDARIES if s < 4096])
def test_open_windows_are_the_parents_formulas(slot):
    s = ProgramShapes()
    # a plain join, a restore, a migration: from slot 0, 64-bucketed
    for limit in (4096, min(4096, ceil_to(slot + 1, 256))):
        for pad in {0, slot // 2, slot - 1}:
            assert s.window(pad, slot, limit) == (0, min(ceil_to(slot, 64), limit))
    # over the pool's prefix (suffix join, an epoch's suffix prefill): ends
    # at the slot, as wide as the uncached tail's bucket, never wider than it
    for fresh in {0, slot // 3, slot - 1}:
        w = min(ceil_to(slot - fresh, 64), slot)
        assert s.window(fresh, slot, slot, reads_pool=True) == (slot - w, w)


@pytest.mark.parametrize("n_seed,max_batch,want", [
    (1, 8, 2), (2, 8, 4), (3, 8, 8), (4, 8, 8), (5, 8, 8), (8, 8, 8), (1, 1, 1),
    (1, 32, 2), (9, 32, 32), (16, 32, 32), (17, 32, 32), (7, 32, 16),
])
def test_lanes(kind, n_seed, max_batch, want):
    assert instance(kind, JAMBA).lanes(n_seed, max_batch) == want


@pytest.mark.parametrize("chunk,cap,slot,want", [
    (8, 256, 100, 8), (8, 256, 247, 8), (8, 256, 248, 7), (8, 256, 254, 1), (4, 4096, 4000, 4),
])
def test_decode_steps(kind, chunk, cap, slot, want):
    assert instance(kind, MISTRAL).decode_steps(chunk, cap, slot) == want


# -------------------------------------------------- (c) the closed window


@pytest.mark.parametrize("pad,slot,want", [
    (300, 450, (194, 256)),    # as wide as the prompt's bucket, ends at the slot
    (386, 450, (386, 64)),     # exactly a width
    (385, 450, (322, 128)),    # one more token: the next width
    (10, 450, (0, 512)),       # longer than the slot's reach: from 0, a dead tail
    (0, 4000, (0, 4096)),      # the table itself is the last width
])
def test_a_closed_window_is_as_wide_as_its_prompt_and_ends_at_the_slot(pad, slot, want):
    s = ProgramShapes.for_model(STATE, 128, 32)
    start, width = s.window(pad, slot, 4096)
    assert (start, width) == want
    assert start <= pad and start + width >= slot and width in s.widths


def test_a_prefix_cache_join_computes_the_uncached_tail_only():
    s = ProgramShapes()
    pad, fresh, slot = 100, 228, 450  # a chain of 128 tokens was forked
    assert fresh > pad
    assert s.window(fresh, slot, slot, reads_pool=True) == (450 - 256, 256)
    assert s.window(pad, slot, slot, reads_pool=True) == (450 - 384, 384)  # a miss
    # a tail wider than the slot can hold: the whole row, off the 64 grid
    assert s.window(2, 100, 100, reads_pool=True) == (0, 100)


# ------------------------------------------------- (d) what start-up runs


def test_the_open_set_runs_nothing_ahead_and_the_closed_one_all_of_it():
    assert ProgramShapes().programs(8) == ()
    programs = ProgramShapes.for_model(STATE, 128, 32).programs(32)
    assert len(programs) == 2 * 11 + 3
    assert programs[:2] == (("prefill", 32, 64), ("join", 1, 64))
    assert programs[-3:] == (("decode", 32, 1024), ("decode", 32, 2048), ("decode", 32, 4096))
    assert [p[0] for p in programs].count("join") == 11


KINDS = types.SimpleNamespace(cache_kind="kv+kinds", state_mixer="mamba")
INDEX = types.SimpleNamespace(cache_kind="latent+index", state_mixer="mamba")


@pytest.mark.parametrize("name,config,lanes,table,n_programs,n_joins,digest", [
    ("open", ATTENTION, 8, 32, 0, 0, "2e38e77b22c3"),
    ("jamba", STATE, 32, 32, 25, 11, "3384ff304c9f"),
    ("olmo", DELTA, 32, 32, 25, 11, "3384ff304c9f"),
    ("pangu", LATENT, 64, 32, 25, 11, "cbab03f57741"),
    ("laguna", KINDS, 48, 192, 15, 6, "e8d5e6809747"),
    ("deepseek", INDEX, 16, 168, 9, 6, "b74014258e52"),
])
def test_every_other_kind_runs_the_programs_it_ran_and_joins_one_a_program(
        name, config, lanes, table, n_programs, n_joins, digest):
    """PR 52 groups a step's joiners for the dear kind alone: every other
    kind's start-up list, at its cell's lanes and table, is what PR 52's
    parent listed (``digest``: of the list's ``repr``, computed there), its
    joins are all one row, and its ``join_groups`` never groups."""
    import hashlib

    shapes = ProgramShapes.for_model(config, 128, table)
    assert (shapes.join_rows, shapes.join_widths, shapes.dead_slots) == (1, (), 0)
    programs = shapes.programs(lanes)
    assert len(programs) == n_programs
    assert hashlib.sha256(repr(programs).encode()).hexdigest()[:12] == digest
    assert [rows for op, rows, _ in programs if op == "join"] == [1] * n_joins
    assert shapes.join_groups([256, 256, 512]) == [[0], [1], [2]]


@pytest.mark.parametrize("rows,width,want", [
    (32, 512, 32), (32, 513, 16), (32, 768, 16), (32, 2048, 8), (32, 4096, 4),
    (2, 4096, 2), (1, 4096, 1), (32, 16385, 1),
])
def test_closed_prefill_groups_hold_16k_tokens(rows, width, want):
    s = ProgramShapes.for_model(STATE, 128, 32)
    assert s.prefill_group(rows, width) == want
    assert want == 1 or want * width <= 16384


@pytest.mark.parametrize("rows,width,want", [
    (32, 256, 32), (32, 257, 16), (32, 512, 16), (32, 2048, 4), (32, 4096, 2),
    (2, 4096, 2), (1, 4096, 1), (32, 8193, 1),
])
def test_a_delta_rule_mixers_groups_hold_8k_tokens(rows, width, want):
    """The same closed sets as Mamba's, half the tokens a prefill program:
    the chunkwise form's float32 intermediates are a head's."""
    s = ProgramShapes.for_model(DELTA, 128, 32)
    assert s.prefill_group(rows, width) == want
    assert want == 1 or want * width <= 8192
    mamba = ProgramShapes.for_model(STATE, 128, 32)
    assert (s.widths, s.capacities) == (mamba.widths, mamba.capacities)
