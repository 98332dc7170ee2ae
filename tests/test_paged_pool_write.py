"""The pool's write as a kernel (ops/pallas/paged_write.py) against its
scatter (paged_cache.paged_write_pool, ``kernel=False``): a write moves
bytes, so EVERY byte of a pool filled with somebody's old data is equal,
written or not, in bf16 and in f32. The kernel runs in the Pallas
interpreter here; the same call compiled for a described v5e at the cells'
widths is in tests/test_paged_pool_carry.py.

The interpreter performs a copy where it is started, and the kernel starts
all of a group's reads before any of its write-backs: so a slab that a dead
or forked row read would be written back AFTER its live owner's write, over
it. The two hazard tests at the end stand on that.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.paged_cache import UNMAPPED, paged_write_pool
from cake_tpu.ops.pallas import paged_write

PS = 128
HD = 16  # the interpreter takes any width; the v5e's 128 is the carry tests'
LAYERS = 3
BF16, F32 = jnp.bfloat16, jnp.float32


def as_bytes(x):
    return np.asarray(x).view(np.uint8)


def stale(seed, n_pages, n_kv, dtype):
    """(k_pool, v_pool): every page holds somebody's old bytes."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, n_pages, n_kv, PS, HD)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype) for _ in range(2))


def fresh(seed, b, width, n_kv, dtype):
    rng = np.random.default_rng(1000 + seed)
    return tuple(
        jnp.asarray(rng.normal(size=(b, width, n_kv, HD)), dtype)
        for _ in range(2)
    )


def scattered_tables(b, n_p, n_pages, seed=0):
    perm = np.random.default_rng(seed).permutation(n_pages)
    return perm[: b * n_p].reshape(b, n_p).astype(np.int32)


def both_forms(pools, layer, new, pos, tables, starts=None):
    """(kernel's pools, scatter's pools), each byte of both compared."""
    args = (*pools, jnp.int32(layer), *new, jnp.int32(pos), jnp.asarray(tables))
    starts = None if starts is None else jnp.asarray(starts, jnp.int32)
    got = paged_write_pool(*args, starts=starts, kernel=True)
    want = paged_write_pool(*args, starts=starts, kernel=False)
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(as_bytes(g), as_bytes(w))
    return got, want


def changed_rows(before, after):
    """How many [head_dim] rows of a pool differ."""
    return int((as_bytes(before) != as_bytes(after)).any(axis=-1).sum())


# ------------------------------------------------------------- a decode step


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("n_kv", [1, 8, 30])
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_decode_step_equals_the_scatter(rows, n_kv, dtype):
    n_p = 2
    n_pages = rows * n_p + 1
    pools = stale(rows + n_kv, n_pages, n_kv, dtype)
    tables = scattered_tables(rows, n_p, n_pages, seed=rows)
    pos = PS + 37  # an odd row of a packed word, in the second page
    (k, _), _ = both_forms(
        pools, 1, fresh(rows, rows, 1, n_kv, dtype), pos, tables)
    # one row a head a lane, in layer 1 alone
    assert changed_rows(pools[0], k) == rows * n_kv
    for layer in (0, 2):
        np.testing.assert_array_equal(
            as_bytes(k[layer]), as_bytes(pools[0][layer]))


@pytest.mark.parametrize("off", [0, 1, 14, 15, 16, 127])
def test_decode_step_at_every_kind_of_offset(off):
    """Both halves of a packed word, the first and the last row of a tile
    and of a page."""
    pools = stale(off, 5, 3, BF16)
    tables = scattered_tables(2, 2, 5)
    both_forms(pools, 2, fresh(off, 2, 1, 3, BF16), PS + off, tables)


def test_decode_step_drops_unmapped_rows_and_pages_past_the_table():
    pools = stale(3, 9, 4, BF16)
    tables = scattered_tables(4, 2, 9)
    tables[1] = UNMAPPED  # a dummy lane
    tables[2, 1] = UNMAPPED  # a lane whose next page is not mapped yet
    (k, v), _ = both_forms(pools, 0, fresh(3, 4, 1, 4, BF16), PS + 5, tables)
    assert changed_rows(pools[0], k) == changed_rows(pools[1], v) == 2 * 4
    # slot 2 * PS: logical page 2 of a table of two
    (k, v), _ = both_forms(pools, 0, fresh(4, 4, 1, 4, BF16), 2 * PS, tables)
    assert changed_rows(pools[0], k) == changed_rows(pools[1], v) == 0


def test_decode_step_below_a_rows_start_is_dropped():
    pools = stale(5, 7, 2, F32)
    tables = scattered_tables(3, 2, 7)
    pos = 40
    (k, _), _ = both_forms(
        pools, 1, fresh(5, 3, 1, 2, F32), pos, tables, starts=[pos + 1, pos, 0])
    assert changed_rows(pools[0], k) == 2 * 2


# ----------------------------------------------------------------- a window

WINDOWS = {
    # (pos, width): what of the page grid it meets
    "unaligned_start": (PS - 29, 200),
    "inside_one_page": (PS + 20, 64),
    "across_three_pages": (PS - 5, PS + 10),
    "ends_on_a_page_edge": (57, 2 * PS - 57),
    "whole_pages": (PS, 2 * PS),
    "a_few_slots_over_a_tile_edge": (PS + 13, 5),
    "wider_than_the_table": (2 * PS + 100, 300),
}


@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("window", WINDOWS)
def test_window_equals_the_scatter(window, dtype):
    pos, width = WINDOWS[window]
    b, n_kv, n_p = 2, 3, 4
    pools = stale(pos, b * n_p + 2, n_kv, dtype)
    tables = scattered_tables(b, n_p, b * n_p + 2, seed=width)
    (k, _), _ = both_forms(pools, 2, fresh(pos, b, width, n_kv, dtype), pos, tables)
    live = min(pos + width, n_p * PS) - pos
    assert changed_rows(pools[0], k) == b * n_kv * live
    for layer in (0, 1):
        np.testing.assert_array_equal(
            as_bytes(k[layer]), as_bytes(pools[0][layer]))


@pytest.mark.parametrize("width", [48, 300])
def test_window_with_starts_unmapped_pages_and_a_dead_row(width):
    """Row 0 rides a warm prefix (its first slots are not written), row 1's
    first page is under its pad and has no storage, row 2 is a dummy."""
    pos, b, n_kv, n_p = PS - 20, 3, 2, 4
    pools = stale(width, 14, n_kv, BF16)
    tables = scattered_tables(b, n_p, 14, seed=width)
    tables[1, 0] = UNMAPPED
    tables[2] = UNMAPPED
    starts = [pos + 33, 0, 0]
    (k, _), _ = both_forms(
        pools, 0, fresh(width, b, width, n_kv, BF16), pos, tables, starts)
    assert changed_rows(pools[0], k) == n_kv * ((width - 33) + (width - 20))


def test_heads_in_blocks_and_several_groups():
    """A budget that holds less than one slab of all heads: the heads are
    taken a block at a time and the units a group at a time, same bytes."""
    small = functools.partial(
        jax.jit(paged_write.paged_pool_write.__wrapped__,
                static_argnames=("interpret",)),
        interpret=True,
    )
    b, n_kv, n_p = 3, 6, 3
    pools = stale(11, 10, n_kv, BF16)
    tables = jnp.asarray(scattered_tables(b, n_p, 10))
    for pos, width in ((PS + 9, 1), (70, 200)):
        new = fresh(pos, b, width, n_kv, BF16)
        args = (*pools, jnp.int32(1), *new, jnp.int32(pos), tables)
        want = paged_write_pool(*args, kernel=False)
        unit = PS if width >= PS else 16
        with pytest.MonkeyPatch.context() as mp:
            # two heads of a unit's K and V (and the window's beside them)
            mp.setattr(paged_write, "_BUFFER_BYTES",
                       2 * (2 if width == 1 else 4) * unit * HD * 2)
            got = small(*args)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(as_bytes(g), as_bytes(w))


def test_a_page_of_no_whole_tiles_is_the_scatters():
    pools = tuple(jnp.zeros((1, 2, 1, 24, HD), BF16) for _ in range(2))
    new = tuple(jnp.ones((1, 1, 1, HD), BF16) for _ in range(2))
    with pytest.raises(ValueError, match="whole 16-row tiles"):
        paged_write.paged_pool_write(
            *pools, 0, *new, 3, jnp.zeros((1, 2), jnp.int32))


# ------------------------------------------------------------ the two hazards


@pytest.mark.parametrize("width", [1, 40, 200])
def test_a_dead_row_clamped_onto_a_live_rows_page_writes_nothing(width):
    """Row 0 owns page 0 and writes it; row 1 is UNMAPPED, and -1 clamped
    into the pool is page 0 at the same offset (the write position is
    shared). A read-modify-write of that slab for row 1 would put the stale
    copy back over row 0's write: the live write survives."""
    pos, n_kv = PS + 3, 4
    pools = stale(width, 6, n_kv, BF16)
    tables = np.asarray([[3, 0, 2, 4], [-1, -1, -1, -1]], np.int32)
    k_new, v_new = fresh(width, 2, width, n_kv, BF16)
    (k, v), _ = both_forms(pools, 1, (k_new, v_new), pos, tables)
    in_page_0 = min(width, PS - 3)
    for pool, new in ((k, k_new), (v, v_new)):
        np.testing.assert_array_equal(
            as_bytes(pool[1, 0, :, 3:3 + in_page_0]),
            as_bytes(jnp.swapaxes(new[0, :in_page_0], 0, 1)),
        )


@pytest.mark.parametrize("width", [1, 40, 200])
def test_a_forked_page_below_starts_is_not_touched(width):
    """Rows 1 and 2 map page 5 as a forked shared prefix: their windows
    cover it, below their ``starts``. Not one byte of it may differ, and no
    copy may be issued for it, not even of its own bytes. The bytes alone
    cannot show that, so row 0 is made to WRITE the same slabs of page 5
    (no allocator would hand that out): a copy issued for the forked rows
    would read page 5 before row 0's write lands and put it back after."""
    n_kv = 2
    pos = PS - (1 if width == 1 else 30)
    pools = stale(width + 7, 8, n_kv, F32)
    tables = np.asarray([[5, 1, 6], [5, 2, 7], [5, 3, 0]], np.int32)
    k_new, v_new = fresh(width, 3, width, n_kv, F32)
    starts = [0, PS, PS]
    (k, _), _ = both_forms(pools, 2, (k_new, v_new), pos, tables, starts)
    shared = PS - pos
    np.testing.assert_array_equal(  # row 0's write, whole
        as_bytes(k[2, 5, :, pos:PS]),
        as_bytes(jnp.swapaxes(k_new[0, :shared], 0, 1)),
    )
    # and without the writer: the forked page keeps every byte
    tables[0] = UNMAPPED
    (k, v), _ = both_forms(pools, 2, (k_new, v_new), pos, tables, starts)
    for got, before in ((k, pools[0]), (v, pools[1])):
        np.testing.assert_array_equal(as_bytes(got[:, 5]), as_bytes(before[:, 5]))
        assert changed_rows(before, got) == 2 * n_kv * (width - shared)
