"""Paged KV cache: allocator bookkeeping + write/gather storage parity.

The allocator is pure host-side state (no jax needed for its tests); the
write/gather tests pin the paged pool against the dense cache as the storage
oracle — every mapped slot must hold exactly what the dense layout holds, and
every unmapped write must drop.
"""
# Deliberate pre-mutation snapshots assert what CoW splits did;
# cake-lint: disable-file=stale-block-table

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.cache import init_cache, write_layer
from cake_tpu.models.llama.paged_cache import (
    PageAllocator,
    PageExhausted,
    copy_pages,
    gather_pages,
    init_paged_cache,
    paged_write_layer,
)
from cake_tpu.utils import metrics


# ---------------------------------------------------------------- allocator


def make_alloc(n_pages=8, page_size=16, batch=4, per_seq=4, reserve=1):
    return PageAllocator(
        n_pages, page_size, batch, per_seq, reserve_pages=reserve
    )


def test_map_range_allocates_only_boundary_crossings():
    a = make_alloc()
    a.map_range(0, 5, 40)  # slots 5..39 -> logical pages 0..2
    assert a.pages_free == 5
    assert (a.block_tables[0, :3] >= 0).all() and a.block_tables[0, 3] < 0
    a.map_range(0, 40, 48)  # still inside page 2: nothing new
    assert a.pages_free == 5
    a.map_range(0, 48, 49)  # first slot of page 3
    assert a.pages_free == 4


def test_release_returns_pages_and_unmaps():
    a = make_alloc()
    a.map_range(0, 0, 64)
    a.map_range(1, 0, 16)
    assert a.pages_free == 3
    a.release(0)
    assert a.pages_free == 7
    assert not a.lane_mapped(0) and a.lane_mapped(1)
    a.release(1)
    assert a.pages_free == 8


def test_front_pages_below_pad_are_not_allocated():
    # Left-padded lockstep: a lane whose live window starts mid-sequence
    # maps only the pages its window touches.
    a = make_alloc()
    a.map_range(2, 35, 60)  # pages 2..3 only
    assert a.pages_free == 6
    assert (a.block_tables[2, :2] < 0).all()
    assert (a.block_tables[2, 2:4] >= 0).all()


def test_exhaustion_is_atomic_and_counted():
    metrics.registry.clear()
    a = make_alloc(n_pages=3)
    a.map_range(0, 0, 32)  # 2 pages
    with pytest.raises(PageExhausted):
        a.map_range(1, 0, 33)  # needs 3, only 1 free
    # Nothing partially mapped, nothing leaked.
    assert not a.lane_mapped(1)
    assert a.pages_free == 1
    assert (
        metrics.registry.counter(
            "cake_kv_page_alloc_failures_total"
        ).value()
        == 1
    )


def test_can_admit_reserve_accounting():
    a = make_alloc(n_pages=4, reserve=1)
    assert a.can_admit(33)  # 3 pages + 1 reserve == 4
    assert not a.can_admit(49)  # 4 + 1 > 4
    a.map_range(0, 0, 16)
    assert not a.can_admit(33)  # 3 + 1 > 3 free


def test_fork_refcounts_and_release_order():
    a = make_alloc()
    a.map_range(0, 0, 48)  # 3 pages
    a.fork(0, 1)
    assert a.pages_shared == 3
    assert (a.block_tables[0] == a.block_tables[1]).all()
    assert a.pages_free == 5  # sharing cost nothing
    a.release(0)
    # Lane 1 still holds every page: nothing freed, nothing shared anymore.
    assert a.pages_free == 5
    assert a.pages_shared == 0
    a.release(1)
    assert a.pages_free == 8


def test_fork_into_mapped_lane_refuses():
    a = make_alloc()
    a.map_range(0, 0, 16)
    a.map_range(1, 0, 16)
    with pytest.raises(ValueError):
        a.fork(0, 1)


def test_make_private_copy_on_write_split():
    a = make_alloc()
    a.map_range(0, 0, 32)
    a.fork(0, 1)
    shared_phys = int(a.block_tables[1, 1])
    pair = a.make_private(1, 1)
    assert pair is not None
    src, dst = pair
    assert src == shared_phys and dst != src
    assert int(a.block_tables[1, 1]) == dst
    assert int(a.block_tables[0, 1]) == src  # owner keeps the original
    assert a.refcount[src] == 1 and a.refcount[dst] == 1
    # Exclusive page: a second split is a no-op.
    assert a.make_private(1, 1) is None


def test_make_private_exhaustion():
    a = make_alloc(n_pages=2)
    a.map_range(0, 0, 32)
    a.fork(0, 1)
    with pytest.raises(PageExhausted):
        a.make_private(1, 0)


def test_pool_gauges_track_state():
    metrics.registry.clear()
    a = make_alloc(n_pages=8)
    reg = metrics.registry
    assert reg.gauge("cake_kv_pages_total").value() == 8
    a.map_range(0, 0, 48)
    a.fork(0, 1)
    assert reg.gauge("cake_kv_pages_free").value() == 5
    assert reg.gauge("cake_kv_pages_shared").value() == 3
    a.release(0)
    a.release(1)
    assert reg.gauge("cake_kv_pages_free").value() == 8
    assert reg.gauge("cake_kv_pages_shared").value() == 0


def test_reset_frees_everything():
    a = make_alloc()
    a.map_range(0, 0, 64)
    a.reset(batch=2)
    assert a.pages_free == 8
    assert a.block_tables.shape == (2, 4)
    assert (a.block_tables < 0).all()


def test_map_range_beyond_table_capacity_raises():
    a = make_alloc(per_seq=2)
    with pytest.raises(ValueError):
        a.map_range(0, 0, 33)  # logical page 2 of a 2-page table


# ------------------------------------------------------------ write / gather


def test_paged_write_matches_dense_across_page_boundary():
    rng = np.random.default_rng(0)
    L, B, n_kv, hd, ps, n_pages, per_seq = 2, 2, 2, 8, 16, 10, 4
    dense = init_cache(L, B, per_seq * ps, n_kv, hd, jnp.float32)
    paged = init_paged_cache(L, n_pages, n_kv, ps, hd, jnp.float32)
    a = PageAllocator(n_pages, ps, B, per_seq)
    a.map_range(0, 0, 40)
    a.map_range(1, 3, 20)
    bt = jnp.asarray(a.block_tables)
    k_new = jnp.asarray(rng.normal(size=(B, 7, n_kv, hd)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, 7, n_kv, hd)), jnp.float32)
    pos = jnp.int32(12)  # slots 12..18 straddle the page-16 boundary
    for layer in range(L):
        dk, dv = write_layer(
            dense.k[layer], dense.v[layer], k_new, v_new, pos
        )
        pk, pv = paged_write_layer(
            paged.k[layer], paged.v[layer], k_new, v_new, pos, bt
        )
        np.testing.assert_array_equal(
            np.asarray(dk)[:, :, : per_seq * ps], np.asarray(gather_pages(pk, bt))
        )
        np.testing.assert_array_equal(
            np.asarray(dv)[:, :, : per_seq * ps], np.asarray(gather_pages(pv, bt))
        )


def test_unmapped_writes_drop():
    B, n_kv, hd, ps, n_pages, per_seq = 2, 2, 8, 16, 6, 4
    paged = init_paged_cache(1, n_pages, n_kv, ps, hd, jnp.float32)
    a = PageAllocator(n_pages, ps, B, per_seq)
    a.map_range(0, 0, 16)  # page 0 only; pages 1..3 unmapped
    bt = jnp.asarray(a.block_tables)
    ones = jnp.ones((B, 4, n_kv, hd), jnp.float32)
    pk, pv = paged_write_layer(
        paged.k[0], paged.v[0], ones, ones, jnp.int32(30), bt
    )
    # Row 0's write targeted unmapped page 1; row 1 has no pages at all.
    assert float(jnp.abs(pk).sum()) == 0.0
    g = gather_pages(pk, bt)
    assert float(jnp.abs(g).sum()) == 0.0


def test_gather_respects_physical_permutation():
    # Two lanes mapping the SAME logical content at different physical pages
    # must gather identical dense views — the indirection oracle.
    rng = np.random.default_rng(1)
    n_kv, hd, ps, n_pages = 2, 8, 16, 8
    pool = jnp.asarray(
        rng.normal(size=(n_pages, n_kv, ps, hd)), jnp.float32
    )
    bt = jnp.asarray([[3, 0, 5], [3, 0, 5]], jnp.int32)
    g = gather_pages(pool, bt)
    np.testing.assert_array_equal(np.asarray(g[0]), np.asarray(g[1]))
    np.testing.assert_array_equal(
        np.asarray(g[0, :, :ps]), np.asarray(pool[3])
    )
    np.testing.assert_array_equal(
        np.asarray(g[0, :, ps : 2 * ps]), np.asarray(pool[0])
    )


def test_copy_pages_moves_bytes_for_cow():
    rng = np.random.default_rng(2)
    cache = init_paged_cache(2, 6, 2, 16, 8, jnp.float32)
    cache = cache._replace(
        k=jnp.asarray(rng.normal(size=cache.k.shape), jnp.float32),
        v=jnp.asarray(rng.normal(size=cache.v.shape), jnp.float32),
    )
    out = copy_pages(cache, jnp.asarray([1, 3]), jnp.asarray([4, 5]))
    np.testing.assert_array_equal(
        np.asarray(out.k[:, 4]), np.asarray(cache.k[:, 1])
    )
    np.testing.assert_array_equal(
        np.asarray(out.v[:, 5]), np.asarray(cache.v[:, 3])
    )
    # Untouched pages keep their bytes.
    np.testing.assert_array_equal(
        np.asarray(out.k[:, 0]), np.asarray(cache.k[:, 0])
    )


def test_cow_fork_write_isolation_end_to_end():
    """fork -> make_private -> copy_pages -> diverging write: the owner's
    page is untouched, the forked lane sees its own bytes."""
    rng = np.random.default_rng(3)
    n_kv, hd, ps, n_pages, per_seq = 2, 8, 16, 8, 3
    cache = init_paged_cache(1, n_pages, n_kv, ps, hd, jnp.float32)
    a = PageAllocator(n_pages, ps, 2, per_seq)
    a.map_range(0, 0, 32)
    base = jnp.asarray(rng.normal(size=(1, 32, n_kv, hd)), jnp.float32)
    k0, v0 = paged_write_layer(
        cache.k[0], cache.v[0], base, base, jnp.int32(0),
        jnp.asarray(a.block_tables[:1]),
    )
    a.fork(0, 1)
    pair = a.make_private(1, 1)
    assert pair is not None
    full = cache._replace(k=k0[None], v=v0[None])
    full = copy_pages(full, np.asarray([pair[0]]), np.asarray([pair[1]]))
    # Lane 1 overwrites slot 20 (page 1) through ITS table only.
    delta = jnp.full((1, 1, n_kv, hd), 7.0, jnp.float32)
    bt1 = jnp.asarray(a.block_tables[1:2])
    k1, v1 = paged_write_layer(
        full.k[0], full.v[0], delta, delta, jnp.int32(20), bt1
    )
    g0 = gather_pages(k1, jnp.asarray(a.block_tables[:1]))
    g1 = gather_pages(k1, bt1)
    np.testing.assert_array_equal(
        np.asarray(g0[0, :, :32]),
        np.asarray(gather_pages(k0, jnp.asarray(a.block_tables[:1]))[0, :, :32]),
    )
    assert float(jnp.abs(g1[0, :, 20] - 7.0).max()) == 0.0
    assert float(jnp.abs(g0[0, :, 20] - 7.0).min()) > 0.0


@pytest.mark.parametrize("layer", [0, 2])
def test_pool_write_touches_one_layer_and_matches_the_layer_form(layer):
    """paged_write_pool on the whole pool == paged_write_layer on that layer
    sliced out, byte for byte; every other layer keeps its bytes; unmapped
    entries and slots below ``starts`` drop in both."""
    from cake_tpu.models.llama.paged_cache import paged_write_pool

    rng = np.random.default_rng(11)
    n_layers, n_pages, n_kv, ps, hd = 3, 6, 2, 16, 8
    a = PageAllocator(n_pages, ps, batch=3, max_pages_per_seq=3)
    a.map_range(0, 20, 48)  # page 0 (slots 0..15) stays unmapped
    a.map_range(1, 0, 40)
    bt = jnp.asarray(a.block_tables)  # row 2: nothing mapped
    shape = (n_layers, n_pages, n_kv, ps, hd)
    k0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v0 = jnp.asarray(rng.normal(size=shape), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(3, 30, n_kv, hd)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(3, 30, n_kv, hd)), jnp.float32)
    starts = jnp.asarray([0, 25, 0], jnp.int32)
    k1, v1 = jax.jit(paged_write_pool)(
        k0, v0, jnp.int32(layer), k_new, v_new, jnp.int32(10), bt, starts
    )
    k_want, v_want = paged_write_layer(
        k0[layer], v0[layer], k_new, v_new, jnp.int32(10), bt, starts
    )
    for got, want, before in ((k1, k_want, k0), (v1, v_want, v0)):
        got, before = np.asarray(got), np.asarray(before)
        np.testing.assert_array_equal(got[layer], np.asarray(want))
        rest = [i for i in range(n_layers) if i != layer]
        np.testing.assert_array_equal(got[rest], before[rest])
    # Row 0's tokens at slots 10..15 fell on its unmapped page and row 1's
    # below slot 25 under its threshold: the dense view reads the old bytes.
    view = np.asarray(gather_pages(k1, bt, jnp.int32(layer)))
    old = np.asarray(gather_pages(k0, bt, jnp.int32(layer)))
    np.testing.assert_array_equal(view[1, :, 10:25], old[1, :, 10:25])
    np.testing.assert_array_equal(
        view[1, :, 25:40], np.moveaxis(np.asarray(k_new[1, 15:30]), 0, 1)
    )
    np.testing.assert_array_equal(view[0, :, :16], 0.0)
    np.testing.assert_array_equal(
        view[0, :, 20:40], np.moveaxis(np.asarray(k_new[0, 10:30]), 0, 1)
    )
    np.testing.assert_array_equal(view[2], 0.0)
