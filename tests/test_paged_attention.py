"""Ragged paged decode attention: kernel (interpret) vs gather fallback vs the
dense kernel/XLA oracles.

The load-bearing property is INDIRECTION correctness: the same logical tokens
scattered across different physical pages must attend identically, and both
paged read paths must match the dense cache holding the same history — the
failure mode the `prefetch-ref-unused` lint rule also guards (a kernel that
ignores its block table and reads page 0 everywhere passes uniform-content
tests; these are deliberately non-uniform).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.batch import decode_positions
from cake_tpu.models.llama.paged_cache import PageAllocator
from cake_tpu.ops.pallas.decode_attention import decode_attention
from cake_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
)

B, N_Q, N_KV, HD = 3, 4, 2, 64
PS = 128  # kernel page size: the 128-lane tile
PER_SEQ = 3  # up to 3 pages per sequence -> 384 slots


def setup(seed=0, lengths=(130, 257, 40), pads=(3, 0, 10), n_pages=12):
    """A pool whose physical pages are deliberately out of order (the LIFO
    free list hands out high pages first), plus the dense mirror."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    pads = np.asarray(pads, np.int32)
    alloc = PageAllocator(n_pages, PS, B, PER_SEQ)
    for r in range(B):
        alloc.map_range(r, int(pads[r]), int(lengths[r]))
    kp = jnp.asarray(
        rng.normal(size=(n_pages, N_KV, PS, HD)), jnp.float32
    )
    vp = jnp.asarray(
        rng.normal(size=(n_pages, N_KV, PS, HD)), jnp.float32
    )
    q = jnp.asarray(rng.normal(size=(B, 1, N_Q, HD)), jnp.float32)
    # Dense mirror: the gathered view IS the dense cache for mapped slots.
    from cake_tpu.models.llama.paged_cache import gather_pages

    bt = jnp.asarray(alloc.block_tables)
    dense_k = gather_pages(kp, bt)
    dense_v = gather_pages(vp, bt)
    return q, kp, vp, dense_k, dense_v, bt, jnp.asarray(lengths), jnp.asarray(pads)


def xla_grids(lengths, pads):
    q_pos = (lengths - 1 - pads)[:, None]
    _, k_pos, _ = decode_positions(jnp.int32(0), pads, PER_SEQ * PS)
    return q_pos, k_pos


def test_kernel_matches_gather_fallback_ragged_lengths():
    q, kp, vp, _, _, bt, lengths, pads = setup()
    got = paged_decode_attention(
        q, kp, vp, lengths, bt, pads, interpret=True
    )
    q_pos, k_pos = xla_grids(lengths, pads)
    want = paged_decode_attention_xla(q, kp, vp, q_pos, k_pos, bt)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_kernel_matches_dense_kernel_same_history():
    # Three-way: paged kernel == dense kernel fed the gathered dense view.
    q, kp, vp, dense_k, dense_v, bt, lengths, pads = setup(seed=1)
    got = paged_decode_attention(
        q, kp, vp, lengths, bt, pads, interpret=True
    )
    want = decode_attention(
        q, dense_k, dense_v, lengths, pads, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_physical_permutation_invariance():
    """Same logical tokens, two different physical layouts -> same output.
    THE indirection test: a kernel reading page 0 for every sequence fails."""
    rng = np.random.default_rng(7)
    n_pages = 9
    logical = rng.normal(size=(B, PER_SEQ * PS, N_KV, HD)).astype(np.float32)
    lengths = jnp.asarray([300, 290, 280], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, 1, N_Q, HD)), jnp.float32)

    def build(order):
        tables = np.asarray(order, np.int32).reshape(B, PER_SEQ)
        kp = np.zeros((n_pages, N_KV, PS, HD), np.float32)
        vp = np.zeros_like(kp)
        for r in range(B):
            for lp in range(PER_SEQ):
                chunk = logical[r, lp * PS : (lp + 1) * PS]  # [PS, n_kv, hd]
                kp[tables[r, lp]] = np.moveaxis(chunk, 1, 0)
                vp[tables[r, lp]] = np.moveaxis(chunk, 1, 0) * 0.5
        return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables)

    kp1, vp1, bt1 = build([0, 1, 2, 3, 4, 5, 6, 7, 8])
    kp2, vp2, bt2 = build([8, 3, 5, 0, 7, 1, 6, 2, 4])
    o1 = paged_decode_attention(q, kp1, vp1, lengths, bt1, interpret=True)
    o2 = paged_decode_attention(q, kp2, vp2, lengths, bt2, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)
    # Sanity that the table matters at all: a wrong table changes the output.
    o3 = paged_decode_attention(q, kp2, vp2, lengths, bt1, interpret=True)
    assert float(jnp.abs(o1 - o3).max()) > 1e-3


def test_sequence_spanning_three_pages_crosses_boundaries():
    # One sequence whose live window covers 3 pages, with the decode position
    # in the last one; another stopping mid-page-1.
    q, kp, vp, _, _, bt, lengths, pads = setup(
        seed=3, lengths=(PER_SEQ * PS - 1, 140, 70), pads=(0, 5, 0)
    )
    got = paged_decode_attention(
        q, kp, vp, lengths, bt, pads, interpret=True
    )
    q_pos, k_pos = xla_grids(lengths, pads)
    want = paged_decode_attention_xla(q, kp, vp, q_pos, k_pos, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_window_folds_into_pruning_start():
    q, kp, vp, _, _, bt, lengths, pads = setup(seed=4)
    got = paged_decode_attention(
        q, kp, vp, lengths, bt, pads, window=64, interpret=True
    )
    q_pos, k_pos = xla_grids(lengths, pads)
    want = paged_decode_attention_xla(
        q, kp, vp, q_pos, k_pos, bt, window=64
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_untiled_page_size_is_refused_by_kernel():
    q, kp, vp, _, _, bt, lengths, pads = setup()
    with pytest.raises(ValueError, match="128-lane"):
        paged_decode_attention(
            q, kp[:, :, :96], vp[:, :, :96], lengths, bt, pads,
            interpret=True,
        )


def test_unmapped_tail_pages_are_harmless():
    # Lanes whose live window ends mid-table leave later entries unmapped;
    # the kernel clamps into the live range and never touches them.
    q, kp, vp, _, _, bt, lengths, pads = setup(
        seed=5, lengths=(100, 90, 80), pads=(0, 0, 0)
    )
    assert (np.asarray(bt)[:, 1:] < 0).all()  # only page 0 mapped per lane
    got = paged_decode_attention(
        q, kp, vp, lengths, bt, pads, interpret=True
    )
    q_pos, k_pos = xla_grids(lengths, pads)
    want = paged_decode_attention_xla(q, kp, vp, q_pos, k_pos, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("layer", [1, 2])
def test_stacked_pool_reads_the_indexed_layer(layer):
    """The whole pool [n_layers, n_pages, ...] plus a layer index reads
    exactly what the same kernel reads from that layer sliced out; the
    other layers hold different bytes, so layer 0 read by mistake shows."""
    q, kp, vp, _, _, bt, lengths, pads = setup(seed=6)
    rng = np.random.default_rng(60)
    k_pool = jnp.asarray(rng.normal(size=(3,) + kp.shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(3,) + vp.shape), jnp.float32)
    got = paged_decode_attention(
        q, k_pool, v_pool, lengths, bt, pads, layer=jnp.int32(layer),
        window=64, interpret=True,
    )
    want = paged_decode_attention(
        q, k_pool[layer], v_pool[layer], lengths, bt, pads, window=64,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    other = paged_decode_attention(
        q, k_pool[0], v_pool[0], lengths, bt, pads, window=64, interpret=True
    )
    assert float(jnp.abs(got - other).max()) > 1e-3
    # The gather twin indexes the layer the same way.
    q_pos, k_pos = xla_grids(lengths, pads)
    twin = paged_decode_attention_xla(
        q, k_pool, v_pool, q_pos, k_pos, bt, window=64,
        layer=jnp.int32(layer),
    )
    np.testing.assert_array_equal(
        np.asarray(twin),
        np.asarray(paged_decode_attention_xla(
            q, k_pool[layer], v_pool[layer], q_pos, k_pos, bt, window=64
        )),
    )


def test_layer_argument_must_match_the_rank():
    q, kp, vp, _, _, bt, lengths, pads = setup()
    with pytest.raises(ValueError, match="takes no `layer`"):
        paged_decode_attention(
            q, kp, vp, lengths, bt, pads, layer=jnp.int32(0), interpret=True
        )
    with pytest.raises(ValueError, match="needs the `layer`"):
        paged_decode_attention(
            q, kp[None], vp[None], lengths, bt, pads, interpret=True
        )


_BIG = np.int32(2**30)  # a key position no query reaches


def ragged_rows(n_q, n_kv, n_p, padded, pool_rank, seed, hd=32):
    """Four rows on one table of ``n_p`` pages: one live token, the full
    table, one token into the second page, a random length; with ``padded``
    the live windows start past slot 0 (the full row's past its first
    page). Only live pages are mapped, in a scattered physical order; the
    pool is one layer (rank 4) or three with layer 2 the one to read."""
    rng = np.random.default_rng(seed)
    full = n_p * PS
    lengths = np.asarray([1, full, PS + 1, rng.integers(2, full)], np.int32)
    starts = np.zeros(4, np.int32)
    if padded:
        starts = np.asarray(
            [0, PS + 3, 5, rng.integers(0, lengths[3])], np.int32
        )
    first, last = starts // PS, (lengths - 1) // PS
    n_live = int((last - first + 1).sum())
    phys = iter(rng.permutation(n_live + 3))
    tables = np.full((4, n_p), -1, np.int32)
    for r in range(4):
        for p in range(first[r], last[r] + 1):
            tables[r, p] = next(phys)
    shape = (n_live + 3, n_kv, PS, hd)
    layer = None
    if pool_rank == 5:
        shape, layer = (3,) + shape, jnp.int32(2)
    kp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, 1, n_q, hd)), jnp.float32)
    return (q, kp, vp, jnp.asarray(lengths), jnp.asarray(tables),
            jnp.asarray(starts), layer)


@pytest.mark.parametrize("pool_rank", [4, 5])
@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("n_p", [4, 13, 32])
@pytest.mark.parametrize("n_q,n_kv", [(32, 8), (20, 1), (8, 8)])
def test_live_pages_walk_matches_gather_fallback(
    n_q, n_kv, n_p, padded, window, pool_rank
):
    """The kernel walks each row's own live pages, all KV heads of a page
    at once: the head layouts the served models have (a group of 4 padded to
    8 rows, one KV head under 20, no grouping), tables narrower and wider
    than its ring of page buffers, rows from one live token to the whole
    table, against the gather twin."""
    q, kp, vp, lengths, tables, starts, layer = ragged_rows(
        n_q, n_kv, n_p, padded, pool_rank, seed=n_p + n_q
    )
    got = paged_decode_attention(
        q, kp, vp, lengths, tables, starts, layer=layer, window=window,
        interpret=True,
    )
    slots = jnp.arange(n_p * PS, dtype=jnp.int32)[None, :]
    live = (slots >= starts[:, None]) & (slots < lengths[:, None])
    want = paged_decode_attention_xla(
        q, kp, vp, (lengths - 1)[:, None], jnp.where(live, slots, _BIG),
        tables, window=window, layer=layer,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_wider_table_with_an_unmapped_tail_changes_no_bit():
    """A row's work is its live window, not the table's width: the same
    rows on a table twice as wide, the new half unmapped, give the same
    bits (a dead page read or scored would show in the rounding)."""
    q, kp, vp, lengths, tables, starts, _ = ragged_rows(
        8, 2, 6, padded=True, pool_rank=4, seed=11
    )
    wide = jnp.concatenate([tables, jnp.full_like(tables, -1)], axis=1)
    narrow_out, wide_out = (
        paged_decode_attention(
            q, kp, vp, lengths, t, starts, window=300, interpret=True
        )
        for t in (tables, wide)
    )
    assert np.isfinite(np.asarray(wide_out)).all()
    np.testing.assert_array_equal(
        np.asarray(narrow_out), np.asarray(wide_out)
    )


def test_a_page_too_large_for_the_buffers_is_walked_in_head_blocks(monkeypatch):
    """Where two pages of all KV heads overrun the kernel's VMEM budget, a
    step takes a block of the heads (here 2 of 6, a ring of two slots under
    rows of up to five live pages) and nothing else changes."""
    from cake_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_BUFFER_BYTES", 128 * 1024)
    q, kp, vp, lengths, tables, starts, _ = ragged_rows(
        12, 6, 5, padded=True, pool_rank=4, seed=17
    )
    got = paged_decode_attention(
        q, kp, vp, lengths, tables, starts, interpret=True
    )
    slots = jnp.arange(5 * PS, dtype=jnp.int32)[None, :]
    live = (slots >= starts[:, None]) & (slots < lengths[:, None])
    want = paged_decode_attention_xla(
        q, kp, vp, (lengths - 1)[:, None], jnp.where(live, slots, _BIG),
        tables,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
