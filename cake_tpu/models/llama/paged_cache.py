"""Paged KV cache: a shared page pool + host-side block-table allocator.

The dense cache (cache.py) reserves a full ``[max_seq]`` strip per batch slot,
so HBM is committed for the LONGEST POSSIBLE sequence per lane and the serving
engine's admission is capped by ``batch * max_seq`` — the memory-capacity wall
the ragged-paged-attention line of work (PAPERS.md) removes. Here KV storage is
a pool of fixed-size pages shared by every lane:

  pool:        [n_layers, n_pages, n_kv_heads, page_size, head_dim]
  block table: int32 [batch, max_pages_per_seq], physical page per logical
               page, UNMAPPED (-1) where the lane holds no storage

The layout is **head-major inside a page** (n_kv before page_size), exactly the
dense cache's stride order, so one page is one contiguous
``page_size * head_dim`` strip per KV head, and all KV heads of a page one
contiguous block that the paged decode kernel (ops/pallas/paged_attention.py)
copies with a single DMA.

HBM committed = pages actually holding live tokens (rounded up to the page),
not ``batch * max_seq`` — a pool sized well below the dense footprint admits
strictly more concurrent short requests (pinned in tests/test_paged_serving.py).

The ``PageAllocator`` is HOST-side bookkeeping (free list, refcounts, block
tables as numpy); only the block tables cross into jit as small int32 operands.
Refcounts let a shared prompt prefix map the same physical pages from several
lanes (``fork``), copy-on-write (``make_private`` + ``copy_pages``) splitting a
page only when a lane is about to write it.

Writes through an UNMAPPED table entry are DROPPED: left-pad garbage, dummy
lanes, and finished lanes cost no storage and can never corrupt a recycled
page. ``paged_write_pool`` has two forms of one contract. Where the paged
attention kernels run (the TPU, a page of whole lane tiles) it is the Pallas
write ``ops/pallas/paged_write.py``, which moves ``[n_kv, rows, head_dim]``
slabs by DMA and issues NO copy for a dropped write (nothing is read, nothing
is written back). Everywhere else (the CPU, where the kernel would be
interpreted; a page that is not whole tiles; the tests' oracle) it is a scatter of ``[head_dim]`` rows whose dropped
indices are out of bounds (``mode="drop"``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.obs.jitwatch import tracked_jit
from cake_tpu.obs.taxonomy import CACHE_WRITE
from cake_tpu.ops.pallas.paged_write import paged_pool_write
from cake_tpu.utils import metrics

UNMAPPED = np.int32(-1)  # block-table sentinel: no physical page mapped

# Metric names (PR 1 observability convention; README "Observability").
_G_TOTAL = "cake_kv_pages_total"
_G_FREE = "cake_kv_pages_free"
_G_SHARED = "cake_kv_pages_shared"
_C_FAIL = "cake_kv_page_alloc_failures_total"


class PagedKVCache(NamedTuple):
    """Page-pool KV storage for a contiguous run of layers.

    A program that is given the pool gives the SAME buffers back: the
    model's layer scan carries ``k`` and ``v`` whole, writes them in place
    (``paged_write_pool``) and reads them through a layer index
    (ops/pallas/paged_attention.py, paged_prefill.py). No layer is sliced
    out of the pool and none is stacked back into it; with the jits'
    donation a served program holds one pool, not two.
    """

    k: jnp.ndarray  # [n_layers, n_pages, n_kv_heads, page_size, head_dim]
    v: jnp.ndarray

    @property
    def n_layers(self) -> int:
        return self.k.shape[0]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[3]


def init_paged_cache(
    n_layers: int,
    n_pages: int,
    n_kv_heads: int,
    page_size: int,
    head_dim: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> PagedKVCache:
    """Allocate a zeroed page pool.

    ``page_size`` is free on the CPU/XLA fallback path; the Pallas kernel
    (ops/pallas/paged_attention.py) requires a multiple of its 128-lane tile —
    that constraint is enforced at kernel dispatch, not here, so CPU tests can
    exercise many-page layouts cheaply.
    """
    shape = (n_layers, n_pages, n_kv_heads, page_size, head_dim)
    return _zero_pool(shape, jnp.dtype(dtype))


_LANES = 128


def kv_pack(n_kv_heads: int, head_dim: int) -> int:
    """KV heads a pool's row may hold SIDE BY SIDE. A head narrower than the
    128 lanes of a TPU tile is stored padded to them (``[page_size, 64]`` in
    bf16 takes the room of ``[page_size, 128]``: twice the pool, twice the
    bytes a decode step's attention streams, and the pool's write as a kernel
    cannot cut a 64-wide slab out of a 128-wide tile at all). So where whole
    heads fill a tile exactly, a pool is built ``[..., n_kv / pack, page_size,
    pack * head_dim]`` (``init_paged_cache``'s caller passes those numbers) and
    the layer that reads and writes it packs its q, K and V to match
    (``pack_heads``): every kernel then sees heads of 128. 1 = as projected
    (every head of 128 or more; heads that do not fill a tile in wholes)."""
    pack = _LANES // head_dim if head_dim < _LANES and _LANES % head_dim == 0 else 1
    return pack if n_kv_heads % pack == 0 else 1


def _own_slot(n_q: int, n_kv: int, pack: int, dtype) -> jnp.ndarray:
    """[n_q, pack] one-hot: which of its packed KV head's ``pack`` slots query
    head ``i`` reads (its own KV head ``i // group`` is slot ``% pack`` of
    packed head ``// pack``: grouped-query order is kept, the groups grow)."""
    slot = (np.arange(n_q) // (n_q // n_kv)) % pack
    return jnp.asarray(np.eye(pack)[slot], dtype)


def pack_heads(q, k, v, pack: int):
    """q [b, t, n_q, d], K and V [b, t, n_kv, d] for a pool whose rows hold
    ``pack`` KV heads side by side: K and V are a reshape ([b, t, n_kv / pack,
    pack * d]); a query head keeps its numbers in its own KV head's slot and
    zeros in the others, so its score against a packed key is its score
    against its own key (the other slots add exact zeros) and its weighted
    sum of packed values holds its own in that slot (``unpack_heads``). The
    score scale stays the unpacked head's."""
    b, t, n_q, d = q.shape
    n_kv = k.shape[2]
    own = _own_slot(n_q, n_kv, pack, q.dtype)
    q = (q[:, :, :, None, :] * own[:, :, None]).reshape(b, t, n_q, pack * d)
    return q, k.reshape(b, t, n_kv // pack, pack * d), v.reshape(b, t, n_kv // pack, pack * d)


def unpack_heads(attn, n_kv: int, pack: int):
    """Attention over packed heads [b, t, n_q, pack * d] -> each query head's
    own slot [b, t, n_q, d]."""
    b, t, n_q, w = attn.shape
    own = _own_slot(n_q, n_kv, pack, attn.dtype)
    return jnp.sum(attn.reshape(b, t, n_q, pack, w // pack) * own[:, :, None], axis=3)


def _zero_pool_impl(shape, dtype) -> PagedKVCache:
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


_zero_pool = tracked_jit(
    _zero_pool_impl, name="paged.init_cache",
    static_argnames=("shape", "dtype"),
)


def paged_write_pool(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    kernel: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write a [batch, chunk, n_kv, head_dim] chunk at sequence offset ``pos``
    into layer ``layer`` of the WHOLE pool, in place.

    The paged sibling of cache.write_layer, in the form the model's layer
    scan uses: the pool [n_layers, n_pages, n_kv, page_size, head_dim] is the
    scan's CARRY and this is its only write — token ``pos + j`` of row
    ``b`` goes to ``[layer, block_tables[b, (pos + j) // page_size], :,
    (pos + j) % page_size, :]``. No layer is ever sliced out of the pool or
    stacked back into it, so the buffer a program was given is the buffer it
    returns. UNMAPPED entries (and logical pages beyond the table) are
    dropped — the caller's allocator decides what holds storage, the write
    path cannot corrupt it.

    ``kernel`` (STATIC) is the callers' one rule for every paged kernel
    (``use_pallas and paged_kernel_supported(page_size)``,
    models/llama/batch.py) where Mosaic compiles the call
    (``paged_write.compiled_here``: not the CPU's interpreter): true, the write is the Pallas kernel
    ``ops/pallas/paged_write.paged_pool_write`` (slabs of all KV heads by
    DMA; a dropped write issues no copy at all); false, one scatter of
    ``[head_dim]`` rows with the dropped ones out of bounds, the kernel's
    twin and oracle. The same bytes either way.

    ``starts`` (optional [B] int32) drops row ``b``'s writes at slots below
    ``starts[b]`` even when those slots ARE mapped: a suffix prefill over a
    forked shared-prefix chain (runtime/prefix_cache.py) re-embeds prefix
    tokens inside its window but must never scribble the shared pages that
    already hold their KV.
    """
    with jax.named_scope(CACHE_WRITE):
        if kernel:
            return paged_pool_write(
                k_pool, v_pool, layer, k_new, v_new, pos, block_tables, starts
            )
        n_pages, page_size = k_pool.shape[1], k_pool.shape[3]
        b, chunk = k_new.shape[0], k_new.shape[1]
        slots = pos + jnp.arange(chunk, dtype=jnp.int32)  # [chunk] absolute
        logical = jnp.broadcast_to(slots // page_size, (b, chunk))
        offs = jnp.broadcast_to(slots % page_size, (b, chunk))
        phys = jnp.take_along_axis(
            block_tables, logical, axis=1, mode="fill", fill_value=UNMAPPED
        )
        # UNMAPPED (-1) -> n_pages: out of bounds, dropped by the scatter.
        phys = jnp.where(phys < 0, n_pages, phys)
        if starts is not None:
            phys = jnp.where(slots[None, :] < starts[:, None], n_pages, phys)
        k_new = k_new.astype(k_pool.dtype)
        v_new = v_new.astype(v_pool.dtype)
        # The KV head is an index of the scatter too, not a window dimension:
        # each update is then one [head_dim] row, contiguous in the pool's own
        # (row-major, head-major) layout, which is the layout the kernels read.
        # With the head in the window XLA lays the pool out token-major for the
        # scatter and converts the WHOLE pool back for every kernel call.
        heads = jnp.arange(k_pool.shape[2], dtype=jnp.int32)
        at = (layer, phys[:, :, None], heads, offs[:, :, None])
        k_pool = k_pool.at[at].set(k_new, mode="drop")
        v_pool = v_pool.at[at].set(v_new, mode="drop")
        return k_pool, v_pool


def paged_write_layer(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``paged_write_pool`` for ONE layer held on its own, [n_pages, n_kv,
    page_size, head_dim]: a pool of one layer. The model does not call this
    (its scan carries the whole pool); the per-layer oracles of
    tests/test_paged_pool_carry.py and callers with a single layer do."""
    k_pool, v_pool = paged_write_pool(
        k_pages[None], v_pages[None], 0, k_new, v_new, pos, block_tables,
        starts=starts,
    )
    return k_pool[0], v_pool[0]


def gather_pages(
    pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Dense head-major view of each row's pages: [b, n_kv, n_p * ps, hd].

    The XLA fallback read path (interpret/CPU, and the numerical oracle the
    kernel is pinned against): gathering a row's pages in logical order
    reconstructs exactly the dense cache layout at every mapped slot; UNMAPPED
    pages read zeros, which the callers' position masks exclude anyway.
    ``pages`` is one layer [n_pages, n_kv, ps, hd], or the whole pool with
    ``layer`` naming the one to read: the gather indexes it, no layer is
    sliced out first.
    """
    if pages.ndim == 4:
        pages, layer = pages[None], 0
    n_pages = pages.shape[1]
    bt = jnp.where(block_tables < 0, n_pages, block_tables)
    # [b, n_p, n_kv, ps, hd], OOB -> 0 fill
    g = pages.at[layer, bt].get(mode="fill", fill_value=0)
    b, n_p, n_kv, ps, hd = g.shape
    return jnp.moveaxis(g, 2, 1).reshape(b, n_kv, n_p * ps, hd)


def copy_pages(
    cache: PagedKVCache, src: jnp.ndarray, dst: jnp.ndarray
) -> PagedKVCache:
    """Copy physical pages ``src[i] -> dst[i]`` across every layer.

    The device half of copy-on-write: ``PageAllocator.make_private`` picks the
    (src, dst) pairs host-side; this moves the bytes so the forked lane's
    private page starts as an exact copy of the shared one.
    """
    return _copy_pages(
        cache, jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    )


def _copy_pages_impl(cache, src, dst):
    return PagedKVCache(
        k=cache.k.at[:, dst].set(cache.k[:, src]),
        v=cache.v.at[:, dst].set(cache.v[:, src]),
    )


# Not donated: a caller that fails between the copy and its use of the
# result still holds a valid pool.
_copy_pages = tracked_jit(_copy_pages_impl, name="paged.copy_pages")


# ------------------------------------------------------------ latent pool
#
# Latent attention (MLA, ``config.cache_kind == "latent"``) keeps ONE vector
# a token a layer, shared by every head: the compressed K/V after its norm
# and the rotary key after RoPE, side by side, padded with zeros to whole
# 128-lane tiles (``config.latent_width``: 512 + 64 -> 640). K-and-V pools of
# ``[.., n_kv, page_size, head_dim]`` cannot say that (V is a slice of K's
# first numbers, and the row is not ``head_dim`` wide), so it is a pool of
# its own. The allocator and the block tables are the SAME: a page is
# ``page_size`` tokens whatever a token holds.


class LatentPagedCache(NamedTuple):
    """The latent page pool. Carried whole through the layer scans, written
    in place (``latent_write_pool``) and read through a layer index
    (ops/pallas/latent_attention.py), as ``PagedKVCache`` is."""

    latent: jnp.ndarray  # [n_layers, n_pages, page_size, latent_width]

    @property
    def n_layers(self) -> int:
        return self.latent.shape[0]

    @property
    def n_pages(self) -> int:
        return self.latent.shape[1]

    @property
    def page_size(self) -> int:
        return self.latent.shape[2]


def init_latent_cache(
    n_layers: int, n_pages: int, page_size: int, width: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> LatentPagedCache:
    return _zero_latent_pool(
        (n_layers, n_pages, page_size, width), jnp.dtype(dtype)
    )


_zero_latent_pool = tracked_jit(
    lambda shape, dtype: LatentPagedCache(latent=jnp.zeros(shape, dtype)),
    name="paged.init_latent_cache", static_argnames=("shape", "dtype"),
)


class LatentIndexPagedCache(NamedTuple):
    """The latent pool and, behind the SAME block table, the pool of a
    learned index's keys (``config.cache_kind == "latent+index"``): a page
    holds ``page_size`` tokens in both, so the allocator is not told. Both
    are carried whole through the layer scans and written in place by
    ``latent_write_pool``; a model without an index keeps
    ``LatentPagedCache``, one leaf."""

    latent: jnp.ndarray  # [n_layers, n_pages, page_size, latent_width]
    index: jnp.ndarray  # [n_layers, n_pages, page_size, index_head_dim]

    @property
    def n_layers(self) -> int:
        return self.latent.shape[0]

    @property
    def n_pages(self) -> int:
        return self.latent.shape[1]

    @property
    def page_size(self) -> int:
        return self.latent.shape[2]


def init_latent_index_cache(
    n_layers: int, n_pages: int, page_size: int, width: int, index_width: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> LatentIndexPagedCache:
    return _zero_latent_index_pools(
        (n_layers, n_pages, page_size), width, index_width, jnp.dtype(dtype)
    )


_zero_latent_index_pools = tracked_jit(
    lambda shape, width, index_width, dtype: LatentIndexPagedCache(
        latent=jnp.zeros((*shape, width), dtype),
        index=jnp.zeros((*shape, index_width), dtype),
    ),
    name="paged.init_latent_index_cache",
    static_argnames=("shape", "width", "index_width", "dtype"),
)


def latent_write_pool(
    pool: jnp.ndarray,
    layer: jnp.ndarray,
    new: jnp.ndarray,  # [batch, chunk, latent_width]
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    ends: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """``paged_write_pool`` for the latent pool: token ``pos + j`` of row
    ``b`` goes to ``[layer, block_tables[b, (pos + j) // page_size], (pos +
    j) % page_size, :]``, one scatter, in place. Unmapped entries, slots
    below ``starts[b]`` and slots at or past ``ends[b]`` (a window's dead
    tail) drop."""
    with jax.named_scope(CACHE_WRITE):
        n_pages, page_size = pool.shape[1], pool.shape[2]
        b, chunk = new.shape[0], new.shape[1]
        slots = pos + jnp.arange(chunk, dtype=jnp.int32)
        logical = jnp.broadcast_to(slots // page_size, (b, chunk))
        offs = jnp.broadcast_to(slots % page_size, (b, chunk))
        phys = jnp.take_along_axis(
            block_tables, logical, axis=1, mode="fill", fill_value=UNMAPPED
        )
        phys = jnp.where(phys < 0, n_pages, phys)
        if starts is not None:
            phys = jnp.where(slots[None, :] < starts[:, None], n_pages, phys)
        if ends is not None:
            phys = jnp.where(slots[None, :] >= ends[:, None], n_pages, phys)
        return pool.at[layer, phys, offs].set(new.astype(pool.dtype), mode="drop")


def gather_latent(
    pool: jnp.ndarray, block_tables: jnp.ndarray, layer: jnp.ndarray
) -> jnp.ndarray:
    """Dense view of each row's pages of one layer, [b, n_p * page_size,
    latent_width]: the XLA read path (CPU, and the kernel's oracle).
    Unmapped pages read zeros; the callers' masks exclude them."""
    bt = jnp.where(block_tables < 0, pool.shape[1], block_tables)
    g = pool.at[layer, bt].get(mode="fill", fill_value=0)
    b, n_p, ps, w = g.shape
    return g.reshape(b, n_p * ps, w)


class PageExhausted(RuntimeError):
    """The pool has no free page for a required mapping."""


class PageAllocator:
    """Host-side page bookkeeping: free list, refcounts, per-lane block tables.

    All state is numpy/python — nothing here runs under jit. The serving
    engine consults it for admission (``can_admit``), maps pages as sequences
    grow (``map_range``), and returns them when streams finish (``release``).
    ``fork``/``make_private`` implement refcounted prefix sharing with
    copy-on-write (the device-side byte copy is ``copy_pages``).

    Pool gauges (``cake_kv_pages_total/free/shared``) and the allocation-
    failure counter update on every mutating call, so ``/metrics`` and
    ``cake-tpu stats`` always show the live pool.
    """

    def __init__(
        self,
        n_pages: int,
        page_size: int,
        batch: int,
        max_pages_per_seq: int,
        reserve_pages: int = 1,
        window: int | None = None,
    ):
        if n_pages <= 0 or page_size <= 0:
            raise ValueError("n_pages and page_size must be positive")
        self.n_pages = n_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.reserve_pages = max(0, reserve_pages)
        # A WINDOWED kind's pool (``PagePools``): a layer of it admits the
        # ``window`` keys behind its query, so a page wholly behind that is
        # never mapped (``map_range`` clips) and goes back to the free list
        # as the shared slot passes it (``free_behind``). None (every pool
        # of a model whose layers are all of one kind) = neither: this
        # class's arithmetic as it always was.
        self.window = window
        self.freed_behind_window = 0  # pages, cumulative
        self.refcount = np.zeros(n_pages, np.int32)
        # LIFO free list: recently-freed pages are re-used first (their bytes
        # are likelier to still be resident in any cache hierarchy).
        self._free: list[int] = list(range(n_pages - 1, -1, -1))
        self.block_tables = np.full(
            (batch, max_pages_per_seq), UNMAPPED, np.int32
        )
        self._update_gauges()

    # ------------------------------------------------------------- accounting

    @property
    def pages_total(self) -> int:
        return self.n_pages

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_shared(self) -> int:
        return int((self.refcount > 1).sum())

    def pages_needed(self, n_tokens: int) -> int:
        return -(-max(0, n_tokens) // self.page_size)

    def can_admit(self, prompt_tokens: int) -> bool:
        """Admission rule: ceil(prompt / page_size) + reserve pages are free.

        ``reserve`` covers the page-boundary straddle of a left-padded layout
        (a prompt of N tokens can span pages_needed(N) + 1 physical pages) and
        gives the first decode tokens headroom.
        """
        return (
            self.pages_needed(prompt_tokens) + self.reserve_pages
            <= self.pages_free
        )

    def reset(self, batch: int) -> None:
        """Fresh epoch: every page free, every lane unmapped."""
        self.refcount[:] = 0
        self._free = list(range(self.n_pages - 1, -1, -1))
        self.block_tables = np.full(
            (batch, self.max_pages_per_seq), UNMAPPED, np.int32
        )
        self._update_gauges()

    def release_lanes(self, batch: int) -> None:
        """Unmap every lane, KEEPING non-lane references (the persistent
        prefix cache's chain refs, runtime/prefix_cache.py) alive.

        The persistent-pool epoch boundary: lane mappings drop (their pages
        free unless a cached chain still holds them) while the cache's pages
        — and the free-list identity of everything else — survive into the
        next epoch. ``reset`` by contrast zeroes ALL refcounts, which would
        silently orphan the cache's bookkeeping.
        """
        for lane in range(self.block_tables.shape[0]):
            self.release(lane)
        if batch != self.block_tables.shape[0]:
            self.block_tables = np.full(
                (batch, self.max_pages_per_seq), UNMAPPED, np.int32
            )
        self._update_gauges()

    # ------------------------------------------------------------- allocation

    def lane_mapped(self, lane: int) -> bool:
        return bool((self.block_tables[lane] >= 0).any())

    def lane_pages(self, lane: int) -> int:
        """Mapped logical pages of ``lane`` — the relief spilling it would
        yield (the continuous scheduler's preemption victim heuristic;
        shared pages count too: the lane's reference still blocks their
        reuse)."""
        return int((self.block_tables[lane] >= 0).sum())

    def pages_missing(self, lanes: list[int], start_slot: int, end_slot: int) -> int:
        """How many pages ``map_range`` over the same slots would allocate,
        over all of ``lanes``."""
        if end_slot <= start_slot or not lanes:
            return 0
        first = start_slot // self.page_size
        last = -(-end_slot // self.page_size)  # exclusive
        if self.window is not None:
            first = max(first, self.first_live_page(end_slot))
        return int((self.block_tables[lanes, first:last] < 0).sum())

    def map_range(self, lane: int, start_slot: int, end_slot: int) -> None:
        """Map pages so slots [start_slot, end_slot) of ``lane`` have storage.

        Already-mapped logical pages are kept (growth is incremental: decode
        calls this with a sliding [slot, slot + chunk) window and only page-
        boundary crossings allocate). Atomic: on exhaustion nothing is mapped
        and PageExhausted raises (the failure counter increments; the caller
        decides between truncating the stream and failing the epoch).
        """
        if end_slot <= start_slot:
            return
        first = start_slot // self.page_size
        last = -(-end_slot // self.page_size)  # exclusive
        if self.window is not None:
            # The next query sits at ``end_slot`` (a prefill's or a join's
            # window ends at the shared slot; a decode chunk's slots are
            # inside the window anyway): what it cannot see is not stored.
            first = max(first, self.first_live_page(end_slot))
        if last > self.max_pages_per_seq:
            raise ValueError(
                f"slots [{start_slot}, {end_slot}) need logical page "
                f"{last - 1} but the table has {self.max_pages_per_seq}"
            )
        row = self.block_tables[lane]
        need = [p for p in range(first, last) if row[p] < 0]
        if len(need) > len(self._free):
            metrics.registry.counter(
                _C_FAIL, "Page allocations refused for an empty free list."
            ).inc()
            self._update_gauges()
            raise PageExhausted(
                f"lane {lane} needs {len(need)} page(s), "
                f"{len(self._free)} free of {self.n_pages}"
            )
        for p in need:
            phys = self._free.pop()
            self.refcount[phys] = 1
            row[p] = phys
        self._update_gauges()

    def release(self, lane: int) -> None:
        """Drop every mapping of ``lane``; pages reaching refcount 0 go free."""
        row = self.block_tables[lane]
        for p in np.flatnonzero(row >= 0):
            phys = int(row[p])
            self.refcount[phys] -= 1
            if self.refcount[phys] == 0:
                self._free.append(phys)
        row[:] = UNMAPPED
        self._update_gauges()

    # ------------------------------------------------- a windowed kind

    def first_live_page(self, slot: int) -> int:
        """The first logical page a query at ``slot`` still reads: it admits
        keys at ``slot + 1 - window`` and after."""
        return max(0, slot + 1 - self.window) // self.page_size

    def free_behind(self, slot: int) -> int:
        """Before the program whose first query sits at the lanes' shared
        ``slot`` is enqueued: unmap every lane's pages wholly behind that
        query's window; returns how many went back to the free list. The
        lanes share the slot, so it is a range of the table's columns. A
        program already enqueued holds its own copy of the tables, and a
        page recycled here is written only by a program enqueued later."""
        stale = self.block_tables[:, : self.first_live_page(slot)]
        lanes, pages = np.nonzero(stale >= 0)
        if not len(lanes):
            return 0
        freed = 0
        for phys in stale[lanes, pages]:
            self.refcount[phys] -= 1
            if self.refcount[phys] == 0:
                self._free.append(int(phys))
                freed += 1
        stale[lanes, pages] = UNMAPPED
        self.freed_behind_window += freed
        self._update_gauges()
        return freed

    # ----------------------------------------------- prefix sharing (CoW)

    def retain_pages(self, pages: list[int]) -> None:
        """Take one non-lane reference on each physical page of a chain.

        The prefix cache's ownership primitive (runtime/prefix_cache.py
        insert): a page referenced by the cache survives every lane release
        until the chain is evicted (``release_pages``). Pages must currently
        be live (refcount > 0) — a chain is always adopted from a mapped
        lane, never conjured from the free list.
        """
        for phys in pages:
            if self.refcount[phys] <= 0:
                raise ValueError(f"page {phys} is free; cannot retain it")
            self.refcount[phys] += 1
        self._update_gauges()

    def release_pages(self, pages: list[int]) -> None:
        """Drop one reference per page (cache eviction / clear); pages
        reaching refcount 0 return to the free list."""
        for phys in pages:
            if self.refcount[phys] <= 0:
                raise ValueError(f"page {phys} is already free")
            self.refcount[phys] -= 1
            if self.refcount[phys] == 0:
                self._free.append(phys)
        self._update_gauges()

    def fork_chain(
        self, lane: int, pages: list[int], first_logical: int
    ) -> None:
        """Map a cached page chain into ``lane`` at logical pages
        [first_logical, first_logical + len(pages)), sharing storage (+1 ref
        per page). The chain-level sibling of ``fork``: the source is a
        prefix-cache chain, not another lane. Target entries must be
        unmapped — splicing over live mappings would leak their pages.
        """
        if first_logical < 0 or (
            first_logical + len(pages) > self.max_pages_per_seq
        ):
            raise ValueError(
                f"chain of {len(pages)} page(s) at logical {first_logical} "
                f"overflows the {self.max_pages_per_seq}-page table"
            )
        row = self.block_tables[lane]
        for i, phys in enumerate(pages):
            if row[first_logical + i] >= 0:
                raise ValueError(
                    f"fork_chain target lane {lane} logical page "
                    f"{first_logical + i} is already mapped"
                )
            self.refcount[phys] += 1
            row[first_logical + i] = phys
        self._update_gauges()

    def unmap_page(self, lane: int, logical_page: int) -> None:
        """Drop one logical-page mapping of ``lane`` (refcount -1, free at
        0) — the degraded path when a copy-on-write split cannot get its
        fresh page: the lane gives the shared page back and recomputes those
        tokens instead."""
        phys = int(self.block_tables[lane, logical_page])
        if phys < 0:
            raise ValueError(f"lane {lane} has no page {logical_page} mapped")
        self.refcount[phys] -= 1
        if self.refcount[phys] == 0:
            self._free.append(phys)
        self.block_tables[lane, logical_page] = UNMAPPED
        self._update_gauges()

    def fork(self, src_lane: int, dst_lane: int) -> None:
        """Map ``dst_lane`` onto ``src_lane``'s physical pages (shared, +1 ref).

        The shared-prompt-prefix seam: a request whose prompt extends another
        request's prompt can fork its lane and pay storage only for the pages
        it later diverges on (``make_private``). ``dst_lane`` must be unmapped.
        """
        if self.lane_mapped(dst_lane):
            raise ValueError(f"fork target lane {dst_lane} is already mapped")
        src = self.block_tables[src_lane]
        for p in np.flatnonzero(src >= 0):
            self.refcount[int(src[p])] += 1
        self.block_tables[dst_lane] = src
        self._update_gauges()

    def make_private(
        self, lane: int, logical_page: int
    ) -> tuple[int, int] | None:
        """Copy-on-write split before ``lane`` writes ``logical_page``.

        Returns (src_phys, dst_phys) when the page was shared — the caller
        must then ``copy_pages(cache, [src], [dst])`` before writing — or
        None when the lane already owns the page exclusively.
        """
        phys = int(self.block_tables[lane, logical_page])
        if phys < 0:
            raise ValueError(f"lane {lane} has no page {logical_page} mapped")
        if self.refcount[phys] <= 1:
            return None
        if not self._free:
            metrics.registry.counter(
                _C_FAIL, "Page allocations refused for an empty free list."
            ).inc()
            self._update_gauges()
            raise PageExhausted("copy-on-write split needs a free page")
        fresh = self._free.pop()
        self.refcount[phys] -= 1
        self.refcount[fresh] = 1
        self.block_tables[lane, logical_page] = fresh
        self._update_gauges()
        return phys, fresh

    # ------------------------------------------------------------- telemetry

    def _update_gauges(self) -> None:
        if self.window is not None:
            return  # the gauges are the primary kind's (``PagePools``)
        reg = metrics.registry
        reg.gauge(_G_TOTAL, "Physical KV pages in the pool.").set(
            self.pages_total
        )
        reg.gauge(_G_FREE, "KV pages currently on the free list.").set(
            self.pages_free
        )
        reg.gauge(
            _G_SHARED, "KV pages mapped by more than one lane (CoW-shared)."
        ).set(self.pages_shared)


class PagePools:
    """The allocators of a model whose attention layers are of more than one
    KIND (``config.attention_kinds``; ``cache_kind`` "kv+kinds"), one
    ``PageAllocator`` a kind over that kind's own pool, behind the interface
    the serving engine drives one allocator by. A model of one kind has no
    ``PagePools``: its backend hands the engine the ``PageAllocator`` itself.

    The FIRST kind is the primary: it stores every token (no window), so its
    pages are what admission is priced in (``pages_needed``, ``pages_free``,
    ``pages_total``, ``reserve_pages``) and what tells a live lane from a
    dead one (``block_tables``, ``lane_mapped``). A windowed kind holds at
    most ``window // page_size + 2`` pages a lane; its pool is sized for
    that (``models/llama/programs.pool_pages``), and every check here
    still counts it: ``can_admit`` asks every kind, ``pages_missing`` adds a
    windowed kind's shortfall, ``map_range`` maps all kinds or none.
    """

    def __init__(self, kinds: dict[str, PageAllocator]):
        self.kinds = kinds
        self.primary = next(iter(kinds.values()))
        if self.primary.window is not None:
            raise ValueError("the first kind stores every token: no window")
        self._windowed = [a for a in kinds.values() if a.window is not None]
        # Each lane's first and last mapped slot while it holds pages (its pad
        # and the end of the chunk it is writing), -1 otherwise: the tokens
        # a lane has cached, to within the chunk mapped ahead of them.
        self._spans(self.primary.block_tables.shape[0])

    # What the engine prices in, and reads a lane's life from: the primary's.
    page_size = property(lambda self: self.primary.page_size)
    reserve_pages = property(lambda self: self.primary.reserve_pages)
    max_pages_per_seq = property(lambda self: self.primary.max_pages_per_seq)
    pages_total = property(lambda self: self.primary.pages_total)
    pages_free = property(lambda self: self.primary.pages_free)
    pages_shared = property(lambda self: self.primary.pages_shared)
    block_tables = property(lambda self: self.primary.block_tables)

    def pages_needed(self, n_tokens: int) -> int:
        return self.primary.pages_needed(n_tokens)

    def lane_mapped(self, lane: int) -> bool:
        return self.primary.lane_mapped(lane)

    def lane_pages(self, lane: int) -> int:
        return self.primary.lane_pages(lane)

    def can_admit(self, prompt_tokens: int) -> bool:
        """By kind: a windowed kind prices a prompt at its window's pages."""
        return all(
            a.can_admit(
                prompt_tokens if a.window is None
                else min(prompt_tokens, a.window + a.page_size)
            )
            for a in self.kinds.values()
        )

    def pages_missing(self, lanes: list[int], start_slot: int, end_slot: int) -> int:
        """The primary's missing pages, and what a windowed kind would be
        SHORT of its own: the engine holds the sum against ``pages_free``."""
        short = sum(
            max(0, a.pages_missing(lanes, start_slot, end_slot) - a.pages_free)
            for a in self._windowed
        )
        return self.primary.pages_missing(lanes, start_slot, end_slot) + short

    def map_range(self, lane: int, start_slot: int, end_slot: int) -> None:
        """``PageAllocator.map_range`` in every kind, or in none."""
        for a in self.kinds.values():
            if a.pages_missing([lane], start_slot, end_slot) > a.pages_free:
                a.map_range(lane, start_slot, end_slot)  # raises, maps nothing
        for a in self.kinds.values():
            a.map_range(lane, start_slot, end_slot)
        if end_slot > start_slot:
            if self.first_slot[lane] < 0:
                self.first_slot[lane] = start_slot
            self.last_slot[lane] = max(self.last_slot[lane], end_slot)

    def _spans(self, batch: int) -> None:
        self.first_slot = np.full(batch, -1, np.int64)
        self.last_slot = np.full(batch, -1, np.int64)

    def release(self, lane: int) -> None:
        for a in self.kinds.values():
            a.release(lane)
        self.first_slot[lane] = self.last_slot[lane] = -1

    def reset(self, batch: int) -> None:
        for a in self.kinds.values():
            a.reset(batch)
        self._spans(batch)

    def release_lanes(self, batch: int) -> None:
        for a in self.kinds.values():
            a.release_lanes(batch)
        self._spans(batch)

    def free_behind(self, slot: int) -> int:
        """The windowed kinds' sweep, once a period (the engine calls it
        where it extends the lanes' pages; it has no such call for a model
        of one kind)."""
        return sum(a.free_behind(slot) for a in self._windowed)

    def cached_tokens(self) -> int:
        """Tokens the lanes that hold pages have mapped storage for, from
        each lane's pad to the end of the chunk it is writing: as of now,
        like ``pages_mapped`` beside it."""
        held = self.first_slot >= 0
        return int((self.last_slot[held] - self.first_slot[held]).sum())

    def facts(self, bytes_per_page: dict[str, int]) -> dict:
        """``GET /stats`` engine.cache.kinds: a kind's pool as it stands.
        ``freed_behind_window`` only grows."""
        return {
            kind: {
                "window": a.window,
                "pages_total": a.pages_total,
                "pages_mapped": a.pages_total - a.pages_free,
                "bytes_per_page": bytes_per_page[kind],
                "freed_behind_window": a.freed_behind_window,
            }
            for kind, a in self.kinds.items()
        }
