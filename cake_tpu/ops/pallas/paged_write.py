"""The paged pool's write: a window's K and V into their pages, as slabs.

The Pallas form of ``models/llama/paged_cache.paged_write_pool`` (whose
scatter is its XLA twin and oracle). The pool is ``[n_layers, n_pages, n_kv,
page_size, head_dim]``, so token ``t`` of a lane is one row in each of
``n_kv`` strips, and a scatter that keeps the pool in that layout has to make
every ``[head_dim]`` row an update of its own: 960 of them for one decode
step of 32 lanes at 30 KV heads, 100 ns each. Here the unit is a SLAB, ``[n_kv,
unit, head_dim]``: ``unit`` consecutive slots of one page, all KV heads, one
DMA descriptor.

Every caller's write position is one scalar for all rows, so the window
``[pos, pos + W)`` cuts every row's pages at the same offsets. The slots are
walked in whole units from ``pos`` rounded down: a unit is a page where the
window is at least a page wide, else one sublane tile of the pool's dtype (8
rows of 32 bits: 16 of bf16, which pack two rows a word). A row's live slots
are ``[max(pos, starts[b]), pos + W)``, so of each (row, unit):

  * **nothing live, or no page behind it** (an UNMAPPED entry, a logical
    page past the table): NO copy is issued. Not a masked write-back: with a
    shared write position a dead row clamped onto somebody's page would read
    the very slab its owner is writing in this call and put the stale copy
    back over it.
  * **all of it live**: one copy, HBM to HBM, from the window laid out
    head-major and shifted to unit alignment (XLA does both in one pass
    over the window: a transpose into a zeroed buffer at ``pos % unit``).
  * **part of it live** (a decode step always; a window's first and last
    unit): the slab is read into VMEM, the live rows are put in with a mask
    over 32-bit words (a bf16 row is half a word: the mask has a half for
    each), and the slab is written back. The dead rows go back as they came.

A decode step (``W == 1``) has no shifted window: its rows ride in VMEM as
32-bit words that hold the row's value in every part, and a head's row is
broadcast over the sublanes where the mask takes it.

Units are walked a GROUP at a time: every read of the group in flight, then
each slab merged and its write started as its read lands, then the writes
waited for. The group is as many units as ``_BUFFER_BYTES`` holds slabs for
(K and V, old and new), ``_GROUP_SLABS`` at most (their semaphores); a slab
larger than the budget is taken a block of KV heads at a time. Nothing names a model and no caller chooses.

Both pools are aliased to the outputs and stay in HBM (``pl.ANY``); the
LAYER, the block tables, ``starts`` and ``pos`` are scalar-prefetch operands,
so the table is read where the copy is built: no gather, no ``where``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8
# VMEM one call's slab buffers may take, of the 16 MiB a v5e core gives a
# kernel by default.
_BUFFER_BYTES = 4 * 1024 * 1024
# Slabs of one group at most: each has six DMA semaphores (K and V times
# ``_SEMAPHORE``'s three), and a core's semaphore memory holds 512 (a group
# of 160 small slabs, 32 rows x 5 tiles at one KV head, did not compile).
_GROUP_SLABS = 32
# A copy's kind and the semaphore of its (side, slot) that it signals: the
# old slab's read, the window's slab's read, and whatever writes the pool (a
# merged slab's write-back, or a whole unit's direct copy: never both).
_SEMAPHORE = {"old": 0, "new": 1, "back": 2, "direct": 2}


def compiled_here() -> bool:
    """Whether a call from here is compiled by Mosaic. The callers' rule for
    the paged kernels (``use_pallas and paged_kernel_supported(page_size)``)
    takes the attention kernels through the interpreter on the CPU; the
    write it takes only where this holds too. Interpreted, every copy of a
    slab is a branch around an update of the whole pool, and a program's
    compile grows by a second a traced write: there the scatter serves, and
    the tests call the kernel themselves."""
    return jax.default_backend() != "cpu"


def _words(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` as uint32 of the same shape, each value's bits in every part
    of its word (a bf16 in both halves), so that a mask over a word's parts
    takes the value whichever row of the word it lands in."""
    bits = 8 * x.dtype.itemsize
    u = lax.bitcast_convert_type(x, jnp.dtype(f"uint{bits}")).astype(jnp.uint32)
    return u * jnp.uint32(sum(1 << s for s in range(0, 32, bits)))


def _shifted(x: jnp.ndarray, shift: jnp.ndarray, rows: int) -> jnp.ndarray:
    """A window ``[b, W, n_kv, head_dim]`` laid out as the pool is, ``[b,
    n_kv, rows, head_dim]``, its first slot at row ``shift``, zeros around."""
    b, _, n_kv, hd = x.shape
    blank = jnp.zeros((b, n_kv, rows, hd), x.dtype)
    return lax.dynamic_update_slice(
        blank, jnp.swapaxes(x, 1, 2), (0, 0, shift, 0))


def _all(*conditions):
    """Their conjunction. Inside a kernel ``lax`` directly: an operator or a
    ``jnp`` function on a traced value is a jitted call of its own, a few
    hundred of them a trace of this kernel (PERF.md, PR 35)."""
    return functools.reduce(lax.bitwise_and, conditions)


def _kernel(
    tables_ref, starts_ref, pos_ref, layer_ref,
    k_src, v_src, _k_in, _v_in, k_out, v_out, *scratch,
    width: int, unit: int, n_units: int, n_blocks: int, n_items: int,
):
    """``k_src`` / ``v_src``: the decode step's rows in VMEM, uint32 ``[b,
    n_kv, head_dim]``, or the shifted window in HBM, ``[b, n_kv, n_units *
    unit, head_dim]``. The pools are read and written through the OUTPUT
    references only: they are the inputs' buffers."""
    step = width == 1
    if step:
        old_buf, sems = scratch
        new_buf = None
    else:
        old_buf, new_buf, sems = scratch
    _, group, n_heads, _, hd = old_buf.shape
    page_size, (n_rows, n_p) = k_out.shape[3], tables_ref.shape
    pack = 4 // old_buf.dtype.itemsize  # rows a 32-bit word holds
    words = unit // pack
    pos, layer = pos_ref[0], layer_ref[0]
    pools, srcs = (k_out, v_out), (k_src, v_src)

    def item(i):
        """(row, first KV head, page, offset in the page, live rows [lo, hi)
        of the unit, anything live) of the i-th (row, unit, block of heads)."""
        hb = lax.rem(i, n_blocks)
        t = lax.div(i, n_blocks)
        j = lax.rem(t, n_units)
        bi = lax.min(lax.div(t, n_units), n_rows - 1)  # the last group's tail
        slot0 = lax.mul(lax.add(lax.div(pos, unit), j), unit)
        logical = lax.div(slot0, page_size)
        page = tables_ref[bi, lax.min(logical, n_p - 1)]
        lo = lax.max(lax.sub(lax.max(pos, starts_ref[bi]), slot0), 0)
        hi = lax.min(lax.sub(lax.add(pos, width), slot0), unit)
        live = _all(lax.lt(i, n_items), lax.lt(logical, n_p),
                    lax.ge(page, 0), lax.lt(lo, hi))
        off = pl.multiple_of(lax.rem(slot0, page_size), unit)
        return bi, j, lax.mul(hb, n_heads), page, off, lo, hi, live

    def slab(side, page, h0, off):
        return pools[side].at[
            layer, page, pl.ds(h0, n_heads), pl.ds(off, unit), :
        ]

    def window_slab(side, bi, j, h0):
        at = pl.multiple_of(lax.mul(j, unit), unit)
        return srcs[side].at[bi, pl.ds(h0, n_heads), pl.ds(at, unit), :]

    def copies(kind, s, bi, j, h0, page, off):
        """A slot's K and V copies of one kind (``_SEMAPHORE``'s keys)."""
        out = []
        for side in range(2):
            pool = slab(side, page, h0, off)
            if kind == "old":
                ends = pool, old_buf.at[side, s]
            elif kind == "new":
                ends = window_slab(side, bi, j, h0), new_buf.at[side, s]
            elif kind == "back":
                ends = old_buf.at[side, s], pool
            else:
                ends = window_slab(side, bi, j, h0), pool
            out.append(pltpu.make_async_copy(
                *ends, sems.at[side, _SEMAPHORE[kind], s]))
        return out

    def merge(s, bi, h0, lo, hi):
        # ``take``: the bits of each word that live rows own
        row = lax.broadcasted_iota(jnp.int32, (words, hd), 0) * pack
        none = jnp.zeros((words, hd), jnp.uint32)
        take = none
        for part in range(pack):
            bits = ((1 << (32 // pack)) - 1) << (part * 32 // pack)
            here = _all(lax.ge(row + part, lo), lax.lt(row + part, hi))
            take = lax.bitwise_or(
                take, lax.select(here, none + jnp.uint32(bits), none))
        keep = lax.bitwise_not(take)
        dtype = old_buf.dtype

        def head(h, _):
            for side in range(2):
                if step:
                    new = lax.broadcast_in_dim(
                        srcs[side][bi, pl.ds(lax.add(h0, h), 1), :],
                        (words, hd), (0, 1))
                else:
                    new = pltpu.bitcast(new_buf[side, s, h], jnp.uint32)
                old = pltpu.bitcast(old_buf[side, s, h], jnp.uint32)
                old_buf[side, s, h] = pltpu.bitcast(
                    lax.bitwise_or(lax.bitwise_and(old, keep),
                                   lax.bitwise_and(new, take)), dtype)

        # traced once, laid out head after head
        lax.fori_loop(0, n_heads, head, None, unroll=True)

    def run_group(g, _):
        reads = ("old",) if step else ("old", "new")

        def phase(of_part, of_whole):
            """One walk over the group's slots: ``of_part(at, lo, hi)`` where
            part of the unit is live, ``of_whole(at)`` where all of it is."""
            def walk(s, _):
                bi, j, h0, page, off, lo, hi, live = item(
                    lax.add(lax.mul(g, group), s))
                at = (s, bi, j, h0, page, off)
                whole = _all(lax.eq(lo, 0), lax.eq(hi, unit))
                pl.when(_all(live, lax.bitwise_not(whole)))(
                    lambda: of_part(at, lo, hi))
                if not step:
                    pl.when(_all(live, whole))(lambda: of_whole(at))
            return walk

        def start(kinds, at):
            for kind in kinds:
                for copy in copies(kind, *at):
                    copy.start()

        def wait(kinds, at):
            for kind in kinds:
                for copy in copies(kind, *at):
                    copy.wait()

        def put(at, lo, hi):
            wait(reads, at)
            merge(at[0], at[1], at[3], lo, hi)
            start(("back",), at)

        begin = phase(lambda at, lo, hi: start(reads, at),
                      lambda at: start(("direct",), at))
        end = phase(lambda at, lo, hi: wait(("back",), at),
                    lambda at: wait(("direct",), at))
        for walk in (begin, phase(put, lambda at: None), end):
            lax.fori_loop(0, group, walk, None)

    lax.fori_loop(0, -(-n_items // group), run_group, None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_pool_write(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    layer: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``paged_write_pool``'s contract, byte for byte: ``k_new`` / ``v_new``
    ``[b, W, n_kv, head_dim]`` go to slots ``[pos, pos + W)`` of layer
    ``layer`` of the whole pools ``[n_layers, n_pages, n_kv, page_size,
    head_dim]`` through ``block_tables`` ``[b, n_p]``; an UNMAPPED entry, a
    logical page past the table and a slot below ``starts[b]`` write nothing.
    ``page_size`` must be whole sublane tiles of the pool's dtype (the
    callers' rule, ``paged_kernel_supported``, asks for whole lane tiles)."""
    n_kv, page_size, hd = k_pool.shape[2:]
    b, width = k_new.shape[:2]
    dtype = k_pool.dtype
    tile = _SUBLANES * 4 // dtype.itemsize
    if page_size % tile:
        raise ValueError(
            f"page_size {page_size} is not whole {tile}-row tiles of {dtype} "
            "(use paged_cache.paged_write_pool's scatter)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    unit = page_size if width >= page_size else tile
    n_units = 1 + -(-(width - 1) // unit)  # a window at any offset touches
    step = width == 1
    # K and V, and beside each old slab the window's unless the rows ride
    # in VMEM
    head_bytes = (2 if step else 4) * unit * hd * dtype.itemsize
    n_heads = max(
        h for h in range(1, n_kv + 1)
        if n_kv % h == 0 and (h == 1 or h * head_bytes <= _BUFFER_BYTES)
    )
    n_blocks = n_kv // n_heads
    n_items = b * n_units * n_blocks
    group = max(1, min(
        _BUFFER_BYTES // (n_heads * head_bytes), _GROUP_SLABS, n_items))

    pos = jnp.asarray(pos, jnp.int32).reshape(1)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
    k_new, v_new = k_new.astype(dtype), v_new.astype(dtype)
    if step:
        srcs = [_words(k_new[:, 0]), _words(v_new[:, 0])]
        src_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    else:
        shift = lax.rem(pos[0], unit)
        srcs = [_shifted(x, shift, n_units * unit) for x in (k_new, v_new)]
        src_spec = pl.BlockSpec(memory_space=pl.ANY)
    slabs = pltpu.VMEM((2, group, n_heads, unit, hd), dtype)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(1,),
        in_specs=[src_spec, src_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
        scratch_shapes=[slabs] * (1 if step else 2)
        + [pltpu.SemaphoreType.DMA((2, 3, group))],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, width=width, unit=unit, n_units=n_units,
            n_blocks=n_blocks, n_items=n_items,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, dtype),
            jax.ShapeDtypeStruct(v_pool.shape, dtype),
        ],
        # operands 6 and 7 (after the four scalars and the two sources) are
        # the pools; they are the outputs
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
        name="paged_pool_write",
    )(
        jnp.asarray(block_tables, jnp.int32), jnp.asarray(starts, jnp.int32),
        pos, jnp.asarray(layer, jnp.int32).reshape(1),
        *srcs, k_pool, v_pool,
    )
