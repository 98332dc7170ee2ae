"""A decode step's index scores over the pool of index keys, read in place.

A model with a learned index (``model_type: deepseek_v32``; ops/
sparse_index.py) keeps one key a token a layer, ``k_I`` [index_head_dim], in
a pool of its own behind the latent pool's block table
(models/llama/paged_cache.py ``LatentIndexPagedCache``). A decode step scores
every cached token of a row with ``index_n_heads`` small heads and a weight a
head,

    I[b, s] = sum_j w[b, j] * relu(q_I[b, j] . k_I[s])      start <= s < length

and ``-inf`` everywhere else of the row's table. The XLA form
(``sparse_index.index_scores``) gathers the row's WHOLE table of keys
whatever the row holds (168 pages a row, 88 MB a layer-step in the
benchmark's cell) and scores all of it; this kernel is built as
ops/pallas/latent_attention.py is: **the grid is the rows of the batch**, the
pool stays in HBM (``pl.ANY``), a grid step reads its row's live window and
its block table from scalar prefetch and walks the row's LIVE pages only,
copying each by hand into a ring of VMEM buffers with the next copies in
flight. A page of keys is 32 KB, a fifth of a latent page, and a loop turn
costs more than its bytes, so a turn takes a GROUP of pages (``group``: table
pages ``[g * group, (g + 1) * group)``, so that a group's scores are one
aligned store and no store leaves the table; of a row's first and last group
only the live pages are copied): one copy a page, one product ``[heads, dim]
x [group * page_size, dim]^T`` accumulated in float32, ``relu``, the weights
and the sum over the heads in float32 on the vector unit (as the XLA form's
compiled program does them: a float32 multiply and reduce), one ``[1, group
* page_size]`` store under the row's ``[start, length)``.

The output block is eight rows of the batch (all of them where the batch is
not whole eights): the row that opens a block fills it with ``-inf``, every
row overwrites the lanes of its own live groups, and the block goes back to
HBM while the next eight rows are scored.

The layer is a scalar-prefetch operand (the model's layer scan carries the
whole pool). An unmapped table entry clamps to page 0: only a dead slot is
there, and it is masked. ``sparse_index.index_scores`` is the twin (the CPU,
shapes that do not tile, the kernel's oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# Pages a loop turn scores together, at most, and the VMEM the ring of groups
# may take (a group of 8 pages x 128 tokens x 128 bf16 is 256 KB). Timed on
# the chip at groups of 4 to 24 of a 168-page table and rings of 1 to 8 MB
# (PERF.md section 6, PR 44): 8 pages read the fewest dead pages of a row's
# two ends for a loop turn that costs little more than its bytes.
_GROUP_PAGES = 8
_RING_BYTES = 2 * 1024 * 1024
_NEG_INF = float("-inf")


def _index_scores_kernel(
    lens_ref,
    starts_ref,
    tables_ref,
    layer_ref,
    q_ref,  # [heads, dim]
    w_ref,  # [heads, 1] float32
    pool_hbm,  # [n_layers, n_pages, page_size, dim]
    o_ref,  # [rows of the block, table slots] float32
    buf,  # [n_slots, group, page_size, dim]
    sems,  # [n_slots]: a group's copies share one
):
    bi = pl.program_id(0)
    n_slots, group, page_size, dim = buf.shape
    row = jax.lax.rem(bi, o_ref.shape[0])
    n_p = tables_ref.shape[1]
    length = lens_ref[bi]
    start = starts_ref[bi]
    layer = layer_ref[0]
    # The row's live pages [first, last], held inside the table whatever a
    # garbage lane's bounds say, and the groups of table pages they lie in.
    last = jnp.minimum(jnp.maximum(length - 1, start) // page_size, n_p - 1)
    first = jnp.minimum(start // page_size, last)
    g_first = first // group
    n_groups = last // group - g_first + 1

    @pl.when(row == 0)
    def _():
        o_ref[...] = jnp.full(o_ref.shape, _NEG_INF, o_ref.dtype)

    def each_live_copy(i, act):
        """``act`` on the copy of every live page of the row's i-th group."""
        slot = jax.lax.rem(i, n_slots)
        base = (g_first + i) * group
        for j in range(group):
            page = jnp.maximum(tables_ref[bi, base + j], 0)
            copy = pltpu.make_async_copy(
                pool_hbm.at[layer, page], buf.at[slot, j], sems.at[slot]
            )
            pl.when((base + j >= first) & (base + j <= last))(
                functools.partial(act, copy)
            )

    def start_group(i, _=None):
        each_live_copy(i, lambda copy: copy.start())

    # Fill the ring but for one slot; each group scored frees the slot of the
    # group before it for the group n_slots - 1 ahead.
    jax.lax.fori_loop(0, jnp.minimum(n_groups, n_slots - 1), start_group, None)

    def score_group(i, _):
        @pl.when(i + n_slots - 1 < n_groups)
        def _():
            start_group(i + n_slots - 1)

        each_live_copy(i, lambda copy: copy.wait())
        slot = jax.lax.rem(i, n_slots)
        # A dead page of the group holds whatever the buffer held: its
        # columns are the products' alone and are masked below.
        keys = buf[slot].reshape(group * page_size, dim)
        s = jax.lax.dot_general(  # [heads, group * page_size]
            q_ref[...], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        scores = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0, keepdims=True)
        slot0 = pl.multiple_of((g_first + i) * (group * page_size), _LANES)
        kpos = slot0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        o_ref[pl.ds(row, 1), pl.ds(slot0, group * page_size)] = jnp.where(
            (kpos >= start) & (kpos < length), scores, _NEG_INF
        )

    jax.lax.fori_loop(0, n_groups, score_group, None)


def paged_index_scores_supported(
    page_size: int, index_head_dim: int, index_n_heads: int
) -> bool:
    """Whole lane tiles wherever the kernel copies, and index heads in whole
    sublane tiles of the queries' block."""
    return not (
        page_size % _LANES or index_head_dim % _LANES
        or index_n_heads % (2 * _SUBLANES)
    )


def pages_a_group(table_pages: int, at_most: int = _GROUP_PAGES) -> int:
    """The largest divisor of the table's pages not above ``at_most``."""
    return max(g for g in range(1, at_most + 1) if table_pages % g == 0)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def paged_index_scores(
    q_i: jnp.ndarray,
    w: jnp.ndarray,
    index_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: jnp.ndarray,
    group: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``sparse_index.index_scores`` with the pool read in place.

    Args:
      q_i: [batch, index_n_heads, index_head_dim] after RoPE.
      w: [batch, index_n_heads] float32, the head weights already scaled.
      index_pool: [n_layers, n_pages, page_size, index_head_dim], read at
        ``layer`` (the queries are brought to its dtype, as the twin's).
      block_tables: [batch, max_pages_per_seq]; entries < 0 unmapped.
      starts, lengths: [batch] the row's live slots ``[start, length)``.
      group: pages a loop turn scores (a divisor of the table's pages).

    Returns [batch, table slots] float32, ``-inf`` where a slot holds no
    token of the row.
    """
    b, n_heads, dim = q_i.shape
    page_size = index_pool.shape[2]
    if not paged_index_scores_supported(page_size, dim, n_heads):
        raise ValueError(
            f"page_size {page_size} and index_head_dim {dim} must be multiples "
            f"of the {_LANES}-lane tile and index_n_heads {n_heads} of "
            f"{2 * _SUBLANES} (use the XLA twin)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_p = block_tables.shape[1]
    group = pages_a_group(n_p) if group is None else group
    if n_p % group:
        raise ValueError(f"a group of {group} pages does not divide a table of {n_p}")
    rows = _SUBLANES if b % _SUBLANES == 0 else b
    group_bytes = group * page_size * dim * index_pool.dtype.itemsize
    n_slots = int(np.clip(_RING_BYTES // group_bytes, 2, max(n_p // group, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, n_heads, dim), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec((None, n_heads, 1), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (rows, n_p * page_size), lambda bi, *_: (bi // rows, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((n_slots, group, page_size, dim), index_pool.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    return pl.pallas_call(
        _index_scores_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_p * page_size), jnp.float32),
        interpret=interpret,
        name="paged_index_scores",
    )(
        jnp.asarray(lengths, jnp.int32), jnp.asarray(starts, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q_i.astype(index_pool.dtype), w.astype(jnp.float32)[..., None], index_pool,
    )
