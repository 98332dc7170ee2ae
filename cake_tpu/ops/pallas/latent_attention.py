"""Absorbed latent (MLA) decode attention over the latent page pool.

Latent attention keeps one vector a token a layer, ``[ckv | k_rope | 0]``
(models/llama/paged_cache.py ``LatentPagedCache``: the compressed K/V after
its norm, the shared rotary key after RoPE, zeros up to whole lane tiles),
and every head reads the same one. With the per-head up-projection of the
keys absorbed into the query (``q~_h = q_nope_h Wuk_h^T``) a head's score
against a cached token is one dot product with that vector, and the head's
value is a weighted sum of the vectors' first ``kv_lora_rank`` numbers
(``o_h = (sum p ckv) Wuv_h``, applied by the caller). So decode is
multi-QUERY attention of ``n_heads`` queries of the latent's width against
one "KV head" whose values are a slice of its keys:

    s_h = (q_h . latent) * scale        q_h = [q~_h | q_rope_h | 0]
    c_h = softmax(s_h) @ latent[:, :rank]

Built as ops/pallas/paged_attention.py is (PR 29): **the grid is the rows of
the batch**, the pool stays in HBM (``pl.ANY``), a grid step reads its row's
live window ``[start, length)`` and its block table from scalar prefetch and
walks the row's LIVE pages in a ``fori_loop``, copying each page by hand
into a ring of VMEM page buffers with the next pages in flight. A page is
ONE copy (there is no second pool for the values: a latent byte is read
once), scored as ``[n_heads, width] x [page_size, width]^T`` and weighed
into ``[n_heads, rank]`` from the same buffer's first ``rank`` lanes. At 128
heads that is 2 x 128 x (576 + 512) operations a cached token against 1152
bytes: 242 operations a byte, the v5e's own ridge (197e12 / 819e9 = 240),
so neither peak may be assumed (bench/architectures/pangu_ultra_moe.py has
the cost functions).

The layer is a scalar-prefetch operand (the model's layer scan carries the
whole pool). An unmapped table entry clamps to page 0: finite garbage, read
only by a garbage lane. Scores and accumulators are float32.

``latent_decode_attention_xla`` is the gather-based twin (CPU, and the
kernel's numerical oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.models.llama.paged_cache import gather_latent

_LANES = 128
_SUBLANES = 8
# VMEM the ring of page buffers may take (a page of 128 tokens x 640 bf16 is
# 160 KB: 25 slots, fewer where the table is narrower).
_PAGE_BUFFER_BYTES = 4 * 1024 * 1024
_MASKED = -0.7 * float(np.finfo(np.float32).max)


def _latent_decode_attention_kernel(
    lens_ref,
    starts_ref,
    tables_ref,
    layer_ref,
    q_ref,  # [heads, width]
    pool_hbm,  # [n_layers, n_pages, page_size, width]
    o_ref,  # [heads, rank]
    buf,  # [n_slots, page_size, width]
    sems,
    *,
    scale,
):
    bi = pl.program_id(0)
    n_slots, page_size, _ = buf.shape
    rank = o_ref.shape[-1]
    n_p = tables_ref.shape[1]
    length = lens_ref[bi]
    start = starts_ref[bi]
    layer = layer_ref[0]
    last = jnp.minimum(jnp.maximum(length - 1, start) // page_size, n_p - 1)
    first = jnp.minimum(start // page_size, last)
    n_live = last - first + 1

    def page_copy(i):
        slot = jax.lax.rem(i, n_slots)
        page = jnp.maximum(tables_ref[bi, first + i], 0)
        return pltpu.make_async_copy(
            pool_hbm.at[layer, page], buf.at[slot], sems.at[slot]
        )

    def start_page(i, _=None):
        page_copy(i).start()

    jax.lax.fori_loop(0, jnp.minimum(n_live, n_slots - 1), start_page, None)

    def score_page(i, carry):
        m_prev, l_prev, acc = carry

        @pl.when(i + n_slots - 1 < n_live)
        def _():
            start_page(i + n_slots - 1)

        slot = jax.lax.rem(i, n_slots)
        page_copy(i).wait()
        q = q_ref[...]
        page = buf[slot]
        if page.dtype != q.dtype:  # a narrower pool widens on read
            page = page.astype(q.dtype)
        s = jax.lax.dot_general(  # [heads, page_size]
            q, page, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        kpos = (first + i) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where((kpos >= start) & (kpos < length), s, _MASKED)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(  # [heads, rank]: the same page's values
            p.astype(page.dtype), page[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha + pv

    heads = q_ref.shape[0]
    _, l, acc = jax.lax.fori_loop(
        0, n_live, score_page,
        (
            jnp.full((heads, 1), _MASKED, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, rank), jnp.float32),
        ),
    )
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def latent_kernel_supported(page_size: int, width: int, rank: int) -> bool:
    """Whole lane tiles everywhere the kernel copies or slices."""
    return not (page_size % _LANES or width % _LANES or rank % _LANES)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_decode_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    *,
    layer: jnp.ndarray,
    rank: int,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One position's absorbed latent attention against the pool.

    Args:
      q: [batch, n_heads, width]: ``[q~ | q_rope | 0]`` per head, in the
        pool's own layout.
      pool: [n_layers, n_pages, page_size, width], read at ``layer``.
      lengths: [batch] live prefix per row (the position's own latent must
        already be written through the table).
      block_tables: [batch, max_pages_per_seq]; entries < 0 unmapped.
      starts: optional [batch] first live slot per row (left pads).
      rank: numbers of a latent that are the values (``kv_lora_rank``).
      scale: the score scale (``(qk_nope + qk_rope) ** -0.5``: of the
        EXPANDED head, which the absorbed product equals).

    Returns [batch, n_heads, rank] in q's dtype: ``sum p ckv`` a head.
    """
    b, n_heads, width = q.shape
    page_size = pool.shape[2]
    if not latent_kernel_supported(page_size, width, rank):
        raise ValueError(
            f"page_size {page_size}, width {width} and rank {rank} must be "
            f"multiples of the {_LANES}-lane tile (use the XLA twin)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_p = block_tables.shape[1]
    rows = -(-n_heads // _SUBLANES) * _SUBLANES
    if rows != n_heads:
        q = jnp.pad(q, ((0, 0), (0, rows - n_heads), (0, 0)))
    lengths = jnp.asarray(lengths, jnp.int32)
    starts = (
        jnp.zeros((b,), jnp.int32) if starts is None
        else jnp.asarray(starts, jnp.int32)
    )
    page_bytes = page_size * width * pool.dtype.itemsize
    n_slots = int(np.clip(_PAGE_BUFFER_BYTES // page_bytes, 2, max(n_p, 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, rows, width), lambda bi, *_: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, rows, rank), lambda bi, *_: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_slots, page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_decode_attention_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(
        lengths, starts, jnp.asarray(block_tables, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1), q, pool,
    )
    return out[:, :n_heads]


def latent_decode_attention_xla(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    *,
    layer: jnp.ndarray,
    rank: int,
    scale: float,
) -> jnp.ndarray:
    """The same arithmetic over a gathered dense view of each row's pages."""
    view = gather_latent(pool, block_tables, layer)  # [b, S, width]
    if view.dtype != q.dtype:
        view = view.astype(q.dtype)
    s = jnp.einsum(
        "bhw,bsw->bhs", q, view, preferred_element_type=jnp.float32
    ) * scale
    slot = jnp.arange(view.shape[1], dtype=jnp.int32)[None, :]
    lo = 0 if starts is None else starts[:, None]
    live = (slot >= lo) & (slot < lengths[:, None])
    s = jnp.where(live[:, None, :], s, _MASKED)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhs,bsr->bhr", p.astype(view.dtype), view[..., :rank],
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
