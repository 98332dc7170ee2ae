"""Ragged paged GQA decode attention over the page-pool KV cache.

The paged sibling of ops/pallas/decode_attention.py: one decode query per
sequence attends over that sequence's live prefix, but KV bytes live in a
shared page pool ([n_layers, n_pages, n_kv, page_size, head_dim], the
models/llama/paged_cache.py layout) and each sequence's pages are scattered.

**The grid is the rows of the batch** (times blocks of KV heads only where
one page of all heads overruns the kernel's VMEM budget: no model served
today). K and V pools stay where they are (``pl.ANY``: no block of them is
pipelined by Pallas), and one grid step does one row: it reads the row's
live window ``[start, length)`` and its block table from the scalar-prefetch
operands, and walks the row's LIVE pages in logical order in a ``fori_loop``,
copying each page by hand (``pltpu.make_async_copy``) into a ring of VMEM
page buffers, the next pages in flight while this one is scored. So:

  * **No step for a dead page.** A row costs its own live pages, whatever
    the width of the table the backend ships: a sequence at position p costs
    O(p) HBM bytes and O(p) work, not O(max_pages * page_size) grid steps.
  * **All KV heads of a page at once.** A page's copy is
    ``pool[layer, page]``: ``[n_kv, page_size, head_dim]``, one descriptor
    for K and one for V, scored as one product batched over the KV heads
    (all ``group`` query heads of a KV head in one ``[rows, page_size]``
    matmul: each KV byte is read once).
  * **The ring's depth follows the shapes**: as many page buffers as
    ``_KV_BUFFER_BYTES`` holds of this call's pages (``n_kv * page_size *
    head_dim`` of the pool's dtype, K and V), at least two, at most the
    table's width; a pool whose page is larger than half the budget is
    walked a block of KV heads at a time, the largest divisor of ``n_kv``
    that fits. Nothing names a model and no caller chooses.

The LAYER is one more scalar-prefetch operand: the model's layer scan carries
the whole pool and the copies pick the layer, so no layer is ever sliced out
of the pool to be read. An UNMAPPED table entry (< 0, possible only for
garbage lanes whose output nobody reads) clamps to page 0: finite garbage, no
OOB DMA. Scores and accumulators are float32; the softmax runs online over
the pages in logical order.

``paged_decode_attention_xla`` is the gather-based fallback (interpret/CPU and
the numerical oracle): it reconstructs each row's dense head-major view via
``gather_pages`` and runs the SAME masked-softmax arithmetic as the dense XLA
decode path (ops/attention.gqa_attention_hm), so dense-vs-paged token streams
compare bit-for-bit on CPU (tests/test_paged_serving.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.models.llama.paged_cache import gather_pages
from cake_tpu.ops.attention import gqa_attention_hm, widen_qkv

_LANES = 128
_SUBLANES = 8  # the query-group dim is padded up to whole sublane tiles
# VMEM one call's ring of K and V page buffers may take, of the 16 MiB a
# v5e core gives a kernel by default (q, the scores and the accumulators of
# one page are well under 1 MiB beside it).
_KV_BUFFER_BYTES = 4 * 1024 * 1024
# A key outside [start, length) scores this, not -inf: exp() of it is an
# exact 0 beside any live key, and a row with no live key at all (a garbage
# lane) still comes out finite.
_MASKED = -0.7 * float(np.finfo(np.float32).max)


def _paged_decode_kernel(
    lens_ref,
    starts_ref,
    tables_ref,
    layer_ref,
    q_ref,
    k_hbm,
    v_hbm,
    o_ref,
    k_buf,
    v_buf,
    sems,
    *,
    scale,
    softcap,
):
    bi = pl.program_id(0)
    n_slots, n_heads, page_size, _ = k_buf.shape
    heads = pl.ds(pl.program_id(1) * n_heads, n_heads)  # this step's KV heads
    n_p = tables_ref.shape[1]
    length = lens_ref[bi]
    start = starts_ref[bi]
    layer = layer_ref[0]
    # The row's live pages [first, last], held inside the table whatever a
    # garbage lane's bounds say.
    last = jnp.minimum(jnp.maximum(length - 1, start) // page_size, n_p - 1)
    first = jnp.minimum(start // page_size, last)
    n_live = last - first + 1

    def page_copies(i):
        """The K and V copies of the row's i-th live page into its slot."""
        slot = jax.lax.rem(i, n_slots)
        page = jnp.maximum(tables_ref[bi, first + i], 0)
        return (
            pltpu.make_async_copy(
                k_hbm.at[layer, page, heads], k_buf.at[slot], sems.at[0, slot]
            ),
            pltpu.make_async_copy(
                v_hbm.at[layer, page, heads], v_buf.at[slot], sems.at[1, slot]
            ),
        )

    def start_page(i, _=None):
        for copy in page_copies(i):
            copy.start()

    # Fill the ring but for one slot; each page scored frees the slot of the
    # page before it for the page n_slots - 1 ahead.
    jax.lax.fori_loop(0, jnp.minimum(n_live, n_slots - 1), start_page, None)

    def score_page(i, carry):
        m_prev, l_prev, acc = carry

        @pl.when(i + n_slots - 1 < n_live)
        def _():
            start_page(i + n_slots - 1)

        slot = jax.lax.rem(i, n_slots)
        for copy in page_copies(i):
            copy.wait()
        q, k, v = widen_qkv(q_ref[...], k_buf[slot], v_buf[slot])
        s = jax.lax.dot_general(  # [n_kv, rows, page_size]
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = (first + i) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2
        )
        s = jnp.where((kpos >= start) & (kpos < length), s, _MASKED)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(  # [n_kv, rows, head_dim]
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha + pv

    stat = q_ref.shape[:2] + (1,)
    _, l, acc = jax.lax.fori_loop(
        0, n_live, score_page,
        (
            jnp.full(stat, _MASKED, jnp.float32),
            jnp.zeros(stat, jnp.float32),
            jnp.zeros(q_ref.shape, jnp.float32),
        ),
    )
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def _apply_window(starts, lengths, window, window_flag):
    """Fold a sliding window into the pruning start (dense-kernel semantics):
    the decode query at position length-1 admits keys >= length - window."""
    if window is None:
        return starts
    w_start = jnp.maximum(starts, lengths - window)
    if window_flag is None:
        return w_start
    return jnp.where(window_flag, w_start, starts)


def as_pool(k_pages, v_pages, layer):
    """(k_pool, v_pool, layer [1] int32) for the paged kernels' operands: a
    5-D pool with its traced layer index, or a 4-D single layer viewed as a
    pool of one (a reshape, no bytes move)."""
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("a 4-D k_pages is one layer; it takes no `layer`")
        return k_pages[None], v_pages[None], jnp.zeros((1,), jnp.int32)
    if layer is None:
        raise ValueError("a 5-D pool needs the `layer` to read")
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "softcap", "interpret"),
)
def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    starts: jnp.ndarray | None = None,
    window_flag: jnp.ndarray | None = None,
    *,
    layer: jnp.ndarray | None = None,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-position GQA attention against the page pool.

    Args:
      q: [batch, 1, n_q_heads, head_dim] — the current token's queries.
      k_pages/v_pages: the whole pool [n_layers, n_pages, n_kv_heads,
        page_size, head_dim] (models/llama/paged_cache.py), read at ``layer``
        through the index maps; or one layer on its own, [n_pages, ...]: a
        pool of one layer, ``layer`` not given. ``page_size`` must be a
        multiple of the 128-lane tile so each page is a full-width block.
      layer: int32 scalar (traced), the layer of a 5-D pool to attend over.
      lengths: [batch] int32 live prefix length per sequence (current pos + 1;
        the token at pos must already be written through the block table).
      block_tables: [batch, max_pages_per_seq] int32 physical page per logical
        page; entries < 0 are unmapped (legal only outside [start, length)).
      starts: optional [batch] int32 first live slot per row (left-padded
        lockstep batches); None = 0. Each row must satisfy start < length.
      window/window_flag/scale/softcap: the dense kernel's knobs, identical
        semantics (window folds into the pruning start).

    Returns [batch, 1, n_q_heads, head_dim] in q's dtype.
    """
    b, q_len, n_q, d = q.shape
    if q_len != 1:
        raise ValueError(
            f"paged_decode_attention takes one position, got q_len={q_len}"
        )
    k_pages, v_pages, layer = as_pool(k_pages, v_pages, layer)
    n_kv, page_size = k_pages.shape[2], k_pages.shape[3]
    if page_size % _LANES:
        raise ValueError(
            f"page_size {page_size} is not a multiple of the {_LANES}-lane "
            "tile (use the XLA fallback for untiled page sizes)"
        )
    n_p = block_tables.shape[1]
    group = n_q // n_kv
    rows = -(-group // _SUBLANES) * _SUBLANES
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # [b, 1, n_q, d] -> [b, n_kv, rows, d]: group queries land on their KV head.
    qg = q.reshape(b, n_kv, group, d)
    if rows != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - group), (0, 0)))

    lengths = jnp.asarray(lengths, jnp.int32)
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    starts = _apply_window(starts, lengths, window, window_flag)
    block_tables = jnp.asarray(block_tables, jnp.int32)

    # One page of all KV heads, K and V, is the unit the kernel copies and
    # scores; the ring holds as many as the budget takes of THIS pool's pages.
    # Only where two such pages overrun the budget (many KV heads, a long or
    # wide page) a step takes a block of the heads, the largest that fits.
    head_bytes = 2 * page_size * d * k_pages.dtype.itemsize
    n_heads = max(
        h for h in range(1, n_kv + 1)
        if n_kv % h == 0 and (h == 1 or 2 * h * head_bytes <= _KV_BUFFER_BYTES)
    )
    n_slots = int(np.clip(
        _KV_BUFFER_BYTES // (n_heads * head_bytes), 2, max(n_p, 2)
    ))
    page_buf = pltpu.VMEM((n_slots, n_heads, page_size, d), k_pages.dtype)
    q_spec = pl.BlockSpec(
        (None, n_heads, rows, d), lambda bi, hi, *_: (bi, hi, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, n_kv // n_heads),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            page_buf, page_buf, pltpu.SemaphoreType.DMA((2, n_slots)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rows, d), q.dtype),
        interpret=interpret,
    )(lengths, starts, block_tables, layer, qg, k_pages, v_pages)
    return out[:, :, :group, :].reshape(b, 1, n_q, d)


def paged_decode_attention_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    q_positions: jnp.ndarray,
    k_positions: jnp.ndarray,
    block_tables: jnp.ndarray,
    window: int | None = None,
    window_flag: jnp.ndarray | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    layer: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Gather-based fallback: the dense XLA decode arithmetic over a gathered
    view of each row's pages (of layer ``layer`` where ``k_pages`` is the
    whole 5-D pool).

    ``q_positions``/``k_positions`` are the left-padded position grids the
    dense path feeds gqa_attention_hm (models/llama/batch.decode_positions) —
    the k grid must span ``max_pages_per_seq * page_size`` slots. Because
    ``gather_pages`` reproduces the dense layout at every mapped slot and the
    position masks exclude everything else, this is bit-identical to the
    dense XLA decode path on equal token histories.
    """
    k = gather_pages(k_pages, block_tables, layer)
    v = gather_pages(v_pages, block_tables, layer)
    return gqa_attention_hm(
        q, k, v, q_positions, k_positions,
        window=window, window_flag=window_flag, scale=scale, softcap=softcap,
    )
