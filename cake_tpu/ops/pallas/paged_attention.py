"""Ragged paged GQA decode attention over the page-pool KV cache.

The paged sibling of ops/pallas/decode_attention.py: one decode query per
sequence attends over that sequence's live prefix, but KV bytes live in a
shared page pool ([n_layers, n_pages, n_kv, page_size, head_dim], the
models/llama/paged_cache.py layout) and each sequence's pages are scattered —
the kernel walks them in logical order through a block table delivered as a
scalar-prefetch operand. The LAYER is one more scalar-prefetch operand: the
model's layer scan carries the whole pool and the index maps pick the layer,
so no layer is ever sliced out of the pool to be read.

What carries over from the dense kernel, because it is the same bandwidth
argument:

  * **Length pruning.** Per-sequence lengths arrive via scalar prefetch; grid
    steps for logical pages outside the live [start, length) window clamp
    their K/V index maps into the live page range, so Mosaic's pipeline skips
    the repeated fetch — a sequence at position p costs O(p) HBM bytes, not
    O(max_pages * page_size).
  * **Grouped streaming.** All ``group`` query heads sharing a KV head score
    in one [group, page_size] matmul per page: each KV byte is read once.

What is new: the K/V index maps read ``block_tables[seq, page]`` — the
physical page — instead of the logical block index. An UNMAPPED entry (< 0,
possible only for garbage lanes whose output nobody reads) clamps to page 0:
finite garbage, no OOB DMA.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.models.llama.paged_cache import gather_pages
from cake_tpu.ops.attention import gqa_attention_hm, widen_qkv

_LANES = 128
_MIN_ROWS = 8  # pad the query-group dim up to a full sublane tile


def _paged_decode_kernel(
    lens_ref,
    starts_ref,
    tables_ref,
    layer_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale,
    page_size,
    softcap,
):
    bi = pl.program_id(0)
    pi = pl.program_id(2)  # LOGICAL page index; k_ref holds the physical page
    length = lens_ref[bi]
    start = starts_ref[bi]
    k_start = pi * page_size

    # The first live page (start // page_size) always contains position
    # ``start`` (callers guarantee start < length), so scratch init happens
    # exactly once, before any executed update.
    @pl.when(pi == start // page_size)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip pages entirely outside [start, length): the bandwidth win.
    @pl.when((k_start < length) & (k_start + page_size > start))
    def _update():
        q, k, v = widen_qkv(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0])
        rows = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1
        )
        s = jnp.where((kpos >= start) & (kpos < length), s, -jnp.inf)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        # The first live page always executes, so writing the running result
        # on every live page leaves the final value in the output block.
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


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
    rows = max(group, _MIN_ROWS)
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

    # Dead grid steps must not cost DMA: clamp the LOGICAL page into the live
    # range before the table lookup, so consecutive dead steps resolve to the
    # same physical page and Mosaic skips the repeated fetch (the dense
    # kernel's clamp, with one extra indirection). Unmapped entries clamp to
    # physical page 0 — finite garbage for lanes whose output nobody reads.
    def _kv_index(bi, hi, pi, lens, st, tables, lyr):
        first_live = st[bi] // page_size
        last_live = jnp.maximum(
            (lens[bi] + page_size - 1) // page_size - 1, 0
        )
        phys = tables[bi, jnp.clip(pi, first_live, last_live)]
        return (lyr[0], jnp.maximum(phys, 0), hi, 0, 0)

    def _q_index(bi, hi, pi, lens, st, tables, lyr):
        return (bi, hi, 0, 0)

    grid = (b, n_kv, n_p)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), _q_index),
            pl.BlockSpec((None, 1, 1, page_size, d), _kv_index),
            pl.BlockSpec((None, 1, 1, page_size, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            scale=scale,
            page_size=page_size,
            softcap=softcap,
        ),
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
