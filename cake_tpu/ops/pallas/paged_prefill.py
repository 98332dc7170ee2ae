"""Flash-class paged prefill: ragged page-resolving chunk attention.

The paged sibling of ops/pallas/chunk_prefill.py, closing the last kernel gap
of the paged serving mode: until now every paged PREFILL attended through
unfused XLA paths — the fresh chunk via an [chunk, chunk] einsum, and every
cache-enabled (suffix / verify) chunk via a gather of the FULL padded-max-seq
pool view plus an O(chunk * max_seq) f32 score tensor per head — at exactly
the long-prompt shapes where dense prefill gets the flash chunk kernel.

One arithmetic serves all three paged prefill shapes (the Ragged Paged
Attention recipe, PAPERS.md):

  * **paged chunked prefill** — a cold prompt's queries at slots
    ``[0, chunk)`` attend the pool-resident prefix their own writes just
    produced (``q_starts = 0``);
  * **paged cached-chunk prefill** — a suffix window's queries at absolute
    slots ``[start, start + W)`` attend cached pages plus their own fresh
    writes (runtime/prefix_cache.py warm prefill, ``q_starts = start``);
  * **paged speculative verify** — the [last, draft...] chunk at the epoch's
    shared slot (``q_starts = slot``), which is what finally lets
    speculative decoding run under ``kv_mode="paged"``.

The kernel is the chunk_prefill online-softmax recurrence with the
paged_attention decode trick folded in: per-row lengths/starts AND the block
table arrive as scalar-prefetch operands, the K/V index maps resolve the
PHYSICAL page inside the pipeline, and the dead-tail/causal/window clamp is
applied to the LOGICAL page before the table lookup — dead grid steps resolve
to an already-resident physical page and cost no DMA, so a chunk reads
O(live tokens) HBM bytes, not O(max_pages * page_size).

``paged_chunk_attention_xla`` is the gather-based twin (interpret/CPU path
and the numerics oracle): it reconstructs each row's dense head-major view
via ``gather_pages`` and runs the SAME masked-softmax arithmetic as the dense
XLA cached-chunk path, so paged-XLA streams compare bit-for-bit against dense
streams on CPU (tests/test_paged_prefill.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.models.llama.paged_cache import gather_pages
from cake_tpu.ops.attention import block_query_end, gqa_attention_hm, widen_qkv
from cake_tpu.ops.pallas.paged_attention import as_pool

_LANES = 128


def paged_kernel_supported(page_size: int) -> bool:
    """Whether the paged chunk/decode kernels can serve this pool layout:
    a page must be a whole number of 128-lane tiles so one page is one
    contiguous K/V block. Callers fall back to the XLA gather twin (and
    should surface a ``kernel-fallback`` flight event) otherwise."""
    return page_size % _LANES == 0


def _paged_chunk_kernel(
    qs_ref,
    lens_ref,
    ks_ref,
    tables_ref,
    flag_ref,
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
    block_q,
    page_size,
    window,
    softcap,
    block=None,
):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    pi = pl.program_id(3)  # LOGICAL page; k_ref/v_ref hold the physical page
    q0 = qs_ref[bi] + qi * block_q  # absolute slot of this q block's row 0
    # The last key slot any query of this q block may see: its own last slot,
    # or under the block-causal mask the end of that slot's block.
    q_hi = q0 + block_q - 1
    if block is not None:
        q_hi = block_query_end(q_hi, block)
    k_start = pi * page_size
    length = lens_ref[bi]
    row_first = ks_ref[bi]  # first live key slot (left-padded batch rows)

    first_block = row_first // page_size
    front_live = k_start + page_size > row_first
    if window is None:
        win_live = True
    else:
        flag = flag_ref[0] != 0
        wfirst = jnp.maximum(0, (q0 - window + 1) // page_size)
        first_block = jnp.maximum(first_block, jnp.where(flag, wfirst, 0))
        win_live = ~flag | (k_start + page_size > q0 - window + 1)
    executed = (
        (k_start <= q_hi) & (k_start < length) & front_live & win_live
    )
    # Largest pi satisfying the causal+length terms of `executed` (the window
    # only prunes the FRONT) — the epilogue runs exactly once, there.
    last_block = jnp.minimum(
        q_hi // page_size,
        jnp.maximum(length - 1, 0) // page_size,
    )
    # Clamp into the visited grid range so _init ALWAYS runs for every q
    # block (dense chunk kernel contract: q blocks with no executed page —
    # fully-padded rows, dead join rows — must still zero o_ref, or stale
    # VMEM NaNs poison later layers through the 0-weight p@v dot).
    first_block = jnp.minimum(first_block, pl.num_programs(3) - 1)

    @pl.when(pi == first_block)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    @pl.when(executed)
    def _update():
        q, k, v = widen_qkv(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, page_size), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1
        )
        # Causality hides the dead tail and padded chunk-tail keys (both sit
        # at kpos > every valid qpos); left-pad key slots sit BEFORE the live
        # region and need the explicit >= row_first mask. Queries below the
        # row's own pad (suffix windows can start before a warm row's pad)
        # end up all-masked and zero out through m_safe.
        if block is not None:
            # Block-causal: the whole of the query's own block. Slot space
            # serves because a row's left pad is whole blocks (batch.py).
            qpos = block_query_end(qpos, block)
        mask = (kpos <= qpos) & (kpos >= row_first)
        if window is not None:
            mask &= (kpos > qpos - window) | ~flag
        s = jnp.where(mask, s, -jnp.inf)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_ref.shape
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv

        # Epilogue on the LAST executed page only (pruning skips the dead
        # tail): renormalize + convert once per q block.
        @pl.when(pi == last_block)
        def _out():
            l_cur = l_ref[:, :1]
            o_ref[0, 0] = (
                acc_ref[...] / jnp.where(l_cur == 0.0, 1.0, l_cur)
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "softcap", "block_q", "interpret", "block"),
)
def paged_chunk_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    q_starts: jnp.ndarray,
    lengths: jnp.ndarray,
    k_starts: jnp.ndarray,
    block_tables: jnp.ndarray,
    window_flag: jnp.ndarray | None = None,
    *,
    layer: jnp.ndarray | None = None,
    window: int | None = None,
    scale: float | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    interpret: bool | None = None,
    block: int | None = None,
) -> jnp.ndarray:
    """Chunk-of-queries GQA attention against the page pool.

    Args:
      q: [batch, chunk, n_q_heads, head_dim] — row r's token i sits at
        absolute slot ``q_starts[r] + i``; the chunk's own keys must already
        be written through the block table.
      k_pages/v_pages: the whole pool [n_layers, n_pages, n_kv_heads,
        page_size, head_dim] (models/llama/paged_cache.py), read at ``layer``
        through the index maps; or one layer on its own, [n_pages, ...]: a
        pool of one layer, ``layer`` not given. ``page_size`` must be a
        multiple of the 128-lane tile (``paged_kernel_supported``).
      layer: int32 scalar (traced), the layer of a 5-D pool to attend over.
      q_starts: [batch] int32 absolute slot of each row's first query —
        zeros for a cold chunked prefill, the window start for a suffix
        prefill, the epoch's shared slot for a speculative verify chunk.
      lengths: [batch] int32 live prefix per row (>= q_starts + valid chunk);
        used only for pruning — causality supplies the masking.
      k_starts: [batch] int32 first live key slot per row (the left pads):
        pad slots are masked AND their pages pruned. Slot-space positions
        are causal/window-invariant because left-padding shifts a row's
        queries and keys equally (models/llama/batch.py).
      block_tables: [batch, n_p] int32 physical page per logical page;
        entries < 0 are unmapped (legal only outside the live window) and
        clamp to page 0 — finite garbage, no OOB DMA. ``n_p`` bounds the
        grid: callers slice the table to the epoch's bounded capacity
        (runtime/serving.py) so dead pages cost no grid steps at all.
      window/window_flag/scale/softcap: the dense chunk kernel's knobs.
      block: STATIC — the block-causal mask (ops/attention.py ``block``), in
        SLOT space: the caller's ``k_starts`` and ``q_starts`` are multiples
        of it, so a slot's block is its position's. Pruning keeps the pages
        up to the end of a q block's last block. None = causal.

    Returns [batch, chunk, n_q_heads, head_dim] in q's dtype.
    """
    b, chunk, n_q, d = q.shape
    k_pages, v_pages, layer = as_pool(k_pages, v_pages, layer)
    n_kv, page_size = k_pages.shape[2], k_pages.shape[3]
    if not paged_kernel_supported(page_size):
        raise ValueError(
            f"page_size {page_size} is not a multiple of the {_LANES}-lane "
            "tile (use paged_chunk_attention_xla for untiled page sizes)"
        )
    n_p = block_tables.shape[1]
    group = n_q // n_kv
    if scale is None:
        scale = d**-0.5
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    # Small chunks shrink the q block instead of padding to 128 rows — but
    # never below 16 sublanes, the minimum tile for sub-32-bit operands
    # (the dense chunk kernel's clamp).
    block_q = min(block_q, max(16, (chunk + 15) // 16 * 16))
    pad_q = (-chunk) % block_q
    qh = jnp.moveaxis(q, 2, 1)  # [b, n_q, chunk, d]
    if pad_q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    sq = chunk + pad_q

    if window_flag is None:
        flag = jnp.ones((1,), jnp.int32)
    else:
        flag = jnp.asarray(window_flag, jnp.int32).reshape(1)

    # Dead grid steps must not cost DMA: clamp the LOGICAL page into the
    # live range BEFORE the table lookup, so consecutive dead steps resolve
    # to the same resident physical page and Mosaic skips the repeated
    # fetch — the paged decode kernel's re-mapping with the chunk kernel's
    # causal/window bounds.
    def _kv_index(bi, hi, qi, ki, qs, lens, ks, tables, fl, lyr):
        q0 = qs[bi] + qi * block_q
        last_live = jnp.maximum(
            (lens[bi] + page_size - 1) // page_size - 1, 0
        )
        q_hi = q0 + block_q - 1
        if block is not None:
            q_hi = block_query_end(q_hi, block)
        last_needed = jnp.minimum(q_hi // page_size, last_live)
        first_needed = ks[bi] // page_size
        if window is not None:
            wfirst = jnp.maximum(0, (q0 - window + 1) // page_size)
            first_needed = jnp.maximum(
                first_needed, jnp.where(fl[0] != 0, wfirst, 0)
            )
        first_needed = jnp.minimum(first_needed, last_needed)
        phys = tables[bi, jnp.clip(ki, first_needed, last_needed)]
        return (lyr[0], jnp.maximum(phys, 0), hi // group, 0, 0)

    def _q_index(bi, hi, qi, ki, qs, lens, ks, tables, fl, lyr):
        return (bi, hi, qi, 0)

    grid = (b, n_q, sq // block_q, n_p)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), _q_index),
            pl.BlockSpec((None, 1, 1, page_size, d), _kv_index),
            pl.BlockSpec((None, 1, 1, page_size, d), _kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), _q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_chunk_kernel,
            scale=scale,
            block_q=block_q,
            page_size=page_size,
            window=window,
            softcap=softcap,
            **({} if block is None else {"block": block}),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_q, sq, d), q.dtype),
        interpret=interpret,
    )(
        jnp.asarray(q_starts, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(k_starts, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        flag,
        layer,
        qh,
        k_pages,
        v_pages,
    )
    return jnp.moveaxis(out[:, :, :chunk, :], 1, 2)


def paged_chunk_attention_xla(
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
    block: int | None = None,
) -> jnp.ndarray:
    """Gather-based twin: the dense XLA cached-chunk arithmetic over a
    gathered view of each row's pages (of layer ``layer`` where ``k_pages``
    is the whole 5-D pool) — the multi-query sibling of
    paged_attention.paged_decode_attention_xla, and the kernel's numerics
    oracle.

    ``q_positions``/``k_positions`` are the left-padded position grids the
    dense path feeds gqa_attention_hm (models/llama/batch.verify_positions /
    prefill_positions); the k grid must span the gathered width
    ``block_tables.shape[1] * page_size``. Because ``gather_pages``
    reproduces the dense layout at every mapped slot and the position masks
    exclude everything else, this is bit-identical to the dense XLA path on
    equal token histories — and bit-identical across block-table capacities
    on the SAME live keys is NOT guaranteed (reduction shapes change), which
    is why the serving engine threads ONE capacity per epoch
    (runtime/serving.py)."""
    k = gather_pages(k_pages, block_tables, layer)
    v = gather_pages(v_pages, block_tables, layer)
    return gqa_attention_hm(
        q, k, v, q_positions, k_positions,
        window=window, window_flag=window_flag, scale=scale, softcap=softcap,
        **({} if block is None else {"block": block}),
    )
