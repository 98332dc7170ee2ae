"""A window's attention under a mask a query, as a Pallas TPU kernel.

A model whose queries attend a CHOSEN subset of the keys before them
(ops/sparse_index.py: the ``index_topk`` best by a learned index) runs a
window's prefill in the mask form: ordinary multi-head attention with
``-inf`` off the chosen set. The set differs from query to query and is the
same for every head, so it comes as one int8 array [batch, queries, keys]
whose tiles ride beside the key blocks. Its XLA twin
(``sparse_index.masked_latent_attention``) materialises [heads, queries,
keys] float32 scores and runs at a tenth of the MXU's peak (PERF.md section 6,
PR 43); this kernel is ops/pallas/chunk_prefill.py's (online softmax over key
blocks, the key blocks wholly behind a row's first token, wholly after a
query block's last query or past the row's length never fetched) with the
mask's tile in place of the causal comparison. The mask must admit no key
after its query and none outside ``[k_starts, lengths)``: pruning relies on
it. Values may be narrower than keys (MLA: 128 beside 192 padded to 256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_MASK_SUBLANES = 32  # an int8 tile's rows
# The largest blocks: a grid step costs about a third of a microsecond whether
# or not it has work (a join's window is as wide as the next width up, so most
# of a short prompt's steps have none), and a key block is read again for
# every query block: at 224 x 384 a call over 13,440 keys was 33,600 steps and
# 18 ms (my chip call 2, PR 43). 512 x 1024 holds a block's float32 scores
# (2 MB) and its operands twice over in 8 MB of VMEM.
_BLOCK_Q, _BLOCK_K = 512, 1024
_VMEM_LIMIT = 48 * 1024 * 1024


def _masked_kernel(
    qs_ref, lens_ref, ks_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
    acc_ref, m_ref, l_ref, *, scale, block_q, block_k,
):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q0 = qs_ref[bi] + qi * block_q  # slot of this q block's first query
    k_start = ki * block_k
    length = lens_ref[bi]
    row_first = ks_ref[bi]
    first_block = jnp.minimum(row_first // block_k, pl.num_programs(3) - 1)
    executed = (
        (k_start <= q0 + block_q - 1) & (k_start < length)
        & (k_start + block_k > row_first)
    )
    last_block = jnp.minimum(
        (q0 + block_q - 1) // block_k, jnp.maximum(length - 1, 0) // block_k
    )

    @pl.when(ki == first_block)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    @pl.when(executed)
    def _update():
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, -jnp.inf)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Rows with nothing admitted yet (pad queries) keep exact zeros.
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

        @pl.when(ki == last_block)
        def _out():
            l_cur = l_ref[:, :1]
            o_ref[0, 0] = (
                acc_ref[...] / jnp.where(l_cur == 0.0, 1.0, l_cur)
            ).astype(o_ref.dtype)


def _dividing(n: int, most: int, step: int) -> int:
    """The largest multiple of ``step`` up to ``most`` that divides ``n``; 0
    where none does."""
    return next((c for c in range(most - most % step, 0, -step) if n % c == 0), 0)


def masked_prefill_supported(queries: int, keys: int, d_k: int, d_v: int) -> bool:
    """Whole tiles everywhere: key blocks of whole 128s that divide the keys,
    query blocks of whole int8 tiles that divide the queries."""
    return bool(
        not (d_k % _LANES or d_v % _LANES)
        and _dividing(keys, _BLOCK_K, _LANES)
        and _dividing(queries, _BLOCK_Q, _MASK_SUBLANES)
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def masked_prefill_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    q_starts: jnp.ndarray,
    lengths: jnp.ndarray,
    k_starts: jnp.ndarray,
    *,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-head attention of a window's queries over the keys ``mask``
    admits.

    Args:
      q: [batch, queries, heads, d_k]; row r's query i sits at slot
        ``q_starts[r] + i`` of the keys' axis.
      k: [batch, heads, keys, d_k]; v: [batch, heads, keys, d_v] (head-major).
      mask: [batch, queries, keys] int8, non-zero where the query attends the
        key. It must admit no key after its query's slot and none outside
        ``[k_starts[r], lengths[r])``: those blocks are never fetched.
      q_starts, lengths, k_starts: [batch] int32.

    Returns [batch, queries, heads, d_v] in q's dtype; a query that attends
    nothing gets zeros.
    """
    b, t, n, d_k = q.shape
    keys, d_v = k.shape[2], v.shape[3]
    if not masked_prefill_supported(t, keys, d_k, d_v):
        raise ValueError(
            f"{t} queries x {keys} keys of {d_k} / {d_v} do not tile (use the "
            "XLA twin)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_k = _dividing(keys, _BLOCK_K, _LANES)
    block_q = _dividing(t, _BLOCK_Q, _MASK_SUBLANES)

    def _key_block(bi, qi, ki, qs, lens, ks):
        """Dead steps clamp onto a block that is needed: no fetch."""
        q0 = qs[bi] + qi * block_q
        last_live = jnp.maximum((lens[bi] + block_k - 1) // block_k - 1, 0)
        last = jnp.minimum((q0 + block_q - 1) // block_k, last_live)
        first = jnp.minimum(ks[bi] // block_k, last)
        return jnp.clip(ki, first, last)

    def kv_index(bi, hi, qi, ki, qs, lens, ks):
        return (bi, hi, _key_block(bi, qi, ki, qs, lens, ks), 0)

    def q_index(bi, hi, qi, ki, *_):
        return (bi, hi, qi, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n, t // block_q, keys // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_k), q_index),
            pl.BlockSpec((1, 1, block_k, d_k), kv_index),
            pl.BlockSpec((1, 1, block_k, d_v), kv_index),
            pl.BlockSpec(
                (1, block_q, block_k),
                lambda bi, hi, qi, ki, qs, lens, ks: (
                    bi, qi, _key_block(bi, qi, ki, qs, lens, ks)
                ),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d_v), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _masked_kernel, scale=scale, block_q=block_q, block_k=block_k
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, t, d_v), q.dtype),
        interpret=interpret,
        name="masked_prefill_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
    )(
        jnp.asarray(q_starts, jnp.int32), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(k_starts, jnp.int32),
        jnp.moveaxis(q, 2, 1), k, v, mask,
    )
    return jnp.moveaxis(out, 1, 2)
