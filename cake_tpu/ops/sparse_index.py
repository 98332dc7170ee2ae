"""A learned index over the cached tokens, and latent attention over the
tokens it chooses (``model_type: deepseek_v32``; models/llama/latent_index.py).

Beside a token's latent the cache holds ONE index key a token a layer,
``k_I`` [index_head_dim]. A query scores every cached token with
``index_n_heads`` small heads and a weight a head,

    I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])          s <= t

and attention's softmax and sum run over the ``index_topk`` positions of
largest ``I[t, .]`` only (all of them while the row is shorter). Ties break
towards the smaller position.

Two forms of one arithmetic, as latent attention's own:

  * **decode** (one query a row): ``index_scores`` over the row's LIVE pages
    of the index pool, read in place (the Pallas kernel
    ops/pallas/index_scores.py on the chip: its time follows what the rows
    hold; the XLA twin gathers the row's whole table of keys and scores all
    of it), ``select_topk`` (one sort a row, the chosen tokens' rows of
    the pool riding through it), ``sparse_latent_attention``: the chosen rows
    of the LATENT pool are gathered, ``index_topk`` of them whatever the row
    holds, and the absorbed attention runs over those: its bytes follow the
    tokens chosen, not the tokens cached;
  * **a window** (prefill, a join), in the mask form (``-inf`` off the chosen
    set) as the published inference code runs a prefill: its queries' choice
    block by block (``window_index_scores``, ``topk_mask``: float32 scores
    are [block, keys] a few index heads at a time), kept as ONE int8 mask
    [queries, keys] for all heads, then ``window_attention`` a group of heads
    at a time: the Pallas kernel ops/pallas/masked_prefill.py on the chip,
    ``masked_latent_attention`` as its XLA twin.

Every function enters its own scope (``obs/taxonomy``: nested inside
``mixer``); the chip's numbers are in PERF.md section 6, PR 43 and PR 44.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama.paged_cache import gather_latent
from cake_tpu.ops.pallas.index_scores import paged_index_scores

INDEX_SCORES, INDEX_SELECT, SPARSE_ATTENTION = (
    "index_scores", "index_select", "sparse_attention",
)
# Queries a block of a window's attention takes, index heads a step of the
# scores' sum and attention heads a step of the masked attention: a block's
# scores are [heads of the step, block, keys] in float32 (at 16k keys 134 MB
# a head), beside 12 GB of weights and pool.
WINDOW_BLOCK = 2048
_INDEX_HEAD_STEP = 4
_ATTENTION_HEAD_STEP = 4
_NEG_INF = float("-inf")
_MASKED = -0.7 * float(np.finfo(np.float32).max)


# ------------------------------------------------------------------- decode


def index_scores(
    q_i: jnp.ndarray,  # [b, heads, dim] after RoPE
    w: jnp.ndarray,  # [b, heads] float32, the head weights already scaled
    index_pool: jnp.ndarray,  # [n_layers, n_pages, page_size, dim]
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,  # [b] first live slot
    lengths: jnp.ndarray,  # [b] one past the last live slot
    *,
    layer: jnp.ndarray,
    kernel: bool = False,
) -> jnp.ndarray:
    """``I`` [b, table slots] float32 of one query a row against the row's
    pages of the index pool; ``-inf`` where a slot holds no token of the row.
    With ``kernel`` the Pallas kernel ops/pallas/index_scores.py, which reads
    the pool in place and walks a row's LIVE pages only; without it (the CPU,
    shapes that do not tile) the XLA twin, which gathers the row's whole
    table of keys and scores all of it."""
    with jax.named_scope(INDEX_SCORES):
        if kernel:
            return paged_index_scores(
                q_i, w, index_pool, block_tables, starts, lengths, layer=layer
            )
        keys = gather_latent(index_pool, block_tables, layer)  # [b, S, dim]
        s = jnp.einsum(
            "bhd,bsd->bhs", q_i.astype(keys.dtype), keys,
            preferred_element_type=jnp.float32,
        )
        scores = jnp.einsum("bh,bhs->bs", w, jax.nn.relu(s))
        slot = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, :]
        live = (slot >= starts[:, None]) & (slot < lengths[:, None])
        return jnp.where(live, scores, _NEG_INF)


def pool_rows(block_tables: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """[b, table slots] int32: where each slot of a row's table lies among the
    ``n_pages * page_size`` token rows of a pool's layer (an unmapped page's
    slots point at row 0: only a dead slot is there). The same for every
    layer of a step, so a step computes it once."""
    pages = jnp.maximum(block_tables, 0)[:, :, None] * page_size
    rows = pages + jnp.arange(page_size, dtype=jnp.int32)
    return rows.reshape(block_tables.shape[0], -1).astype(jnp.int32)


def select_topk(
    scores: jnp.ndarray, k: int, carried: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(what the ``k`` largest scores a row carry [b, k'], which of them are
    tokens [b, k']), ``k' = min(k, slots)``; equal scores go to the smaller
    slot. ``carried`` [b, slots] rides through the sort beside the scores
    (``pool_rows``: the chosen tokens' rows of the pool come out of the
    choice itself, where a gather of 2,048 table entries a row afterwards
    took as long as the sort: PERF.md section 6, PR 43); None carries the
    slots themselves. A row with fewer live slots than ``k`` gets them all
    and dead slots marked so. The TPU compiler sorts the whole row for a
    ``top_k`` of this size too."""
    with jax.named_scope(INDEX_SELECT):
        if carried is None:
            carried = jnp.broadcast_to(
                jnp.arange(scores.shape[-1], dtype=jnp.int32), scores.shape
            )
        k = min(k, scores.shape[-1])
        top, picked = jax.lax.sort(
            (-scores, carried), dimension=-1, is_stable=True, num_keys=1
        )
        return picked[:, :k], top[:, :k] < jnp.inf


def sparse_latent_attention(
    q: jnp.ndarray,  # [b, heads, width]: [q~ | q_rope | 0], the pool's layout
    pool: jnp.ndarray,  # [n_layers, n_pages, page_size, width]
    rows: jnp.ndarray,  # [b, k] the chosen tokens' rows of a layer (``pool_rows``)
    chosen: jnp.ndarray,  # [b, k] bool: the row holds a token of the query's row
    *,
    layer: jnp.ndarray,
    rank: int,
    scale: float,
) -> jnp.ndarray:
    """Absorbed latent attention over the CHOSEN tokens: their rows of the
    latent pool are gathered (``k`` rows of ``width`` numbers a row of the
    batch, whatever the row has cached) and scored as
    ``latent_decode_attention`` scores a page. [b, heads, rank] in q's dtype."""
    with jax.named_scope(SPARSE_ATTENTION):
        n_layers, n_pages, page_size, width = pool.shape
        tokens = pool.reshape(n_layers, n_pages * page_size, width)
        got = tokens[layer, jnp.where(chosen, rows, 0)]  # [b, k, width]
        if got.dtype != q.dtype:
            got = got.astype(q.dtype)
        s = jnp.einsum(
            "bhw,bkw->bhk", q, got, preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(chosen[:, None, :], s, _MASKED)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhk,bkr->bhr", p.astype(got.dtype), got[..., :rank],
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)


# ----------------------------------------------------------------- a window


def _head_steps(x: jnp.ndarray, axis: int, step: int) -> jnp.ndarray:
    """``x`` with its head axis moved first and split [steps, step, ...]."""
    x = jnp.moveaxis(x, axis, 0)
    step = step if x.shape[0] % step == 0 else 1
    return x.reshape(x.shape[0] // step, step, *x.shape[1:])


def window_index_scores(
    q_i: jnp.ndarray,  # [b, B, heads, dim] a block's index queries after RoPE
    w: jnp.ndarray,  # [b, B, heads] float32
    k_i: jnp.ndarray,  # [b, E, dim] the window's index keys
    admitted: jnp.ndarray,  # [b, B, E] bool: key is a token of the row, not after the query
) -> jnp.ndarray:
    """``I`` [b, B, E] float32 of a block of a window's queries, ``-inf``
    where not ``admitted``; summed a few index heads at a time."""
    with jax.named_scope(INDEX_SCORES):
        def add(total, step):
            q, wj = step  # [step, b, B, dim], [step, b, B]
            s = jnp.einsum(
                "jbtd,bsd->jbts", q.astype(k_i.dtype), k_i,
                preferred_element_type=jnp.float32,
            )
            return total + jnp.einsum("jbt,jbts->bts", wj, jax.nn.relu(s)), None

        total, _ = jax.lax.scan(
            add, jnp.zeros(admitted.shape, jnp.float32),
            (_head_steps(q_i, 2, _INDEX_HEAD_STEP), _head_steps(w, 2, _INDEX_HEAD_STEP)),
        )
        return jnp.where(admitted, total, _NEG_INF)


def _sortable(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Bool mask, ``scores``' shape, of the ``k`` largest a row (last axis)
    that are above ``-inf``; equal scores go to the smaller position. The
    k-th largest is found bit by bit (32 counts over the row, no sort): a
    window's rows are thousands of queries by thousands of keys."""
    with jax.named_scope(INDEX_SELECT):
        if scores.shape[-1] <= k:
            return scores > _NEG_INF
        key = _sortable(scores)

        def bit(i, prefix):
            cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
            enough = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
            return jnp.where(enough, cand, prefix)

        kth = jax.lax.fori_loop(
            0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32)
        )[..., None]
        above, ties = key > kth, key == kth
        room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
        return (above | (ties & first)) & (scores > _NEG_INF)


def window_attention(
    q_nope: jnp.ndarray,  # [b, t, heads, nope]
    q_rope: jnp.ndarray,  # [b, t, heads, rope] after RoPE
    ckv: jnp.ndarray,  # [b, E, rank] the window's normed compressed K/V
    k_rope: jnp.ndarray,  # [b, E, rope] the shared rotary key after RoPE
    w_uk: jnp.ndarray,  # [heads, rank, nope]
    w_uv: jnp.ndarray,  # [heads, rank, v]
    mask: jnp.ndarray,  # [b, t, E] int8: non-zero where the query attends the key
    *,
    scale: float,
    starts: jnp.ndarray,  # [b] the row's first token among the keys
    lengths: jnp.ndarray,  # [b] one past its last
    kernel: bool,
) -> jnp.ndarray:
    """Expanded latent attention of a window's queries (query i at key slot
    i) over the keys its ``mask`` admits, for the heads given, [b, t, heads,
    v]: these heads' keys and values are expanded from the latents, and the
    Pallas kernel (ops/pallas/masked_prefill.py) streams them under the
    mask's tiles; without ``kernel`` (the CPU, widths that do not tile) the
    XLA twin."""
    if not kernel:
        return masked_latent_attention(
            q_nope, q_rope, ckv, k_rope, w_uk, w_uv, mask != 0, scale=scale
        )
    from cake_tpu.ops.pallas.masked_prefill import masked_prefill_attention

    with jax.named_scope(SPARSE_ATTENTION):
        b, t, n, nope = q_nope.shape
        d = nope + q_rope.shape[-1]
        pad = -d % 128
        k_nope = jnp.einsum("bsc,hcd->bhsd", ckv, w_uk)
        v = jnp.einsum("bsc,hcd->bhsd", ckv, w_uv)
        e = ckv.shape[1]
        k = jnp.concatenate([
            k_nope, jnp.broadcast_to(k_rope[:, None], (b, n, e, k_rope.shape[-1])),
            jnp.zeros((b, n, e, pad), k_nope.dtype),
        ], axis=-1)
        q = jnp.concatenate(
            [q_nope, q_rope, jnp.zeros((b, t, n, pad), q_nope.dtype)], axis=-1
        )
        return masked_prefill_attention(
            q, k, v, mask, jnp.zeros((b,), jnp.int32), lengths, starts, scale=scale
        )


def window_kernel_supported(width: int, nope: int, rope: int, v: int) -> bool:
    from cake_tpu.ops.pallas.masked_prefill import masked_prefill_supported

    return masked_prefill_supported(width, width, -(-(nope + rope) // 128) * 128, v)


def masked_latent_attention(
    q_nope: jnp.ndarray,  # [b, B, heads, nope]
    q_rope: jnp.ndarray,  # [b, B, heads, rope] after RoPE
    ckv: jnp.ndarray,  # [b, E, rank] the window's normed compressed K/V
    k_rope: jnp.ndarray,  # [b, E, rope] the shared rotary key after RoPE
    w_uk: jnp.ndarray,  # [heads, rank, nope]
    w_uv: jnp.ndarray,  # [heads, rank, v]
    mask: jnp.ndarray,  # [b, B, E] bool: the keys each query attends
    *,
    scale: float,
) -> jnp.ndarray:
    """Expanded latent attention of a block of queries over the keys its
    ``mask`` admits, [b, B, heads, v]: a few heads at a time, each step
    expanding its own heads' keys and values from the latents. A query whose
    mask is empty (a pad slot) gets a finite garbage row nobody reads."""
    with jax.named_scope(SPARSE_ATTENTION):
        bias = jnp.where(mask, 0.0, _MASKED)[:, None]  # [b, 1, B, E]

        def heads(step):
            qn, qr, uk, uv = step  # [j, b, B, .], [j, rank, .]
            k_nope = jnp.einsum("bsc,jcd->bjsd", ckv, uk)
            v = jnp.einsum("bsc,jcd->bjsd", ckv, uv)
            s = (
                jnp.einsum("jbtd,bjsd->bjts", qn, k_nope,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("jbtd,bsd->bjts", qr, k_rope,
                             preferred_element_type=jnp.float32)
            ) * scale + bias
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum(
                "bjts,bjsd->jbtd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            ).astype(q_nope.dtype)

        step = _ATTENTION_HEAD_STEP
        out = jax.lax.map(heads, (
            _head_steps(q_nope, 2, step), _head_steps(q_rope, 2, step),
            _head_steps(w_uk, 0, step), _head_steps(w_uv, 0, step),
        ))  # [steps, j, b, B, v]
        out = out.reshape(-1, *out.shape[2:])  # [heads, b, B, v]
        return jnp.moveaxis(out, 0, 2)
