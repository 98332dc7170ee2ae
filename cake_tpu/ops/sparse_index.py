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
    of it), ``select_topk`` (the window's search for the k-th largest, on
    the chip as one operation, ops/pallas/kth_largest.py; then the chosen
    set's rows of the pool from counts a page and one one-hot product: dense
    work only, no sort, and a SET: its order is the slots', not the scores'),
    ``sparse_latent_attention``: the chosen rows of the
    LATENT pool are gathered, ``index_topk`` of them whatever the row holds,
    and the absorbed attention runs over those: its bytes follow the tokens
    chosen, not the tokens cached;
  * **a window** (prefill, a join), in the mask form (``-inf`` off the chosen
    set) as the published inference code runs a prefill: its queries' choice
    block by block (``window_index_scores``, ``topk_mask``: float32 scores
    are [block, keys] a few index heads at a time), kept as ONE int8 mask
    [queries, keys] for all heads, then ``window_attention`` a group of heads
    at a time: the Pallas kernel ops/pallas/masked_prefill.py on the chip,
    ``masked_latent_attention`` as its XLA twin.

Every function enters its own scope (``obs/taxonomy``: nested inside
``mixer``); the chip's numbers are in PERF.md section 6, PRs 43 to 45.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama.paged_cache import gather_latent
from cake_tpu.ops.pallas.index_scores import paged_index_scores
from cake_tpu.ops.pallas.kth_largest import kth_largest_key

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
    scores: jnp.ndarray,
    k: int,
    block_tables: jnp.ndarray | None = None,
    page_size: int | None = None,
    *,
    kernel: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(what the ``k`` largest scores a row carry [b, k'], which of them are
    tokens [b, k']), ``k' = min(k, slots)``; equal scores go to the smaller
    slot, ``-inf`` is never chosen. With ``block_tables`` [b, pages] and
    ``page_size`` a chosen token carries its row of the pool (``pool_rows``'
    value at its slot), without them its slot. A row with fewer live slots
    than ``k`` gets them all and the rest marked not chosen. **The order of
    the ``k'`` entries is no part of the contract**: attention sums over a
    set (they come out by slot, not by score).

    A table no wider than ``k``: every live slot, as it lies. A wider one:
    the k-th largest by 32 counts (``_kth_largest``, the window's search;
    with ``kernel`` one operation, ops/pallas/kth_largest.py: rows of whole
    128s), the tie rule and then the chosen set's ``k`` pool rows
    (``_compact``) from counts a GROUP of slots at a time (the page, where it
    is given and no wider than 256) and the groups' running totals, all of it
    dense products, compares and sums: no sort, gather, scatter or scan of
    the row's width. A stable sort of 21,504 scores a row with the rows
    riding through it took 450 us a layer-step where this takes 95; a gather
    of 2,048 table entries a row after the choice took as long as that sort
    (PERF.md section 6, PRs 45 and 43)."""
    with jax.named_scope(INDEX_SELECT):
        b, slots = scores.shape
        if slots <= k:
            carried = (
                jnp.broadcast_to(jnp.arange(slots, dtype=jnp.int32), scores.shape)
                if block_tables is None else pool_rows(block_tables, page_size)
            )
            return carried, scores > _NEG_INF
        if block_tables is None:
            page_size, starts = slots, jnp.zeros((1, 1), jnp.int32)
        else:
            starts = jnp.maximum(block_tables, 0).astype(jnp.int32) * page_size
        g = next(g for g in range(min(page_size, 256), 0, -1) if page_size % g == 0)
        # [b or 1, groups]: what a group's first slot carries.
        base = (
            starts[:, :, None] + jnp.arange(0, page_size, g, dtype=jnp.int32)
        ).reshape(starts.shape[0], -1)
        kth, room = _kth_largest(scores, k, kernel)
        x = scores.reshape(b, slots // g, g)
        key, kth, room = _sortable(x), kth[:, :, None], room[:, :, None]
        ties = key == kth
        inside, _, before = _group_counts(ties)
        first = inside + before[:, :, None] <= room
        chosen = ((key > kth) | (ties & first)) & (x > _NEG_INF)
        return _compact(chosen, base, k)


def _ones_below(n: int, strictly: bool = False) -> jnp.ndarray:
    """[n, n] bfloat16: 1 where the row's index is below (or at) the column's."""
    i = jnp.arange(n, dtype=jnp.int32)
    return (i[:, None] < i[None, :] if strictly else i[:, None] <= i[None, :]).astype(
        jnp.bfloat16
    )


def _group_counts(m: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Of a bool [b, groups, g], ``g`` no more than 256: (the set bits up to
    and with each slot INSIDE its group [b, groups, g], a group's own count,
    the count of the groups before it [b, groups]), int32. Two products with
    triangles of ones: operands of whole numbers up to 256, exact in
    bfloat16, summed in float32 (a ``cumsum`` over the groups is eight
    operations in the compiled program, and one over the row's width 60 us)."""
    f32 = jnp.float32
    inside = jnp.einsum(
        "bpi,ig->bpg", m.astype(jnp.bfloat16), _ones_below(m.shape[-1]),
        preferred_element_type=f32,
    )
    total = inside[..., -1]
    before = jnp.einsum(
        "bp,pq->bq", total.astype(jnp.bfloat16), _ones_below(m.shape[1], strictly=True),
        preferred_element_type=f32,
    )
    return tuple(a.astype(jnp.int32) for a in (inside, total, before))


def _compact(
    mask: jnp.ndarray,  # [b, groups, g] bool
    base: jnp.ndarray,  # [b or 1, groups] int32: what a group's first slot carries
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(``base`` of its group + its place in the group, of the j-th set bit
    of a row's ``mask`` in slot order, j < k: [b, k] int32; j < the row's
    count: [b, k]). Output slot j lies in the group whose running totals
    straddle it; ONE product of that choice of group, as a one-hot, with the
    groups' counts-under-the-mask brings the group's counts beside j, and j's
    bit is where they equal its rank in the group (a set bit's inclusive
    count is its rank). ``k`` lies on the lanes throughout, and the one-hot
    is made where it is used."""
    inside, total, before = _group_counts(mask)
    after = before + total
    under = jnp.where(mask, inside, 0).astype(jnp.bfloat16)  # <= 256: exact
    j = jnp.arange(k, dtype=jnp.int32)
    holds = (before[:, :, None] <= j) & (j < after[:, :, None])  # [b, groups, k]

    def of_its_group(a):  # [b or 1, groups] -> [b, k]: a's entry at j's group
        return jnp.sum(jnp.where(holds, a[:, :, None], 0), axis=1)

    brought = jnp.einsum(
        "bpg,bpk->bgk", under, holds.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    rank = j + 1 - of_its_group(before)
    lane = jnp.arange(mask.shape[-1], dtype=jnp.int32)
    place = jnp.sum(jnp.where(brought == rank[:, None, :], lane[:, None], 0), axis=1)
    return of_its_group(base) + place, j < after[:, -1:]


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
    """float32 -> uint32 whose unsigned order is the floats' order, the two
    zeros one key (a select: ``x + 0.0`` is folded away by the compiler)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def _kth_largest(
    scores: jnp.ndarray, k: int, kernel: bool = False
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Of rows (the last axis) wider than ``k``: (the k-th largest score as
    ``_sortable``'s key, uint32 [..., 1]; ``k`` less the scores above it,
    int32 [..., 1]: how many of the scores EQUAL to it the ``k`` hold, the
    first by position, the caller's to count). The key is found bit by bit:
    32 counts over the row, no sort; with ``kernel`` (rows [b, slots] of
    whole 128s) as ONE operation, ops/pallas/kth_largest.py. A row with fewer
    than ``k`` above ``-inf`` ends at ``-inf``'s key: the caller takes
    ``-inf`` out."""
    if kernel:
        return kth_largest_key(scores, k=k)
    key = _sortable(scores)

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32)
    )[..., None]
    return kth, k - jnp.sum(key > kth, axis=-1, keepdims=True, dtype=jnp.int32)


def topk_mask(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """Bool mask, ``scores``' shape, of the ``k`` largest a row (last axis)
    that are above ``-inf``; equal scores go to the smaller position. No
    sort (``_kth_largest``, which the decode step's choice shares): a
    window's rows are thousands of queries by thousands of keys."""
    with jax.named_scope(INDEX_SELECT):
        if scores.shape[-1] <= k:
            return scores > _NEG_INF
        key = _sortable(scores)
        kth, room = _kth_largest(scores, k)
        ties = key == kth
        first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
        return ((key > kth) | (ties & first)) & (scores > _NEG_INF)


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
