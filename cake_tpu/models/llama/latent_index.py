"""Latent attention over the tokens a learned index chooses (``model_type:
deepseek_v32``; ``config.cache_kind == "latent+index"``).

The layer is latent.py's (MLA through two low-rank latents, a run of layers a
``lax.scan``, the routed experts told which are held) with three differences
that are the model's, all of them data in the config:

  * **the index** (ops/sparse_index.py): beside its latent a token keeps ONE
    index key a layer, ``k_I = RoPE(LN(h W_Ik))`` [index_head_dim], in a pool
    of its own behind the SAME block table (``paged_cache.
    LatentIndexPagedCache``). A query scores every cached token, ``I[t, s] =
    sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` with ``q_I = RoPE(cq W_Iq)`` from
    the SAME normed query latent and ``w = h W_Iw / sqrt(heads x dim)``, and
    attention's softmax runs over the ``index_topk`` best and no other;
  * YaRN over the 64 rotary numbers (``config.latent_rope``; the rows are
    computed from the positions, not gathered from a table over a
    21,504-slot lane) and its ``m`` squared into the score scale
    (``config.mla_scale``);
  * one norm on each branch's input and none on its output, and a router that
    chooses inside the best groups with a correction bias (ops/moe.py).

Two forms, as latent.py's:

  * **decode**, absorbed: the latent and the index key are written, the
    row's index keys scored where they lie in the pool (a Pallas kernel over
    the row's live pages on the chip), ``index_topk`` slots chosen, and their
    rows of the latent pool gathered and attended: what the attention reads
    follows the tokens chosen, not the tokens cached;
  * **a window** (an epoch's prefill, a join), four passes a layer: what the
    layer keeps of every token (latents and index keys, written through the
    table); each block's queries' index scores against the window's keys
    under the causal mask and their choice, kept as ONE int8 mask [queries,
    keys] for all heads; attention in the expanded form under that mask, a
    group of heads at a time (ops/pallas/masked_prefill.py on the chip); the
    tail (``model.block_finish``: the grouped experts' combine is rows x
    tokens). Passes one, two and four go a block of at most
    ``sparse_index.WINDOW_BLOCK`` tokens at a time as ``lax.map``s, so a
    window's program holds one block's code whatever its width, and a block
    without a token is passed through. The window holds the row's whole
    prompt (no prefix cache over these pools: ``capability.py``), so nothing
    is read back from them.

The decode program returns latent.py's account of the expert layer and, behind
it, the index's (``SPARSE_COUNTS``): what was scanned and what was chosen.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import SPARSE, LlamaConfig
from cake_tpu.models.llama.latent import (
    _EXPERT_STACKS, MOE_COUNTS, _add_counts, into_heads, token_latent,
)
from cake_tpu.models.llama.paged_cache import (
    LatentIndexPagedCache, init_latent_index_cache, latent_write_pool,
)
from cake_tpu.obs.taxonomy import MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops import sparse_index as SI
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas.index_scores import paged_index_scores_supported
from cake_tpu.ops.pallas.kth_largest import kth_largest_supported
from cake_tpu.ops.quant import qmat
from cake_tpu.ops.rope import apply_rope, kind_rope_rows

# What a program returns behind ``MOE_COUNTS``, int32 [4]: index dispatches
# (steps x layers), live rows in them, cached tokens their queries scored,
# tokens attention then read. A window's counts are of its live queries.
SPARSE_COUNTS = ("dispatches", "rows", "scanned", "chosen")
_INDEX_LN_EPS = 1e-6
# Heads a step of a window's attention: a step expands its own heads' keys
# and values from the window's latents ([keys, heads, 256 + 128] in bf16:
# 0.26 GB at 16 heads and 21,504 keys, beside 12 GB of weights and pools).
_HEAD_GROUP = 16


def init_cache(
    config: LlamaConfig, n_pages: int, page_size: int, dtype
) -> LatentIndexPagedCache:
    return init_latent_index_cache(
        config.num_hidden_layers, n_pages, page_size, config.latent_width,
        config.index_head_dim, dtype,
    )


def cache_bytes_per_token(config: LlamaConfig, dtype) -> dict[str, int]:
    """What a cached token takes over all layers, by what it is: the latent
    as the pool stores it (whole lane tiles), the numbers of it the
    arithmetic needs, and the index key."""
    size, n = jnp.dtype(dtype).itemsize, config.num_hidden_layers
    return {
        "latent": n * size * config.latent_width,
        "latent_needed": n * size * (config.kv_lora_rank + config.qk_rope_head_dim),
        "index": n * size * config.index_head_dim,
    }


# ----------------------------------------------------------------- forward


def project_keys(lp, x, cos, sin, config: LlamaConfig):
    """What a layer keeps of a token and what its queries are made from:
    (latent [b, t, latent_width]: ``[rms(ckv) | RoPE(k_rope) | 0]``, the index
    key k_I [b, t, dim] after its LayerNorm and RoPE on its first rotary
    numbers, the normed query latent cq [b, t, q_lora_rank], the index's head
    weights w [b, t, heads] float32 with both scales in them)."""
    with jax.named_scope(MIXER_IN):
        eps = config.rms_norm_eps
        heads, dim = config.index_n_heads, config.index_head_dim
        h = rms_norm(x, lp["ln_attn"], eps)
        cq = rms_norm(qmat(h, lp["wq_a"]), lp["q_a_ln"], eps)
        latent = token_latent(lp, h, cos, sin, None, config)
        k = qmat(h, lp["wi_k"]).astype(jnp.float32)
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        k = (k - mean) * jax.lax.rsqrt(var + _INDEX_LN_EPS)
        k = k * lp["i_k_ln"].astype(jnp.float32) + lp["i_k_ln_b"].astype(jnp.float32)
        k_i = apply_rope(k.astype(x.dtype)[:, :, None, :], cos, sin, None)[:, :, 0]
        w = qmat(h, lp["wi_w"]).astype(jnp.float32) * (heads * dim) ** -0.5
        return latent, k_i, cq, w


def attention_queries(wq_b, cq, cos, sin, config: LlamaConfig):
    """Queries of the heads ``wq_b`` [q_lora_rank, heads * (nope + rope)]
    holds, from the normed query latent: (q_nope [b, t, heads, nope], q_rope
    [b, t, heads, rope] after RoPE)."""
    with jax.named_scope(MIXER_IN):
        nope = config.qk_nope_head_dim
        q = into_heads(qmat(cq, wq_b), nope + config.qk_rope_head_dim)
        return q[..., :nope], apply_rope(q[..., nope:], cos, sin, None)


def index_queries(lp, cq, cos, sin, config: LlamaConfig):
    """The index's q_I [b, t, index heads, dim] from the SAME normed query
    latent, after RoPE on its first rotary numbers."""
    with jax.named_scope(MIXER_IN):
        q_i = into_heads(qmat(cq, lp["wi_q"]), config.index_head_dim)
        return apply_rope(q_i, cos, sin, None)


def _kernel_switch(config: LlamaConfig, allow_pallas: bool) -> bool:
    """This layer kind's kernels follow the attention kernels' switch."""
    return allow_pallas and M.resolve_attention_impl(config.attention_impl) == "pallas"


def scores_form(config: LlamaConfig, page_size: int, allow_pallas: bool) -> str:
    """Which form a decode step's index scores take, by the predicate the
    program itself follows: ``"pallas"`` (ops/pallas/index_scores.py: the
    pool of index keys read in place, a row's live pages only) or ``"xla"``
    (the twin: the row's whole table gathered). ``GET /stats``
    engine.sparse.scores_form."""
    kernel = _kernel_switch(config, allow_pallas) and paged_index_scores_supported(
        page_size, config.index_head_dim, config.index_n_heads
    )
    return "pallas" if kernel else "xla"


def select_form(config: LlamaConfig, page_size: int, allow_pallas: bool) -> str:
    """Which form the search of a decode step's choice takes (the k-th
    largest score a row; the rest of the choice is one form everywhere):
    ``"pallas"`` (ops/pallas/kth_largest.py: one operation) or ``"xla"`` (the
    twin: a loop of 32 counts). ``GET /stats`` engine.sparse.select_form."""
    kernel = _kernel_switch(config, allow_pallas) and kth_largest_supported(page_size)
    return "pallas" if kernel else "xla"


def window_block(rows: int, width: int) -> int:
    """Slots of a row one block of a window takes: the largest number of
    whole 16s that divides the width and keeps the rows' block together
    within ``WINDOW_BLOCK`` tokens; all of the width where that fits."""
    if rows * width <= SI.WINDOW_BLOCK:
        return width
    for block in range(SI.WINDOW_BLOCK // rows // 16 * 16, 15, -16):
        if width % block == 0:
            return block
    return width


def latent_index_blocks_forward(
    runs: list,
    x: jnp.ndarray,
    cache: LatentIndexPagedCache,
    positions: jnp.ndarray,  # [b, t] RoPE positions (relative to the pad)
    config: LlamaConfig,
    *,
    decode: bool,
    pads: jnp.ndarray,  # [b] first live slot of each row (absolute)
    ends: jnp.ndarray,  # [b] one past the last live slot (decode: slot + 1)
    write_pos: jnp.ndarray,  # the first slot of ``x`` (absolute)
    block_tables: jnp.ndarray,
    live: jnp.ndarray,  # [b, t] positions that are tokens of a row
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, LatentIndexPagedCache, jnp.ndarray, jnp.ndarray]:
    """The model's layers in order, run by run: (x, cache, ``MOE_COUNTS`` of
    this pass over its sparse layers, ``SPARSE_COUNTS`` over all layers)."""
    fusion = resolve_fusion(config, allow_pallas)
    use_kernel = _kernel_switch(config, allow_pallas)
    scores_kernel = scores_form(config, cache.page_size, allow_pallas) == "pallas"
    select_kernel = select_form(config, cache.page_size, allow_pallas) == "pallas"
    rank, n, rope = config.kv_lora_rank, config.num_attention_heads, config.qk_rope_head_dim
    scale, topk = config.mla_scale, config.index_topk
    b, t, _ = x.shape
    with jax.named_scope(MIXER_IN):
        cos, sin = kind_rope_rows(config.latent_rope, positions)  # once, not a layer
    # Absolute slots of the window's positions.
    grid = write_pos + jnp.arange(t, dtype=jnp.int32)
    write = dict(
        pos=write_pos, block_tables=block_tables, starts=pads,
        ends=None if decode else ends,
    )
    no_counts = jnp.zeros((len(MOE_COUNTS),), jnp.int32)

    def finish(lp, experts, k, x, attn, valid):
        """A block's tail; (x, MOE_COUNTS of it: zeros of a dense layer)."""
        if experts is None:
            return M.block_finish(
                lp, x, attn, config, moe_valid=valid, fusion=fusion
            ), no_counts
        x, c = M.block_finish(
            {**lp, **experts}, x, attn, config, moe_valid=valid, fusion=fusion,
            moe_counts=True, moe_layer=k,
        )
        return x, jnp.concatenate([jnp.ones((1,), jnp.int32), c])

    def decode_layer(lp, experts, x, pool, ipool, li, k):
        latent, k_i, cq, w = project_keys(lp, x, cos, sin, config)
        q_nope, q_rope = attention_queries(lp["wq_b"], cq, cos, sin, config)
        q_i = index_queries(lp, cq, cos, sin, config)
        pool = latent_write_pool(pool, li, latent, **write)
        ipool = latent_write_pool(ipool, li, k_i, **write)
        with jax.named_scope(MIXER_IN):
            q_abs = jnp.einsum("bhd,hcd->bhc", q_nope[:, 0], lp["w_uk"])
            q_full = jnp.concatenate([
                q_abs, q_rope[:, 0],
                jnp.zeros((b, n, config.latent_width - rank - rope), q_abs.dtype),
            ], axis=-1).astype(x.dtype)
        with jax.named_scope(MIXER):
            # A dead lane's row is nobody's: it is given one slot to score.
            starts = jnp.where(live[:, 0], pads, ends - 1)
            scores = SI.index_scores(
                q_i[:, 0], w[:, 0], ipool, block_tables, starts, ends, layer=li,
                kernel=scores_kernel,
            )
            rows_of, chosen = SI.select_topk(
                scores, topk, block_tables, cache.page_size, kernel=select_kernel
            )
            c = SI.sparse_latent_attention(
                q_full, pool, rows_of, chosen, layer=li, rank=rank, scale=scale,
            )
            rows = live[:, 0]
            sparse = jnp.stack([
                jnp.int32(1), jnp.sum(rows),
                jnp.sum(jnp.where(rows, ends - pads, 0)),
                jnp.sum(chosen & rows[:, None]),
            ]).astype(jnp.int32)
        with jax.named_scope(MIXER_OUT):
            attn = jnp.einsum("bhc,hcd->bhd", c, lp["w_uv"])[:, None]
        x, counts = finish(lp, experts, k, x, attn.astype(x.dtype), live)
        return x, pool, ipool, counts, sparse

    def window_layer(lp, experts, x, pool, ipool, li, k):
        """Four passes (module docstring): what the layer keeps of every
        token, the queries' choice as a mask, attention a group of heads at a
        time, the tail. A block that holds no token of any row (a join's
        window is as wide as the next width up, its tokens at its end) is
        passed through."""
        block = window_block(b, t)
        n_blocks = t // block
        no_sparse = jnp.zeros((len(SPARSE_COUNTS),), jnp.int32)

        def blocks(a):  # [b, t, ...] -> [n_blocks, b, block, ...]
            return jnp.moveaxis(a.reshape(b, n_blocks, block, *a.shape[2:]), 1, 0)

        def whole(a):  # and back
            return jnp.moveaxis(a, 0, 1).reshape(b, t, *a.shape[3:])

        def keys_of(xs):
            xb, cos_b, sin_b, live_b = xs
            return jax.lax.cond(
                jnp.any(live_b), lambda: project_keys(lp, xb, cos_b, sin_b, config),
                lambda: (
                    jnp.zeros((b, block, config.latent_width), x.dtype),
                    jnp.zeros((b, block, config.index_head_dim), x.dtype),
                    jnp.zeros((b, block, config.q_lora_rank), x.dtype),
                    jnp.zeros((b, block, config.index_n_heads), jnp.float32),
                ),
            )

        latent, k_i, cq, w = (whole(a) for a in jax.lax.map(
            keys_of, (blocks(x), blocks(cos), blocks(sin), blocks(live))
        ))
        pool = latent_write_pool(pool, li, latent, **write)
        ipool = latent_write_pool(ipool, li, k_i, **write)

        def choose(xs):
            cq_b, w_b, cos_b, sin_b, grid_b, live_b = xs
            q_i = index_queries(lp, cq_b, cos_b, sin_b, config)
            with jax.named_scope(MIXER):
                admitted = live[:, None, :] & (grid[None, None, :] <= grid_b[None, :, None])
                mask = SI.topk_mask(
                    SI.window_index_scores(q_i, w_b, k_i, admitted), topk
                )
                sparse = jnp.stack([
                    jnp.int32(1), jnp.sum(live_b),
                    jnp.sum(admitted & live_b[..., None]),
                    jnp.sum(mask & live_b[..., None]),
                ]).astype(jnp.int32)
                return mask.astype(jnp.int8), sparse

        mask, sparse = jax.lax.map(
            lambda xs: jax.lax.cond(
                jnp.any(xs[-1]), choose,
                lambda xs: (jnp.zeros((b, block, t), jnp.int8), no_sparse), xs,
            ),
            (blocks(cq), blocks(w), blocks(cos), blocks(sin),
             grid.reshape(n_blocks, block), blocks(live)),
        )
        mask = whole(mask)  # [b, t, t]: one for all heads

        step = _HEAD_GROUP if n % _HEAD_GROUP == 0 else n
        kernel = use_kernel and SI.window_kernel_supported(
            t, config.qk_nope_head_dim, rope, config.v_head_dim
        )

        def heads(ws):
            wq_b, w_uk, w_uv = ws
            q_nope, q_rope = attention_queries(wq_b, cq, cos, sin, config)
            with jax.named_scope(MIXER):
                return SI.window_attention(
                    q_nope, q_rope, latent[..., :rank],
                    latent[..., rank : rank + rope], w_uk, w_uv, mask,
                    scale=scale, starts=pads - write_pos, lengths=ends - write_pos,
                    kernel=kernel,
                )

        wq_b = lp["wq_b"].reshape(lp["wq_b"].shape[0], n // step, -1)
        attn = jax.lax.map(heads, (
            jnp.moveaxis(wq_b, 1, 0),
            lp["w_uk"].reshape(n // step, step, *lp["w_uk"].shape[1:]),
            lp["w_uv"].reshape(n // step, step, *lp["w_uv"].shape[1:]),
        ))  # [groups, b, t, step, v]
        attn = jnp.moveaxis(attn, 0, 2).reshape(b, t, -1).astype(x.dtype)

        def tail(xs):
            xb, attn_b, live_b = xs
            return finish(lp, experts, k, xb, attn_b, live_b)

        out, counts = jax.lax.map(
            lambda xs: jax.lax.cond(
                jnp.any(xs[-1]), tail, lambda xs: (xs[0], no_counts), xs
            ),
            (blocks(x), blocks(attn), blocks(live)),
        )
        counts = jnp.concatenate(
            [jnp.sum(counts[:, :4], axis=0), jnp.max(counts[:, 4:], axis=0)]
        )
        return whole(out), pool, ipool, counts, jnp.sum(sparse, axis=0)

    one_layer = decode_layer if decode else window_layer

    def layer(carry, per_layer, *, experts):
        x, pool, ipool, counts, sparse = carry
        lp, li, k = per_layer
        x, pool, ipool, c, s = one_layer(lp, experts, x, pool, ipool, li, k)
        return (x, pool, ipool, _add_counts(counts, c), sparse + s), None

    carry = (
        x, cache.latent, cache.index, no_counts,
        jnp.zeros((len(SPARSE_COUNTS),), jnp.int32),
    )
    for lp, (kind, lo, hi) in zip(runs, config.ff_runs, strict=True):
        experts = None
        if kind == SPARSE:
            # The run's routed experts ride outside the scanned tree, whole,
            # with the layer's index (latent.py says why).
            experts = {k: lp[k] for k in _EXPERT_STACKS}
            lp = {k: v for k, v in lp.items() if k not in _EXPERT_STACKS}
        carry, _ = jax.lax.scan(
            functools.partial(layer, experts=experts), carry,
            (lp, jnp.arange(lo, hi, dtype=jnp.int32),
             jnp.arange(hi - lo, dtype=jnp.int32)),
        )
    x, pool, ipool, counts, sparse = carry
    return x, LatentIndexPagedCache(latent=pool, index=ipool), counts, sparse


def latent_index_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [b, W]: absolute slots [start, start + W)
    cache: LatentIndexPagedCache,
    pads: jnp.ndarray,
    ends: jnp.ndarray,
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    start: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, LatentIndexPagedCache, jnp.ndarray]:
    """``latent.latent_prefill`` for this cache: every prefill, an epoch's
    and a joiner's. The third value is the window's ``MOE_COUNTS`` and
    ``SPARSE_COUNTS``, one vector."""
    start = jnp.asarray(start, jnp.int32)
    x = M.embed_tokens(params, tokens, config)
    grid = start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    live = (grid >= pads[:, None]) & (grid < ends[:, None])
    x, cache, counts, sparse = latent_index_blocks_forward(
        params["layers"], x, cache, jnp.maximum(grid - pads[:, None], 0), config,
        decode=False, pads=pads, ends=ends, write_pos=start,
        block_tables=block_tables, live=live, allow_pallas=allow_pallas,
    )
    logits = M.head_forward(params, x, ends[0] - start, config)
    return logits, cache, jnp.concatenate([counts, sparse])


def latent_index_forward_one(
    params: M.Params,
    pads: jnp.ndarray,
    block_tables: jnp.ndarray,
    live: jnp.ndarray,
    config: LlamaConfig,
    padded_seq: int | None = None,
    allow_pallas: bool = True,
):
    """``latent.latent_forward_one`` over both pools: the step's
    ``MOE_COUNTS`` and ``SPARSE_COUNTS`` ride back beside the cache."""
    fusion = resolve_fusion(config, allow_pallas)

    def forward_one(tok, cache, slot):
        x = M.embed_tokens(params, tok, config)
        ends = jnp.broadcast_to(slot + 1, pads.shape).astype(jnp.int32)
        x, cache, counts, sparse = latent_index_blocks_forward(
            params["layers"], x, cache, (slot - pads)[:, None], config,
            decode=True, pads=pads, ends=ends, write_pos=slot,
            block_tables=block_tables, live=live,
            allow_pallas=allow_pallas,
        )
        logits = M.head_forward(params, x, jnp.int32(1), config, fusion=fusion)
        return logits, cache, counts, sparse

    return forward_one


class IndexAccount:
    """The cumulative account of the learned index of a kind whose programs
    return ``SPARSE_COUNTS`` behind ``MOE_COUNTS``: ``GET /stats``
    engine.sparse."""

    section, names, add = "sparse", SPARSE_COUNTS, staticmethod(jnp.add)
    keeps_traced = True  # ``traced``: the backend asks ``timeline.recording()``

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.counts = dict.fromkeys(SPARSE_COUNTS, 0)
        self.traced = dict.fromkeys(SPARSE_COUNTS, 0)
        self.join_counts = dict.fromkeys(SPARSE_COUNTS, 0)

    def facts(self) -> dict:
        """Cumulative over decode chunks READ: ``dispatches`` decode steps x
        layers, ``rows`` live rows in them, ``scanned`` cached tokens their
        queries scored, ``chosen`` tokens attention then read; ``traced``:
        the same of the chunks DISPATCHED while ``timeline.recording()``
        (until a profiler's stop is CALLED, not through its closing: what a
        device trace's times are of); ``join``: of the joins' windows."""
        topk = self.config.index_topk
        return {
            "index_topk": topk, **self.counts,
            "traced": {"index_topk": topk, **self.traced},
            "join": dict(self.join_counts),
        }

    def absorb(self, got: dict[str, int], decode: bool, traced: bool, rows: int) -> dict:
        del rows  # the index scores the tokens its rows hold, whatever their number
        totals = [self.counts if decode else self.join_counts]
        if decode and traced:
            totals.append(self.traced)
        for total in totals:
            for key, v in got.items():
                total[key] += v
        return {f"index_{k}": v for k, v in got.items()}
