"""Attention layers of more than one kind over a pool a kind (``model_type:
laguna``; ``config.cache_kind == "kv+kinds"``).

Laguna mixes ``full`` attention layers with ``sliding`` ones (a window of
``config.sliding_window`` keys) that have MORE query heads on the same KV
heads (72 and 48 on 8: groups of 9 and 6), each kind with its own rotary
term, and gates every head's attention output:

    x' = rms(x)
    q = x' Wq [H_kind, d]    k = x' Wk [n_kv, d]    v = x' Wv [n_kv, d]
    q, k = rope_kind(q, k, pos)      (``ops/rope.kind_rope_rows``: YaRN over
                                      half a head for one kind, plain for the other)
    a_h = softmax(q_h k_g(h)^T / sqrt(d) + mask_kind) v_g(h)
    a_h <- act(x' Wg)_h * a_h        (``config.attn_gate``; Wg [hidden, H_kind])
    x <- x + concat_h(a_h) Wo;  x <- x + FF(rms(x))     dense SwiGLU, or routed
                                      experts beside a shared one (``ops/moe.py``)

So the layers cannot share one stacked scan and a ``win_flag`` (a sliding
layer's ``wq``/``wo`` are wider than a full one's): they stack by RUN of one
attention kind and one feed-forward kind (``config.stack_runs``), one
``lax.scan`` a run, as a hybrid's (models/llama/hybrid.py) and a latent
model's (latent.py) do.

**The cache is a pool a kind** (``KindsCache``: a ``PagedKVCache`` over each
kind's own layers) read and written through a block table a kind
(``paged_cache.PagePools``). A full layer's table maps every token of a lane.
A sliding layer's maps only the pages its next query can still see: a write
through an unmapped entry drops (``paged_write_pool``'s contract), the decode
kernel walks a row's pages from the window's start (``paged_decode_attention``
folds the window into its pruning start), and the allocator unmaps what the
shared slot has passed. Two forms of one arithmetic, as latent.py's:

  * a **window of tokens** (an epoch's prefill, a join) attends over its OWN
    keys and values (no prefix cache over these pools, ``capability.py``: the
    window is the row's whole prompt), on the chip through
    ``chunk_prefill_attention`` with the kind's window, and writes them
    through the kind's table: a sliding kind stores the prompt's tail only,
    whatever the prompt's length;
  * a **decode step** writes its token and reads the pool back through
    ``paged_decode_attention``, handed the kind's pool, table and window.

A sparse run's routed experts ride outside the scanned tree with a layer index
and the decode program returns its account of them (``latent.MOE_COUNTS``),
exactly as a latent model's do. A wide window's tail (out-projection and
feed-forward) runs a block of tokens at a time, so that the grouped experts'
rows and their combine stay a block's.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.batch import PAD_SENTINEL
from cake_tpu.models.llama.config import SPARSE, LlamaConfig
from cake_tpu.models.llama.latent import (
    _EXPERT_STACKS, MOE_COUNTS, _add_counts,
)
from cake_tpu.models.llama.paged_cache import (
    PagedKVCache, init_paged_cache, paged_write_pool,
)
from cake_tpu.obs.jitwatch import tracked_jit as _tracked_jit
from cake_tpu.obs.taxonomy import MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops.attention import gqa_attention
from cake_tpu.ops.fuse import FUSED_QKV, resolve_fusion
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention
from cake_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_attention_xla,
)
from cake_tpu.ops.pallas.paged_prefill import paged_kernel_supported
from cake_tpu.ops.pallas.paged_write import compiled_here
from cake_tpu.ops.quant import qmat
from cake_tpu.ops.rope import apply_rope, kind_rope_rows

# Tokens a window's tail (out-projection, feed-forward) takes at once: the
# grouped experts are given ``4 * tokens * top_k * held / ranked`` sorted rows
# and their combine is rows x tokens x hidden (ops/moe._grouped_dispatch), so
# a 12,288-token join at once would hold a 61,440 x 12,288 matrix (1.5 GB)
# and multiply by it eight times; a block of 2,048 holds 42 MB.
_TAIL_TOKENS = 2048
_GATE_ACTS = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


class KindsCache(NamedTuple):
    """The pools, in ``config.attention_kinds``' order; pool k holds the
    layers of kind k alone, [n_layers_of_kind, n_pages_of_kind, n_kv,
    page_size, head_dim]. Carried whole through the layer scans and written
    in place, as ``PagedKVCache`` is."""

    pools: tuple[PagedKVCache, ...]


def init_cache(
    config: LlamaConfig, n_pages: tuple[int, ...], page_size: int, dtype
) -> KindsCache:
    """Zeroed pools, ``n_pages[k]`` pages for kind k."""
    return KindsCache(pools=tuple(
        init_paged_cache(
            len(config.kind_layers(kind)), pages, config.num_key_value_heads,
            page_size, config.head_dim, dtype,
        )
        for kind, pages in zip(config.attention_kinds, n_pages, strict=True)
    ))


def bytes_per_page(config: LlamaConfig, page_size: int, dtype) -> dict[str, int]:
    """What one page of a kind's pool holds over that kind's layers."""
    per_layer = (
        2 * config.num_key_value_heads * config.head_dim * page_size
        * jnp.dtype(dtype).itemsize
    )
    return {
        kind: per_layer * len(config.kind_layers(kind))
        for kind in config.attention_kinds
    }


# ------------------------------------------------------------------ params


def run_shapes(config: LlamaConfig, heads: int, ff_kind: str) -> dict[str, tuple[int, ...]]:
    """Per-layer shapes of one run's tree; matrices are [in, out]."""
    h, hd = config.hidden_size, config.head_dim
    kv = config.num_key_value_heads * hd
    shapes = {
        "wq": (h, heads * hd), "wk": (h, kv), "wv": (h, kv),
        "wo": (heads * hd, h), "ln_attn": (h,), "ln_mlp": (h,),
    }
    if config.attn_gate:
        shapes["wg"] = (h, heads)
    if config.qk_norm:
        shapes.update(q_norm=(hd,), k_norm=(hd,))
    if ff_kind == SPARSE:
        e, inter = config.num_local_experts, config.moe_intermediate_size
        shapes.update({
            "router": (h, config.n_router_experts),
            "w_gate": (e, h, inter), "w_up": (e, h, inter),
            "w_down": (e, inter, h),
        })
        if config.shared_expert_intermediate_size:
            s = config.shared_expert_intermediate_size
            shapes.update(
                {"sh_gate": (h, s), "sh_up": (h, s), "sh_down": (s, h)}
            )
    else:
        inter = config.intermediate_size
        shapes.update(
            {"w_gate": (h, inter), "w_up": (h, inter), "w_down": (inter, h)}
        )
    return shapes


def init_params(
    config: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16, std: float = 0.02
) -> M.Params:
    """Random-init params in the by-run layout (tests and compile checks)."""

    def draw(k, name, shape):
        if name.startswith("ln_") or name.endswith("_norm"):
            return jnp.ones(shape, dtype)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    runs = []
    for r, (_, ff, lo, hi, _) in enumerate(config.stack_runs):
        shapes = run_shapes(config, config.heads_per_layer[lo], ff)
        keys = jax.random.split(jax.random.fold_in(key, r), len(shapes))
        runs.append({
            name: draw(k, name, (hi - lo, *shape))
            for k, (name, shape) in zip(keys, shapes.items())
        })
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, len(runs)))
    v, h = config.vocab_size, config.hidden_size
    return {
        "embed": draw(k_embed, "embed", (v, h)),
        "layers": runs,
        "ln_f": jnp.ones((h,), dtype),
        "lm_head": draw(k_head, "lm_head", (h, v)),
    }


# ----------------------------------------------------------------- forward


def project(lp, x, cos, sin, config: LlamaConfig):
    """A layer's input norm, its projections and its kind's rotary term:
    (normed input, q [b, t, heads, d], k and v [b, t, n_kv, d]). The head
    count is the tree's own (a run's layers share it)."""
    with jax.named_scope(MIXER_IN):
        b, t, _ = x.shape
        hd, kv = config.head_dim, config.num_key_value_heads * config.head_dim
        h = rms_norm(x, lp["ln_attn"], config.rms_norm_eps)
        if FUSED_QKV in lp:
            qkv = qmat(h, lp[FUSED_QKV])
            q, k, v = qkv[..., : -2 * kv], qkv[..., -2 * kv : -kv], qkv[..., -kv:]
        else:
            q, k, v = qmat(h, lp["wq"]), qmat(h, lp["wk"]), qmat(h, lp["wv"])
        q, k = q.reshape(b, t, -1, hd), k.reshape(b, t, -1, hd)
        if "q_norm" in lp:  # a norm a head before the rope (``config.qk_norm``)
            q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
        q, k = apply_rope(q, cos, sin, None), apply_rope(k, cos, sin, None)
        return h, q, k, v.reshape(b, t, -1, hd)


def gate_heads(lp, h, attn, config: LlamaConfig):
    """``attn`` [b, t, heads, d] scaled a head by the gate of the layer's
    normed input ``h``; as it was where the model has no gate."""
    if "wg" not in lp:
        return attn
    with jax.named_scope(MIXER_OUT):
        gate = _GATE_ACTS[config.attn_gate_act](
            qmat(h, lp["wg"]).astype(jnp.float32)
        )
        return (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)


def _tail_block(t: int, rows: int) -> int:
    """Slots of a row one block of a window's tail takes: all of them where
    the window's tokens fit ``_TAIL_TOKENS``, else the largest number of
    whole 128s that divides the width and fits."""
    if rows * t <= _TAIL_TOKENS:
        return t
    for block in range(_TAIL_TOKENS, 127, -128):
        if t % block == 0:
            return block
    return t


def kinds_blocks_forward(
    runs: list,
    x: jnp.ndarray,
    cache: KindsCache,
    positions: jnp.ndarray,  # [b, t] rotary positions (relative to the pad)
    config: LlamaConfig,
    *,
    decode: bool,
    pads: jnp.ndarray,  # [b] first live slot of each row (absolute)
    ends: jnp.ndarray,  # [b] one past the last live slot (decode: slot + 1)
    write_pos: jnp.ndarray,  # the first slot of ``x`` (absolute)
    block_tables: tuple[jnp.ndarray, ...],  # a kind, ``attention_kinds``' order
    live: jnp.ndarray,  # [b, t] positions that are tokens of a row
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, KindsCache, jnp.ndarray]:
    """The model's layers in order, run by run: (x, cache, the account
    ``MOE_COUNTS`` of this pass over its sparse layers)."""
    use_pallas = (
        allow_pallas and M.resolve_attention_impl(config.attention_impl) == "pallas"
    )
    fusion = resolve_fusion(config, allow_pallas)
    kinds = config.attention_kinds
    page_size = cache.pools[0].page_size
    capacity = block_tables[0].shape[1] * page_size
    kernel_ok = use_pallas and paged_kernel_supported(page_size)
    b, t, _ = x.shape
    # Each kind's rotary rows, once and not a layer.
    with jax.named_scope(MIXER_IN):
        ropes = {
            kind: kind_rope_rows(rope, positions)
            for kind, rope in config.kind_ropes
        }
    if decode:
        # A dead lane's row is nobody's: it is given one slot to walk, not
        # the shared slot's worth of pages.
        starts = jnp.where(live[:, 0], pads, ends - 1)
        slots = jnp.arange(capacity, dtype=jnp.int32)[None, :]
        k_grid = jnp.where(
            (slots >= starts[:, None]) & (slots < ends[:, None]),
            slots - pads[:, None], PAD_SENTINEL,
        )
    else:
        idx = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
        k_own = jnp.where(live, idx, PAD_SENTINEL)
        k_starts = jnp.maximum(pads - write_pos, 0)
        lengths = jnp.maximum(ends - write_pos, 1)
    block = _tail_block(t, b)

    def attention(lp, x, k_pool, v_pool, li, *, kind):
        table, window = block_tables[kinds.index(kind)], config.kind_window(kind)
        h, q, k, v = project(lp, x, *ropes[kind], config)
        k_pool, v_pool = paged_write_pool(
            k_pool, v_pool, li, k, v, write_pos, table,
            starts=None if decode else pads,
            kernel=kernel_ok and compiled_here(),
        )
        with jax.named_scope(MIXER):
            if decode and kernel_ok:
                attn = paged_decode_attention(
                    q, k_pool, v_pool, ends, table, starts, layer=li,
                    window=window,
                )
            elif decode:
                attn = paged_decode_attention_xla(
                    q, k_pool, v_pool, positions, k_grid, table, layer=li,
                    window=window,
                )
            elif use_pallas:
                attn = chunk_prefill_attention(
                    q, jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
                    jnp.zeros((b,), jnp.int32), lengths, None, k_starts,
                    window=window,
                )
            else:
                attn = gqa_attention(q, k, v, idx, k_own, window=window)
        return gate_heads(lp, h, attn, config).astype(x.dtype), k_pool, v_pool

    def finish(lp, x, attn, live, k, *, sparse):
        if sparse:
            return M.block_finish(
                lp, x, attn, config, moe_valid=live, fusion=fusion,
                moe_counts=True, moe_layer=k,
            )
        return M.block_finish(lp, x, attn, config, fusion=fusion), jnp.zeros(
            (len(MOE_COUNTS) - 1,), jnp.int32
        )

    def tail(lp, x, attn, k, *, sparse):
        """``block_finish`` over the window, a block of tokens at a time."""
        if block == t:
            return finish(lp, x, attn, live, k, sparse=sparse)
        n = b * t // block

        def one(_, args):
            return None, finish(lp, *args, k, sparse=sparse)

        _, (out, counts) = jax.lax.scan(one, None, (
            x.reshape(n, 1, block, -1), attn.reshape(n, 1, block, *attn.shape[2:]),
            live.reshape(n, 1, block),
        ))
        counts = jnp.concatenate(
            [jnp.sum(counts[:, :-1], axis=0), jnp.max(counts[:, -1:], axis=0)]
        )
        return out.reshape(x.shape), counts

    def layer(carry, per_layer, *, kind, experts):
        x, k_pool, v_pool, counts = carry
        lp, li, k = per_layer
        attn, k_pool, v_pool = attention(lp, x, k_pool, v_pool, li, kind=kind)
        x, c = tail({**lp, **(experts or {})}, x, attn, k, sparse=experts is not None)
        if experts is not None:
            counts = _add_counts(
                counts, jnp.concatenate([jnp.ones((1,), jnp.int32), c])
            )
        return (x, k_pool, v_pool, counts), None

    pools = list(cache.pools)
    counts = jnp.zeros((len(MOE_COUNTS),), jnp.int32)
    for lp, (kind, ff, lo, hi, first) in zip(runs, config.stack_runs, strict=True):
        experts = None
        if ff == SPARSE:
            # The run's routed experts ride outside the scanned tree, whole,
            # with the layer's index (latent.py says why).
            experts = {k: lp[k] for k in _EXPERT_STACKS}
            lp = {k: v for k, v in lp.items() if k not in _EXPERT_STACKS}
        ki = kinds.index(kind)
        n = hi - lo
        (x, k_pool, v_pool, counts), _ = jax.lax.scan(
            functools.partial(layer, kind=kind, experts=experts),
            (x, pools[ki].k, pools[ki].v, counts),
            (lp, first + jnp.arange(n, dtype=jnp.int32), jnp.arange(n, dtype=jnp.int32)),
        )
        pools[ki] = PagedKVCache(k=k_pool, v=v_pool)
    return x, KindsCache(pools=tuple(pools)), counts


def kinds_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [b, W]: absolute slots [start, start + W)
    cache: KindsCache,
    pads: jnp.ndarray,  # [b] each row's first slot (absolute)
    ends: jnp.ndarray,  # [b] one past each row's last slot (absolute)
    block_tables: tuple[jnp.ndarray, ...],
    config: LlamaConfig,
    start: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, KindsCache, jnp.ndarray]:
    """Every prefill, an epoch's and a joiner's, in the closed shapes' layout
    (``latent.latent_prefill`` says it: each row's tokens at slots [pads,
    ends) of a window that starts at ``start``). The window holds the row's
    whole prompt, so each kind attends over the window's own keys and values
    under its own mask and writes them through its own table. Logits are the
    first row's last slot's; the third value is ``MOE_COUNTS``."""
    start = jnp.asarray(start, jnp.int32)
    x = M.embed_tokens(params, tokens, config)
    grid = start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    live = (grid >= pads[:, None]) & (grid < ends[:, None])
    x, cache, counts = kinds_blocks_forward(
        params["layers"], x, cache, jnp.maximum(grid - pads[:, None], 0), config,
        decode=False, pads=pads, ends=ends, write_pos=start,
        block_tables=block_tables, live=live, allow_pallas=allow_pallas,
    )
    return M.head_forward(params, x, ends[0] - start, config), cache, counts


def kinds_decode_step(
    params: M.Params,
    tok: jnp.ndarray,  # [b, 1] every lane's token at the shared slot
    cache: KindsCache,
    slot: jnp.ndarray,
    pads: jnp.ndarray,
    block_tables: tuple[jnp.ndarray, ...],
    live: jnp.ndarray,  # [b, 1] lanes that hold a request
    config: LlamaConfig,
    fusion: tuple | None = None,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, KindsCache, jnp.ndarray]:
    """One decode step of every lane: the token's K and V through each
    kind's table, attention over each kind's pool, (logits [b, vocab],
    cache, ``MOE_COUNTS``)."""
    x = M.embed_tokens(params, tok, config)
    ends = jnp.broadcast_to(slot + 1, pads.shape).astype(jnp.int32)
    x, cache, counts = kinds_blocks_forward(
        params["layers"], x, cache, jnp.maximum(slot - pads, 0)[:, None], config,
        decode=True, pads=pads, ends=ends, write_pos=slot,
        block_tables=block_tables, live=live, allow_pallas=allow_pallas,
    )
    return M.head_forward(params, x, jnp.int32(1), config, fusion=fusion), cache, counts


_kinds_prefill_jit = _tracked_jit(
    kinds_prefill,
    name="batch.kinds_prefill",
    module="prefill_paged_kinds",
    static_argnames=("config", "allow_pallas"),
    donate_argnames=("cache",),
)


@functools.lru_cache(maxsize=32)
def _kinds_join_fn(config: LlamaConfig, width: int, allow_pallas: bool = True):
    """One joining (or restored) row's prefill: its own jit so that a join is
    a program of its own name. One compile per window width."""

    def run(params, cache, tokens, pads1, ends1, lane_tables, start):
        return kinds_prefill(
            params, tokens, cache, pads1, ends1, lane_tables, config,
            start=start, allow_pallas=allow_pallas,
        )

    return _tracked_jit(
        run, name=f"batch.kinds_join[w={width}]",
        module="prefill_join_paged_kinds", donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=16)
def _kinds_decode_fn(
    config: LlamaConfig,
    n_steps: int,
    temperature: float,
    top_k,
    top_p,
    repeat_penalty: float,
    allow_pallas: bool = True,
):
    """``latent._latent_decode_fn`` over the pools a kind: the fused sampled
    decode scan with the ``KindsCache`` as its carried, donated cache, the
    tables a kind as one operand; returns the scan's five values and the
    chunk's ``MOE_COUNTS``."""
    from cake_tpu.models.llama.fused import sampled_decode_scan

    fusions, fimpl = resolve_fusion(config, allow_pallas)
    tail_impl = fimpl if "tail" in fusions else None

    def run(params, cache, tok, slot, pads, block_tables, valid, key, ring, ring_idx):
        live = valid[:, None]

        def forward_one(tok, carry, slot):
            cache, counts = carry
            logits, cache, c = kinds_decode_step(
                params, tok, cache, slot, pads, block_tables, live, config,
                fusion=(fusions, fimpl), allow_pallas=allow_pallas,
            )
            return logits, (cache, _add_counts(counts, c))

        toks, (cache, counts), key, ring, ring_idx = sampled_decode_scan(
            forward_one, (cache, jnp.zeros((len(MOE_COUNTS),), jnp.int32)),
            tok, slot, key, ring, ring_idx,
            n_steps=n_steps, temperature=temperature, top_k=top_k,
            top_p=top_p, repeat_penalty=repeat_penalty, tail_impl=tail_impl,
        )
        return toks, cache, key, ring, ring_idx, counts

    return _tracked_jit(
        run,
        name=(
            f"batch.kinds_decode[n={n_steps},t={temperature},k={top_k},"
            f"p={top_p},rp={repeat_penalty}]"
        ),
        module="decode_chunk_paged_kinds",
        donate_argnums=(1,),
    )
