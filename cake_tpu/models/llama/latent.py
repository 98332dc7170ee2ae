"""Latent attention over a latent paged cache (``model_type:
pangu_ultra_moe``; ``config.cache_kind == "latent"``).

Multi-head latent attention (MLA, the DeepSeek-V2/V3 family's) projects a
token through two low-rank latents:

    cq  = rms(u Wqa)                       [q_lora_rank]
    q_h = cq Wqb  ->  [q_nope_h | q_rope_h]                 a head
    [ckv | k_rope] = u Wkva;  ckv = rms(ckv);  k_rope = RoPE(k_rope)
    [k_nope_h | v_h] = ckv Wkvb_h           (Wuk_h | Wuv_h: its two halves)
    s_h = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope) * scale

and a token's cache in a layer is ``[ckv | k_rope]`` alone (512 + 64 numbers
whatever the number of heads: ``paged_cache.LatentPagedCache``). Two forms of
one arithmetic, as the family's serving systems run it:

  * **expanded**, for an epoch's prefill and for joins: the window's own K
    and V are computed from its latents (``k_h = [ckv Wuk_h | k_rope]``,
    ``v_h = ckv Wuv_h``) and attended as ordinary multi-head attention of
    128 + 64 query/key dims and 128 value dims (``ops/attention.
    mla_prefill_attention``; the Pallas chunk kernel on the chip). The
    window is the row's whole prompt (no prefix cache over this pool:
    ``capability.py``), so nothing is read back from the pool;
  * **absorbed**, for decode: ``q~_h = q_nope_h Wuk_h^T`` [kv_lora_rank], the
    scores are dot products with the cached latents themselves, the weighted
    sum is of latents, and ``o_h = (sum p ckv) Wuv_h`` afterwards: the pool
    is read once, by ``ops/pallas/latent_attention.py``.

Layers stack by RUN of one feed-forward kind (``config.ff_runs``: the leading
dense layers, then the sparse ones), one ``lax.scan`` a run, the pool in the
carry and written in place (PR 26's rule). A layer's tail is
``model.block_finish`` with its post-branch norms (``sandwich_norm``) and,
in a sparse run, ``ops/moe.py``'s routed experts beside the shared one, told
which experts are held (``config.expert_offset``). The decode program also
returns its expert-load account (``moe.held_counts`` summed over steps and
sparse layers), which the engine reads with the chunk's tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import SPARSE, LlamaConfig
from cake_tpu.models.llama.paged_cache import (
    LatentPagedCache, init_latent_cache, latent_write_pool,
)
from cake_tpu.obs.taxonomy import MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops.attention import mla_prefill_attention
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas.latent_attention import (
    latent_decode_attention, latent_decode_attention_xla,
    latent_kernel_supported,
)
from cake_tpu.ops.quant import qmat
from cake_tpu.ops.rope import apply_rope, rope_table

# What the decode program returns beside its tokens, int32 [5]: sparse-layer
# dispatches (decode steps x sparse layers), assignments routed, assignments
# to held experts, held experts touched (summed: what the steps had to
# read), the largest load of one held expert in one dispatch.
MOE_COUNTS = ("dispatches", "routed", "held", "touched", "max_load")
_EXPERT_STACKS = ("w_gate", "w_up", "w_down")  # a sparse run's [n, e, ...]


def _add_counts(total: jnp.ndarray, more: jnp.ndarray) -> jnp.ndarray:
    """Two ``MOE_COUNTS``: sums, and the larger ``max_load``."""
    return jnp.concatenate(
        [total[:4] + more[:4], jnp.maximum(total[4:], more[4:])]
    )


def init_cache(
    config: LlamaConfig, n_pages: int, page_size: int, dtype
) -> LatentPagedCache:
    return init_latent_cache(
        config.num_hidden_layers, n_pages, page_size, config.latent_width, dtype
    )


def cache_bytes_per_token(config: LlamaConfig, dtype) -> dict[str, int]:
    """What a cached token takes over all layers: the numbers the arithmetic
    needs (``kv_lora_rank + qk_rope_head_dim`` a layer) and what the pool
    stores (``latent_width``: whole lane tiles)."""
    size, n = jnp.dtype(dtype).itemsize, config.num_hidden_layers
    return {
        "needed": n * size * (config.kv_lora_rank + config.qk_rope_head_dim),
        "stored": n * size * config.latent_width,
    }


# ------------------------------------------------------------------ params


def run_shapes(config: LlamaConfig, ff_kind: str) -> dict[str, tuple[int, ...]]:
    """Per-layer shapes of one run's tree. Matrices are [in, out]; the
    up-projection of the compressed K/V is held as its two per-head halves,
    ``w_uk`` and ``w_uv`` [heads, kv_lora_rank, dims] (io/safetensors_io.py
    splits ``kv_b_proj``), which both forms of the attention read."""
    h, n = config.hidden_size, config.num_attention_heads
    nope, rope, vd = (
        config.qk_nope_head_dim, config.qk_rope_head_dim, config.v_head_dim
    )
    shapes = {
        "wq_a": (h, config.q_lora_rank), "q_a_ln": (config.q_lora_rank,),
        "wq_b": (config.q_lora_rank, n * (nope + rope)),
        "wkv_a": (h, config.kv_lora_rank + rope),
        "kv_a_ln": (config.kv_lora_rank,),
        "w_uk": (n, config.kv_lora_rank, nope),
        "w_uv": (n, config.kv_lora_rank, vd),
        "wo": (n * vd, h),
        "ln_attn": (h,), "ln_mlp": (h,),
    }
    if config.post_block_norms:
        shapes.update({"ln_post_attn": (h,), "ln_post_mlp": (h,)})
    if config.index_topk:
        # The learned index (ops/sparse_index.py): its queries from the
        # query latent, ONE key a token behind a LayerNorm, a weight a head.
        ih, idim = config.index_n_heads, config.index_head_dim
        shapes.update({
            "wi_q": (config.q_lora_rank, ih * idim), "wi_k": (h, idim),
            "wi_w": (h, ih), "i_k_ln": (idim,), "i_k_ln_b": (idim,),
        })
    if ff_kind == SPARSE:
        e, inter = config.num_local_experts, config.moe_intermediate_size
        shapes.update({
            "router": (h, config.n_router_experts),
            "w_gate": (e, h, inter), "w_up": (e, h, inter),
            "w_down": (e, inter, h),
        })
        if config.router_bias:
            shapes["router_bias"] = (config.n_router_experts,)
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
        if name.startswith("ln_") or name.endswith("_ln"):
            return jnp.ones(shape, dtype)
        if name.endswith("_ln_b"):
            return jnp.zeros(shape, dtype)
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    runs = []
    for r, (kind, lo, hi) in enumerate(config.ff_runs):
        shapes = run_shapes(config, kind)
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


def into_heads(y: jnp.ndarray, dims: int) -> jnp.ndarray:
    """A projection's result [b, t, heads * dims] as [b, t, heads, dims],
    behind an optimisation barrier. Without it the chip's compiler makes ONE
    convolution of the product and this reshape, over the weight seen as
    [heads, dims, in], and that form wants the weight transposed: the run's
    stack was transposed whole at a decode chunk's entry and a layer of it
    laid out again every layer-step (``wq_b``: 75 MB, as long as the product
    it fed; PERF.md, PR 56). Behind the barrier the product is a plain one
    that reads its layer where it lies in the stack, as every other weight
    is read, and what is reshaped is the small result. ``pool_audit.
    weight_ops_in_hlo`` holds the compiled decode chunk to it."""
    return jax.lax.optimization_barrier(y).reshape(*y.shape[:2], -1, dims)


def mla_project(lp, x, cos, sin, positions, config: LlamaConfig):
    """A layer's input norm and the two low-rank projections: (q_nope [b, t,
    heads, nope], q_rope [b, t, heads, rope] after RoPE, latent [b, t,
    latent_width]: ``[rms(ckv) | RoPE(k_rope) | 0]``, what the pool holds)."""
    with jax.named_scope(MIXER_IN):
        eps, nope = config.rms_norm_eps, config.qk_nope_head_dim
        h = rms_norm(x, lp["ln_attn"], eps)
        cq = rms_norm(qmat(h, lp["wq_a"]), lp["q_a_ln"], eps)
        q = into_heads(qmat(cq, lp["wq_b"]), nope + config.qk_rope_head_dim)
        q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin, positions)
        return q_nope, q_rope, token_latent(lp, h, cos, sin, positions, config)


def token_latent(lp, h, cos, sin, positions, config: LlamaConfig):
    """What the pool holds of a token, from the layer's normed input ``h``
    [b, t, hidden]: ``[rms(ckv) | RoPE(k_rope) | 0]`` [b, t, latent_width].
    Its callers stand in ``mixer_in`` (a part's scope is never nested)."""
    b, t, _ = h.shape
    rank = config.kv_lora_rank
    kv = qmat(h, lp["wkv_a"])
    ckv = rms_norm(kv[..., :rank], lp["kv_a_ln"], config.rms_norm_eps)
    k_rope = apply_rope(kv[..., None, rank:], cos, sin, positions)[:, :, 0]
    pad = config.latent_width - rank - config.qk_rope_head_dim
    return jnp.concatenate(
        [ckv, k_rope, jnp.zeros((b, t, pad), ckv.dtype)], axis=-1
    )


def latent_blocks_forward(
    runs: list,
    x: jnp.ndarray,
    cache: LatentPagedCache,
    positions: jnp.ndarray,  # [b, t] RoPE positions (relative to the pad)
    config: LlamaConfig,
    *,
    decode: bool,
    pads: jnp.ndarray,  # [b] first live slot of each row (absolute)
    ends: jnp.ndarray,  # [b] one past the last live slot (decode: slot + 1)
    write_pos: jnp.ndarray,  # the first slot of ``x`` (absolute)
    block_tables: jnp.ndarray,
    live: jnp.ndarray | None,  # [b, t] positions that are tokens of a row
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, LatentPagedCache, jnp.ndarray]:
    """The model's layers in order, run by run: (x, cache, the account
    ``MOE_COUNTS`` of this pass over its sparse layers)."""
    use_pallas = (
        allow_pallas and M.resolve_attention_impl(config.attention_impl) == "pallas"
    )
    fusion = resolve_fusion(config, allow_pallas)
    rank, n = config.kv_lora_rank, config.num_attention_heads
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    cos, sin = rope_table(
        config.qk_rope_head_dim, block_tables.shape[1] * cache.page_size,
        config.rope_theta,
    )
    cos, sin = cos[positions], sin[positions]  # gathered once, not a layer
    kernel_ok = use_pallas and latent_kernel_supported(
        cache.page_size, config.latent_width, rank
    )
    b, t, _ = x.shape
    window = write_pos + jnp.arange(t, dtype=jnp.int32)[None, :]

    def attention(lp, x, pool, li):
        q_nope, q_rope, latent = mla_project(lp, x, cos, sin, None, config)
        pool = latent_write_pool(
            pool, li, latent, write_pos, block_tables,
            starts=pads, ends=None if decode else ends,
        )
        if decode:
            # Absorbed: the keys' up-projection moves onto the query, the
            # values' onto the weighted sum of latents.
            with jax.named_scope(MIXER_IN):
                q_abs = jnp.einsum("bhd,hcd->bhc", q_nope[:, 0], lp["w_uk"])
                q_full = jnp.concatenate([
                    q_abs, q_rope[:, 0],
                    jnp.zeros(
                        (b, n, config.latent_width - rank - q_rope.shape[-1]),
                        q_abs.dtype,
                    ),
                ], axis=-1).astype(x.dtype)
            attend = (
                latent_decode_attention if kernel_ok
                else latent_decode_attention_xla
            )
            with jax.named_scope(MIXER):
                # A dead lane's row is nobody's: it is given one slot to
                # walk, not the shared slot's worth of pages.
                c = attend(
                    q_full, pool, ends, block_tables,
                    jnp.where(live[:, 0], pads, ends - 1),
                    layer=li, rank=rank, scale=scale,
                )
            with jax.named_scope(MIXER_OUT):
                attn = jnp.einsum("bhc,hcd->bhd", c, lp["w_uv"])[:, None]
        else:
            # Expanded: the window's own K and V from its latents.
            with jax.named_scope(MIXER_IN):
                ckv = latent[..., :rank]
                k_rope = latent[..., rank : rank + config.qk_rope_head_dim]
                k_nope = jnp.einsum("btc,hcd->bthd", ckv, lp["w_uk"])
                v = jnp.einsum("btc,hcd->bthd", ckv, lp["w_uv"])
            with jax.named_scope(MIXER):
                attn = mla_prefill_attention(
                    q_nope, q_rope, k_nope, k_rope, v, live, scale=scale,
                    starts=pads - write_pos, lengths=ends - write_pos,
                    use_pallas=use_pallas,
                )
        return attn.astype(x.dtype), pool

    def layer(carry, per_layer, *, experts):
        x, pool, counts = carry
        lp, li, k = per_layer
        attn, pool = attention(lp, x, pool, li)
        if experts is not None:
            # The run's routed experts ride outside the scanned tree, whole,
            # with the layer's index: a grouped kernel reads its layer
            # through the index, where a scanned slice would be copied out
            # of the stack for it (ops/moe._ragged).
            x, c = M.block_finish(
                {**lp, **experts}, x, attn, config, moe_valid=live,
                fusion=fusion, moe_counts=True, moe_layer=k,
            )
            counts = _add_counts(
                counts, jnp.concatenate([jnp.ones((1,), jnp.int32), c])
            )
        else:
            x = M.block_finish(
                lp, x, attn, config, moe_valid=live, fusion=fusion
            )
        return (x, pool, counts), None

    carry = (x, cache.latent, jnp.zeros((len(MOE_COUNTS),), jnp.int32))
    for lp, (kind, lo, hi) in zip(runs, config.ff_runs, strict=True):
        experts = None
        if kind == SPARSE:
            experts = {k: lp[k] for k in _EXPERT_STACKS}
            lp = {k: v for k, v in lp.items() if k not in _EXPERT_STACKS}
        li = jnp.arange(lo, hi, dtype=jnp.int32)
        carry, _ = jax.lax.scan(
            functools.partial(layer, experts=experts), carry,
            (lp, li, jnp.arange(hi - lo, dtype=jnp.int32)),
        )
    x, pool, counts = carry
    return x, LatentPagedCache(latent=pool), counts


def latent_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [b, W]: absolute slots [start, start + W)
    cache: LatentPagedCache,
    pads: jnp.ndarray,  # [b] each row's first slot (absolute)
    ends: jnp.ndarray,  # [b] one past each row's last slot (absolute)
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    start: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, LatentPagedCache, jnp.ndarray]:
    """Every prefill of a latent model, an epoch's and a joiner's: each row's
    tokens sit at slots [pads, ends) of a window that starts at ``start``
    (the closed shapes' layout, ``runtime/shapes.py``: an epoch starts at 0
    with a dead tail, a joiner's window is as wide as its prompt and ends at
    the shared slot). The window holds the row's whole prompt, so attention
    is over the window's own expanded K and V; the latents are written
    through the table for decode to read. Logits are the first row's last
    slot's, ``ends[0] - 1``; the third value is the window's account of its
    sparse layers (``MOE_COUNTS``)."""
    start = jnp.asarray(start, jnp.int32)
    x = M.embed_tokens(params, tokens, config)
    grid = start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
    live = (grid >= pads[:, None]) & (grid < ends[:, None])
    x, cache, counts = latent_blocks_forward(
        params["layers"], x, cache, jnp.maximum(grid - pads[:, None], 0), config,
        decode=False, pads=pads, ends=ends, write_pos=start,
        block_tables=block_tables, live=live, allow_pallas=allow_pallas,
    )
    return M.head_forward(params, x, ends[0] - start, config), cache, counts


def latent_forward_one(
    params: M.Params,
    pads: jnp.ndarray,
    block_tables: jnp.ndarray,
    live: jnp.ndarray,  # [b, 1]: a dead lane's token takes no expert's rows
    config: LlamaConfig,
    padded_seq: int | None = None,
    allow_pallas: bool = True,
):
    """``batch.paged_forward_one`` for a latent model: one token of every
    lane, the latent pool the carried cache; the step's ``MOE_COUNTS`` ride
    back beside it (``programs.decode_program`` scans it and adds them up).
    A dead lane is not counted."""
    fusion = resolve_fusion(config, allow_pallas)

    def forward_one(tok, cache, slot):
        x = M.embed_tokens(params, tok, config)
        ends = jnp.broadcast_to(slot + 1, pads.shape).astype(jnp.int32)
        x, cache, counts = latent_blocks_forward(
            params["layers"], x, cache, (slot - pads)[:, None], config,
            decode=True, pads=pads, ends=ends, write_pos=slot,
            block_tables=block_tables, live=live,
            allow_pallas=allow_pallas,
        )
        logits = M.head_forward(params, x, jnp.int32(1), config, fusion=fusion)
        return logits, cache, counts

    return forward_one


class ExpertAccount:
    """The cumulative account of the expert layer of a kind whose programs
    return ``MOE_COUNTS`` beside their tokens: ``GET /stats`` engine.moe."""

    section, names, add = "moe", MOE_COUNTS, staticmethod(_add_counts)
    keeps_traced = False  # no count apart of the chunks a profiler saw

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.counts = dict.fromkeys(MOE_COUNTS, 0)
        self.dense_dispatches = 0
        self.join_counts = {"joins": 0, "routed": 0, "held": 0}

    def facts(self) -> dict:
        """Cumulative over decode chunks READ: ``dispatches`` decode steps x
        sparse layers, ``dense_dispatches`` those of them that took the dense
        combine (the HOST's count: ``ops/moe.dispatch_path``'s answer for the
        rows of the program launched, which is what its trace asked),
        ``routed`` assignments of live lanes' tokens, ``held`` those to
        experts held here, ``touched`` held experts with an assignment summed
        over dispatches (what the grouped path had to read; the dense combine
        reads every held expert), ``max_load`` the largest load of one held
        expert in one dispatch; ``join``: the joins' windows read, the
        assignments of their tokens and those to held experts."""
        c = self.config
        return {
            **self.counts, "dense_dispatches": self.dense_dispatches,
            "join": dict(self.join_counts),
            "experts_held": c.num_local_experts,
            "experts_ranked": c.n_router_experts,
            "first_held": c.expert_offset, "top_k": c.num_experts_per_tok,
        }

    def absorb(self, got: dict[str, int], decode: bool, traced: bool, rows: int) -> dict:
        """A read program's counts into the cumulative ones (a decode
        chunk's, a step of ``rows`` rows; or a join's window); what the
        timeline's span says of them."""
        if not decode:
            self.join_counts["joins"] += 1
            for key in ("routed", "held"):
                self.join_counts[key] += got[key]
            return got
        for key, v in got.items():
            self.counts[key] = (
                max(self.counts[key], v) if key == "max_load"
                else self.counts[key] + v
            )
        from cake_tpu.ops.moe import dispatch_path  # (nothing else of this module asks the rule)

        c = self.config
        if dispatch_path(rows, 1, c.num_experts_per_tok, c.n_router_experts) == "dense":
            self.dense_dispatches += got["dispatches"]
        return got
