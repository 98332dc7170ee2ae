"""Hybrid stacks: layers that keep a recurrent state beside attention
(``model_type: jamba``: Mamba-1 mixers; ``olmo_hybrid``, ``qwen3_next``: a gated delta rule;
``lfm2_moe``: gated short convolutions; routed experts in most layers of the last two).

Every other family is a stack of identical attention blocks: one stacked
tree, one ``lax.scan`` (models/llama/batch.batched_blocks_forward), K and V
the only per-lane device state. A hybrid model has layers of two KINDS
(``config.layer_kinds``) with different weight trees and a second kind of
per-lane state, so here:

  * ``params["layers"]`` is a LIST of stacked trees, one a maximal run of
    layers alike in mixer AND feed-forward in the model's order
    (``config.layer_runs`` with ``config.run_ff_kinds``; Jamba2-3B:
    7 state, 1 attention, 13 state, 1 attention, 6 state; Olmo-Hybrid: 3
    state, 1 attention, a period; LFM2 cut to 16: 2 state dense, then 1
    attention and 3 state, all sparse, a period). A run is one ``lax.scan``;
    a sparse run's routed experts ride outside the scanned tree, whole, with
    the layer's index (``latent.py``'s rule), its tail runs a block of tokens
    at a time where a window is wide (``kinds.py``'s rule), and the programs
    of a model that has a sparse layer return the account of them
    (``latent.MOE_COUNTS``) third. The attention
    runs go through the paged branch of ``batched_blocks_forward`` itself
    (same kernels, same write), told which pool layers they own; a state run
    scans the layer's mixer, which the config names (``config.state_mixer``:
    ``ops/ssm.mixer_forward``, ``ops/delta_rule.mixer_forward`` or
    ``ops/short_conv.mixer_forward``). Both
    kinds share the block's tail (residual, norms where the tree has them,
    SwiGLU: ``model.block_finish``; a state layer's out-projection is its
    ``wo``).
  * ``HybridCache`` is the one cache value: a ``PagedKVCache`` that holds
    the ATTENTION layers only, and the lane state ``ssm`` / ``conv`` of the
    state layers (``config.state_shape`` / ``conv_window``: the mixer's;
    ``ssm`` is None for a mixer whose state is its window alone: nothing is
    allocated, carried or stepped for it), indexed by lane and not by page.
    It is passed wherever the
    paged backend passes ``kv``, donated, and carried through every scan: a
    layer reads and writes its slice in place (PR 26's rule, extended to the
    state: ``pool_audit.audit_programs``).
  * Left pads, a join window's dead tail, and lanes that are not live in a
    decode dispatch are all one mask, ``live`` [b, L]: where it is false the
    recurrence passes its state through (``ops/ssm.py``, ``ops/delta_rule.py``).

What cannot run over a recurrent state is refused at start-up, in one place
(``capability.refuse_unsupported``): everything that restores, shares,
rewinds or shards K and V only.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import (
    ATTENTION, DENSE, GATED_DELTA, SHORT_CONV, SPARSE, STATE, LlamaConfig,
)
from cake_tpu.models.llama.latent import _EXPERT_STACKS, MOE_COUNTS, _add_counts
from cake_tpu.models.llama.paged_cache import (
    PagedKVCache, init_paged_cache, kv_pack,
)
from cake_tpu.obs.taxonomy import CACHE_WRITE, FEED_FORWARD, MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops import delta_rule as D
from cake_tpu.ops import short_conv as C
from cake_tpu.ops import ssm as S
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.norm import rms_norm


class HybridCache(NamedTuple):
    """Per-lane device state of a hybrid model, in the layer's mixer's
    shapes (``config.state_shape`` / ``conv_window``). Both keep a multiple
    of 128 as the minor axis at published widths so that no TPU tile is
    padded (Mamba's ``d_inner``; the delta rule's ``H * dv`` and ``H (2 dk +
    dv)``); ``ssm`` is float32 (an accumulator), ``conv`` the served type."""

    kv: PagedKVCache  # the attention layers' page pool, [n_attention, ...]
    # [n_state, lanes, *state_shape] float32; None where the mixer keeps none
    ssm: jnp.ndarray | None
    conv: jnp.ndarray  # [n_state, taps - 1, lanes, channels]


def init_hybrid_cache(
    config: LlamaConfig, lanes: int, n_pages: int, page_size: int, dtype
) -> HybridCache:
    """Zeroed: a lane's recurrence starts from s = 0 and a window of zeros."""
    n_state = len(config.layers_of(STATE))
    kept, channels = config.conv_window
    # Heads narrower than a lane tile lie side by side in the pool's rows
    # (LFM2's 64: two a row), and the attention runs pack to match.
    pack = kv_pack(config.num_key_value_heads, config.head_dim)
    return HybridCache(
        kv=init_paged_cache(
            len(config.layers_of(ATTENTION)), n_pages,
            config.num_key_value_heads // pack, page_size,
            config.head_dim * pack, dtype,
        ),
        ssm=None if config.state_shape is None else jnp.zeros(
            (n_state, lanes, *config.state_shape), jnp.float32),
        conv=jnp.zeros((n_state, kept, lanes, channels), dtype),
    )


# ------------------------------------------------------------------ params

def run_shapes(
    config: LlamaConfig, kind: str, ff: str = DENSE
) -> dict[str, tuple[int, ...]]:
    """Per-layer shapes of one run's tree (stacked over its run), as this
    module holds them: the mixer of ``kind`` and the feed-forward of ``ff``.
    Matrices are [in, out] like every other weight here;
    Mamba's ``A_log`` is stored [d_state, d_inner] and a ``conv_w`` [taps,
    channels] (io/safetensors_io.py transposes both); a state layer's
    out-projection is its ``wo``. The delta rule's ``in_proj`` is q | k | v |
    z side by side, ``ab_proj`` a | b and ``conv_w`` q's, k's and v's taps
    (the loader joins the checkpoint's tensors); the short convolution's
    ``in_proj`` is B | C | u as the checkpoint has it. Which norms a layer
    has is the config's (``pre_block_norms`` / ``post_block_norms``). A
    sparse feed-forward is a ``router``, its selection bias where the config
    has one, and the stacked experts (``latent.run_shapes``' names)."""
    h, inter = config.hidden_size, config.intermediate_size
    if ff == SPARSE:
        e, inter = config.num_local_experts, config.moe_intermediate_size
        ffn = {"router": (h, config.n_router_experts), **_shared_expert_shapes(config)}
        if config.router_bias:
            ffn["router_bias"] = (config.n_router_experts,)
        ffn.update(w_gate=(e, h, inter), w_up=(e, h, inter), w_down=(e, inter, h))
    else:
        ffn = {"w_gate": (h, inter), "w_up": (h, inter), "w_down": (inter, h)}
    if config.pre_block_norms:
        ffn.update(ln_attn=(h,), ln_mlp=(h,))
    if config.post_block_norms:
        ffn.update(ln_post_attn=(h,), ln_post_mlp=(h,))
    if kind == ATTENTION:
        hd = config.head_dim
        q, kv = config.num_attention_heads * hd, config.num_key_value_heads * hd
        qk = {"q_norm": (q,), "k_norm": (kv,)} if config.qk_norm_whole else {}
        if config.qk_norm:  # a norm a head (Qwen3's), its weight the heads share
            qk = {"q_norm": (hd,), "k_norm": (hd,)}
        return {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h), **qk, **_gate_shapes(config), **ffn}
    if config.state_mixer == SHORT_CONV:
        kept, channels = config.conv_window
        return {
            "in_proj": (h, 3 * channels), "conv_w": (kept + 1, channels),
            "wo": (channels, h), **ffn,
        }
    if config.state_mixer == GATED_DELTA:
        heads, dv = config.linear_num_value_heads, config.linear_value_head_dim
        kept, channels = config.conv_window
        return {
            "in_proj": (h, channels + heads * dv), "ab_proj": (h, 2 * heads),
            "conv_w": (kept + 1, channels), "A_log": (heads,),
            "dt_bias": (heads,), "o_norm": (dv,), "wo": (heads * dv, h), **ffn,
        }
    d, n, r = config.mamba_d_inner, config.mamba_d_state, config.mamba_dt_rank
    return {
        "in_proj": (h, 2 * d), "conv_w": (config.mamba_d_conv, d),
        "conv_b": (d,), "x_proj": (d, r + 2 * n), "dt_ln": (r,),
        "b_ln": (n,), "c_ln": (n,), "dt_proj": (r, d), "dt_bias": (d,),
        "A_log": (n, d), "D": (d,), "wo": (d, h), **ffn,
    }


# How ``init_params`` draws a name: norms (and Mamba's D) are ones; the
# convolution's taps and the gates' terms are drawn wide enough (0.2) for
# the recurrence to be visible at a tiny width (alpha and beta spread, beta
# on both sides of 1; a router's selection bias, so that the chosen set is
# the biased scores' and not the scores'); everything else at 0.02.
_ONES = frozenset((
    "D", "dt_ln", "b_ln", "c_ln", "ln_attn", "ln_mlp", "ln_post_attn",
    "ln_post_mlp", "q_norm", "k_norm", "o_norm",
))
_WIDE = frozenset((
    "conv_w", "conv_b", "A_log", "dt_bias", "ab_proj", "router_bias",
))


def init_params(config: LlamaConfig, key: jax.Array, dtype=jnp.bfloat16) -> M.Params:
    """Random-init params in the by-run layout (tests and compile checks)."""
    std = 0.02

    def draw(k, name, shape):
        if name in _ONES and not (config.rmsnorm_offset and name != "o_norm"):
            return jnp.ones(shape, dtype)  # a (1 + w) norm's weight is drawn about 0
        scale = 0.2 if name in _WIDE else std
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    runs = []
    for r, ((kind, lo, hi), ff) in enumerate(
        zip(config.layer_runs, config.run_ff_kinds, strict=True)
    ):
        shapes = run_shapes(config, kind, ff)
        keys = jax.random.split(jax.random.fold_in(key, r), len(shapes))
        runs.append({
            name: draw(k, name, (hi - lo, *shape))
            for k, (name, shape) in zip(keys, shapes.items())
        })
    k_embed, k_head = jax.random.split(jax.random.fold_in(key, len(runs)))
    v, h = config.vocab_size, config.hidden_size
    params = {
        "embed": draw(k_embed, "embed", (v, h)),
        "layers": runs,
        "ln_f": (jnp.zeros if config.rmsnorm_offset else jnp.ones)((h,), dtype),
    }
    if not config.tie_word_embeddings:
        params["lm_head"] = draw(k_head, "lm_head", (h, v))
    return params


# ----------------------------------------------------------------- forward


def _kernel_switch(config: LlamaConfig, allow_pallas: bool) -> bool:
    """The state layers' kernels follow the attention kernels' switch."""
    return allow_pallas and M.resolve_attention_impl(config.attention_impl) == "pallas"


def window_form(config: LlamaConfig, allow_pallas: bool) -> str | None:
    """Which form a window (``L > 1``: every prefill and join) of the state
    layers' recurrence takes, decided once from the config's widths and the
    kernel switch: ``"pallas"`` (``ops/pallas/delta_rule.py`` or
    ``selective_scan.py``) or ``"xla"`` (the twin; a short convolution has
    no other form); None without state layers. ``GET /stats``
    engine.state.window_form."""
    if not config.layers_of(STATE):
        return None
    switch = _kernel_switch(config, allow_pallas)
    if config.state_mixer == SHORT_CONV:
        return "xla"
    if config.state_mixer == GATED_DELTA:
        kernel = D.window_in_kernel(
            config.state_shape, config.linear_num_value_heads, switch)
    else:
        n, d = config.state_shape
        kernel = switch and S.pallas_scan.tiles(d, n)
    return "pallas" if kernel else "xla"


def _state_ops(config: LlamaConfig):
    """The state layers' mixer as data: (its module, what its forms take of
    the config beside ``eps``, what its ``steps_in_place`` takes beside the
    state)."""
    if config.state_mixer == GATED_DELTA:
        return (
            D, {"neg_eigval": config.linear_allow_neg_eigval},
            (config.linear_num_value_heads,),
        )
    if config.state_mixer == SHORT_CONV:
        return C, {}, ()
    return S, {}, ()


def _steps_in_place(config: LlamaConfig, ssm, switch: bool) -> bool:
    """Whether a decode step updates the stack's state ``ssm`` ([n_state,
    lanes, *state_shape]; its shape is all that is read) in place through the
    mixer's Pallas kernel, which takes the stack whole: the kernel switch and
    widths that tile. Never where the mixer keeps no such state."""
    if ssm is None:
        return False
    ops, _, widths = _state_ops(config)
    return switch and ops.steps_in_place(ssm, *widths)


def step_form(config: LlamaConfig, allow_pallas: bool) -> str | None:
    """Which form the one-token update of the state layers' recurrence takes
    in a decode program, by the predicate the program itself follows:
    ``"pallas"`` (``ops/pallas/delta_step.py`` or ``selective_step.py``: the
    state read once and written once, in place) or ``"xla"`` (the twin); None
    without state layers. ``GET /stats`` engine.state.step_form."""
    if not config.layers_of(STATE):
        return None
    stack = config.state_shape and jax.ShapeDtypeStruct(
        (1, 1, *config.state_shape), jnp.float32)
    kernel = _steps_in_place(config, stack, _kernel_switch(config, allow_pallas))
    return "pallas" if kernel else "xla"


def _routed_tail(lp, x, gated, live, k, config: LlamaConfig, fusion):
    """``block_finish`` of a sparse layer whose tree holds its RUN's expert
    stacks (``k``: the layer's index in them), a block of tokens at a time
    where the window is wide (``kinds._TAIL_TOKENS``: the grouped experts'
    rows and their combine stay a block's): (x, ``moe.held_counts`` of the
    layer, summed over its blocks and the largest load the largest)."""
    from cake_tpu.models.llama.kinds import _tail_block

    def finish(x, gated, live):
        return M.block_finish(
            lp, x, gated, config, moe_valid=live, fusion=fusion,
            moe_counts=True, moe_layer=k,
        )

    b, t, _ = x.shape
    block = _tail_block(t, b)
    if block == t:
        return finish(x, gated, live)
    n = b * t // block
    _, (out, counts) = jax.lax.scan(lambda _, args: (None, finish(*args)), None, (
        x.reshape(n, 1, block, -1), gated.reshape(n, 1, block, *gated.shape[2:]),
        live.reshape(n, 1, block),
    ))
    counts = jnp.concatenate(
        [jnp.sum(counts[:, :-1], axis=0), jnp.max(counts[:, -1:], axis=0)]
    )
    return out.reshape(x.shape), counts


def hybrid_blocks_forward(
    runs: list,
    x: jnp.ndarray,
    cache: HybridCache,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    config: LlamaConfig,
    *,
    decode: bool,
    pads: jnp.ndarray,
    lengths: jnp.ndarray,
    write_pos: jnp.ndarray,
    block_tables: jnp.ndarray,
    live: jnp.ndarray,  # [b, L] bool: positions that are tokens of the row
    ends: jnp.ndarray | None,  # [b] one past the last live position
    lane: jnp.ndarray | None = None,
    cached_chunk: bool = False,
    write_starts: jnp.ndarray | None = None,
    allow_pallas: bool = True, live_rows: jnp.ndarray | None = None,  # a decode dispatch's ``_stepped_rows``
):
    """The model's layers in order, run by run: (x, cache), and where the
    model has a sparse layer a third value, the pass's account of them
    (``latent.MOE_COUNTS``; a position that is not ``live`` takes no
    expert's rows and is not counted). With ``lane`` (a traced
    scalar; every prefill) the rows of ``x`` are NEW tenants of lanes
    ``lane``, ``lane + 1``, ...: their recurrence starts from zero and their
    final state OVERWRITES those lanes' (never continues the last tenant's).
    Without it (decode) row r of ``x`` continues lane r's state."""
    from cake_tpu.models.llama.batch import batched_blocks_forward, paged_seq_len
    from cake_tpu.ops.rope import model_rope_tables

    fusion = resolve_fusion(config, allow_pallas)
    use_pallas = _kernel_switch(config, allow_pallas)
    kv, ssm, conv = cache
    eps = config.rms_norm_eps
    rows = x.shape[0]
    ops, of_config, _ = _state_ops(config)
    mixer = functools.partial(
        ops.mixer_forward, eps=eps, allow_pallas=use_pallas, **of_config
    )
    # A decode step updates the carry's state in place through the mixer's Pallas kernel, which takes the stack whole
    stepped = {} if live_rows is None else {"rows": live_rows}  # and, the delta rule's, ``_stepped_rows``
    in_place = (
        lane is None and x.shape[1] == 1
        and _steps_in_place(config, ssm, use_pallas)
    )
    if lane is not None:
        lanes = jnp.arange(conv.shape[2], dtype=jnp.int32)
        mine = (lanes >= lane) & (lanes < lane + rows)
    cos = sin = None
    if config.use_rope:
        # The attention layers' rotary term, at a row's own positions
        # (``q_pos``: relative to its pad, whatever slot its window starts at).
        cos, sin = model_rope_tables(config, paged_seq_len(kv, block_tables))

    def routed(lp, x, gated, k, counts, *, experts):
        """A sparse layer's tail and the account with it. The run's routed
        experts ride outside the scanned tree, whole, with the layer's index
        (``latent.latent_blocks_forward`` says why)."""
        x, c = _routed_tail({**lp, **experts}, x, gated, live, k, config, fusion)
        with jax.named_scope(FEED_FORWARD):  # the account is the experts' own
            one = jnp.ones((1,), jnp.int32)
            return x, _add_counts(counts, jnp.concatenate([one, c]))

    def state_layer(carry, per_layer, *, experts=None):
        # The lane state rides in the carry like the page pool: a layer
        # takes its own slice and puts it back in place.
        x, ssm, conv, *counts = carry
        lp, li, *k = per_layer
        # The layer's own slices of the state are the mixer's inputs; what
        # goes back into the carry is the program's cache write.
        with jax.named_scope(MIXER_IN):
            c_old = jax.lax.dynamic_index_in_dim(conv, li, 0, keepdims=False)
            h = rms_norm(x, lp["ln_attn"], eps, config.rmsnorm_offset) if "ln_attn" in lp else x
        if in_place:
            gated, ssm, c_l = ops.mixer_step_stacked(
                lp, h, ssm, li, c_old, live, eps, **of_config, **stepped
            )
        elif ssm is None:
            # The window is all the state there is: a new tenant's starts
            # from zeros, a decode step continues the lane's own.
            c_l = c_old if lane is None else jnp.zeros(
                (conv.shape[1], rows, conv.shape[3]), conv.dtype)
            gated, _, c_l = mixer(lp, h, None, c_l, live, ends)
        elif lane is None:
            with jax.named_scope(MIXER):
                s_l = jax.lax.dynamic_index_in_dim(ssm, li, 0, keepdims=False)
            gated, s_l, c_l = mixer(lp, h, s_l, c_old, live, ends)
            with jax.named_scope(CACHE_WRITE):
                ssm = jax.lax.dynamic_update_index_in_dim(ssm, s_l, li, 0)
        else:
            s_l = jnp.zeros((rows, *ssm.shape[2:]), ssm.dtype)
            c_l = jnp.zeros((conv.shape[1], rows, conv.shape[3]), conv.dtype)
            gated, s_l, c_l = mixer(lp, h, s_l, c_l, live, ends)
            with jax.named_scope(CACHE_WRITE):
                zero = jnp.int32(0)
                ssm = jax.lax.dynamic_update_slice(
                    ssm, s_l[None], (li, lane, zero, zero)
                )
        if lane is not None:
            with jax.named_scope(CACHE_WRITE):
                # The window's lane axis is a tiled one ([.., lanes,
                # channels]): an update-slice at a lane there makes the TPU
                # compiler re-lay the whole array out and back (two copies
                # of it, seen compiling for a described v5e). The rows are
                # placed in a buffer of the layer's own size, 1 MB, by a
                # gather, and selected in.
                placed = jnp.take(
                    c_l, jnp.clip(lanes - lane, 0, rows - 1), axis=1
                )
                c_l = jnp.where(mine[None, :, None], placed, c_old)
        with jax.named_scope(CACHE_WRITE):
            conv = jax.lax.dynamic_update_index_in_dim(conv, c_l, li, 0)
        if experts is None:
            x = M.block_finish(lp, x, gated, config, fusion=fusion)
        else:
            x, *counts = routed(lp, x, gated, *k, *counts, experts=experts)
        return (x, ssm, conv, *counts), None

    counts = None
    if SPARSE in config.ff_kinds:
        counts = jnp.zeros((len(MOE_COUNTS),), jnp.int32)
    for lp, (kind, lo, hi), ff in zip(
        runs, config.layer_runs, config.run_ff_kinds, strict=True
    ):
        more, experts = {}, None
        if ff == SPARSE:
            experts = {k: lp[k] for k in _EXPERT_STACKS}
            lp = {k: v for k, v in lp.items() if k not in _EXPERT_STACKS}
        if kind == ATTENTION:
            if experts is not None:
                more = dict(
                    tail=functools.partial(_gate_first(routed, lp, config), experts=experts),
                    tail_carry=counts,
                )
            x, kv, *got = batched_blocks_forward(
                lp, x, kv, cos, sin, q_pos, k_pos, config,
                decode=decode, pads=pads, lengths=lengths,
                write_pos=write_pos, allow_pallas=allow_pallas,
                block_tables=block_tables, layer_base=lo,
                cached_chunk=cached_chunk, write_starts=write_starts, **more,
            )
        else:
            # a sparse run carries the account and scans its layers' indices
            # in the run's expert stacks beside their indices in the state (a
            # dense run's body keeps its own name: it is in the lowered text)
            sparse = experts is not None
            (x, ssm, conv, *got), _ = jax.lax.scan(
                functools.partial(state_layer, experts=experts) if sparse else state_layer,
                (x, ssm, conv, *((counts,) if sparse else ())),
                (lp, jnp.arange(lo, hi, dtype=jnp.int32),
                 *((jnp.arange(hi - lo, dtype=jnp.int32),) if sparse else ())),
            )
        if got:
            (counts,) = got
    cache = HybridCache(kv=kv, ssm=ssm, conv=conv)
    return (x, cache) if counts is None else (x, cache, counts)


def hybrid_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [b, W]: absolute slots [start, start + W)
    cache: HybridCache,
    pads: jnp.ndarray,  # [b] each row's first slot (absolute)
    ends: jnp.ndarray,  # [b] one past each row's last slot (absolute)
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    start: jnp.ndarray | int = 0,
    lane: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
):
    """Every prefill of a hybrid model: the rows are NEW tenants of lanes
    ``lane``... (``block_tables`` holds those lanes' rows), each row's
    tokens sit at slots [pads, ends) of a window that starts at ``start``.
    An epoch's prefill starts at 0 and ends every row at the shared slot; a
    joiner's window is only as wide as its prompt and ENDS at the shared
    slot — neither kind of layer needs the slots before it (the state
    layers start from zero, and the attention layers read only this row's
    keys and carry no positional term, or a rotary one at the row's OWN
    positions, which count from its pad), so a join costs its prompt, not
    the batch's slot.

    The attention layers run the paged cached-chunk arithmetic (the window's
    K and V are written through the table, then read back with the pool's
    prefix: ``batch.paged_suffix_prefill``'s grids); the state layers'
    recurrence stands still wherever the window is not the row's. Logits
    are the first row's last slot's, ``ends[0] - 1``: the shared slot; a
    third value, the window's account of its sparse layers
    (``latent.MOE_COUNTS``), where the model has one."""
    from cake_tpu.models.llama.batch import paged_seq_len, verify_positions

    b, w = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    x = M.embed_tokens(params, tokens, config)
    capacity = paged_seq_len(cache.kv, block_tables)
    q_pos, k_pos, _ = verify_positions(w, pads, start, capacity)
    grid = start + jnp.arange(w, dtype=jnp.int32)[None, :]
    live = (grid >= pads[:, None]) & (grid < ends[:, None])
    x, cache, *counts = hybrid_blocks_forward(
        params["layers"], x, cache, q_pos, k_pos, config,
        decode=False, cached_chunk=True, pads=pads, lengths=ends,
        write_pos=start, write_starts=pads, block_tables=block_tables,
        live=live, ends=ends - start, lane=jnp.asarray(lane, jnp.int32),
        allow_pallas=allow_pallas,
    )
    return M.head_forward(params, x, ends[0] - start, config), cache, *counts


def hybrid_forward_one(
    params: M.Params,
    pads: jnp.ndarray,
    block_tables: jnp.ndarray,
    live: jnp.ndarray,  # [b, 1]: lanes that are not live keep their state
    config: LlamaConfig,
    padded_seq: int,
    allow_pallas: bool = True,
):
    """``batch.paged_forward_one`` for a hybrid model: one token of every
    lane through the by-run walk, the whole ``HybridCache`` the carried
    cache (``programs.decode_program`` scans it); the step's account of its
    sparse layers rides back beside it where the model has one."""
    from cake_tpu.models.llama.batch import decode_positions
    rows = _stepped_rows(config, live, allow_pallas)  # once a dispatch
    fusion = resolve_fusion(config, allow_pallas)

    def forward_one(tok, cache, slot):
        x = M.embed_tokens(params, tok, config)
        q_pos, k_pos, lengths = decode_positions(slot, pads, padded_seq)
        x, cache, *counts = hybrid_blocks_forward(
            params["layers"], x, cache, q_pos, k_pos, config,
            decode=True, pads=pads, lengths=lengths, write_pos=slot,
            block_tables=block_tables, live=live, ends=None,
            allow_pallas=allow_pallas, live_rows=rows,
        )
        logits = M.head_forward(params, x, jnp.int32(1), config, fusion=fusion)
        return logits, cache, *counts

    return forward_one


def hybrid_join_rows(
    params: M.Params,
    tokens: jnp.ndarray,  # [R, W]: absolute slots [start, start + W)
    cache: HybridCache,
    pads: jnp.ndarray,  # [R]
    ends: jnp.ndarray,  # [R]; a dead row's is its pad
    block_tables: jnp.ndarray,  # [R, pages]; a dead row's holds no page
    config: LlamaConfig,
    *,
    lanes: jnp.ndarray,  # [R] each row's lane; a dead row's is no lane (-1)
    start: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
):
    """The joiners of one step as ONE window of R rows (``hybrid_prefill``
    says what a window is): every row ends at the shared slot, row r is the
    new tenant of lane ``lanes[r]``, whichever lanes were free, and a row the
    step did not fill is dead: no position of it is ``live``, so it takes no
    expert's rows, its table row holds no page, so its K and V drop, no lane
    is its own, so its state goes nowhere, and nobody reads its logits. The
    first row is never dead (the logits are read at ITS last slot, which is
    every live row's). The experts somebody chose are read once for all the
    rows, which is what the group is for.

    The rows' recurrence runs in a lane state of R lanes of its own, from
    zeros as every new tenant's does, and each layer's result is then placed
    in the tenants' lanes by a gather and a select over the whole array (6 MB
    at LFM2's widths; ``state_layer`` says why no update-slice at a lane):
    ``hybrid_prefill`` itself is as it was, to the operation."""
    rows = tokens.shape[0]
    with jax.named_scope(CACHE_WRITE):
        scratch = HybridCache(
            kv=cache.kv,
            ssm=None if cache.ssm is None else jnp.zeros(
                (cache.ssm.shape[0], rows, *cache.ssm.shape[2:]), cache.ssm.dtype),
            conv=jnp.zeros(
                (*cache.conv.shape[:2], rows, cache.conv.shape[3]), cache.conv.dtype),
        )
    logits, new, *counts = hybrid_prefill(
        params, tokens, scratch, pads, ends, block_tables, config,
        start=start, lane=0, allow_pallas=allow_pallas,
    )
    with jax.named_scope(CACHE_WRITE):
        lanes = jnp.asarray(lanes, jnp.int32)
        tenant = jnp.arange(cache.conv.shape[2], dtype=jnp.int32)[:, None] == lanes[None, :]
        mine, row = tenant.any(axis=1), jnp.argmax(tenant, axis=1)
        conv = jnp.where(
            mine[None, None, :, None], jnp.take(new.conv, row, axis=2), cache.conv)
        ssm = cache.ssm
        if ssm is not None:
            ssm = _place_rows(ssm, new.ssm, lanes)
    return logits, HybridCache(kv=new.kv, ssm=ssm, conv=conv), *counts


def _shared_expert_shapes(config: LlamaConfig) -> dict[str, tuple[int, ...]]:
    """A sparse layer's shared expert, where the config has one, in the
    scanned tree beside the router (``model.block_finish``'s names: computed
    whole on this chip, under the scope ``shared_expert``), behind its
    sigmoid gate (``se_gate``: one number a token)."""
    s = config.shared_expert_intermediate_size
    if not s:
        return {}
    h = config.hidden_size
    return {"sh_gate": (h, s), "sh_up": (h, s), "sh_down": (s, h), "se_gate": (h, 1)}


def _gate_shapes(config: LlamaConfig) -> dict[str, tuple[int, ...]]:
    """The attention output's gate a NUMBER (``config.attn_gate``
    "per-number"): ``wg`` as wide as ``wq``, a head at a time."""
    if config.attn_gate != "per-number":
        return {}
    return {"wg": (config.hidden_size, config.num_attention_heads * config.head_dim)}


def _gate_first(tail, lp, config: LlamaConfig):
    """An attention run's ``tail`` (``batched_blocks_forward``'s hook), the
    attention output gated a number first where the run's tree has the
    gate's matrix ``wg``: ``attn * sigmoid(h wg)`` before ``wo``, ``h`` the
    layer's normed input (the norm ``block_qkv`` computed, once in the
    compiled program). A tree without one gets ``tail`` itself. The parser
    that gives a model such a gate makes every layer sparse, so every
    attention run has a tail."""
    if "wg" not in lp:
        return tail

    def gated_tail(lp, x, attn, *rest, **kw):
        with jax.named_scope(MIXER_OUT):
            h = rms_norm(x, lp["ln_attn"], config.rms_norm_eps, config.rmsnorm_offset)
            gate = jax.nn.sigmoid(M.qmat(h, lp["wg"]).astype(jnp.float32))
            attn = (attn * gate.reshape(attn.shape)).astype(attn.dtype)
        return tail(lp, x, attn, *rest, **kw)

    return gated_tail


def _place_rows(ssm: jnp.ndarray, new: jnp.ndarray, lanes: jnp.ndarray) -> jnp.ndarray:
    """A group's rows' float32 states ``new`` [n_state, R, ...] into their
    tenants' lanes of ``ssm`` [n_state, lanes, ...], in place and a row at a
    time: the lane axis is no tiled one here, and a select over the whole
    array (the window's way above) would copy a matrix state whole (1.2 GB at
    Qwen3-Next's widths and 64 lanes). A dead row (lane -1) puts back what
    lane 0 holds."""
    for r in range(new.shape[1]):
        lane = jnp.maximum(lanes[r], 0)
        old = jax.lax.dynamic_slice_in_dim(ssm, lane, 1, axis=1)
        row = jnp.where(lanes[r] >= 0, new[:, r : r + 1], old)
        ssm = jax.lax.dynamic_update_slice_in_dim(ssm, row, lane, axis=1)
    return ssm


def steps_live_rows(config: LlamaConfig, allow_pallas: bool) -> bool:
    """Whether a decode step's one-token update walks the dispatch's live
    lanes ALONE (a dead lane's state neither read nor written): the delta
    rule's kernel does (``ops/pallas/delta_step.py``), no other form
    (``batch_backend._count_stepped`` has the list)."""
    return config.state_mixer == GATED_DELTA and step_form(config, allow_pallas) == "pallas"


def _stepped_rows(config: LlamaConfig, live: jnp.ndarray, allow_pallas: bool):
    """A decode dispatch's live lanes as that kernel walks them
    (``delta_step.live_rows``), from ``live`` [b, 1]: made here ONCE a
    dispatch, outside the step scan and the runs' layer scans, for every
    state layer's call; None where ``steps_live_rows`` does not hold."""
    if not steps_live_rows(config, allow_pallas):
        return None
    with jax.named_scope(MIXER_IN):  # the kernel's operand, like q and k
        return D.live_rows(live[:, 0])
