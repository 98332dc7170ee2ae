"""Sparse mixture-of-experts SwiGLU block.

The reference is dense-Llama-only (SURVEY.md §2.7 marks expert parallelism
absent); this is a beyond-parity family. ONE routing definition
(``route_topk_select``): router logits in float32 -> scores over ALL the
experts the router ranks (a full softmax, HF Mixtral / Qwen-MoE; or a
sigmoid of each logit, the DeepSeek-V3 family and ``pangu_ultra_moe``) ->
top-k -> optional renormalisation over the chosen -> times a scaling
factor. Pinned token-for-token against transformers in tests/test_moe.py.

**The layer is told which experts it holds.** The stacked expert weights
are ``e_local`` experts, the ``expert_offset``-th to the ``expert_offset +
e_local``-th of the ``E`` the router ranks. It routes over all ``E`` and
computes the part of the result its own experts give; an assignment to an
absent expert adds nothing HERE. With ``e_local == E`` that is the whole
layer; under ``--tp`` the offset is ``axis_index * e_local`` and the
per-branch ``psum`` in block_finish adds the shards' parts; on one chip that
serves one rank's share of an expert-parallel deployment the offset comes
from the configuration (``config.expert_offset``) and the absent experts'
part is left out, in the program and in the plain reference alike. Nothing
stands in for the absent chips or their exchange.

Three ways to compute it, ONE rule to choose (``dispatch_path``, at the
end of this file, where the timings are):

  * **Grouped, drop-free** (``dispatch="auto"`` wherever the dense combine's
    two conditions do not both hold; ``dispatch="grouped"`` forces it): the
    token-expert assignments are sorted by held expert, assignments to
    absent experts and of pad slots past the end, the rows filled to whole
    tiles, and each expert multiplies its own contiguous rows (``_ragged``:
    the Pallas grouped matmul on the TPU, ``jax.lax.ragged_dot`` elsewhere):
    FLOPs follow the assignments that land here, and an expert nobody chose
    is never read. Shapes are static, only the group boundaries are data;
    no assignment is ever dropped.
  * **Dense combine** (``dispatch="auto"`` where the dispatch touches every
    held expert anyway and is at most a row tile wide; ``dispatch="dense"``
    forces it): every held expert's SwiGLU runs on every token as batched
    einsums, the routing weight (zero where the expert was not chosen)
    applied on the way. It reads every held expert whatever the routing, and
    pays no sort, gather, tile filling or one-hot combine.
  * **Capacity buckets** (a ``--tp``-sharded PREFILL chunk only: ``tp_axis``
    set and ``chunk >= EP_CAPACITY_MIN_CHUNK``): a fixed row budget an
    expert, overflow DROPS (``EP_CAPACITY_FACTOR``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cake_tpu.ops.quant import Quant4Weight, QuantS4Weight, QuantWeight


def _qeinsum(spec: str, x: jnp.ndarray, w) -> jnp.ndarray:
    """Einsum against a stacked expert weight, plain or int8-quantized.

    The QuantWeight scale is [n_experts, 1, out]; both specs used here emit
    [..., n_experts, out], so the scale broadcasts as [n_experts, out]."""
    if isinstance(w, QuantWeight):
        out = jnp.einsum(spec, x, w.w.astype(x.dtype))
        e, _, o = w.scale.shape
        return out * w.scale.reshape(e, o).astype(x.dtype)
    return jnp.einsum(spec, x, w)


def route_topk_select(
    logits: jnp.ndarray,
    top_k: int,
    norm_topk: bool = True,
    scoring: str = "softmax",
    scale: float = 1.0,
    bias: jnp.ndarray | None = None,
    n_group: int = 1,
    topk_group: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scores (f32) over every ranked expert -> top-k -> optional
    renormalise -> scale.

    ``bias`` [E] (the DeepSeek-V3 family's ``e_score_correction_bias``) is
    added to the scores for CHOOSING only: the values returned are the
    chosen experts' scores without it. ``n_group`` > 1 limits the choice to
    the best ``topk_group`` of ``n_group`` equal groups of experts, a group
    scored by the sum of its two largest (biased) scores.

    ``softmax``: Mixtral always renormalises the selected probabilities to
    sum 1; Qwen2-MoE gates this with ``norm_topk_prob`` (usually off).
    ``sigmoid``: each logit on its own, the chosen renormalised by their sum
    (+1e-20) and multiplied by ``scale`` (``routed_scaling_factor``). THE one
    routing definition: every dispatch builds on these (values [..., k],
    expert indices [..., k])."""
    logits = logits.astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown MoE scoring {scoring!r}")
    if bias is None and n_group == 1:
        topv, topi = jax.lax.top_k(scores, top_k)
    else:
        choice = scores if bias is None else scores + bias.astype(jnp.float32)
        if n_group > 1:
            grouped = choice.reshape(*choice.shape[:-1], n_group, -1)
            group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, best = jax.lax.top_k(group_score, topk_group)
            stays = jnp.any(
                best[..., None] == jnp.arange(n_group), axis=-2
            )  # [..., n_group]
            choice = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(
                choice.shape
            )
        _, topi = jax.lax.top_k(choice, top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    if norm_topk:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        topv = topv * scale
    return topv, topi


def router_logits(x: jnp.ndarray, router_w: jnp.ndarray) -> jnp.ndarray:
    """[..., E] float32 logits: the activations widened, the product in
    float32 at the highest matmul precision (the router is a sliver of the
    layer, and a near-tie turned by bf16 picks another expert)."""
    return jnp.einsum(
        "...h,he->...e", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


# What tests force ONE path process-wide with, before tracing: 0 = never the
# dense combine by shape (always grouped when ungated), a huge value = always
# dense. 1, the default, leaves the rule to the shapes (``dispatch_path``).
#
# ACCEPTED NUMERICS SEAM: the two paths reduce expert contributions in
# different orders. Parity tests compare within tolerance.
GROUPED_MIN_TOKENS = 1
# ``dispatch="auto"`` takes the dense combine where BOTH hold (the timings
# behind the two numbers are with the rule, ``dispatch_path``):
#   1. every held expert is read anyway: the expected share of them that the
#      dispatch's rows leave untouched, ``(1 - top_k / n_ranked) ** rows``, is
#      at most this;
DENSE_MAX_UNTOUCHED = 0.01
#   2. the extra products hide under the weights' stream: the rows are at most
#      this, one row tile of the grouped kernel.
DENSE_MAX_TOKENS = 128


_TILE = 128  # rows, and every matrix dimension, the TPU's grouped kernel tiles by


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(rows, contraction, columns) of one step of the grouped kernel. The
    kernel visits every (tile of rows, expert with rows in it) pair and reads
    that expert's whole matrix for it, so a visit costs the larger of the
    matrix's read and the tile's product: at 256 rows the two are level on a
    v5e (7680 x 2048 bf16: 38 us to read, 41 to multiply), at 512 the
    product doubles (measured: 4.7 ms for 1024 rows, 2.4 predicted at 256;
    PERF.md, PR 32). The weights go through in the largest slabs that divide
    the dimension (multiples of the lane tile, at most 1280 x 1024 or 1024 x
    1280: 2.6 MB, double-buffered well inside a core's VMEM)."""
    def slab(x, most):
        return max(t for t in range(_TILE, most + 1, _TILE) if x % t == 0)

    tm = 2 * _TILE if m % (2 * _TILE) == 0 else _TILE
    tk, tn = (slab(k, 1280), slab(n, 1024)) if k >= n else (slab(k, 1024), slab(n, 1280))
    return tm, tk, tn


def _of_layer(w, layer):
    """One layer's expert stack out of a run's (``layer`` None: ``w`` is one
    layer's already). Inside an XLA product the index fuses into the read."""
    return w if layer is None else w[layer]


def _ragged(
    xs: jnp.ndarray, w, group_sizes: jnp.ndarray, eids: jnp.ndarray, layer=None
):
    """Each expert's contiguous rows of ``xs`` times that expert's matrix,
    against stacked expert weights [e, k, n] (with ``layer``: a run of
    layers' stacks [n_layers, e, k, n] and the traced index of the one to
    use), plain or int8-quantized. On the TPU the Pallas grouped matmul JAX
    ships (``megablox.gmm``: a step is a tile of rows of ONE expert against a
    slab of its weights, an expert without rows is never read); elsewhere,
    and for shapes it does not tile, ``jax.lax.ragged_dot``. Rows past the
    last group are left as they come.

    **A kernel's operand is a whole array**: handed one layer's slice of a
    run's stack, XLA copies the slice out first (1.5 GB a layer at
    ``pangu-ultra-ep16-chat-closed``'s sizes, three times the product's own
    time: PERF.md, PR 32). So with ``layer`` the kernel is given the whole
    run's experts as one group list [n_layers * e, k, n] and this layer's
    group sizes at their place in it, zeros elsewhere: what PR 26 did for the
    page pool.

    The QuantWeight scale is per-expert per-output-channel [E, 1, out]; each
    sorted row multiplies its own expert's scale row (gathered by ``eids``)."""
    if isinstance(w, QuantWeight):
        w = jax.tree.map(lambda a: _of_layer(a, layer), w)
        out = jax.lax.ragged_dot(xs, w.w.astype(xs.dtype), group_sizes)
        e, _, o = w.scale.shape
        return out * w.scale.reshape(e, o)[eids].astype(xs.dtype)
    m, (e, k, n) = xs.shape[0], w.shape[-3:]
    if jax.default_backend() == "tpu" and not (m % _TILE or k % _TILE or n % _TILE):
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        if layer is not None:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((w.shape[0] * e,), jnp.int32), group_sizes, (layer * e,)
            )
            w = w.reshape(-1, k, n)
        return gmm(
            xs, w, group_sizes, preferred_element_type=xs.dtype,
            tiling=_gmm_tiling(m, k, n),
        )
    return jax.lax.ragged_dot(xs, _of_layer(w, layer), group_sizes)


# Expert-capacity dispatch (tp-sharded prefill): per-LOCAL-expert row budget
# C = ceil(EP_CAPACITY_FACTOR * n * top_k / E_total). Expected load per expert
# is n*k/E, so 2.0 gives 2x headroom before any token-expert assignment is
# DROPPED (the token loses that expert's weighted contribution — the standard
# capacity-factor trade; routing remains exact for every kept assignment).
# Raise for drop-free-but-slower, lower for tighter compute. Static shapes by
# construction, which is what lets tp-sharded prefill run FLOPs ∝ k/tp
# instead of the dense all-experts combine.
EP_CAPACITY_FACTOR = 2.0
# The chunk width from which a ``--tp`` chunk is a PREFILL chunk and takes
# the buckets (what ``GROUPED_MIN_TOKENS`` was when it decided this too): a
# decode step, one token a lane, must not drop.
EP_CAPACITY_MIN_CHUNK = 8


def _capacity_dispatch(
    x: jnp.ndarray,  # [b, t, h]
    topv: jnp.ndarray,  # [b, t, k] combine weights of the chosen
    topi: jnp.ndarray,  # [b, t, k] the chosen, among the ranked
    n_ranked: int,
    w_gate, w_up, w_down,  # [e_local, ...]
    e_local: int,
    offset: jnp.ndarray,  # the first held expert among the ranked
    valid: jnp.ndarray | None = None,  # [b, t] bool; False = pad slot
) -> jnp.ndarray:
    """Capacity-bucketed expert dispatch for tp-sharded prefill.

    Each shard gathers up to C routed rows PER LOCAL EXPERT into a static
    [e_local * C, h] buffer (overflow assignments drop), runs the expert
    SwiGLUs as uniform batched einsums, and scatter-adds the weighted
    results back — a PARTIAL sum over the tp axis (block_finish psums).
    Shard FLOPs: e_local * C ~= EP_CAPACITY_FACTOR * n * k / tp rows of MLP
    — ∝ k/tp, where the dense combine pays n * E/tp (E/(k*cf)x more).
    """
    b, t, h = x.shape
    top_k = topi.shape[-1]
    n = b * t
    nk = n * top_k
    cap = max(1, -(-int(EP_CAPACITY_FACTOR * nk) // n_ranked))

    eid = topi.reshape(nk) - offset  # local expert id; out of [0, e_local) = remote
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    wts = topv.reshape(nk)
    # Remote assignments sort past every local group (stable sort keeps
    # arrival order within an expert — "first come, first served" capacity).
    # PAD slots (left-padded lockstep batches) are excluded the same way:
    # their garbage hidden states routed en masse would otherwise consume
    # capacity AHEAD of real tokens (pads sit at the row FRONT) and evict
    # real contributions.
    local = (eid >= 0) & (eid < e_local)
    if valid is not None:
        local &= jnp.repeat(valid.reshape(n), top_k)
    sort_key = jnp.where(local, eid, e_local)
    order = jnp.argsort(sort_key, stable=True)
    eid_s, tok_s, wts_s = sort_key[order], tok[order], wts[order]
    # Rank within the expert group: position minus the group's first index.
    rank = jnp.arange(nk, dtype=jnp.int32) - jnp.searchsorted(
        eid_s, eid_s, side="left"
    ).astype(jnp.int32)
    keep = (eid_s < e_local) & (rank < cap)
    buf_pos = jnp.where(keep, eid_s * cap + rank, e_local * cap)  # OOB drops
    xs = jnp.zeros((e_local * cap, h), x.dtype).at[buf_pos].set(
        x.reshape(n, h)[tok_s], mode="drop"
    )
    xs = xs.reshape(e_local, cap, h)
    g = jax.nn.silu(_qeinsum("ech,ehi->eci", xs, w_gate))
    u = _qeinsum("ech,ehi->eci", xs, w_up)
    y = _qeinsum("eci,eih->ech", g * u, w_down).reshape(e_local * cap, h)
    # Gather each kept assignment's result (dropped ones read the zero pad).
    y_pad = jnp.concatenate([y, jnp.zeros((1, h), y.dtype)], axis=0)
    y_slot = y_pad[jnp.minimum(buf_pos, e_local * cap)]
    out = jnp.zeros((n, h), y.dtype).at[tok_s].add(
        y_slot * wts_s[:, None].astype(y.dtype)
    )
    return out.reshape(b, t, h).astype(x.dtype)


def _row_budget(nk: int, e_local: int, n_ranked: int) -> int:
    """Sorted rows the grouped products and the combine are given, of ``nk``
    assignments. A share sees ``e_local / n_ranked`` of them on average, and
    gather, products and combine pay for every row they are given (the
    combine is rows x tokens x hidden): with fewer experts held than ranked
    the rows are cut to four times that mean, in whole tiles, and a dispatch
    whose held assignments overrun it (a skewed router) takes all rows
    instead. Either way every held assignment is computed."""
    return -(-4 * nk * e_local // (n_ranked * _TILE)) * _TILE


def _grouped_dispatch(
    x: jnp.ndarray,  # [b, t, h]
    topv: jnp.ndarray,  # [b, t, k]
    topi: jnp.ndarray,  # [b, t, k]
    n_ranked: int,
    w_gate, w_up, w_down,  # [e_local, ...]
    e_local: int,
    offset,  # the first held expert among the ranked (int or traced)
    valid: jnp.ndarray | None = None,  # [b, t] bool; False = not a token
    layer=None,  # the weights are a run's stacks: the layer to use
) -> jnp.ndarray:
    """The drop-free grouped path over the HELD experts: every assignment
    that lands on one of them is a row of its group; the others (absent
    experts, pad slots, dead lanes) sort past the last group, multiply
    nothing and add nothing."""
    b, t, h = x.shape
    top_k = topi.shape[-1]
    n, nk = b * t, b * t * top_k
    eid = topi.reshape(nk) - offset
    held = (eid >= 0) & (eid < e_local)
    if valid is not None:
        held &= jnp.repeat(valid.reshape(n), top_k)
    key = jnp.where(held, eid, e_local).astype(jnp.int32)
    # Whole row tiles, so that any number of tokens takes the TPU's grouped
    # kernel: the filling belongs to no expert, like an absent expert's rows
    # (its token index lies past the last token: what a gather returns there
    # is selected away below, and the combine's one-hot row is zero).
    rows_all = -(-nk // _TILE) * _TILE
    key = jnp.concatenate([key, jnp.full((rows_all - nk,), e_local, jnp.int32)])
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    tok_s = (order // top_k).astype(jnp.int32)
    wts_s = jnp.where(key_s < e_local, topv.reshape(nk)[order], 0.0)
    eid_s = jnp.minimum(key_s, e_local - 1)  # a quantised scale's row
    group_sizes = jnp.bincount(key_s, length=e_local + 1)[:e_local].astype(
        jnp.int32
    )
    x_flat = x.reshape(n, h)

    def experts(rows: int) -> jnp.ndarray:
        """The first ``rows`` sorted assignments through their experts
        (every held assignment is among them), summed into their tokens."""
        xs = x_flat[tok_s[:rows]]  # [rows, hidden], sorted by held expert
        with jax.named_scope("moe_experts_grouped"):
            g = jax.nn.silu(_ragged(xs, w_gate, group_sizes, eid_s[:rows], layer))
            u = _ragged(xs, w_up, group_sizes, eid_s[:rows], layer)
            y = _ragged(g * u, w_down, group_sizes, eid_s[:rows], layer)
        # Rows past the last group belong to no expert: whatever the grouped
        # product left there is selected away, not multiplied by zero.
        y = jnp.where((key_s[:rows] < e_local)[:, None], y, 0)
        # The combine is a product with the [rows, tokens] matrix of routing
        # weights, not a scatter-add: the TPU scatters a row at a time (26 ms
        # for 4096 rows of 7680 where this takes 3.5; PERF.md, PR 32).
        place = jax.nn.one_hot(tok_s[:rows], n, dtype=y.dtype) * (
            wts_s[:rows, None].astype(y.dtype)
        )
        return jnp.einsum(
            "rn,rh->nh", place, y, preferred_element_type=jnp.float32
        ).astype(y.dtype)

    budget = _row_budget(nk, e_local, n_ranked)
    if budget >= rows_all:
        out = experts(rows_all)
    else:
        out = jax.lax.cond(
            jnp.sum(group_sizes) <= budget,
            lambda: experts(budget), lambda: experts(rows_all),
        )
    return out.reshape(b, t, h).astype(x.dtype)


def moe_swiglu(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    w_gate,
    w_up,
    w_down,
    top_k: int,
    tp_axis: str | None = None,
    norm_topk: bool = True,
    valid: jnp.ndarray | None = None,
    dispatch: str = "auto",
    scoring: str = "softmax",
    scale: float = 1.0,
    expert_offset: int = 0,
    with_counts: bool = False,
    layer=None,
    router_bias: jnp.ndarray | None = None,
    n_group: int = 1,
    topk_group: int = 1,
):
    """Routed SwiGLU over the stacked experts held here.

    Args:
      x: [batch, chunk, hidden] (post-norm activations).
      router_w: [hidden, E]: every expert the router ranks; REPLICATED
        under tp.
      w_gate/w_up: [e_local, hidden, inter]; w_down: [e_local, inter,
        hidden]: the experts held here, ``e_local <= E`` (the tp shard axis,
        or one rank's share of an expert-parallel deployment).
      top_k: experts combined per token (config.num_experts_per_tok).
      tp_axis: mesh axis name when running inside shard_map with sharded
        experts: the held experts then start at ``axis_index * e_local``
        and the result is a PARTIAL sum (caller psums, matching the
        dense-MLP row-parallel convention in block_finish).
      layer: None, or a traced index: ``w_gate``/``w_up``/``w_down`` are then
        the stacks of a RUN of layers [n_layers, e_local, ...] and this is the
        one to use. A model whose layer scan would hand a Pallas kernel one
        layer's slice passes the run whole (``_ragged`` says why).
      expert_offset: where the held experts start among the ranked when
        ``tp_axis`` is None (config.expert_offset; 0 with the whole model).
      norm_topk / scoring / scale / router_bias / n_group / topk_group:
        ``route_topk_select``'s.
      valid: optional [batch, chunk] bool. False marks slots that are no
        token (left pads, dead lanes): their assignments take no expert's
        rows or capacity; their own outputs are garbage nobody reads.

    ``dispatch`` = "dense" forces the drop-free dense combine whatever the
    tokens: REQUIRED for speculative verify chunks under tp (the capacity
    path may drop expert contributions, and greedy speculation promises
    byte-exact streams; runtime/batch_backend.py's tp verify ops set this);
    "grouped" the grouped path; "auto" (default) is ``dispatch_path``'s rule.

    Returns [batch, chunk, hidden] in x's dtype (partial under tp, or of a
    share); with ``with_counts`` a pair of that and ``held_counts``' int32
    [4] of this call.
    """
    if dispatch not in ("auto", "dense", "grouped"):
        raise ValueError(f"unknown MoE dispatch {dispatch!r}")
    # Expert stacks are never int4 (quantize_layer_tree keeps them int8 under
    # mode="int4" — the documented mixed mode); guard hand-built trees HERE,
    # ahead of every dispatch branch (dense einsum, ragged_dot, capacity).
    if any(
        isinstance(w, (Quant4Weight, QuantS4Weight))
        for w in (w_gate, w_up, w_down)
    ):
        raise TypeError(
            "MoE expert stacks do not support int4; use "
            "quantize_layer_tree(mode='int4') which keeps experts int8"
        )
    e_local = (
        w_gate.w if isinstance(w_gate, (QuantWeight, Quant4Weight)) else w_gate
    ).shape[-3]
    logits = router_logits(x, router_w)  # [b, t, E] float32
    b, t, h = x.shape
    n_ranked = logits.shape[-1]
    topv, topi = route_topk_select(
        logits, top_k, norm_topk, scoring, scale,
        bias=router_bias, n_group=n_group, topk_group=topk_group,
    )
    offset = (
        expert_offset if tp_axis is None
        else jax.lax.axis_index(tp_axis) * e_local
    )
    how = dispatch_path(b * t, t, top_k, n_ranked, tp_axis is not None, dispatch)
    if how != "dense":
        path = (
            _capacity_dispatch  # (--tp prefill: one layer's stacks)
            if how == "capacity"
            else functools.partial(_grouped_dispatch, layer=layer)
        )
        out = path(
            x, topv, topi, n_ranked, w_gate, w_up, w_down, e_local, offset,
            valid=valid,
        )
    else:
        out = _dense_combine(
            x, topv, topi, w_gate, w_up, w_down, e_local, offset, valid, layer
        )
    if with_counts:
        return out, held_counts(topi, e_local, offset, valid)
    return out


# WHERE THE TIMINGS ARE (ms a sparse layer alone on the clock, the run's stacks
# with a layer index, ``ops/pallas/check.timed_expert_layer``):
#
#   * A share of a large set: ``pangu-ultra-ep16-chat-closed``'s layer, 16 held
#     experts of 256 ranked, 8 a token (PERF.md section 6, PR 32). The dense
#     combine reads all 16 whatever the routing and takes 2.19 to 2.30 at any
#     of these sizes; the grouped path reads the experts somebody chose: 0.18
#     at 1 token, 0.25 at 2, 0.40 at 4, 0.67 at 8, 0.96 at 16, 1.38 at 32,
#     2.14 at 64 (11 of the 16 touched).
#   * A whole small set: ``lfm2-8b-a1b-chat-closed``'s layer, 32 held of 32, 4
#     a token. Every expert is touched from some thirty rows on, so both paths
#     read all 704 MB (0.86 ms at the HBM peak): the grouped path 1.180 to
#     1.189 at 36 to 64 rows alike, the dense combine 0.980 to 0.990 (PR 48);
#     over the sizes (PR 50, every third row dead through ``valid``), dense
#     against grouped: 0.983 against 0.809 at 16 rows, 0.983 against 0.949 at
#     32, 0.993 against 1.185 at 64, 0.996 against 1.249 at 128, 1.154 against
#     1.313 at 256 (all rows live: 0.988 against 0.917, 0.981 against 1.205,
#     0.990 against 1.267, 1.163 against 1.363 at 16, 64, 128, 256; at 512 the
#     grouped path 1.582 and the dense combine about 2): the dense combine's
#     time is the stream's up to a tile of rows, its products show from 256 on
#     and it has lost by 512. (Pangu's layer again: 2.147 against 1.652 at 64
#     rows, 2.113 against 2.288 at 128, 2.302 against 2.504 at 256.)
#
# The two conditions (``DENSE_MAX_UNTOUCHED``, ``DENSE_MAX_TOKENS``) at the
# cells' shapes: LFM2's decode chunk (64 rows, 4 of 32) leaves 0.02% of the
# held untouched; Pangu's (64 rows, 8 of 256) 13%, and its 128-slot join 1.7%;
# Laguna's (32 rows, 10 of 256) 28%; DeepSeek's (16 rows, 8 of 256) 60%; every
# other join and prefill of theirs and of LFM2's is wider than a tile.
# Mixtral's 2 of 8 passes from 17 rows. Up to a tile of rows the dense combine
# multiplies no more than the grouped kernel, which multiplies a whole tile
# for every expert it visits, and ``rows`` operations a byte stay at half the
# chip's ridge (197 TFLOP/s over 819 GB/s: 240).
def dispatch_path(
    tokens: int, chunk: int, top_k: int, n_ranked: int,
    tp: bool = False, dispatch: str = "auto",
) -> str:
    """THE rule: "dense", "grouped" or "capacity" for a dispatch of
    ``tokens`` = batch * ``chunk`` rows that chooses ``top_k`` of
    ``n_ranked`` experts (``tp``: inside a ``--tp`` shard_map). Pure, of
    static shapes and this module's constants alone: ``moe_swiglu`` asks it
    while it traces and the account of ``/stats`` engine.moe asks it again
    for the program it launched. How many experts are held does not enter:
    an expert stays untouched with the same probability wherever it lies, and
    both paths' work grows alike with the number held."""
    if dispatch != "auto":
        return dispatch
    if tokens < GROUPED_MIN_TOKENS:
        return "dense"
    if tp and chunk >= EP_CAPACITY_MIN_CHUNK:
        return "capacity"  # a --tp prefill chunk
    if (
        GROUPED_MIN_TOKENS
        and tokens <= DENSE_MAX_TOKENS
        and (1 - top_k / n_ranked) ** tokens <= DENSE_MAX_UNTOUCHED
    ):
        return "dense"
    return "grouped"


def _dense_combine(
    x: jnp.ndarray,  # [b, t, h]
    topv: jnp.ndarray,  # [b, t, k]
    topi: jnp.ndarray,  # [b, t, k]
    w_gate, w_up, w_down,  # [e_local, ...], or a run's stacks with ``layer``
    e_local: int,
    offset,  # the first held expert among the ranked (int or traced)
    valid: jnp.ndarray | None = None,  # [b, t] bool; False = not a token
    layer=None,
) -> jnp.ndarray:
    """Every held expert's SwiGLU on every row as batched einsums, combined
    under the routing weights (zero where the expert was not chosen, or is
    not held, or the row is no token: its result is zero, as the grouped
    path's is) in float32. A row's result is its own. (The weight
    applied to ``g * u`` and one contraction over expert and inter into
    ``w_down``, which never makes [b, t, e, h], was timed beside this: 0.980
    for 0.981 ms at 64 rows, 1.002 for 0.990 at 128, 1.081 for 1.163 at 256,
    1.992 at 512; it is not taken: the rule stops at 128.)"""
    onehot = jax.nn.one_hot(topi - offset, e_local, dtype=jnp.float32)
    weights = jnp.einsum("...k,...ke->...e", topv, onehot)
    if valid is not None:
        weights = jnp.where(valid[..., None], weights, 0.0)
    with jax.named_scope("moe_experts_dense"):
        g = jax.nn.silu(_qeinsum("bth,ehi->btei", x, _of_layer(w_gate, layer)))
        u = _qeinsum("bth,ehi->btei", x, _of_layer(w_up, layer))
        y = _qeinsum("btei,eih->bteh", g * u, _of_layer(w_down, layer))
    return jnp.einsum(
        "bteh,bte->bth", y, weights.astype(y.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def held_counts(
    topi: jnp.ndarray, e_local: int, offset, valid: jnp.ndarray | None
) -> jnp.ndarray:
    """int32 [4] of one routed dispatch: assignments routed (of tokens that
    are tokens), assignments to HELD experts, held experts with at least one
    (what the step had to read), the largest load of one held expert."""
    live = (
        jnp.ones(topi.shape[:-1], bool) if valid is None
        else valid.reshape(topi.shape[:-1])
    )
    onehot = jax.nn.one_hot(topi - offset, e_local, dtype=jnp.int32)
    load = jnp.sum(onehot * live[..., None, None], axis=tuple(range(topi.ndim)))
    routed = jnp.sum(live) * topi.shape[-1]
    return jnp.stack([
        routed.astype(jnp.int32), jnp.sum(load), jnp.sum(load > 0), jnp.max(load),
    ]).astype(jnp.int32)
