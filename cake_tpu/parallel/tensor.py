"""Megatron-style tensor parallelism over a "tp" mesh axis.

The reference has no tensor parallelism (SURVEY.md §2.7: each layer lives wholly
on one device); on TPU, TP over ICI is the natural way to make one layer's
matmuls span chips. Sharding follows the standard 1-D Megatron recipe:

  * wq/wk/wv and w_gate/w_up are column-sharded (heads / intermediate split
    across ``tp``) — each shard computes its heads' attention and its slice of
    the SwiGLU with no communication.
  * wo and w_down are row-sharded — each shard produces a partial sum over the
    hidden dim, reduced with ONE ``psum`` per residual branch
    (models/llama/model.py block_forward's ``tp_axis`` seam).
  * Norms, embedding, and the LM head are replicated; the KV cache shards with
    its kv heads, so cache HBM also scales 1/tp.

The per-shard model code is the SAME pure function as the single-device path —
``block_forward`` infers head counts from the weight shapes — so TP cannot
diverge numerically except through reduction order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import KVCache, init_cache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.fused import FusedDecodeCapability
from cake_tpu.ops.rope import model_rope_tables

TP_AXIS = "tp"


def checked_shard_map(body, **specs):
    """shard_map with replication checking off, shared by every shard_map
    site in parallel/ and runtime/batch_backend.py."""
    return shard_map(body, check_vma=False, **specs)


def host_staging():
    """Context in which a model tree is built in HOST memory: arrays made
    inside it live on JAX's CPU backend, so loading, fusing and stage-padding
    a model that is about to be sharded never puts a whole copy on device 0
    (where it would sit beside that chip's own shard, or not fit at all).
    Placement then hands every chip its shard once, straight from the host.
    Needs the CPU backend, which JAX always starts unless ``JAX_PLATFORMS``
    names platforms without it."""
    return jax.default_device(jax.local_devices(backend="cpu")[0])


def place_tp_model(config: "LlamaConfig", params, mesh: Mesh):
    """Place a model for 1-D tensor parallelism: sharded layer stack +
    replicated head/embed. Shared by TensorParallelRunner and the serving
    engine's TPBatchBackend so their placements cannot diverge.

    QKV and gate/up are fused at prep time (ops/fuse.py) with SHARD-MAJOR
    column order, so the contiguous 1/tp column split below hands each shard
    exactly its heads' q/k/v (resp. its intermediate slice) — placement-
    identical to sharding the unfused weights.

    Returns (layer_specs, layer_params, head_params)."""
    from cake_tpu.ops.fuse import fuse_layer_tree

    # Fusing AND placing: device_put cuts an uncommitted array into shards
    # with a jitted slice, which runs on the default device — outside this
    # context that is chip 0, and the whole tree would pass through it.
    with host_staging():
        layers = fuse_layer_tree(params["layers"], tp=mesh.shape[TP_AXIS])
        layer_specs = layer_partition_specs(params=layers)
        layer_params = put_layer_params(layers, mesh, layer_specs)
        head_params = jax.device_put(
            {
                "embed": params["embed"],
                "ln_f": params["ln_f"],
                **(
                    {}
                    if config.tie_word_embeddings
                    else {"lm_head": params["lm_head"]}
                ),
            },
            NamedSharding(mesh, P()),
        )
    return layer_specs, layer_params, head_params

# Sharding of each stacked layer weight [n_layers, in, out] (model.LAYER_WEIGHTS):
# which non-layer dim is split across tp. None = replicated.
_LAYER_SHARD_DIM = {
    "wq": 2,       # [n, hidden, n_q*hd]    column (heads)
    "wk": 2,       # [n, hidden, n_kv*hd]   column (kv heads)
    "wv": 2,
    "wqkv": 2,     # [n, hidden, (n_q+2*n_kv)*hd] fused, shard-major columns
    "wo": 1,       # [n, n_q*hd, hidden]    row
    "w_gate": 2,   # [n, hidden, inter]     column
    "w_up": 2,
    "w_gu": 2,     # [n, hidden, 2*inter]   fused gate|up, shard-major columns
    "w_down": 1,   # [n, inter, hidden]     row
    "ln_attn": None,
    "ln_mlp": None,
}


def layer_partition_specs(
    leading: tuple[str | None, ...] = (None,), tp: bool = True, params=None
) -> dict[str, P]:
    """PartitionSpecs for the stacked layer tree.

    ``leading`` names the axes ahead of each weight's [in, out] dims — ``(None,)``
    for plain layer stacking, ``(STAGE_AXIS, None)`` for pipeline stage-stacked
    params [S, L_pad, in, out]. ``tp=False`` drops the tensor-parallel sharding
    (leading axes only).

    With ``params`` given, quantized leaves get a matching NamedTuple-of-specs:
    the packed weight shards like the plain weight. int8's per-output-channel
    scale [*leading, 1, out] shards with the out dim for column-parallel
    weights and is REPLICATED for row-parallel ones (its size-1 in dim cannot
    shard — and replication is exact, since ``(x @ w) * scale`` distributes
    over the later tp psum). int4's per-group scale [*leading, G, out] shards
    at the SAME dim position as the packed weight in both orientations: a
    contiguous split of the packed in-axis is a contiguous split of the
    logical in-axis (adjacent nibble pairing), and group boundaries align with
    shard boundaries whenever tp divides G (validated at placement,
    put_layer_params)."""
    from cake_tpu.ops.quant import Quant4Weight, QuantS4Weight, QuantWeight

    if params is not None and any(
        isinstance(l, QuantS4Weight)
        for l in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantS4Weight)
        )
    ):
        raise NotImplementedError(
            "the native-s4 int4 representation (CAKE_INT4_REPR=s4) is "
            "single-chip only; unset it for tp/pipeline serving (packed "
            "Quant4Weight shards group-aligned)"
        )
    out = {}
    moe = params is not None and "router" in params
    shard_dims = dict(_LAYER_SHARD_DIM)
    if moe:
        # Qwen2-MoE shared expert: a dense SwiGLU — standard Megatron
        # column/row sharding over its own intermediate dim; the scalar
        # sigmoid gate weight and the router are replicated (all shards
        # route alike).
        for k, dim in (("sh_gate", 2), ("sh_up", 2), ("sh_gu", 2),
                       ("sh_down", 1), ("se_gate", None), ("router", None)):
            if k in params:
                shard_dims[k] = dim
    for k, dim in shard_dims.items():
        if params is not None and k not in params:
            # A fused tree (ops/fuse.py) drops wq/wk/wv/w_gate/w_up; the spec
            # dict must mirror the params tree exactly (shard_map pytrees).
            continue
        if moe and k in ("w_gate", "w_up", "w_down"):
            # MoE expert weights [*leading, n_experts, in, out]: shard the
            # EXPERT axis (expert parallelism); the int8 scale
            # [*leading, n_experts, 1, out] shards with it.
            spec = P(*leading, TP_AXIS) if tp else P(*leading)
            if isinstance(params.get(k), (QuantWeight, Quant4Weight)):
                out[k] = type(params[k])(w=spec, scale=spec)
            else:
                out[k] = spec
            continue
        if dim is None or not tp:
            # Norm/router/gate weights: leading axes only (replicated).
            spec = P(*leading)
        else:
            s = list(leading) + [None, None]
            s[len(leading) - 1 + dim] = TP_AXIS
            spec = P(*s)
        if params is not None and isinstance(params.get(k), QuantWeight):
            if tp and dim == 1:  # row-parallel: replicated scale
                out[k] = QuantWeight(w=spec, scale=P(*leading))
            else:
                out[k] = QuantWeight(w=spec, scale=spec)
        elif params is not None and isinstance(params.get(k), Quant4Weight):
            # Packed weight and group scale shard at the same dim position
            # (see docstring); row-split needs shard-aligned groups.
            out[k] = Quant4Weight(w=spec, scale=spec)
        else:
            out[k] = spec
    if params is not None:
        # QKV biases (Qwen2 family): [*leading, out] — column-sharded with
        # their projections (the fused ``bqkv`` is shard-major like ``wqkv``),
        # so each shard adds its own bias slice.
        for k in (*M.LAYER_BIASES, "bqkv"):
            if k in params:
                out[k] = P(*leading, TP_AXIS) if tp else P(*leading)
        # Anything else in the layer tree (Gemma-2 extra norms, the win_flag
        # layer metadata) replicates over tp with the leading axes.
        for k in params:
            out.setdefault(k, P(*leading))
    return out


def put_layer_params(layer_params, mesh, specs, put=None):
    """Place the (possibly quantized) layer tree onto ``mesh`` per ``specs``.

    ``specs`` comes from layer_partition_specs(params=...): per-key either a
    PartitionSpec or a QuantWeight/Quant4Weight of specs. ``put`` defaults to
    multihost-safe shard_put (parallel/multihost.py)."""
    from cake_tpu.ops.quant import Quant4Weight, QuantWeight

    if put is None:
        from cake_tpu.parallel.multihost import shard_put as put

    out = {}
    for k, w in layer_params.items():
        spec = specs[k]
        if isinstance(w, Quant4Weight):
            # Row-parallel int4: shard boundaries must land on group
            # boundaries (G % shards == 0 ⟺ aligned, see
            # layer_partition_specs). Fail HERE with the actionable message,
            # not deep inside device_put with a divisibility error. Only the
            # GROUP dim (-2) gets this remedy — out-dim misalignment is a
            # head-geometry problem group_size cannot fix, and jax's own
            # divisibility error covers it like any other weight.
            gdim = w.scale.ndim - 2
            ax = spec.scale[gdim] if gdim < len(spec.scale) else None
            if ax is not None:
                shards = mesh.shape.get(ax, 1)
                if w.scale.shape[gdim] % shards:
                    raise ValueError(
                        f"int4 weight {k!r}: {w.scale.shape[gdim]} scale "
                        f"groups do not divide over {shards} {ax!r}-shards; "
                        "re-quantize with a smaller group_size (or one whose "
                        "group count divides the mesh axis)"
                    )
        if isinstance(w, (QuantWeight, Quant4Weight)):
            out[k] = type(w)(
                w=put(w.w, mesh, spec.w), scale=put(w.scale, mesh, spec.scale)
            )
        else:
            out[k] = put(w, mesh, spec)
    return out


def validate_tp(config: LlamaConfig, tp: int) -> None:
    if config.num_key_value_heads % tp or config.num_attention_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_attention_heads "
            f"{config.num_attention_heads} and num_key_value_heads "
            f"{config.num_key_value_heads}"
        )
    if config.num_local_experts:
        # MoE layers shard the expert axis, not the intermediate dim.
        if config.num_local_experts % tp:
            raise ValueError(
                f"tp={tp} must divide num_local_experts "
                f"{config.num_local_experts}"
            )
        si = config.shared_expert_intermediate_size
        if si and si % tp:
            raise ValueError(
                f"tp={tp} must divide shared_expert_intermediate_size {si}"
            )
    elif config.intermediate_size % tp:
        raise ValueError(
            f"tp={tp} must divide intermediate_size {config.intermediate_size}"
        )


class TensorParallelRunner(FusedDecodeCapability):
    """All layers on every device, heads/intermediate split across a 1-D mesh.

    The ForwardStep-compatible analogue of LocalForwardStep for one model
    replicated in depth but sharded in width. (Depth sharding composes in
    parallel/pipeline.py's 2-D stage x tp mesh.) Fused decode comes from
    FusedDecodeCapability — the tp psums ride inside the scanned step.
    """

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        *,
        tp: int | None = None,
        mesh: Mesh | None = None,
        batch_size: int = 1,
        max_seq_len: int | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
    ):
        if mesh is None:
            devs = jax.devices()
            tp = tp or len(devs)
            if len(devs) < tp:
                raise ValueError(f"tp={tp} needs {tp} devices, have {len(devs)}")
            mesh = Mesh(np.array(devs[:tp]), (TP_AXIS,))
        self.mesh = mesh
        self.tp = mesh.shape[TP_AXIS]
        validate_tp(config, self.tp)
        self.config = config
        self._max_seq = int(max_seq_len or config.max_position_embeddings)
        self._batch = batch_size
        self._cache_dtype = cache_dtype

        self._layer_specs, self.layer_params, self.head_params = place_tp_model(
            config, params, mesh
        )
        # Built outside any trace (see pipeline.py: lazy _step_for may run
        # inside a jit trace; array creation there would leak tracers).
        self._rope = model_rope_tables(config, self._max_seq)
        self._steps: dict[bool, object] = {}
        self._fwd = self._build_forward()
        self.reset()

    @property
    def max_seq_len(self) -> int:
        return self._max_seq

    def reset(self) -> None:
        kv = init_cache(
            self.config.num_hidden_layers,
            self._batch,
            self._max_seq,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self._cache_dtype,
        )
        # KV heads shard with their projections: [n_layers, b, n_kv, s, hd].
        self._kv = jax.device_put(
            kv, NamedSharding(self.mesh, P(None, None, TP_AXIS))
        )

    def _step_for(self, cached_prefill: bool):
        """Un-jitted step per static attention variant (used by both the jitted
        __call__ path and the fused decode scan)."""
        if cached_prefill not in self._steps:
            self._steps[cached_prefill] = self._build_step(cached_prefill)
        return self._steps[cached_prefill]

    def _build_step(self, cached_prefill: bool):
        cfg = self.config
        cos, sin = self._rope
        layer_specs = self._layer_specs
        kv_spec = P(None, None, TP_AXIS)

        def body(head, layers, x, kv, pos, seq_len):
            x, kv = M.blocks_forward(
                layers, x, kv, cos, sin, pos, cfg, tp_axis=TP_AXIS,
                cached_prefill=cached_prefill,
            )
            return M.head_forward(head, x, seq_len, cfg), kv

        mapped = checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), layer_specs, P(), KVCache(k=kv_spec, v=kv_spec), P(), P()),
            out_specs=(P(), KVCache(k=kv_spec, v=kv_spec)),
        )

        def step(head, layers, tokens, kv, pos, seq_len):
            x = M.embed_tokens(head, tokens, cfg)
            return mapped(head, layers, x, kv, pos, seq_len)

        return step

    def _build_forward(self):
        def dispatch(head, layers, tokens, kv, pos, seq_len, cached_prefill=False):
            return self._step_for(cached_prefill)(
                head, layers, tokens, kv, pos, seq_len
            )

        return jax.jit(
            dispatch,
            static_argnames=("cached_prefill",),
            donate_argnames=("kv",),
        )

    def _fused_forward_one(self):
        head, layers = self.head_params, self.layer_params
        step = self._step_for(False)

        def forward_one(tok, kv, pos):
            return step(head, layers, tok, kv, pos, jnp.int32(1))

        return forward_one

    def __call__(self, tokens: np.ndarray, pos: int, seq_len: int) -> np.ndarray:
        logits, self._kv = self._fwd(
            self.head_params,
            self.layer_params,
            jnp.asarray(tokens, jnp.int32),
            self._kv,
            jnp.int32(pos),
            jnp.int32(seq_len),
            cached_prefill=M.is_cached_prefill(pos, tokens.shape[1]),
        )
        return np.asarray(logits)
