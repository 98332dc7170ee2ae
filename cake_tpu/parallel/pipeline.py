"""In-slice pipeline parallelism: topology stages -> mesh devices -> ppermute chain.

This is the TPU-native replacement for the reference's per-token master<->worker TCP
round trips (llama.rs:95-114 -> client.rs:117-126 -> worker.rs:190-251). The entire
token step — embedding, every pipeline stage, final norm and LM head — is ONE jitted
SPMD computation over a `jax.sharding.Mesh` with a "stage" axis:

  * Each mesh device holds the stacked params and KV cache of its contiguous block
    range (the topology's stage plan, parallel/topology.py).
  * Inside `shard_map`, a `fori_loop` walks the stages: at iteration i only the
    device whose `axis_index == i` runs its block range (`lax.cond` keeps the
    non-active branch free at runtime), then the activation rotates to the next
    device with `lax.ppermute` over ICI.
  * Ragged topologies are handled by padding every stage to the max layer count
    with inert layers (a per-layer valid mask gates their writes), so the SPMD
    program is identical on every device.

Per-token cost: sum of per-stage compute + S ICI hops — the same sequential
pipeline discipline as the reference, but with ~µs collective-permute hops instead
of ~ms TCP round trips, and zero host involvement per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import KVCache, init_cache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.fused import FusedDecodeCapability
from cake_tpu.ops.rope import model_rope_tables
from cake_tpu.parallel.tensor import (
    TP_AXIS,
    checked_shard_map,
    layer_partition_specs,
    validate_tp,
)

STAGE_AXIS = "stage"


def place_stage_model(config, params, boundaries, mesh, tp: int):
    """Place a model for pipeline (x tp) parallelism: stage-stacked padded
    layer shards + valid mask + replicated head. Shared by PipelineRunner
    and the serving engine's PipelineBatchBackend so their placements cannot
    diverge.

    Returns (layer_specs, stage_params, valid, head_params, l_pad)."""
    from cake_tpu.ops.fuse import fuse_layer_tree
    from cake_tpu.parallel.multihost import shard_put
    from cake_tpu.parallel.tensor import host_staging, put_layer_params

    # Fuse QKV / gate|up before stacking (ops/fuse.py): concat rides the
    # leading [S, L_pad] axes, and shard-major column order composes with the
    # tp column split exactly as in place_tp_model. In host memory, the
    # regrouping (a second copy of every layer) and the placement alike
    # (see place_tp_model).
    with host_staging():
        stacked, valid = pad_stages(
            fuse_layer_tree(params["layers"], tp=tp), boundaries
        )
        layer_specs = layer_partition_specs(
            (STAGE_AXIS, None), tp=tp > 1, params=stacked
        )
        stage_params = put_layer_params(stacked, mesh, layer_specs)
        valid_arr = shard_put(np.asarray(valid), mesh, P(STAGE_AXIS))
        head_params = {
            # tree.map reaches QuantWeight leaves (quantized lm_head) too.
            k: jax.tree.map(lambda a: shard_put(a, mesh, P()), w)
            for k, w in {
                "embed": params["embed"],
                "ln_f": params["ln_f"],
                **(
                    {}
                    if config.tie_word_embeddings
                    else {"lm_head": params["lm_head"]}
                ),
            }.items()
        }
    return layer_specs, stage_params, valid_arr, head_params, valid.shape[1]




def pad_stages(
    layers: M.Params, boundaries: list[tuple[int, int]]
) -> tuple[M.Params, np.ndarray]:
    """Regroup stacked layer params [n_layers, ...] into [S, L_pad, ...] + valid mask.

    ``boundaries`` is the ordered list of (lo, hi) block ranges from the topology
    stage plan. Stages shorter than the longest are padded with zero layers that a
    [S, L_pad] valid mask disables. int8-quantized leaves (ops/quant.QuantWeight)
    regroup their weight and scale arrays independently (padded scales are zero —
    inert, like the padded weights they would multiply).
    """
    s = len(boundaries)
    l_pad = max(hi - lo for lo, hi in boundaries)
    valid = np.zeros((s, l_pad), bool)

    def regroup(w):
        stage_arrs = []
        for i, (lo, hi) in enumerate(boundaries):
            n = hi - lo
            valid[i, :n] = True
            chunk = w[lo:hi]
            if n < l_pad:
                pad_width = [(0, l_pad - n)] + [(0, 0)] * (chunk.ndim - 1)
                chunk = jnp.pad(chunk, pad_width)
            stage_arrs.append(chunk)
        return jnp.stack(stage_arrs)

    # QuantWeight leaves are pytrees: tree.map regroups w and scale alike.
    return {k: jax.tree.map(regroup, w) for k, w in layers.items()}, valid


class PipelineRunner(FusedDecodeCapability):
    """Owns the sharded params/cache and the single-jit pipelined step.

    ``boundaries`` must cover [0, num_hidden_layers) contiguously — exactly what
    ``Topology.stage_plan`` produces. One mesh device per stage.

    Fused decode (decode_chunk, via FusedDecodeCapability) scans the whole
    shard_mapped pipeline step N tokens per dispatch: every ppermute hop of
    every token rides ICI inside ONE compiled computation — N * n_stages hops,
    zero host round trips.
    """

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        boundaries: list[tuple[int, int]],
        *,
        tp: int = 1,
        mesh: Mesh | None = None,
        batch_size: int = 1,
        max_seq_len: int | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
    ):
        self.config = config
        self.n_stages = len(boundaries)
        self.boundaries = boundaries
        if boundaries[0][0] != 0 or boundaries[-1][1] != config.num_hidden_layers:
            raise ValueError(f"stage boundaries {boundaries} do not cover the model")
        for (_, a), (b, _) in zip(boundaries, boundaries[1:]):
            if a != b:
                raise ValueError(f"stage boundaries {boundaries} not contiguous")
        if tp > 1:
            validate_tp(config, tp)

        if mesh is None:
            need = self.n_stages * tp
            devs = jax.devices()
            if len(devs) < need:
                raise ValueError(
                    f"{self.n_stages} stages x tp={tp} need {need} devices, "
                    f"have {len(devs)}"
                )
            mesh = Mesh(
                np.array(devs[:need]).reshape(self.n_stages, tp),
                (STAGE_AXIS, TP_AXIS),
            )
        self.mesh = mesh
        self.tp = tp
        self._max_seq = int(max_seq_len or config.max_position_embeddings)
        self._batch = batch_size
        self._cache_dtype = cache_dtype

        # shard_put placement (not device_put) so the same code serves
        # multihost meshes (parallel/multihost.py): each process materializes
        # only the index slices its local devices own.
        (
            self._layer_specs,
            self.stage_params,
            self.valid,
            self.head_params,
            self.l_pad,
        ) = place_stage_model(config, params, boundaries, mesh, tp)
        # KV [S, L_pad, b, n_kv, s, hd]: stage axis + kv heads over tp.
        self._kv_spec = P(STAGE_AXIS, None, None, TP_AXIS if tp > 1 else None)
        # RoPE tables are built HERE, outside any trace: _pipe_for may be hit
        # lazily inside a jit trace, and arrays created there would leak as
        # tracers into the cached closure.
        self._rope = model_rope_tables(config, self._max_seq)
        self._pipes: dict[bool, object] = {}
        self._step_jit = jax.jit(
            self._step_impl,
            static_argnames=("cached_prefill",),
            donate_argnames=("kv",),
        )
        self.reset()

    @property
    def max_seq_len(self) -> int:
        return self._max_seq

    def reset(self) -> None:
        kv = init_cache(
            self.n_stages * self.l_pad,
            self._batch,
            self._max_seq,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self._cache_dtype,
        )
        from cake_tpu.parallel.multihost import shard_put

        # No np.asarray here: shard_put's single-process branch device_puts
        # the on-device zeros directly (its multihost branch hosts-copies
        # internally) — a host round trip of the KV would dominate reset.
        self._kv = KVCache(
            k=shard_put(
                kv.k.reshape(self.n_stages, self.l_pad, *kv.k.shape[1:]),
                self.mesh,
                self._kv_spec,
            ),
            v=shard_put(
                kv.v.reshape(self.n_stages, self.l_pad, *kv.v.shape[1:]),
                self.mesh,
                self._kv_spec,
            ),
        )

    # ------------------------------------------------------------------ step

    def _pipe_for(self, cached_prefill: bool):
        """One shard_mapped pipeline per static attention variant (plain
        prefill/decode vs. chunked-prefill continuation)."""
        if cached_prefill not in self._pipes:
            self._pipes[cached_prefill] = self._build_pipeline(cached_prefill)
        return self._pipes[cached_prefill]

    def _build_pipeline(self, cached_prefill: bool = False):
        """Build the shard_mapped stage loop: stage-local compute + ppermute."""
        cfg = self.config
        n = self.n_stages
        tp_axis = TP_AXIS if self.tp > 1 else None
        cos, sin = self._rope
        perm = [(j, (j + 1) % n) for j in range(n)]
        layer_block_specs = self._layer_specs

        def body(stage_params, valid, x, kv, pos):
            # Everything here sees its own (stage, tp) shard: params
            # [1, L_pad, ...] with heads/intermediate divided by tp, kv
            # [1, L_pad, ...] likewise, x replicated [b, chunk, hidden].
            stage = jax.lax.axis_index(STAGE_AXIS)
            local_params = jax.tree.map(lambda a: a[0], stage_params)
            local_valid = valid[0]
            local_kv = KVCache(k=kv.k[0], v=kv.v[0])

            def run(x, kv_in):
                return M.blocks_forward(
                    local_params, x, kv_in, cos, sin, pos, cfg,
                    valid=local_valid, tp_axis=tp_axis,
                    cached_prefill=cached_prefill,
                )

            def skip(x, kv_in):
                return x, kv_in

            def loop(i, carry):
                x, kv_c = carry
                # The stage predicate is uniform across the tp axis, so run's
                # tp psums stay collective-consistent inside the cond.
                x, kv_c = jax.lax.cond(i == stage, run, skip, x, kv_c)
                x = jax.lax.ppermute(x, STAGE_AXIS, perm)
                return x, kv_c

            x, local_kv = jax.lax.fori_loop(0, n, loop, (x, local_kv))
            # After n rotations the finished activation has cycled back to
            # stage 0; it is the only device holding the true output.
            return x, KVCache(k=local_kv.k[None], v=local_kv.v[None])

        kv_body_spec = self._kv_spec
        return checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                layer_block_specs,
                P(STAGE_AXIS),
                P(),
                KVCache(k=kv_body_spec, v=kv_body_spec),
                P(),
            ),
            out_specs=(
                P(STAGE_AXIS),
                KVCache(k=kv_body_spec, v=kv_body_spec),
            ),
        )

    def _step_impl(
        self, head, stage_params, valid, tokens, kv, pos, seq_len,
        cached_prefill=False,
    ):
        cfg = self.config
        x = M.embed_tokens(head, tokens, cfg)
        x_stages, kv = self._pipe_for(cached_prefill)(stage_params, valid, x, kv, pos)
        # x_stages: [n_stages * b, chunk, hidden] stacked over stage shards; the
        # true output lives in stage 0's shard.
        x = x_stages[: tokens.shape[0]]
        return M.head_forward(head, x, seq_len, cfg), kv

    def __call__(self, tokens: np.ndarray, pos: int, seq_len: int) -> np.ndarray:
        from cake_tpu.parallel.multihost import fetch, shard_put

        logits, self._kv = self._step_jit(
            self.head_params,
            self.stage_params,
            self.valid,
            shard_put(np.asarray(tokens, np.int32), self.mesh, P()),
            self._kv,
            shard_put(np.int32(pos), self.mesh, P()),
            shard_put(np.int32(seq_len), self.mesh, P()),
            cached_prefill=M.is_cached_prefill(pos, tokens.shape[1]),
        )
        return fetch(logits)

    def _fused_forward_one(self):
        head, stage_params, valid = self.head_params, self.stage_params, self.valid

        def forward_one(tok, kv, pos):
            return self._step_impl(
                head, stage_params, valid, tok, kv, pos, jnp.int32(1)
            )

        return forward_one

    # ------------------------------------------------- microbatched prefill

    def _build_microbatch_prefill(self, m_count: int, chunk: int):
        """GPipe-schedule prefill: M chunks overlap across the S stages.

        The serialized walk (_build_pipeline) runs ONE chunk through the
        stages while S-1 of them idle — per-token decode's discipline, but
        pure waste for a multi-chunk prompt. Here chunk m runs stage s at
        step t = m + s: at any step up to S chunks are in flight on S
        different stages, so M chunks finish in M + S - 1 stage-steps
        instead of M * S. KV-write ordering is preserved by the schedule
        itself (chunk m-1 ran stage s at step m-1+s, strictly before chunk m
        arrives there), so every chunk's cache-prefix attention sees exactly
        the prefix the serial walk would have written — numerics are
        identical, pinned in tests/test_pipeline.py.

        The activation conveyor is one [b, chunk, hidden] buffer per stage,
        rotated by the same ppermute ring the decode walk uses; stage 0
        injects chunk t while t < M and the completed chunks' activations
        are discarded (mid-prompt logits are never read — the generator's
        bucketed tail chunk, which always exists, produces the first logits
        that matter).
        """
        cfg = self.config
        n = self.n_stages
        tp_axis = TP_AXIS if self.tp > 1 else None
        cos, sin = self._rope
        perm = [(j, (j + 1) % n) for j in range(n)]

        def body(stage_params, valid, x_chunks, kv, pos0):
            stage = jax.lax.axis_index(STAGE_AXIS)
            local_params = jax.tree.map(lambda a: a[0], stage_params)
            local_valid = valid[0]
            local_kv = KVCache(k=kv.k[0], v=kv.v[0])

            def run(x, kv_in, pos):
                return M.blocks_forward(
                    local_params, x, kv_in, cos, sin, pos, cfg,
                    valid=local_valid, tp_axis=tp_axis, cached_prefill=True,
                )

            def skip(x, kv_in, pos):
                return x, kv_in

            def loop(t, carry):
                x_carry, kv_c = carry
                m = t - stage  # the chunk index this stage works on at step t
                x_in = jnp.where(
                    stage == 0,
                    x_chunks[jnp.clip(t, 0, m_count - 1)],
                    x_carry,
                )
                pos = pos0 + jnp.clip(m, 0, m_count - 1).astype(jnp.int32) * chunk
                active = (m >= 0) & (m < m_count)
                # Uniform across the tp axis (active depends on stage only),
                # so run's tp psums stay collective-consistent in the cond.
                y, kv_c = jax.lax.cond(active, run, skip, x_in, kv_c, pos)
                y = jax.lax.ppermute(y, STAGE_AXIS, perm)
                return y, kv_c

            x0 = jnp.zeros_like(x_chunks[0])
            _, local_kv = jax.lax.fori_loop(
                0, m_count + n - 1, loop, (x0, local_kv)
            )
            return KVCache(k=local_kv.k[None], v=local_kv.v[None])

        kv_spec = self._kv_spec
        mapped = checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self._layer_specs, P(STAGE_AXIS), P(),
                KVCache(k=kv_spec, v=kv_spec), P(),
            ),
            out_specs=KVCache(k=kv_spec, v=kv_spec),
        )

        def run_all(head, stage_params, valid, tokens, kv, pos0):
            b, l = tokens.shape
            x = M.embed_tokens(head, tokens, self.config)
            # [b, M*chunk, h] -> [M, b, chunk, h]: the conveyor's feed order.
            x_chunks = jnp.swapaxes(
                x.reshape(b, m_count, chunk, x.shape[-1]), 0, 1
            )
            return mapped(stage_params, valid, x_chunks, kv, pos0)

        return jax.jit(run_all, donate_argnums=(4,))

    def prefill_chunks(self, tokens: np.ndarray, pos0: int, chunk: int) -> None:
        """Prefill M = width/chunk FULL chunks through the pipelined mesh in
        ONE dispatch, chunks overlapped across stages (see
        _build_microbatch_prefill). Logits are not produced — the caller's
        bucketed tail chunk (which always exists, generator._prefill) is the
        first position whose logits are read."""
        b, l = tokens.shape
        if l % chunk:
            raise ValueError(f"width {l} is not a multiple of chunk {chunk}")
        m_count = l // chunk
        cache = getattr(self, "_mb_prefill_cache", None)
        if cache is None:
            from collections import OrderedDict

            cache = self._mb_prefill_cache = OrderedDict()
        key = (m_count, chunk)
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = self._build_microbatch_prefill(m_count, chunk)
            # Bounded: each distinct full-chunk count jits the whole pipeline
            # prefill; varied prompt lengths on a long-lived server must not
            # accumulate executables without end.
            while len(cache) > 8:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        from cake_tpu.parallel.multihost import shard_put

        self._kv = fn(
            self.head_params,
            self.stage_params,
            self.valid,
            shard_put(np.asarray(tokens, np.int32), self.mesh, P()),
            self._kv,
            shard_put(np.int32(pos0), self.mesh, P()),
        )
