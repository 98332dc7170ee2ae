"""Batch execution backends: the device seam under the serving engine.

The reference serves one request at a time behind a global lock
(api/mod.rs:76); runtime/serving.py replaces that with a continuous-batching
engine. This module is the engine's ONE device interface — four operations
(init_kv / prefill / decode / join) over the left-padded lockstep batch
layout (models/llama/batch.py) — with three implementations:

  * ``LocalBatchBackend`` — single-device, full params resident (the round-2
    behavior, now behind the seam).
  * ``TPBatchBackend`` — Megatron tensor parallelism: every batch op runs as
    one ``shard_map`` over a 1-D tp mesh (heads/intermediate split, psums at
    the two partial-sum points), the same sharding recipe as
    parallel/tensor.TensorParallelRunner but over the pad-aware batched
    bodies (batch.batched_blocks_forward).
  * ``PipelineBatchBackend`` — in-mesh pipeline parallelism (optionally
    x tp on a 2-D mesh): the stage-loop + ppermute walk of
    parallel/pipeline.PipelineRunner, again over the pad-aware batched
    bodies with ragged-stage valid masks; decode defaults to the 1F1B
    interleaved microbatch walk (see the class docstring).
  * ``DistributedBatchBackend`` — the TCP topology (master <-> workers over
    StageClient spans): the lockstep layout rides a ``batch`` extension of
    the FORWARD header; workers run the same pad-aware bodies
    (batch.make_lockstep_range_ops) on their ranges.

This is what makes ``--api-batch`` compose with ``--backend mesh``, ``--tp``,
AND ``--backend tcp``: continuous batching and model distribution were
mutually exclusive in round 2 (the engine closed over the local model); now
the engine drives whichever backend owns the devices, token-exactly
(tests/test_serving.py pins engine-over-tp/pipeline against
engine-over-local; tests/test_distributed_batch.py pins the live-cluster
TCP path).

All four share the sampling arithmetic (fused.sample_step) and the batch
layout helpers, so the per-row PRNG/ring/first-token arithmetic exists once
regardless of backend.
"""

from __future__ import annotations

import functools
import uuid
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.batch import (
    _decode_fn,
    _prefill_jit,
    batched_blocks_forward,
    batched_prefill,
    decode_positions,
    make_lockstep_range_ops,
    prefill_positions,
)
from cake_tpu.models.llama.cache import KVCache, init_cache
from cake_tpu.models.llama.paged_cache import PageAllocator
from cake_tpu.models.llama.config import CACHE_KV, LlamaConfig
from cake_tpu.models.llama.fused import sample_step, sampled_decode_scan
from cake_tpu.ops.rope import model_rope_tables
from cake_tpu.parallel.pipeline import STAGE_AXIS, place_stage_model
from cake_tpu.parallel.tensor import (
    TP_AXIS,
    checked_shard_map,
    place_tp_model,
    validate_tp,
)
from cake_tpu.runtime.shapes import ProgramShapes

# Compiled fused-decode scans per (n_steps, sampling knobs): bounded like the
# local path's lru_cache'd _decode_fn — per-request sampling overrides on a
# long-lived server must not leak executables without bound.
_DECODE_CACHE_MAX = 16


class BackendWorkerError(RuntimeError):
    """A backend op failed because a worker (or an injected fault standing in
    for one) died after the retry/replay budget was exhausted.

    The serving engine treats this as a RECOVERABLE serving event, not a bug:
    the epoch's live streams finish with ``finish_reason="error"`` (pages
    released, lanes recycled), already-finished co-batched streams are
    untouched, and the engine keeps serving the queue
    (runtime/serving.py failure isolation). Any other exception still
    surfaces to every consumer as a raised error.
    """

    def __init__(self, node: str, op: str, cause: Exception | None = None):
        super().__init__(
            f"worker {node!r} failed during batch {op} "
            f"({cause if cause is not None else 'fault injected'})"
        )
        self.node = node
        self.op = op


def _note_fusion_kernels(backend, s) -> None:
    """Timeline breadcrumbs for the decode-fusion kernel family (the PR 9
    ``kernel:<op>`` convention): one ``kernel:fused_<name>`` instant per
    enabled fusion at every decode dispatch, carrying the impl the fused
    entry will actually resolve — plus a ONE-TIME ``kernel-fallback``
    flight event when the sampling tail wants pallas but must take the XLA
    sort path (top-p set, or an untileable vocab): a fallback to the
    unfused path is never silent."""
    from cake_tpu.obs.timeline import timeline
    from cake_tpu.ops.fuse import resolve_fusion
    from cake_tpu.ops.pallas.fused_sample_tail import sample_tail_supported
    from cake_tpu.utils import metrics

    fusions, fimpl = resolve_fusion(
        backend.config, getattr(backend, "allow_pallas", True)
    )
    if not fusions:
        return
    # Per-fusion ACTUAL dispatch, not just the resolved wish: a breadcrumb
    # claiming impl=pallas while the twin ran would say a kernel engaged on
    # a config where none can. Norm: the decode sites
    # need a PLAIN 128-lane-tileable projection (quantized trees keep the
    # twin); tail: top_p / untileable vocab take the sort twin
    # (fused.sample_step downgrades through the same sample_tail_supported
    # rule, so note and dispatch cannot drift).
    lp = getattr(backend, "params", {}).get("layers", {})
    wqkv = lp.get("wqkv")
    norm_ok = (
        isinstance(wqkv, jnp.ndarray) and wqkv.shape[-1] % 128 == 0
    )
    impls = {
        "fused_norm_matmul": ("norm", fimpl if norm_ok else "xla"),
        "fused_sample_tail": (
            "tail",
            fimpl
            if sample_tail_supported(backend.config.vocab_size, s.top_p)
            else "xla",
        ),
    }
    for kernel, (name, impl) in impls.items():
        if name not in fusions:
            continue
        if (
            impl != fimpl
            and fimpl == "pallas"
            and not getattr(backend, "_fusion_fallback_noted", False)
        ):
            backend._fusion_fallback_noted = True
            metrics.flight.record(
                "kernel-fallback", op=kernel,
                reason=(
                    "top_p needs the XLA sort path"
                    if kernel == "fused_sample_tail" and s.top_p is not None
                    else "shape not a multiple of the 128-lane tile"
                ),
            )
        timeline.instant(
            f"kernel:{kernel}", track="engine", args={"impl": impl}
        )


def _cache_get_or_build(cache: OrderedDict, key, build):
    fn = cache.get(key)
    if fn is None:
        fn = cache[key] = build()
        while len(cache) > _DECODE_CACHE_MAX:
            cache.popitem(last=False)
    else:
        cache.move_to_end(key)
    return fn


@functools.lru_cache(maxsize=32)
def _local_join_fn(config, width, max_seq_len, cache_dtype):
    """Jit one continuous-batching join: single-row prefill whose prompt ends
    at the epoch's shared slot, scattered wholesale into the free lane's KV
    row (stale lane contents are fully replaced). One compile per window width
    (``shapes.window``)."""

    def run(params, kv, tokens, pads1, ends1, lane):
        kv_row = init_cache(
            config.num_hidden_layers,
            1,
            max_seq_len,
            config.num_key_value_heads,
            config.head_dim,
            cache_dtype,
        )
        logits, kv_row = batched_prefill(
            params, tokens, kv_row, pads1, config, ends=ends1, seq_len=ends1[0]
        )
        k = jax.lax.dynamic_update_slice(kv.k, kv_row.k, (0, lane, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(kv.v, kv_row.v, (0, lane, 0, 0, 0))
        return logits, KVCache(k=k, v=v)

    from cake_tpu.obs.jitwatch import tracked_jit

    return tracked_jit(
        run, name=f"batch.join[w={width}]", module="prefill_join_dense",
        donate_argnums=(1,),
    )


class LocalBatchBackend:
    """Single-device batch ops: the engine's default."""

    shapes = ProgramShapes()  # dense backends: the open instance

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        *,
        max_seq_len: int,
        cache_dtype: jnp.dtype,
    ):
        from cake_tpu.ops.fuse import fuse_params

        self.config = config
        self.params = fuse_params(params)  # ops/fuse.py, column-identical
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype

    def init_kv(self, b: int) -> KVCache:
        return init_cache(
            self.config.num_hidden_layers,
            b,
            self.max_seq_len,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self.cache_dtype,
        )

    def prefill(self, tokens, kv, pads, ends=None):
        # ``ends`` (per-row absolute end slot < width) serves failover
        # migration (runtime/serving.py): live streams' accumulated tokens
        # re-prefill into a window ENDING at the epoch's shared slot.
        kw = {}
        if ends is not None:
            ends = jnp.asarray(ends, jnp.int32)
            kw = {"ends": ends, "seq_len": ends[0]}
        return _prefill_jit(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            self.config, **kw,
        )

    def decode(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        _note_fusion_kernels(self, s)
        fn = _decode_fn(
            self.config, self.max_seq_len, n,
            s.temperature, s.top_k, s.top_p, s.repeat_penalty,
        )
        return fn(
            self.params, kv, tok, jnp.int32(slot), pads, keys, ring, ring_idx
        )

    def join(self, kv, row_tokens, pads1, ends1, lane, start=0):
        assert start == 0, "a dense join computes its row from slot 0"
        fn = _local_join_fn(
            self.config, row_tokens.shape[1], self.max_seq_len, self.cache_dtype
        )
        return fn(
            self.params, kv, jnp.asarray(row_tokens), pads1, ends1,
            jnp.int32(lane),
        )

    # Speculative verify (engine-side batched prompt-lookup decoding): the
    # presence of these two methods is the engine's capability gate.

    def verify_greedy(self, kv, tokens, slot, pads):
        from cake_tpu.models.llama.batch import _verify_greedy_fn

        fn = _verify_greedy_fn(self.config, tokens.shape[1])
        return fn(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            jnp.int32(slot),
        )

    def verify_sampled(self, kv, tokens, slot, pads, drafts, n_drafts, keys, s):
        from cake_tpu.models.llama.batch import _verify_sampled_fn

        fn = _verify_sampled_fn(
            self.config, tokens.shape[1], s.temperature, s.top_k, s.top_p
        )
        return fn(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            jnp.int32(slot), jnp.asarray(drafts),
            jnp.asarray(n_drafts, jnp.int32), keys,
        )


class _PagedBackend:
    """Single-device batch ops over the paged KV pool (``kv_mode="paged"``),
    whatever kind of cache the pages hold: the driver of any cache kind
    through its record (``models/llama/programs.KINDS``, found by
    ``config.cache_kind`` at construction), which says how the cache is
    made, which programs ``prefill`` / ``decode`` / ``join`` dispatch, which
    operands they take and what rides back beside the tokens. It has no
    suffix, verify or copy-on-write operation and takes no prefix cache:
    that absence is what the engine's capability gates read
    (``capability.REFUSED`` says why); plain K and V alone can do those
    (``PagedLocalBackend``). ``paged_backend`` picks between the two.

    Same four-operation seam as LocalBatchBackend, with storage routed
    through a page pool + host-side PageAllocator (models/llama/paged_cache):
    HBM is committed per live page, not per ``batch * max_seq`` strip, so the
    pool can be sized well below the dense footprint and the serving engine
    admits by free pages (runtime/serving.py). The engine owns the allocation
    protocol (map at layout/join, extend at page boundaries, release on
    finish); this backend reads ``self.allocator.block_tables`` at each
    dispatch and ships a COPY of it as a small traced int32 operand. Every
    operation only enqueues: nothing here waits for the device, and the
    engine goes on mapping and releasing pages on the host while the
    program runs, so an operand is what the tables said when the program
    was ENQUEUED (a page released since is recycled only by a program
    enqueued later, behind this one in device order). ``lookahead`` states
    that to the engine: a failed chunk is never redone from pre-chunk
    state here, so its step loop may enqueue one decode chunk ahead of the
    tokens it has read (runtime/serving.py ``_run_epoch``). Which programs
    get compiled is ``self.shapes`` (runtime/shapes.py): the backend owns
    its instance, the engine reads it from here.

    **Bounded capacity** (``set_epoch_capacity``): the serving engine
    computes ONE live capacity per epoch (``shapes.capacity``) — enough
    slots for every admitted row's maximum reach plus a chunk of slack — and
    every dispatch slices the block-table operand to it. Attention grids,
    position masks, and the XLA gather view then cover the live capacity
    instead of the padded ``max_seq`` table width. The capacity is
    deliberately backend STATE set once per epoch, not a per-op argument:
    every cache-enabled prefill (epoch suffix prefill, joins, failover
    re-prefills) MUST run under the same capacity or the bit-identity chain
    across joins and failover breaks at the ulp level on real hardware
    (reduction shapes change with the gather width) — and a per-op "local"
    capacity smaller than the epoch's silently truncates live keys
    (tests/test_paged_prefill.py pins the trap). None = the full table.
    """

    kv_mode = "paged"
    lookahead = 1  # decode chunks the engine may enqueue ahead of its reads

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        *,
        max_seq_len: int,
        cache_dtype: jnp.dtype,
        page_size: int = 128,
        max_pages: int | None = None,
        page_reserve: int = 1,
        allow_pallas: bool = True,
        lanes: int = 0,
    ):
        from cake_tpu.models.llama import programs
        from cake_tpu.ops.fuse import fuse_params, resolve_fusion

        self.config = config
        self.kind = programs.kind_of(config)
        self.cache_kind = self.kind.name  # what the cache holds
        self._prefill_program = programs.prefill_program(self.kind)
        self._join_program = functools.partial(programs.join_program, self.kind)
        self._decode_program = functools.partial(programs.decode_program, self.kind)
        # The most lanes an epoch will have (the engine's ``max_batch``; 0 =
        # not told): only a kind that sizes a pool by the lane reads it.
        self.lanes = lanes
        self.params = fuse_params(params)
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        self.page_size = page_size
        self.pages_per_seq = -(-max_seq_len // page_size)
        # The paged analogue of the dense cache's SEQ_MULTIPLE padding: every
        # position grid sizes to the block-table capacity.
        self.padded_seq = self.pages_per_seq * page_size
        # Default pool = one dense-equivalent 8-lane footprint; servers size
        # it DOWN (that is the capacity win) via ServeConfig.max_pages.
        self.max_pages = max_pages or 8 * self.pages_per_seq
        self.allocator = self._make_allocator(page_reserve)
        self.allow_pallas = allow_pallas
        # Epoch-bounded table capacity in PAGES (None = full table).
        self._cap_pages: int | None = None
        self._fallback_noted = False
        self.shapes = ProgramShapes.for_model(
            config, page_size, self.pages_per_seq
        )
        # Lanes whose recurrent state a prefill or a join wrote, cumulative
        # (``GET /stats`` engine.state.lane_writes).
        self.state_lane_writes = 0
        self.state_decode_dispatches = 0
        self.state_decode_rows = self.state_decode_lanes = 0
        self._state_lanes = 0
        self._fusions = resolve_fusion(config, allow_pallas)[0]
        # What the programs return beside the tokens (``take_chunk_counters``),
        # by ``/stats`` section. The engine's ``accounts()`` reads the PRESENCE
        # of ``moe_facts`` and ``sparse_facts`` (runtime/serving.py): a kind
        # whose programs count neither reports neither.
        self._accounts = {a.section: a(config) for a in self.kind.accounts_of(config)}
        self._traced = any(a.keeps_traced for a in self._accounts.values())
        # (the engine adds its own two counts: ``DiffusionAccount.note``)
        self.diffusion = self._accounts.get("diffusion")
        self._chunk_counters = None
        for section in self._accounts:
            setattr(self, f"{section}_facts",
                    functools.partial(self._account_facts, section))
        self._note_cache()

    def _make_allocator(self, page_reserve: int):
        """One pool, one allocator; for a model whose attention layers are of
        more than one kind a ``PagePools``, an allocator and a block table a
        kind behind the one interface the engine drives. ``max_pages`` sizes
        the FIRST kind's pool, ``programs.pool_pages`` a windowed kind's."""
        def allocator(pages, **kw):
            return PageAllocator(
                pages, self.page_size, batch=1,
                max_pages_per_seq=self.pages_per_seq, **kw,
            )

        if not self.kind.pools_by_kind:
            return allocator(self.max_pages, reserve_pages=page_reserve)
        from cake_tpu.models.llama.paged_cache import PagePools
        from cake_tpu.models.llama.programs import pool_pages

        c = self.config
        return PagePools({
            kind: allocator(
                pages, window=c.kind_window(kind),
                reserve_pages=page_reserve if c.kind_window(kind) is None else 0,
            )
            for kind, pages in zip(c.attention_kinds, pool_pages(
                c, self.max_pages, self.page_size, self.lanes), strict=True)
        })

    # --------------------------------------------------- kernel dispatch

    def kernel_impl(self) -> str:
        """Which attention impl the paged prefill/verify family will use:
        "pallas" iff the resolved attention_impl wants it AND the pool
        layout supports the kernels (page = whole lane tiles)."""
        from cake_tpu.ops.pallas.paged_prefill import paged_kernel_supported

        wants = (
            self.allow_pallas
            and M.resolve_attention_impl(self.config.attention_impl)
            == "pallas"
        )
        if not wants:
            return "xla"
        if not paged_kernel_supported(self.page_size):
            return "fallback"
        return "pallas"

    def pool_write(self) -> str:
        """Which form a layer's write into the pool takes, by the same rule
        as the reads where the kernel is compiled and not interpreted:
        "pallas" (ops/pallas/paged_write.py: slabs by DMA) or "xla" (a
        scatter of rows). ``GET /stats`` engine.cache.pool_write."""
        from cake_tpu.ops.pallas.paged_write import compiled_here

        kernel = (
            self.kind.pool_write_kernel and self.kernel_impl() == "pallas"
            and compiled_here()
        )
        return "pallas" if kernel else "xla"

    def _kernel_note(self, op: str, end_slot: int) -> None:
        """Every paged dispatch starts here. A timeline breadcrumb (which
        kernel path engaged), a ONE-TIME ``kernel-fallback`` flight event when a paged path silently
        downgrades to XLA (attention_impl wanted pallas, pool layout says
        no: the pool's write and the attention reads alike), and the write
        bound: a write past the sliced table would DROP silently (a logical
        page the table does not hold) and corrupt the stream, so fail loudly
        instead; the engine's capacity formula is supposed to make this
        unreachable."""
        from cake_tpu.obs.timeline import timeline
        from cake_tpu.utils import metrics

        if end_slot > self.capacity_slots():
            raise ValueError(
                f"paged {op} writes through slot {end_slot} but the epoch "
                f"capacity is {self.capacity_slots()} slots — the engine's "
                "one-capacity-per-epoch bound was violated"
            )
        impl = self.kernel_impl()
        if impl == "fallback" and not self._fallback_noted:
            self._fallback_noted = True
            metrics.flight.record(
                "kernel-fallback", op=op, page_size=self.page_size,
                pool_write=self.pool_write(), attention="xla",
                reason="page_size not a multiple of the 128-lane tile",
            )
        timeline.instant(
            f"kernel:{op}", track="engine",
            args={"impl": "pallas" if impl == "pallas" else "xla"},
        )

    # --------------------------------------------------- bounded capacity

    def set_epoch_capacity(self, capacity_slots: int | None) -> None:
        """Bound every dispatch's block-table operand to ``capacity_slots``
        (rounded up to whole pages); None restores the full table. ONCE per
        epoch, or per segment under the continuous scheduler: why it must
        not vary within one is in the class docstring."""
        if capacity_slots is None:
            self._cap_pages = None
            return
        pages = -(-int(capacity_slots) // self.page_size)
        self._cap_pages = max(1, min(pages, self.pages_per_seq))

    def capacity_slots(self) -> int:
        """The slot capacity every position grid currently sizes to."""
        if self._cap_pages is None:
            return self.padded_seq
        return self._cap_pages * self.page_size

    def _tables(self, lane: int | None = None, whole: bool = False):
        """The block-table operand: every lane's row, or one lane's, under
        the epoch's capacity or ``whole``; one table a kind of attention
        layer, in ``config.attention_kinds``' order, where the cache is a
        pool a kind. Copies: the allocator's array changes under a program
        that is enqueued and has not run (class docstring)."""
        rows = slice(None) if lane is None else slice(lane, lane + 1)
        pages = None if whole else self._cap_pages
        if self.kind.pools_by_kind:
            return tuple(
                jnp.asarray(a.block_tables[rows, :pages].copy())
                for a in self.allocator.kinds.values()
            )
        return jnp.asarray(self.allocator.block_tables[rows, :pages].copy())

    def _forms(self, section: str) -> dict:
        """Which form the kind's programs take of what a ``/stats`` section
        reports: ``"pallas"`` or ``"xla"``, from the predicates the record
        lists, the config's widths and the kernel switch."""
        return {
            key: form(self.config, self.page_size, self.allow_pallas)
            for where, key, form in self.kind.forms if where == section
        }

    def cache_facts(self) -> dict:
        """``GET /stats`` engine.cache: what kind of cache the lanes' pages
        hold and what it costs. ``bytes_per_token`` is what a cached token
        takes in the pool over all layers (``_needed``: without the pool's
        padding to whole tiles), ``bytes`` the whole pool's; then what the
        kind's record adds."""
        needed, stored = self.kind.token_bytes(self.config, self.cache_dtype)
        return {
            "kind": self.config.cache_kind,
            "bytes_per_token": stored,
            "bytes_per_token_needed": needed,
            "page_size": self.page_size,
            "pages": self.max_pages,
            "bytes": stored * self.page_size * self.max_pages,
            "pool_write": self.pool_write(),
            **self.kind.cache_facts(
                self.config, self.allocator, self.page_size, self.cache_dtype
            ),
        }

    def _note_cache(self) -> None:
        """The gauges beside ``cake_kv_pages_*``: facts of the construction."""
        from cake_tpu.utils import metrics

        facts = self.cache_facts()
        metrics.registry.gauge(
            "cake_kv_bytes_per_token",
            "Bytes one cached token takes in the page pool, all layers.",
        ).set(facts["bytes_per_token"])
        metrics.registry.gauge(
            "cake_kv_pool_bytes", "Bytes of the whole page pool.",
        ).set(facts["bytes"])

    def state_facts(self) -> dict:
        """``GET /stats`` engine.state: the recurrent state beside the page
        pool (zeros for a model without state layers). ``bytes`` is what
        the current epoch's lanes hold; ``lane_writes`` is cumulative."""
        from cake_tpu.models.llama.config import STATE

        per_lane = self.config.state_bytes_per_lane
        layers = len(self.config.layers_of(STATE))
        return {
            "layers": layers,
            # which mixer the state layers run, and a delta rule's head counts
            "mixer": self.config.state_mixer if layers else None, **_delta_heads(self.config),
            # ``window_form``: the form every window (a prefill, a join) of
            # their recurrence takes; ``step_form``: a decode step's
            # one-token update ("pallas": a stepped row's state read once and
            # written once, in place; the delta rule's passes over dead rows)
            **self._forms("state"),
            "bytes_per_lane": per_lane,
            "bytes": per_lane * self._state_lanes,
            "lane_writes": self.state_lane_writes,
            # decode chunks enqueued, the rows they STEPPED together and the
            # lanes they were wide (``_count_stepped`` says which mixer steps what)
            "decode_dispatches": self.state_decode_dispatches, "decode_lanes": self.state_decode_lanes,
            "decode_rows": self.state_decode_rows,
        }

    def _epoch_groups(self, tokens, pads, ends):
        """An epoch's prefill as the closed shapes cut it: the tokens
        right-padded to a program width (a dead tail under ``ends``), and the
        rows in groups of ``shapes.prefill_group``, every group one program
        over its own lanes' table rows. (tokens, pads, ends, tables, the
        groups' row slices)."""
        tokens = np.asarray(tokens)
        b, width = tokens.shape
        self._kernel_note(
            "prefill", width if ends is None else int(np.max(ends))
        )
        ends = jnp.asarray(
            np.full((b,), width, np.int32) if ends is None else ends, jnp.int32
        )
        tokens = jnp.asarray(np.pad(
            tokens, ((0, 0), (0, self.shapes.program_width(width) - width))
        ))
        group = self.shapes.prefill_group(b, tokens.shape[1])
        groups = [slice(lo, min(lo + group, b)) for lo in range(0, b, group)]
        return tokens, jnp.asarray(pads), ends, self._tables(whole=True), groups

    @staticmethod
    def _group_span(index: int, rows: slice, slots: int):
        """One engine-track span a group of an epoch's prefill, inside the
        engine's ``prefill`` span: a profiler window that opens mid-epoch
        holds the groups still to come (a span that opened before the window
        did is not in the trace). Read through ``breakdown.idle_gaps``."""
        from cake_tpu.obs.timeline import PROFILED_TRACK, timeline

        return timeline.span(
            "prefill-group", track=PROFILED_TRACK,
            args={"group": index, "rows": rows.stop - rows.start,
                  "slots": int(slots)},
        )

    @staticmethod
    def _group_logits(logits: list):
        """The groups' last-position logits as the epoch's: the
        ``concatenate`` waits for nothing but is a program of its own, and
        the host sits in it while the groups run (its own span)."""
        from cake_tpu.obs.timeline import PROFILED_TRACK, timeline

        if len(logits) == 1:
            return logits[0]
        with timeline.span(
            "prefill-logits", track=PROFILED_TRACK,
            args={"groups": len(logits)},
        ):
            return jnp.concatenate(logits)

    # ------------------------------------------------- the four operations

    def _new_cache(self, b: int):
        pages = self.max_pages
        if self.kind.pools_by_kind:
            pages = tuple(a.n_pages for a in self.allocator.kinds.values())
        return self.kind.init_cache(
            self.config, b, pages, self.page_size, self.cache_dtype
        )

    def init_kv(self, b: int):
        """New-epoch cache: the allocator reset, zeroed pools and, where the
        kind keeps one, zeroed lane state for ``b`` lanes. The pools' HBM
        footprint is ``max_pages`` pages whatever ``b``: lanes only consume
        pages the engine maps."""
        self.allocator.reset(batch=b)
        if self.kind.lane_state is not None:
            from cake_tpu.utils import metrics

            self._state_lanes = b
            metrics.registry.gauge(
                "cake_state_bytes",
                "Recurrent state the epoch's lanes hold beside the KV pool.",
            ).set(b * self.config.state_bytes_per_lane)
        return self._new_cache(b)

    def _window_operands(self, start: int, lane: int) -> list:
        """A join's scalar operands behind its table row, as the record
        orders them."""
        assert start == 0 or "start" in self.kind.window_operands, (
            "the plain paged join computes its row from slot 0"
        )
        have = {"start": start, "lane": lane}
        return [jnp.int32(have[name]) for name in self.kind.window_operands]

    def prefill(self, tokens, kv, pads, ends=None):
        """An epoch's prefill in groups of rows (``shapes.prefill_group``),
        every group one program that writes its own lanes' cache through
        their table rows (the WHOLE rows: a dead page is a grid step the
        attention kernel skips). A one-row group is the JOIN's program of
        that width, from slot 0, where the shapes say so: one executable a
        width (``shapes.one_row_prefill_is_join``)."""
        tokens, pads, ends, tables, groups = self._epoch_groups(tokens, pads, ends)
        if self.kind.lane_state is not None:
            self.state_lane_writes += tokens.shape[0]
        width = tokens.shape[1]
        spare = None
        if self.shapes.whole_batch:
            # Every epoch is ``max_batch`` lanes wide: a spare lane's dummy
            # row is not run. It holds no pages, so its K and V would drop,
            # a joiner starts its lane's state from zeros, and nobody reads
            # its logits.
            spare = ~(self.allocator.block_tables[:tokens.shape[0]] >= 0).any(axis=1)
        logits = []
        for index, rows in enumerate(groups):
            if spare is not None and spare[rows].all():
                logits.append(None)
                continue
            table = jax.tree.map(lambda t: t[rows], tables)
            with self._group_span(index, rows, width):
                if self.shapes.one_row_prefill_is_join and rows.stop - rows.start == 1:
                    out, kv, *_ = self._join_program(
                        self.config, width, self.allow_pallas
                    )(
                        self.params, kv, tokens[rows], pads[rows], ends[rows],
                        table, *self._window_operands(0, rows.start),
                    )
                else:
                    more = {"lane": rows.start} if (
                        "lane" in self.kind.window_operands) else {}
                    out, kv, *_ = self._prefill_program(
                        self.params, tokens[rows], kv, pads[rows], ends[rows],
                        table, self.config, **more,
                        allow_pallas=self.allow_pallas,
                    )
            logits.append(out)
        if self.config.block_length:
            # nobody samples from a prefill of a model that generates by blocks
            # (a block's tokens come from its own passes): no logits are put
            # together, for a spare row or a real one
            return None, kv
        if spare is not None:
            ran = next(out for out in logits if out is not None)
            logits = [
                jnp.zeros((rows.stop - rows.start, *ran.shape[1:]), ran.dtype)
                if out is None else out
                for out, rows in zip(logits, groups)
            ]
        return self._group_logits(logits), kv

    def decode(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        self._kernel_note("decode", int(slot) + n)
        if self.config.block_length:
            return _decode_blocks(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s)
        if self._fusions:
            _note_fusion_kernels(self, s)
        # Position grids size to the epoch capacity, not the padded max_seq
        # (one compile per capacity bucket; steady state within an epoch
        # never retraces).
        fn = self._decode_program(
            self.config,
            self.capacity_slots() if self.kind.closes_over_capacity else None,
            n, s.temperature, s.top_k, s.top_p, s.repeat_penalty,
            self.allow_pallas,
        )
        lanes = ()
        if self.kind.masks_lanes:
            # A lane is live while it holds pages (of the first kind): the
            # engine releases a finished row's pages at the boundary where
            # it sees the end (one chunk late for an EOS id: that chunk
            # steps the dead row's state, which nobody reads and a joiner
            # starts from zero) and maps a joiner's before its prefill, and
            # spare lanes never hold any. The same fact that drops a dead
            # lane's K/V writes keeps its state (the delta rule's step does not
            # even read it) and its token out of the experts' rows.
            b = int(jnp.shape(tok)[0])
            lanes = (jnp.asarray(
                live := (self.allocator.block_tables[:b] >= 0).any(axis=1)),)
            if self.kind.lane_state is not None:
                self.state_decode_dispatches += 1
                _count_stepped(self, b, int(live.sum()))
        out = fn(
            self.params, kv, tok, jnp.int32(slot), pads, self._tables(),
            *lanes, keys, ring, ring_idx,
        )
        if self._accounts:
            out, self._chunk_counters = out[:-1], (out[-1], tok.shape[0])
        return tuple(out)

    def join(self, kv, row_tokens, pads1, ends1, lane, start=0):
        """One row's window [start, start + width) as the engine cut it
        (``shapes.window``) into lane ``lane``, through its table row(s); the
        lane's state, where the kind keeps one, from zero."""
        self._kernel_note("join", int(np.asarray(ends1).max()))
        if self.kind.lane_state is not None:
            self.state_lane_writes += 1
        fn = self._join_program(
            self.config, row_tokens.shape[1], self.allow_pallas
        )
        logits, kv, *counters = fn(
            self.params, kv, jnp.asarray(row_tokens), pads1, ends1,
            self._tables(lane, whole=not self.kind.capped_windows),
            *self._window_operands(start, lane),
        )
        self._chunk_counters = (counters[0], row_tokens.shape[1]) if counters else None
        return logits, kv

    # The programs' account beside the tokens (a record with ``accounts``):
    # ``decode`` and ``join`` keep it on the device; the engine takes it with
    # the tokens (``take_chunk_counters``) and hands it back when it READS
    # them (``absorb_chunk_counters``), so the account is read at the same
    # boundary as the tokens and never makes the host wait for a chunk of
    # its own.

    def take_chunk_counters(self):
        counters, self._chunk_counters = self._chunk_counters, None
        if counters is None:
            return None
        return *counters, self._traced and _recording()

    def absorb_chunk_counters(self, counters, decode: bool = True) -> dict:
        """A read program's counts into the cumulative accounts (a decode
        chunk's, or a join's window, of ``rows`` rows; ``traced``: dispatched
        while a profiler was recording); returns them as a dict (the
        timeline's span arguments)."""
        counters, rows, traced = counters
        values = iter(int(v) for v in np.asarray(counters))
        said = {}
        for account in self._accounts.values():
            got = dict(zip(account.names, values))
            said.update(account.absorb(got, decode, traced, rows))
        assert next(values, None) is None, "a count vector no account names"
        return said

    def _account_facts(self, section: str) -> dict:
        """``GET /stats`` engine.moe / engine.sparse: the section's account
        and the forms the record lists for it."""
        return {**self._accounts[section].facts(), **self._forms(section)}

    def warm_programs(self, lanes: int, sampling, n_steps: int) -> dict:
        """Run ``shapes.programs(lanes)`` once each, on a scratch cache. A
        server that did this at start-up traces and loads none of them while
        it serves: each is a stall of every live stream otherwise (0.4 s
        from the persistent cache, 3 to 10 s without). Nothing is mapped, so
        no K or V is written. {programs, seconds}."""
        import time

        t0 = time.perf_counter()
        programs = self.shapes.programs(lanes)
        cache = self.init_kv(lanes)
        zeros = jnp.zeros((lanes,), jnp.int32)
        # the decode chunk's token operand: a block's known tokens, all masked,
        # where the model generates by blocks
        tok = zeros if not self.config.block_length else jnp.full(
            (lanes, self.config.block_length), self.config.mask_token_id, jnp.int32)
        keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
        ring = jnp.zeros((lanes, sampling.repeat_last_n), jnp.int32)
        if self.shapes.whole_batch:
            # An epoch's prefill cuts its groups' operands with eager slices,
            # which compile once a shape; a warmed epoch's groups are all
            # spare and skipped before the cut. Cut a group a width here, or
            # a width's first live epoch compiles while it serves
            # (``compile.untracked``).
            for width in self.shapes.widths:
                *cut, groups = self._epoch_groups(
                    np.zeros((lanes, width), np.int32), zeros, None)
                for group in (groups[0], groups[-1]):
                    jax.tree.map(lambda t: t[group], cut)
        for op, rows, slots in programs:
            blank = np.zeros((rows, slots), np.int32)
            if op == "prefill":
                _, cache = self.prefill(blank, cache, zeros)
            elif op == "join" and rows == 1:
                _, cache = self.join(
                    cache, blank, zeros[:1], jnp.asarray([slots], jnp.int32), 0
                )
            elif op == "join":  # a step's joiners together: every row dead
                cache = self.join_rows(cache, blank, [slots] * rows, [slots] * rows, [-1] * rows)[1]
            else:  # a tail: what is left under the ceiling (slots are whole chunks from a width on)
                tail = op == "decode_tail"
                n = self.shapes.decode_steps(n_steps, slots, slots - n_steps) if tail else n_steps
                self.set_epoch_capacity(slots)
                cache = self.decode(
                    cache, tok, 0, zeros, keys, ring, zeros, n, sampling
                )[1]
        jax.block_until_ready(cache)
        self.set_epoch_capacity(None)
        self.allocator.reset(batch=1)
        self.state_lane_writes = 0
        self.state_decode_dispatches = self.state_decode_rows = self.state_decode_lanes = 0
        return {
            "programs": len(programs),
            "seconds": round(time.perf_counter() - t0, 3),
        }


class PagedLocalBackend(_PagedBackend):
    """What plain K and V alone can do, beside what its record says
    (``decode`` and the plain ``join`` are the driver's): an epoch's prefill
    as ONE open-shape program, a prefix cache, the two verifies.

    With a prefix cache attached (``attach_prefix_cache``,
    runtime/prefix_cache.py) the pool becomes PERSISTENT: ``init_kv`` keeps
    the retained device pool (``retain_kv`` at epoch end) and releases only
    the lane mappings, so cached chains' pages — and their bytes — survive
    across epochs; ``suffix_prefill`` computes just a prompt's uncached tail
    over forked chains, and ``cow_copy`` is the device half of the
    make-private split."""

    prefix_cache = None
    _retained_kv = None

    def attach_prefix_cache(self, cache) -> None:
        """Switch the pool to PERSISTENT mode for the engine's prefix cache
        (runtime/prefix_cache.py): epochs stop zeroing it."""
        self.prefix_cache = cache

    def retain_kv(self, kv) -> None:
        """Epoch end (persistent mode): keep the final pool buffer so the
        next epoch's ``init_kv`` hands it back with cached chains intact."""
        self._retained_kv = kv

    def drop_retained_kv(self) -> None:
        self._retained_kv = None

    def init_kv(self, b: int):
        """New-epoch pool. Default: allocator reset + fresh zeroed pages.
        Persistent (prefix cache attached): lane mappings release — cached
        chains keep their pages — and the retained device pool is reused;
        the pool is rebuilt zeroed only when nothing was retained (first
        epoch, or a failed one that dropped the buffer — the engine clears
        the cache on that path, so chains never outlive their bytes). The
        pool's HBM footprint is ``max_pages`` pages regardless of ``b`` —
        lanes only consume pages the engine actually maps."""
        if self.prefix_cache is None:
            return super().init_kv(b)
        self.allocator.release_lanes(batch=b)
        kv, self._retained_kv = self._retained_kv, None
        return kv if kv is not None else self._new_cache(b)

    def prefill(self, tokens, kv, pads, ends=None):
        from cake_tpu.models.llama.batch import _paged_prefill_jit

        kw = {}
        if ends is not None:
            ends = jnp.asarray(ends, jnp.int32)
            kw = {"ends": ends, "seq_len": ends[0]}
        self._kernel_note("prefill", int(jnp.shape(tokens)[1]))
        return _paged_prefill_jit(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            self._tables(), self.config,
            allow_pallas=self.allow_pallas, **kw,
        )

    def suffix_prefill(self, tokens, kv, pads, write_starts, start):
        """Prefix-cache prefill: compute only the window [start, start + W)
        over the live pool prefix, each row's writes below its fresh
        threshold dropped (batch.paged_suffix_prefill). EVERY cache-enabled
        prefill routes here — cold epochs included, with start at the
        youngest pad — so warm and cold runs share ONE attention arithmetic
        and greedy streams stay bit-identical (the fresh-chunk path's
        reduction differs at the ulp level). One compile per width."""
        from cake_tpu.models.llama.batch import _paged_suffix_jit

        self._kernel_note(
            "suffix_prefill", int(start) + int(jnp.shape(tokens)[1])
        )
        return _paged_suffix_jit(
            self.params, jnp.asarray(tokens), kv,
            jnp.asarray(pads, jnp.int32),
            jnp.asarray(write_starts, jnp.int32),
            self._tables(), self.config, jnp.int32(start),
            allow_pallas=self.allow_pallas,
        )

    def suffix_join(self, kv, row_tokens, pads1, write_starts1, lane, start):
        """The continuous-batching join on the prefix-cache arithmetic: one
        row's window [start, slot) over ITS lane table, same cached-chunk
        attention as suffix_prefill — so a cache-enabled join is
        bit-identical whether its prefix was forked (writes below the
        threshold drop) or computed fresh. The lane table is sliced to the
        SAME epoch capacity as every other dispatch (the one-capacity rule,
        class docstring)."""
        from cake_tpu.models.llama.batch import _paged_suffix_join_jit

        self._kernel_note(
            "suffix_join", int(start) + int(jnp.shape(row_tokens)[1])
        )
        return _paged_suffix_join_jit(
            self.params, jnp.asarray(row_tokens), kv,
            jnp.asarray(pads1, jnp.int32),
            jnp.asarray(write_starts1, jnp.int32),
            self._tables(lane), self.config, jnp.int32(start),
            allow_pallas=self.allow_pallas,
        )

    def cow_copy(self, kv, src: list[int], dst: list[int]):
        """Device half of the copy-on-write split: duplicate shared pages
        before a lane's first divergent write (paged_cache.copy_pages)."""
        from cake_tpu.models.llama.paged_cache import copy_pages

        return copy_pages(
            kv, np.asarray(src, np.int32), np.asarray(dst, np.int32)
        )

    # Speculative verify through the paged cached-chunk arithmetic — the
    # presence of these two methods is the engine's capability gate, so
    # defining them is what turns speculation back ON under kv_mode="paged".

    def verify_greedy(self, kv, tokens, slot, pads):
        from cake_tpu.models.llama.batch import _paged_verify_greedy_fn

        self._kernel_note("verify", int(slot) + tokens.shape[1])
        fn = _paged_verify_greedy_fn(
            self.config, tokens.shape[1], self.allow_pallas
        )
        return fn(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            jnp.int32(slot), self._tables(),
        )

    def verify_sampled(self, kv, tokens, slot, pads, drafts, n_drafts, keys, s):
        from cake_tpu.models.llama.batch import _paged_verify_sampled_fn

        self._kernel_note("verify", int(slot) + tokens.shape[1])
        fn = _paged_verify_sampled_fn(
            self.config, tokens.shape[1], s.temperature, s.top_k, s.top_p,
            self.allow_pallas,
        )
        return fn(
            self.params, jnp.asarray(tokens), kv, jnp.asarray(pads),
            jnp.int32(slot), self._tables(), jnp.asarray(drafts),
            jnp.asarray(n_drafts, jnp.int32), keys,
        )


def _recording() -> bool:
    """Whether a profiler is recording this process's annotations: the
    timeline's ``recording()`` (obs/timeline.py), the tree's one test of it
    (the benchmark's control socket opens a session; ``POST /profile``
    another). False from the instant the session's stop is CALLED: the
    engine serves through a closing of seconds to minutes."""
    from cake_tpu.obs.timeline import timeline

    return timeline.recording()


def paged_backend(config: LlamaConfig, params: M.Params, **kw) -> _PagedBackend:
    """The paged backend of this model's cache kind: the one place it is
    chosen (the shapes are chosen beside it, ``ProgramShapes.for_model``).
    Plain K and V alone has a subclass, for what only it can do over a
    one-token step (a model that generates by diffusion over blocks is the
    driver's own: ``programs.kind_of``)."""
    plain = config.cache_kind == CACHE_KV and not config.block_length
    leaf = PagedLocalBackend if plain else _PagedBackend
    return leaf(config, params, **kw)


class TPBatchBackend:
    """Tensor-parallel batch ops: one shard_map per op over a 1-D tp mesh.

    Layer weights shard per parallel/tensor.layer_partition_specs (Megatron
    column/row + expert axis for MoE); KV heads shard with their
    projections; the head/embed replicate. The batched bodies themselves
    come from models/llama/batch.py with ``tp_axis`` threading the psums —
    numerics are the local path's, shard count only changes the reduction
    order.
    """

    shapes = ProgramShapes()

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        *,
        tp: int | None = None,
        mesh: Mesh | None = None,
        max_seq_len: int,
        cache_dtype: jnp.dtype,
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
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype

        self._layer_specs, self.layer_params, self.head_params = place_tp_model(
            config, params, mesh
        )
        self._kv_spec = P(None, None, TP_AXIS)
        self._rope = model_rope_tables(config, max_seq_len)
        self._finish_init()

    def _finish_init(self) -> None:
        self._prefill = self._build_prefill()
        self._join = self._build_join()
        self._decode_cache: OrderedDict = OrderedDict()

    @classmethod
    def from_runner(cls, runner, *, max_seq_len: int, cache_dtype):
        """Adopt a TensorParallelRunner's already-placed shards (no second
        device_put of the weights) — the --api-batch + --tp CLI path."""
        self = cls.__new__(cls)
        self.mesh = runner.mesh
        self.tp = runner.tp
        self.config = runner.config
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype
        self._layer_specs = runner._layer_specs
        self.layer_params = runner.layer_params
        self.head_params = runner.head_params
        self._kv_spec = P(None, None, TP_AXIS)
        self._rope = model_rope_tables(self.config, max_seq_len)
        self._finish_init()
        return self

    def init_kv(self, b: int) -> KVCache:
        kv = init_cache(
            self.config.num_hidden_layers,
            b,
            self.max_seq_len,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self.cache_dtype,
        )
        return jax.device_put(kv, NamedSharding(self.mesh, self._kv_spec))

    # -- shared shard_mapped bodies ---------------------------------------

    def _mapped_prefill_body(self):
        cfg = self.config
        cos, sin = self._rope

        def body(head, layers, tokens, kv, pads, ends, seq_len):
            b, l = tokens.shape
            x = M.embed_tokens(head, tokens, cfg)
            q_pos, k_pos = prefill_positions(l, pads, ends)
            x, kv = batched_blocks_forward(
                layers, x, kv, cos, sin, q_pos, k_pos, cfg,
                decode=False, pads=pads, lengths=ends,
                write_pos=jnp.int32(0), tp_axis=TP_AXIS,
            )
            return M.head_forward(head, x, seq_len, cfg), kv

        return checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                P(), self._layer_specs, P(),
                KVCache(k=self._kv_spec, v=self._kv_spec), P(), P(), P(),
            ),
            out_specs=(P(), KVCache(k=self._kv_spec, v=self._kv_spec)),
        )

    def _build_prefill(self):
        mapped = self._mapped_prefill_body()

        def run(head, layers, tokens, kv, pads, ends, seq_len):
            return mapped(head, layers, tokens, kv, pads, ends, seq_len)

        return jax.jit(run, donate_argnums=(3,))

    def prefill(self, tokens, kv, pads, ends=None):
        tokens = jnp.asarray(tokens)
        b, l = tokens.shape
        ends = (
            jnp.full((b,), l, jnp.int32)
            if ends is None
            else jnp.asarray(ends, jnp.int32)
        )
        return self._prefill(
            self.head_params, self.layer_params, tokens, kv,
            jnp.asarray(pads), ends, jnp.int32(l),
        )

    def _build_join(self):
        mapped = self._mapped_prefill_body()

        def run(head, layers, kv, tokens, pads1, ends1, lane):
            kv_row = init_cache(
                self.config.num_hidden_layers,
                1,
                self.max_seq_len,
                self.config.num_key_value_heads,
                self.config.head_dim,
                self.cache_dtype,
            )
            kv_row = jax.lax.with_sharding_constraint(
                kv_row, NamedSharding(self.mesh, self._kv_spec)
            )
            logits, kv_row = mapped(
                head, layers, tokens, kv_row, pads1, ends1, ends1[0]
            )
            k = jax.lax.dynamic_update_slice(kv.k, kv_row.k, (0, lane, 0, 0, 0))
            v = jax.lax.dynamic_update_slice(kv.v, kv_row.v, (0, lane, 0, 0, 0))
            return logits, KVCache(k=k, v=v)

        return jax.jit(run, donate_argnums=(2,))

    def join(self, kv, row_tokens, pads1, ends1, lane, start=0):
        assert start == 0, "a dense join computes its row from slot 0"
        return self._join(
            self.head_params, self.layer_params, kv,
            jnp.asarray(row_tokens), pads1, ends1, jnp.int32(lane),
        )

    def _forward_one(self, head, layers, pads):
        """Pad-closure one-token step: shard_mapped, for the decode scan.
        ``head``/``layers`` are the jitted caller's ARGUMENTS: weights a
        jitted function closes over are baked into its program as constants
        (gigabytes of them, minutes of compile, and jax 0.9.0 then fails to
        pass the sharded ones at call time)."""
        cfg = self.config
        cos, sin = self._rope

        def body(head, layers, tok, kv, pads, slot):
            # The cache's PADDED length (SEQ_MULTIPLE rounding), not the user
            # max_seq_len — the mask grid must cover every physical slot.
            x = M.embed_tokens(head, tok, cfg)
            q_pos, k_pos, lengths = decode_positions(slot, pads, kv.k.shape[-2])
            x, kv = batched_blocks_forward(
                layers, x, kv, cos, sin, q_pos, k_pos, cfg,
                decode=True, pads=pads, lengths=lengths, write_pos=slot,
                tp_axis=TP_AXIS,
            )
            return M.head_forward(head, x, jnp.int32(1), cfg), kv

        mapped = checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                P(), self._layer_specs, P(),
                KVCache(k=self._kv_spec, v=self._kv_spec), P(), P(),
            ),
            out_specs=(P(), KVCache(k=self._kv_spec, v=self._kv_spec)),
        )

        def forward_one(tok, kv, slot):
            return mapped(head, layers, tok[:, 0][:, None], kv, pads, slot)

        return forward_one

    def decode(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        knobs = (n, s.temperature, s.top_k, s.top_p, s.repeat_penalty)

        def build():
            # The sampling tail runs OUTSIDE the shard_mapped forward, so
            # the tail fusion (ops/pallas/fused_sample_tail.py) applies to
            # the tp backend exactly as to the local one.
            from cake_tpu.ops.fuse import resolve_fusion

            fusions, fimpl = resolve_fusion(self.config)
            tail_impl = fimpl if "tail" in fusions else None

            def run(head, layers, kv, tok, slot, pads, keys, ring, ring_idx):
                return sampled_decode_scan(
                    self._forward_one(head, layers, pads),
                    kv, tok, slot, keys, ring, ring_idx,
                    n_steps=n,
                    temperature=s.temperature,
                    top_k=s.top_k,
                    top_p=s.top_p,
                    repeat_penalty=s.repeat_penalty,
                    tail_impl=tail_impl,
                )

            return jax.jit(run, donate_argnums=(2,))

        fn = _cache_get_or_build(self._decode_cache, knobs, build)
        return fn(
            self.head_params, self.layer_params,
            kv, tok, jnp.int32(slot), pads, keys, ring, ring_idx,
        )

    # Speculative verify over the tp mesh: one shard_mapped cached-chunk
    # forward scores every draft position (MoE forced drop-free dense under
    # tp — batched_verify_logits); acceptance runs replicated on-device.

    def _verify_mapped(self):
        from cake_tpu.models.llama.batch import batched_verify_logits

        cfg = self.config

        def body(head, layers, tokens, kv, pads, slot):
            params = dict(head)
            params["layers"] = layers
            return batched_verify_logits(
                params, tokens, kv, pads, slot, cfg, tp_axis=TP_AXIS
            )

        return checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                P(), self._layer_specs, P(),
                KVCache(k=self._kv_spec, v=self._kv_spec), P(), P(),
            ),
            out_specs=(P(), KVCache(k=self._kv_spec, v=self._kv_spec)),
        )

    def verify_greedy(self, kv, tokens, slot, pads):
        key = ("verify_greedy", tokens.shape[1])

        def build():
            from cake_tpu.models.llama.batch import verify_greedy_ids

            mapped = self._verify_mapped()

            def run(head, layers, tokens, kv, pads, slot):
                logits, kv = mapped(head, layers, tokens, kv, pads, slot)
                return verify_greedy_ids(logits), kv

            return jax.jit(run, donate_argnums=(3,))

        fn = _cache_get_or_build(self._decode_cache, key, build)
        return fn(
            self.head_params, self.layer_params, jnp.asarray(tokens), kv,
            jnp.asarray(pads), jnp.int32(slot),
        )

    def verify_sampled(self, kv, tokens, slot, pads, drafts, n_drafts, keys, s):
        key = (
            "verify_sampled", tokens.shape[1],
            s.temperature, s.top_k, s.top_p,
        )

        def build():
            from cake_tpu.models.llama.batch import verify_sampled_accept

            mapped = self._verify_mapped()

            def run(head, layers, tokens, kv, pads, slot, drafts, n_drafts, keys):
                logits, kv = mapped(head, layers, tokens, kv, pads, slot)
                n_accs, nxts, keys = verify_sampled_accept(
                    logits, drafts, n_drafts, keys,
                    s.temperature, s.top_k, s.top_p,
                )
                return n_accs, nxts, kv, keys

            return jax.jit(run, donate_argnums=(3,))

        fn = _cache_get_or_build(self._decode_cache, key, build)
        return fn(
            self.head_params, self.layer_params, jnp.asarray(tokens), kv,
            jnp.asarray(pads), jnp.int32(slot), jnp.asarray(drafts),
            jnp.asarray(n_drafts, jnp.int32), keys,
        )


class PipelineBatchBackend:
    """Pipelined (stage [x tp]) batch ops over an in-mesh stage walk.

    The stage loop + ppermute rotation of parallel/pipeline.PipelineRunner,
    with the pad-aware batched bodies per stage (ragged stages padded with
    inert layers, gated by the valid mask). One jitted SPMD computation per
    op.

    Decode has TWO walks:

      * serialized (the single-stream discipline, llama.rs:81-117): the whole
        batch advances one stage per wall-step — S-1 stages idle. Correct for
        one stream; wasteful for a serving batch.
      * **1F1B interleaved** (default when the batch divides by S and per-row
        keys are used): the batch splits into S microbatch GROUPS in
        staggered flight — at every wall-step each stage serves a different
        group, sampling rides the LAST stage so the fresh embedding ppermutes
        straight into stage 0 for that group's next token. N tokens for all
        groups take N*S + S - 1 wall-steps of 1/S-batch stage work instead of
        N*S wall-steps of full-batch work: per-device work per wall-step
        drops S-fold at equal token output, which is the pipelined serving
        throughput the serialized walk forfeits. Token streams are
        bit-identical to the serialized walk (same per-row PRNG splits, same
        penalty-ring arithmetic, same slots — pinned in
        tests/test_interleaved_pipeline.py, along with the measured
        per-device compiled-FLOPs drop).
        KV stays the shared full-batch cache: groups read/write their row
        window in place (batch.batched_blocks_forward row_offset mode).
    """

    shapes = ProgramShapes()

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        boundaries: list[tuple[int, int]],
        *,
        tp: int = 1,
        mesh: Mesh | None = None,
        max_seq_len: int,
        cache_dtype: jnp.dtype,
        interleave: bool = True,
    ):
        self.interleave = interleave
        self.config = config
        self.n_stages = len(boundaries)
        self.boundaries = boundaries
        if boundaries[0][0] != 0 or boundaries[-1][1] != config.num_hidden_layers:
            raise ValueError(f"stage boundaries {boundaries} do not cover the model")
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
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype

        (
            self._layer_specs,
            self.stage_params,
            self.valid,
            self.head_params,
            self.l_pad,
        ) = place_stage_model(config, params, boundaries, mesh, tp)
        self._kv_spec = P(STAGE_AXIS, None, None, TP_AXIS if tp > 1 else None)
        self._rope = model_rope_tables(config, max_seq_len)
        self._finish_init()

    def _finish_init(self) -> None:
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1,))
        self._join_jit = jax.jit(self._join_impl, donate_argnums=(1,))
        self._decode_cache: OrderedDict = OrderedDict()
        # Every jitted entry takes the placed weights as its FIRST ARGUMENT
        # (stage_params, valid, head): weights a jitted function closes over
        # are baked into its program as constants — gigabytes of them,
        # minutes of compile — and jax 0.9.0 then fails to pass the sharded
        # ones at call time.
        self._weights = (self.stage_params, self.valid, self.head_params)
        # The stage walks (prefill/decode/verify modes) live outside the
        # bounded knob cache: there are at most three, reused by every entry.
        self._walk_cache: dict = {}

    @classmethod
    def from_runner(cls, runner, *, max_seq_len: int, cache_dtype,
                    interleave: bool = True):
        """Adopt a PipelineRunner's already-placed stage shards (no second
        device_put of the weights) — the --api-batch + --backend mesh path."""
        self = cls.__new__(cls)
        self.interleave = interleave
        self.config = runner.config
        self.n_stages = runner.n_stages
        self.boundaries = runner.boundaries
        self.mesh = runner.mesh
        self.tp = runner.tp
        self.max_seq_len = max_seq_len
        self.cache_dtype = cache_dtype
        self.l_pad = runner.l_pad
        self._layer_specs = runner._layer_specs
        self.stage_params = runner.stage_params
        self.valid = runner.valid
        self.head_params = runner.head_params
        self._kv_spec = P(
            STAGE_AXIS, None, None, TP_AXIS if runner.tp > 1 else None
        )
        self._rope = model_rope_tables(self.config, max_seq_len)
        self._finish_init()
        return self

    def init_kv(self, b: int) -> KVCache:
        from cake_tpu.parallel.multihost import shard_put

        kv = init_cache(
            self.n_stages * self.l_pad,
            b,
            self.max_seq_len,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self.cache_dtype,
        )
        return KVCache(
            k=shard_put(
                kv.k.reshape(self.n_stages, self.l_pad, *kv.k.shape[1:]),
                self.mesh, self._kv_spec,
            ),
            v=shard_put(
                kv.v.reshape(self.n_stages, self.l_pad, *kv.v.shape[1:]),
                self.mesh, self._kv_spec,
            ),
        )

    def _mapped_walk(self, mode: str):
        """The shard_mapped stage loop over pad-aware batched bodies.

        ``mode``: "prefill" (full-width chunk at slot 0), "decode" (one
        token at wpos), or "verify" (cached chunk at wpos — speculative
        verify; MoE forced drop-free dense under tp)."""
        cfg = self.config
        n = self.n_stages
        tp_axis = TP_AXIS if self.tp > 1 else None
        cos, sin = self._rope
        perm = [(j, (j + 1) % n) for j in range(n)]
        decode = mode == "decode"
        cached_chunk = mode == "verify"
        moe_dispatch = (
            "dense" if cached_chunk and tp_axis is not None else "auto"
        )

        def body(stage_params, valid, x, kv, q_pos, k_pos, pads, lengths, wpos):
            stage = jax.lax.axis_index(STAGE_AXIS)
            local_params = jax.tree.map(lambda a: a[0], stage_params)
            local_valid = valid[0]
            local_kv = KVCache(k=kv.k[0], v=kv.v[0])

            def run(x, kv_in):
                return batched_blocks_forward(
                    local_params, x, kv_in, cos, sin, q_pos, k_pos, cfg,
                    decode=decode, cached_chunk=cached_chunk, pads=pads,
                    lengths=lengths, write_pos=wpos,
                    valid=local_valid, tp_axis=tp_axis,
                    moe_dispatch=moe_dispatch,
                )

            def skip(x, kv_in):
                return x, kv_in

            def loop(i, carry):
                x, kv_c = carry
                x, kv_c = jax.lax.cond(i == stage, run, skip, x, kv_c)
                x = jax.lax.ppermute(x, STAGE_AXIS, perm)
                return x, kv_c

            x, local_kv = jax.lax.fori_loop(0, n, loop, (x, local_kv))
            return x, KVCache(k=local_kv.k[None], v=local_kv.v[None])

        return checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self._layer_specs, P(STAGE_AXIS), P(),
                KVCache(k=self._kv_spec, v=self._kv_spec),
                P(), P(), P(), P(), P(),
            ),
            out_specs=(P(STAGE_AXIS), KVCache(k=self._kv_spec, v=self._kv_spec)),
        )

    def _walks(self, mode: str):
        if mode not in self._walk_cache:
            self._walk_cache[mode] = self._mapped_walk(mode)
        return self._walk_cache[mode]

    def _prefill_impl(self, weights, kv, tokens, pads, ends, seq_len):
        cfg = self.config
        stage_params, valid, head = weights
        b, l = tokens.shape
        x = M.embed_tokens(head, tokens, cfg)
        q_pos, k_pos = prefill_positions(l, pads, ends)
        x_stages, kv = self._walks("prefill")(
            stage_params, valid, x, kv, q_pos, k_pos,
            pads, ends, jnp.int32(0),
        )
        x = x_stages[:b]  # the true output cycles back to stage 0's shard
        return M.head_forward(head, x, seq_len, cfg), kv

    def prefill(self, tokens, kv, pads, ends=None):
        tokens = jnp.asarray(tokens)
        b, l = tokens.shape
        ends = (
            jnp.full((b,), l, jnp.int32)
            if ends is None
            else jnp.asarray(ends, jnp.int32)
        )
        return self._prefill(
            self._weights, kv, tokens, jnp.asarray(pads), ends, jnp.int32(l)
        )

    def _join_impl(self, weights, kv, tokens, pads1, ends1, lane):
        kv_row = init_cache(
            self.n_stages * self.l_pad,
            1,
            self.max_seq_len,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self.cache_dtype,
        )
        kv_row = KVCache(
            k=kv_row.k.reshape(self.n_stages, self.l_pad, *kv_row.k.shape[1:]),
            v=kv_row.v.reshape(self.n_stages, self.l_pad, *kv_row.v.shape[1:]),
        )
        kv_row = jax.lax.with_sharding_constraint(
            kv_row, NamedSharding(self.mesh, self._kv_spec)
        )
        logits, kv_row = self._prefill_impl(
            weights, kv_row, tokens, pads1, ends1, ends1[0]
        )
        k = jax.lax.dynamic_update_slice(
            kv.k, kv_row.k, (0, 0, lane, 0, 0, 0)
        )
        v = jax.lax.dynamic_update_slice(
            kv.v, kv_row.v, (0, 0, lane, 0, 0, 0)
        )
        return logits, KVCache(k=k, v=v)

    def join(self, kv, row_tokens, pads1, ends1, lane, start=0):
        assert start == 0, "a dense join computes its row from slot 0"
        return self._join_jit(
            self._weights, kv, jnp.asarray(row_tokens), pads1, ends1,
            jnp.int32(lane),
        )

    # Speculative verify through the pipelined stage walk: one cached-chunk
    # SPMD computation scores every row's draft; acceptance runs replicated.

    def _verify_walk(self, weights, kv, tokens, slot, pads):
        from cake_tpu.models.llama.batch import verify_positions

        cfg = self.config
        stage_params, valid, head = weights
        tokens = jnp.asarray(tokens)
        b, w = tokens.shape
        pads = jnp.asarray(pads, jnp.int32)
        x = M.embed_tokens(head, tokens, cfg)
        max_seq = kv.k.shape[-2]
        q_pos, k_pos, lengths = verify_positions(
            w, pads, jnp.int32(slot), max_seq
        )
        x_stages, kv = self._walks("verify")(
            stage_params, valid, x, kv, q_pos, k_pos,
            pads, lengths, jnp.int32(slot),
        )
        return x_stages[:b], kv

    def verify_greedy(self, kv, tokens, slot, pads):
        key = ("verify_greedy", tokens.shape[1])

        def build():
            from cake_tpu.models.llama.batch import verify_greedy_ids

            cfg = self.config

            def run(weights, kv, tokens, slot, pads):
                x, kv = self._verify_walk(weights, kv, tokens, slot, pads)
                logits = M.head_forward_all(weights[2], x, cfg)
                return verify_greedy_ids(logits), kv

            return jax.jit(run, donate_argnums=(1,))

        fn = _cache_get_or_build(self._decode_cache, key, build)
        return fn(
            self._weights, kv, jnp.asarray(tokens), jnp.int32(slot),
            jnp.asarray(pads),
        )

    def verify_sampled(self, kv, tokens, slot, pads, drafts, n_drafts, keys, s):
        key = (
            "verify_sampled", tokens.shape[1],
            s.temperature, s.top_k, s.top_p,
        )

        def build():
            from cake_tpu.models.llama.batch import verify_sampled_accept

            cfg = self.config

            def run(weights, kv, tokens, slot, pads, drafts, n_drafts, keys):
                x, kv = self._verify_walk(weights, kv, tokens, slot, pads)
                logits = M.head_forward_all(weights[2], x, cfg)
                n_accs, nxts, keys = verify_sampled_accept(
                    logits, drafts, n_drafts, keys,
                    s.temperature, s.top_k, s.top_p,
                )
                return n_accs, nxts, kv, keys

            return jax.jit(run, donate_argnums=(1,))

        fn = _cache_get_or_build(self._decode_cache, key, build)
        return fn(
            self._weights, kv, jnp.asarray(tokens), jnp.int32(slot),
            jnp.asarray(pads), jnp.asarray(drafts),
            jnp.asarray(n_drafts, jnp.int32), keys,
        )

    def _forward_one(self, weights, pads):
        cfg = self.config
        stage_params, valid, head = weights
        walk = self._walks("decode")

        def forward_one(tok, kv, slot):
            b = tok.shape[0]
            # Padded physical cache length (SEQ_MULTIPLE rounding), as above.
            x = M.embed_tokens(head, tok, cfg)
            q_pos, k_pos, lengths = decode_positions(slot, pads, kv.k.shape[-2])
            x_stages, kv = walk(
                stage_params, valid, x, kv, q_pos, k_pos,
                pads, lengths, slot,
            )
            x = x_stages[:b]
            return M.head_forward(head, x, jnp.int32(1), cfg), kv

        return forward_one

    def decode(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        b = int(tok.shape[0])
        if (
            self.interleave
            and self.n_stages > 1
            and b % self.n_stages == 0
            and getattr(keys, "ndim", 1) == 2  # per-row streams required
        ):
            return self._decode_interleaved(
                kv, tok, slot, pads, keys, ring, ring_idx, n, s
            )
        knobs = (n, s.temperature, s.top_k, s.top_p, s.repeat_penalty)

        def build():
            # Serialized walk: sampling is outside the stage shard_map, so
            # the tail fusion applies. (The 1F1B interleaved walk below
            # samples INSIDE the stage loop and keeps the unfused tail —
            # bit-identical either way, fused.sample_step.)
            from cake_tpu.ops.fuse import resolve_fusion

            fusions, fimpl = resolve_fusion(self.config)
            tail_impl = fimpl if "tail" in fusions else None

            def run(weights, kv, tok, slot, pads, keys, ring, ring_idx):
                return sampled_decode_scan(
                    self._forward_one(weights, pads),
                    kv, tok, slot, keys, ring, ring_idx,
                    n_steps=n,
                    temperature=s.temperature,
                    top_k=s.top_k,
                    top_p=s.top_p,
                    repeat_penalty=s.repeat_penalty,
                    tail_impl=tail_impl,
                )

            return jax.jit(run, donate_argnums=(1,))

        fn = _cache_get_or_build(self._decode_cache, knobs, build)
        return fn(
            self._weights, kv, tok, jnp.int32(slot), pads, keys, ring,
            ring_idx,
        )

    # ---- 1F1B interleaved decode (S microbatch groups in flight) ----------

    def _interleaved_body(self, n: int, window: int, s):
        """The shard_mapped 1F1B wall-step scan (see class docstring).

        Group g's token k runs on stage s at wall-step t = k*S + g + s; the
        LAST stage samples (repeat penalty -> per-row key split -> sample,
        the exact serialized-walk arithmetic on this group's row slice) and
        embeds the next token, whose ppermute hop lands on stage 0 exactly
        when that group's next stage-0 step begins. Warmup injects the
        engine-provided last tokens (k == 0); total wall-steps
        T = n*S + S - 1 cover the drain.
        """
        cfg, S = self.config, self.n_stages
        tp_axis = TP_AXIS if self.tp > 1 else None
        cos, sin = self._rope
        perm = [(j, (j + 1) % S) for j in range(S)]
        T = n * S + S - 1

        def body(stage_params, valid, head, tok0, kv, slot0, pads,
                 keys, ring, ring_idx):
            s_idx = jax.lax.axis_index(STAGE_AXIS)
            local_params = jax.tree.map(lambda a: a[0], stage_params)
            local_valid = valid[0]
            k_loc, v_loc = kv.k[0], kv.v[0]
            b = tok0.shape[0]
            bg = b // S
            max_seq = k_loc.shape[-2]
            emb_dtype = head["embed"].dtype
            hidden = head["embed"].shape[1]

            def rows(a, row0):
                return jax.lax.dynamic_slice_in_dim(a, row0, bg, 0)

            def step(carry, t):
                x_res, k_c, v_c, out, keys_c, ring_c, ridx_c = carry
                rel = t - s_idx
                g = jnp.where(rel >= 0, rel % S, 0)
                ktok = jnp.where(rel >= 0, rel // S, 0)
                active = (rel >= 0) & (ktok < n)
                row0 = g * bg
                # Stage 0 warmup: inject the engine-provided last tokens.
                tok_g = rows(tok0, row0)
                x_inject = M.embed_tokens(head, tok_g[:, None], cfg).astype(
                    emb_dtype
                )
                x_in = jnp.where(
                    (s_idx == 0) & (ktok == 0), x_inject, x_res
                )

                wpos = slot0 + ktok
                pads_g = rows(pads, row0)
                q_pos, k_pos, lengths = decode_positions(wpos, pads_g, max_seq)

                def run(x, k_c, v_c):
                    x2, kvo = batched_blocks_forward(
                        local_params, x, KVCache(k=k_c, v=v_c), cos, sin,
                        q_pos, k_pos, cfg, decode=True, pads=pads_g,
                        lengths=lengths, write_pos=wpos, valid=local_valid,
                        tp_axis=tp_axis, row_offset=row0,
                    )
                    return x2, kvo.k, kvo.v

                def skip(x, k_c, v_c):
                    return x, k_c, v_c

                x_mid, k_c, v_c = jax.lax.cond(active, run, skip, x_in, k_c, v_c)

                # Last stage: head -> penalty -> per-row sample -> emit +
                # embed the group's next token. No collectives inside (tp
                # peers take the same branch and compute identically).
                def sample_branch(args):
                    x_mid, out, keys_c, ring_c, ridx_c = args
                    logits = M.head_forward(head, x_mid, jnp.int32(1), cfg)
                    # The group's row slice walks the ONE sampling arithmetic
                    # (fused.sample_step) — bit-identical to the serialized
                    # walk by construction.
                    nxt, keys_g, ring_g, ridx_g = sample_step(
                        logits, rows(keys_c, row0), rows(ring_c, row0),
                        rows(ridx_c, row0),
                        temperature=s.temperature, top_k=s.top_k,
                        top_p=s.top_p, repeat_penalty=s.repeat_penalty,
                    )
                    if window > 0:
                        ring_c = jax.lax.dynamic_update_slice_in_dim(
                            ring_c, ring_g, row0, 0
                        )
                        ridx_c = jax.lax.dynamic_update_slice_in_dim(
                            ridx_c, ridx_g, row0, 0
                        )
                    keys_c = jax.lax.dynamic_update_slice_in_dim(
                        keys_c, keys_g, row0, 0
                    )
                    out = jax.lax.dynamic_update_slice(
                        out, nxt[:, None], (row0, ktok)
                    )
                    x_new = M.embed_tokens(head, nxt[:, None], cfg).astype(
                        emb_dtype
                    )
                    return x_new, out, keys_c, ring_c, ridx_c

                def no_sample(args):
                    return args

                x_out, out, keys_c, ring_c, ridx_c = jax.lax.cond(
                    (s_idx == S - 1) & active,
                    sample_branch, no_sample,
                    (x_mid, out, keys_c, ring_c, ridx_c),
                )
                x_res = jax.lax.ppermute(x_out, STAGE_AXIS, perm)
                return (x_res, k_c, v_c, out, keys_c, ring_c, ridx_c), None

            carry0 = (
                jnp.zeros((bg, 1, hidden), emb_dtype),
                k_loc, v_loc,
                jnp.zeros((b, n), jnp.int32),
                keys, ring, ring_idx,
            )
            (x_f, k_loc, v_loc, out, keys_f, ring_f, ridx_f), _ = jax.lax.scan(
                step, carry0, jnp.arange(T)
            )
            # Sampling state lives on the LAST stage's copy; return everything
            # stage-stacked and let the caller slice index S-1.
            return (
                out[None],
                KVCache(k=k_loc[None], v=v_loc[None]),
                keys_f[None], ring_f[None], ridx_f[None],
            )

        stack = P(STAGE_AXIS)
        return checked_shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self._layer_specs, P(STAGE_AXIS), P(), P(),
                KVCache(k=self._kv_spec, v=self._kv_spec),
                P(), P(), P(), P(), P(),
            ),
            out_specs=(
                stack,
                KVCache(k=self._kv_spec, v=self._kv_spec),
                stack, stack, stack,
            ),
        )

    def _decode_interleaved(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        window = int(ring.shape[1])
        knobs = (
            "1f1b", n, window,
            s.temperature, s.top_k, s.top_p, s.repeat_penalty,
        )

        def build():
            mapped = self._interleaved_body(n, window, s)

            def run(weights, kv, tok, slot, pads, keys, ring, ring_idx):
                stage_params, valid, head = weights
                out, kv, keys_f, ring_f, ridx_f = mapped(
                    stage_params, valid, head, tok, kv, slot, pads,
                    keys, ring, ring_idx,
                )
                last = self.n_stages - 1
                return out[last], kv, keys_f[last], ring_f[last], ridx_f[last]

            return jax.jit(run, donate_argnums=(1,))

        fn = _cache_get_or_build(self._decode_cache, knobs, build)
        b = int(tok.shape[0])
        # A scalar ring_idx (equal-length prompts) is valid on the serialized
        # walk; the group row-slicing here needs per-row rank — broadcast.
        ring_idx = jnp.broadcast_to(
            jnp.asarray(ring_idx, jnp.int32), (b,)
        )
        return fn(
            self._weights, kv, jnp.asarray(tok, jnp.int32), jnp.int32(slot),
            pads, keys, jnp.asarray(ring, jnp.int32), ring_idx,
        )


class DistributedBatchBackend:
    """Continuous batching over the TCP topology (master <-> workers).

    The reference's defining deployment — heterogeneous hosts over TCP
    (README.md:89-121) — serves API requests ONE at a time behind a global
    lock (api/mod.rs:76). This backend runs the engine's init_kv/prefill/
    decode/join seam over the SAME StageClient spans the serialized master
    walks (runtime/master.py), with the left-padded lockstep layout riding
    a ``batch`` extension of the FORWARD header (runtime/proto.py): B
    concurrent rows share every wire round trip, so TCP serving throughput
    scales with the batch instead of the request count.

    State split: the master holds embed/ln_f/lm_head + its OWN local block
    ranges (kv here = a dict of those ranges' caches; may be empty); each
    worker keeps per-connection caches for its ranges, re-made at epoch
    prefill and lane-scattered on join (runtime/worker.py _forward_batch).
    Sampling runs master-side through fused.sample_step — the one
    arithmetic every backend walks, so engine streams are token-identical
    to the local backend (pinned in tests/test_distributed_batch.py).

    Failure semantics: every epoch runs under a replay session (one sid per
    init_kv, riding each FORWARD as sid/seq — runtime/proto.py), so a
    transient wire failure mid-op is absorbed by StageClient's deadline +
    idempotent resend: the worker re-executes the lost op or answers from
    its replay cache, and the epoch continues bit-identically. Only when the
    retry budget is exhausted or the worker truly lost the session (process
    death -> SessionLost) does ``_walk`` raise ``BackendWorkerError`` — and
    then the engine finishes just this epoch's LIVE streams with
    ``finish_reason="error"`` and keeps serving (runtime/serving.py); it no
    longer takes the whole engine down. The serialized generator path keeps
    its full-history replay on top of the same per-op machinery.
    """

    shapes = ProgramShapes()

    def __init__(self, step, *, max_seq_len: int | None = None,
                 cache_dtype: jnp.dtype = jnp.bfloat16):
        from cake_tpu.parallel.topology import MASTER_NODE

        self.step = step  # DistributedForwardStep: plan, clients, head, locals
        # Capability gate: an OLD worker ignores the FORWARD ``batch`` header
        # and would run padded rows as a plain chunk — silently wrong
        # activations. Its handshake omits batch_ops (defaults False), so
        # refuse loudly here instead.
        all_verify = True
        for node, client in step.clients.items():
            info = getattr(client, "info", None)
            if info is None or not getattr(info, "batch_ops", False):
                ver = getattr(info, "version", "unknown")
                raise RuntimeError(
                    f"worker {node!r} (version {ver}) does not support "
                    "lockstep batch ops; upgrade it or drop --api-batch"
                )
            all_verify &= bool(getattr(info, "verify_ops", False))
        if not all_verify:
            # A worker without the ``verify`` kind would reject speculative
            # frames MID-EPOCH; shadow the methods so the engine's
            # capability gate falls back to plain decode instead.
            self.verify_greedy = None
            self.verify_sampled = None
        self.config = step.config
        self.max_seq_len = int(max_seq_len or step.max_seq_len)
        self.cache_dtype = cache_dtype
        self._master_node = MASTER_NODE
        # Per-epoch trace attribution: the engine sets this to the epoch's
        # head request id (runtime/serving.py) and every remote round trip
        # below carries it in the FORWARD header (runtime/proto.py).
        self.trace_id: str | None = None
        cfg = self.config
        cos, sin = model_rope_tables(cfg, self.max_seq_len)

        from cake_tpu.obs.jitwatch import tracked_jit

        bprefill, bdecode, bjoin, bverify = make_lockstep_range_ops(
            cfg, cos, sin
        )
        self._local = {
            kind: tracked_jit(
                fn, name=f"master.batch_{kind}", donate_argnames=("kv",)
            )
            for kind, fn in (
                ("prefill", bprefill),
                ("decode", bdecode),
                ("join", bjoin),
                ("verify", bverify),
            )
        }

        def embed(head, tokens):
            return M.embed_tokens(head, tokens, cfg).astype(step.dtype)

        def head_at(head, x, seq_len):
            return M.head_forward(head, x, seq_len, cfg)

        def head_all_greedy(head, x):
            from cake_tpu.models.llama.batch import verify_greedy_ids

            return verify_greedy_ids(M.head_forward_all(head, x, cfg))

        self._embed = jax.jit(embed)
        self._head = jax.jit(head_at)
        self._head_all_greedy = jax.jit(head_all_greedy)
        self._sample_cache: OrderedDict = OrderedDict()
        self._accept_cache: OrderedDict = OrderedDict()

    def init_kv(self, b: int) -> dict:
        # New epoch = new route: the replica router advances each group to
        # its next healthy member (round-robin; ejected members sit out
        # until rejoin — runtime/router.py). The route is stable for the
        # whole epoch: its replay session lives on the routed workers.
        routed = set(self.step.router.refresh().values())
        # New epoch = new replay session on every ROUTED worker: the prefill
        # at seq 0 creates fresh worker-side caches under this sid, and
        # every subsequent op of the epoch is idempotently resendable after
        # a reconnect (runtime/client.py retry path). The PREVIOUS epoch's
        # session is retired explicitly (RESET sid) wherever one exists —
        # relying on the worker's LRU alone would pin up to MAX_SESSIONS
        # dead epochs' KV pools in its device memory.
        sid = f"ep-{uuid.uuid4().hex[:12]}"
        for name, client in self.step.clients.items():
            if client.sid is not None:
                try:
                    client.reset()
                except (ConnectionError, TimeoutError, OSError):
                    pass  # dead socket: nothing deliverable to retire; the
                    # old session ages out of the worker's LRU instead
                client.sid = None
            if name in routed:
                client.begin_session(sid)
        cfg = self.config
        return {
            (lo, hi): init_cache(
                hi - lo, b, self.max_seq_len, cfg.num_key_value_heads,
                cfg.head_dim, self.cache_dtype,
            )
            for (lo, hi) in self.step.local_params
        }

    # ------------------------------------------------------------ span walk

    def _walk(self, kind: str, x, pos: int, kv: dict, batch_hdr: dict,
              local_args: tuple):
        """Run ``x`` through the full stage plan: local ranges via the jitted
        pad-aware bodies, remote spans as ONE batched round trip each."""
        from cake_tpu.runtime.worker import jax_to_wire, wire_to_jax

        step = self.step
        i = 0
        plan = step.plan
        while i < len(plan):
            s = plan[i]
            if s.node == self._master_node:
                r = (s.lo, s.hi)
                x, kv[r] = self._local[kind](
                    step.local_params[r], x, kv[r], *local_args
                )
                i += 1
            else:
                ranges = []
                primary = s.node
                while i < len(plan) and plan[i].node == primary:
                    ranges.append((plan[i].lo, plan[i].hi))
                    i += 1
                # Replica routing: the plan names the primary; the epoch's
                # route (set at init_kv, possibly flipped by failover)
                # names the serving member.
                node = step.router.route(primary)
                try:
                    out = step.clients[node].forward(
                        jax_to_wire(x), ranges, pos, batch=batch_hdr,
                        trace=self.trace_id,
                    )
                except (ConnectionError, TimeoutError, OSError) as e:
                    # Deadline/retry/replay exhausted, or the worker lost
                    # the epoch's session (SessionLost): the epoch cannot
                    # continue. Structured failure (same counter/event as
                    # the serialized path), best-effort reconnect so the
                    # NEXT epoch has a live socket, then the typed error
                    # the engine isolates instead of dying on.
                    from cake_tpu.utils import metrics

                    metrics.registry.counter(
                        "cake_hop_failures_total",
                        "Worker hops abandoned after deadline/retry "
                        "exhaustion or session loss (each one either "
                        "triggers history replay or fails its streams "
                        "with finish_reason=error).",
                    ).inc(node=node)
                    metrics.flight.record(
                        "hop-failed", self.trace_id,
                        node=node, pos=int(pos), op=kind,
                        error=str(e)[:200],
                    )
                    try:
                        step.clients[node].reconnect()
                    except (ConnectionError, TimeoutError, OSError):
                        pass  # next epoch's init_kv / walk retries the dial
                    raise BackendWorkerError(node, kind, e) from e
                # A served hop clears any probation early — the node is
                # demonstrably back (standby rejoin without waiting out
                # the cooldown).
                step.router.report_success(node)
                x = wire_to_jax(out, step.dtype)
        return x, kv

    def failover(self, node: str) -> bool:
        """Eject ``node`` and re-route its replica group for the REST of
        this epoch (runtime/router.py). True iff a healthy replica took
        over — the engine then migrates live streams onto the new route
        (runtime/serving.py); False degrades to error isolation."""
        return self.step.router.failover(node) is not None

    # ------------------------------------------------------------ engine ops

    def prefill(self, tokens, kv, pads, ends=None):
        tokens = jnp.asarray(tokens)
        b, w = tokens.shape
        pads = jnp.asarray(pads, jnp.int32)
        ends = (
            jnp.full((b,), w, jnp.int32)
            if ends is None
            else jnp.asarray(ends, jnp.int32)
        )
        x = self._embed(self.step.head, tokens)
        hdr = {
            "kind": "prefill",
            "pads": [int(p) for p in np.asarray(pads)],
            "ends": [int(e) for e in np.asarray(ends)],
        }
        x, kv = self._walk("prefill", x, 0, kv, hdr, (pads, ends))
        return self._head(self.step.head, x, ends[0]), kv

    def decode(self, kv, tok, slot, pads, keys, ring, ring_idx, n, s):
        pads = jnp.asarray(pads, jnp.int32)
        hdr_pads = [int(p) for p in np.asarray(pads)]
        knobs = (s.temperature, s.top_k, s.top_p, s.repeat_penalty)

        def build():
            # Master-side sampling: the tail fusion applies here too — the
            # wire carries activations, the tail runs on the master.
            from cake_tpu.ops.fuse import resolve_fusion

            fusions, fimpl = resolve_fusion(self.config)
            tail_impl = fimpl if "tail" in fusions else None

            def one(logits, keys, ring, ring_idx):
                return sample_step(
                    logits, keys, ring, ring_idx,
                    temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
                    repeat_penalty=s.repeat_penalty, tail_impl=tail_impl,
                )

            return jax.jit(one)

        sampler = _cache_get_or_build(self._sample_cache, knobs, build)
        tok = jnp.asarray(tok, jnp.int32)
        out = []
        for i in range(n):
            pos = int(slot) + i
            x = self._embed(self.step.head, tok[:, None])
            hdr = {"kind": "decode", "pads": hdr_pads}
            x, kv = self._walk("decode", x, pos, kv, hdr, (pads, jnp.int32(pos)))
            logits = self._head(self.step.head, x, jnp.int32(1))
            tok, keys, ring, ring_idx = sampler(logits, keys, ring, ring_idx)
            out.append(tok)
        return jnp.stack(out, axis=1), kv, keys, ring, ring_idx

    def join(self, kv, row_tokens, pads1, ends1, lane, start=0):
        assert start == 0, "a dense join computes its row from slot 0"
        row_tokens = jnp.asarray(row_tokens)
        pads1 = jnp.asarray(pads1, jnp.int32)
        ends1 = jnp.asarray(ends1, jnp.int32)
        x = self._embed(self.step.head, row_tokens)
        hdr = {
            "kind": "join",
            "pads": [int(pads1[0])],
            "ends": [int(ends1[0])],
            "lane": int(lane),
        }
        x, kv = self._walk(
            "join", x, 0, kv, hdr, (pads1, ends1, jnp.int32(lane))
        )
        return self._head(self.step.head, x, ends1[0]), kv

    # Speculative verify over the wire: ONE batched cached-chunk round trip
    # per span verifies every row's draft; acceptance runs on the master.

    def _verify_walk(self, kv, tokens, slot, pads):
        tokens = jnp.asarray(tokens)
        pads = jnp.asarray(pads, jnp.int32)
        hdr = {
            "kind": "verify",
            "pads": [int(p) for p in np.asarray(pads)],
        }
        x = self._embed(self.step.head, tokens)
        return self._walk(
            "verify", x, int(slot), kv, hdr, (pads, jnp.int32(slot))
        )

    def verify_greedy(self, kv, tokens, slot, pads):
        x, kv = self._verify_walk(kv, tokens, slot, pads)
        return self._head_all_greedy(self.step.head, x), kv

    def verify_sampled(self, kv, tokens, slot, pads, drafts, n_drafts, keys, s):
        from cake_tpu.models.llama.batch import verify_sampled_accept

        x, kv = self._verify_walk(kv, tokens, slot, pads)
        knobs = (s.temperature, s.top_k, s.top_p)

        def build():
            cfg = self.config

            def run(head, x, drafts, n_drafts, keys):
                logits = M.head_forward_all(head, x, cfg)
                return verify_sampled_accept(
                    logits, drafts, n_drafts, keys, *knobs
                )

            return jax.jit(run)

        fn = _cache_get_or_build(self._accept_cache, knobs, build)
        n_accs, nxts, keys = fn(
            self.step.head, x, jnp.asarray(drafts),
            jnp.asarray(n_drafts, jnp.int32), keys,
        )
        return n_accs, nxts, kv, keys


# ------------------------------------------------- the joiners of one step
# Below every class and bound to the paged backend here: a Mosaic kernel's
# payload carries its callers' line numbers (the dispatches above among
# them), so a line that moves up there is a new compile-cache key for every
# cell's programs, and two trees that take turns on one machine then evict
# each other's (PERF.md section 7, rows 17 and 26). A PR whose cells all
# change anyway moves this into ``_PagedBackend`` beside ``join``.


def _join_rows(self, kv, tokens, pads, ends, lanes, start=0):
    """``join`` for the joiners one step accepted together, as ONE program
    of ``shapes.join_rows`` rows (``programs.join_rows_program``): row r's
    window [start, start + width) into lane ``lanes[r]`` through that lane's
    table row, the lane's state from zero. A row the step did not fill is
    DEAD: lane -1, a table row that holds no page, pad == end; the first row
    never is. Logits [rows, vocab]: a dead row's are nobody's."""
    from cake_tpu.models.llama.programs import join_rows_program

    tokens = np.asarray(tokens)
    lanes = np.asarray(lanes, np.int32)
    live = lanes >= 0
    self._kernel_note("join", int(np.max(ends)))
    if self.kind.lane_state is not None:
        self.state_lane_writes += int(live.sum())
    table = np.where(
        live[:, None], self.allocator.block_tables[np.maximum(lanes, 0)], -1
    ).astype(np.int32)
    fn = join_rows_program(self.kind, self.config, *tokens.shape, self.allow_pallas)
    logits, kv, *counters = fn(
        self.params, kv, jnp.asarray(tokens), jnp.asarray(pads, jnp.int32),
        jnp.asarray(ends, jnp.int32), jnp.asarray(table), jnp.int32(start),
        jnp.asarray(lanes),
    )
    self._chunk_counters = (counters[0], tokens.size) if counters else None
    return logits, kv


_PagedBackend.join_rows = _join_rows


def _delta_heads(config) -> dict:
    """``engine.state``'s ``key_heads`` and ``value_heads``: a gated delta
    rule's two head counts (value heads read their key heads in groups where
    the counts differ: ``ops/delta_rule._to_value_heads``); nothing for
    another mixer. Down here for the reason above."""
    from cake_tpu.models.llama.config import GATED_DELTA

    if config.state_mixer != GATED_DELTA or not config.has_state_layers:
        return {}
    return {
        "key_heads": config.linear_num_key_heads,
        "value_heads": config.linear_num_value_heads,
    }


def _count_stepped(self, lanes: int, live: int) -> None:
    """One decode dispatch into ``engine.state``'s ``decode_lanes`` (the rows
    it is wide) and ``decode_rows`` (the rows whose state its one-token
    update READS AND WRITES, every step and state layer alike). Which mixer
    counts what: the gated delta rule's kernel walks the live rows alone
    (``ops/pallas/delta_step.py``), so it counts ``live``; Jamba's
    ``selective_step`` takes eight rows a block and steps them all, the XLA
    twins step every row through gates that make a dead one the identity,
    and a short convolution's window shifts for every row: they count
    ``lanes``. ``1 - decode_rows / decode_lanes`` is the share passed over.
    Down here for the reason above."""
    from cake_tpu.models.llama.hybrid import steps_live_rows

    self.state_decode_lanes += lanes
    self.state_decode_rows += live if steps_live_rows(self.config, self.allow_pallas) else lanes


def _decode_blocks(self, kv, known, slot, pads, keys, ring, ring_idx, n, s):
    """``_PagedBackend.decode`` for a model that generates by diffusion over
    blocks (``programs.block_decode_program``): ``n`` slots, whole blocks,
    from ``slot``; ``known`` [lanes, B] the first block's known tokens in the
    last token's place. The penalty ring passes through untouched. A lane is
    live while it holds pages, as for every kind that masks lanes."""
    from cake_tpu.models.llama import programs

    fn = programs.block_decode_program(
        self.kind, self.config, n, s.temperature, s.top_k, s.top_p, self.allow_pallas
    )
    b = int(jnp.shape(known)[0])
    live = (self.allocator.block_tables[:b] >= 0).any(axis=1)
    toks, kv, keys, counts = fn(
        self.params, kv, known, jnp.int32(slot), pads, self._tables(),
        jnp.asarray(live), keys,
    )
    # an expert dispatch is a PASS's rows: lanes x block_length
    self._chunk_counters = (counts, b * self.config.block_length)
    return toks, kv, keys, ring, ring_idx
