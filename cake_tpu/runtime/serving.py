"""Concurrent batched serving: a request queue feeding lockstep batch decode.

The reference serializes API requests behind a global write lock (api/mod.rs:76)
— SURVEY.md §2.6 calls that a quirk, not a contract. This module replaces the
lock with a scheduler: HTTP handler threads ``submit()`` requests into a queue;
one engine thread drains it, groups requests whose sampling knobs compile to
the same fused-decode trace, left-pads the group into ONE batch (the
models/llama/batch.py layout), and decodes all rows in lockstep — streaming
each row's tokens to its own consumer as every chunk lands.

Batching is CONTINUOUS: an epoch owns ``max_batch`` fixed lockstep lanes, and
at every decode-chunk boundary finished lanes free up and queued requests with
the same sampling knobs join the RUNNING epoch — a single-row prefill,
left-padded to end at the epoch's shared slot, scattered into the free lane's
KV row. Nobody waits for the batch to drain (vLLM-style admission, minus
paging: lanes are fixed-shape cache rows).

Per-request correctness is exact, not approximate:
  * Every row carries its OWN PRNG key (ops/sampling.sample_per_row), split
    per step exactly like LlamaGenerator's host loop — so row r's token stream
    is bit-identical to a single-request run with row r's seed, regardless of
    what else happens to share the batch. Tests pin this oracle.
  * Per-row repeat-penalty rings, budgets (max_tokens), and EOS: a finished
    row's lockstep lane computes discarded garbage until the batch drains
    (bounded by the chunk size times remaining rows' budgets).
  * Requests whose knobs differ (temperature/top-k/top-p/penalty — compiled
    into the trace) are NOT merged; they run as separate consecutive batches.

Decode FLOPs grow ~linearly with rows while weight HBM traffic stays constant,
so on TPU a batch of B requests streams at nearly the single-request rate for
each of them — aggregate throughput scales until the MXU saturates.

The step loop keeps ONE DECODE CHUNK IN FLIGHT ahead of the host
(``_run_epoch``): it never reads a value back from the device before it has
enqueued the next program that does not depend on that read. With chunk k
enqueued and unread, a boundary frees the lanes of rows whose budget ends
inside chunk k (that much the host can count), enqueues the joins'
prefills, first samples and lane writes and then chunk k+1 behind them, and
only then waits for chunk k's tokens and the joiners' first tokens; emitting,
sweeping, admission and page bookkeeping run while the device runs chunk k+1.
Only an EOS id is seen one chunk late: that row's lane computes one more
chunk nobody reads, over pages the row still holds. The order stays serial,
by what the code can observe and not by a flag, on a backend that may have
to redo a chunk from pre-chunk state or dispatches through the watchdog's
thread (everything but the paged single-device backends, which state
``lookahead``), in a speculative round (the host accepts the drafts), and
at a boundary that restores a spilled lane, finds the pool too short for the
next chunk, loses a worker or is stopped: such a boundary first reads what
is in flight (``_settle``). Cancellation and deadlines take effect at the
next boundary the host reaches, as before; tokens of the chunk then in
flight are dropped. ``GET /stats`` ``engine.period`` counts ``ahead`` and,
by reason, ``serial``.

Failure semantics (README "Failure semantics"): finish reasons are
``stop`` / ``length`` / ``error`` / ``cancelled`` / ``deadline``. A worker
failure that exhausts the wire retry/replay budget (BackendWorkerError)
finishes only the epoch's live streams as ``error`` — already-finished
co-batched streams were bit-identical to a fault-free run — and the engine
keeps serving. ``cancel(request_id)`` ends a queued request immediately or
a running one at the next chunk boundary, returning its KV pages mid-epoch.
Admission sheds (``EngineOverloaded`` -> HTTP 503 + Retry-After) at the
configured queue depth / free-page floor. Fault checkpoints
(runtime/faults.py ``backend.*`` sites) make all of it deterministically
testable on any backend.

Admission SLOs (README "Admission control & SLOs", runtime/admission.py):
every request carries a tenant — per-tenant token-bucket quotas and stream
caps refuse with ``QuotaExceeded`` (HTTP **429** + Retry-After, distinct
from the 503 shed), and the queue itself is deficit-weighted round-robin
across tenant subqueues so one tenant's flood cannot starve another's
admissions or joins. ``deadline_s`` is an end-to-end SLO: queued requests
expire BEFORE admission (no lane, no pages), running streams finish
``"deadline"`` at chunk boundaries, and doomed submissions (deadline below
the estimated queue wait) are shed outright. ``epoch_stall_s`` arms the
stuck-epoch watchdog: a backend dispatch that neither returns nor raises
within the bound is abandoned and isolated through the same
BackendWorkerError path a dead worker takes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
import types
from collections import deque
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message, encode_dialog
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig, Token, decode_delta
from cake_tpu.models.llama.tokenizer import Tokenizer
from cake_tpu.obs import memwatch
from cake_tpu.obs.jitwatch import tracked_jit
from cake_tpu.obs.period import PeriodAccount
from cake_tpu.obs.timeline import PROFILED_TRACK, timeline
from cake_tpu.runtime import faults, generation
from cake_tpu.runtime.admission import (
    DEFAULT_TENANT,
    FairQueue,
    QuotaExceeded,
    StallGuard,
    TenantMeter,
    WaitEstimator,
)
from cake_tpu.utils import metrics

__all__ = [
    "BatchEngine", "EngineOverloaded", "QuotaExceeded", "ServeConfig",
    "StreamHandle",
]

log = logging.getLogger("cake_tpu.serving")

_DONE = "__done__"

def _set_lane_rows(arrays, lane, values):
    return tuple(a.at[lane].set(v) for a, v in zip(arrays, values))


# Write one lane's row of each per-lane state array (token, PRNG key, pad,
# penalty ring): a join or a restore updates them together, in one program
# where eager ``.at[lane].set`` took five dispatches an array.
_set_lane = tracked_jit(_set_lane_rows, name="engine.set_lane")


def _seat_joiner_rows(
    tok, keys, ring, ring_idx, lane, first, key, row_ring, row_ring_idx
):
    window = ring.shape[1]
    if window > 0:
        ring = ring.at[lane].set(
            row_ring[0].at[row_ring_idx[0]].set(first[0])
        )
        ring_idx = ring_idx.at[lane].set((row_ring_idx[0] + 1) % window)
    return (
        tok.at[lane].set(first[0]), keys.at[lane].set(key[0]), ring, ring_idx
    )


# A joiner takes its lane ON THE DEVICE: its first token (``first`` [1], which
# the host has not read yet), the key it carries on, and its penalty ring with
# that token pushed (``batch.first_sample``'s ring arithmetic). One program,
# no host value in it, so the next decode chunk can be enqueued behind it.
_seat_joiner = tracked_jit(_seat_joiner_rows, name="engine.seat_joiner")


class EngineOverloaded(RuntimeError):
    """Admission refused by load shedding (queue depth / pool pressure).

    The API layer maps this to HTTP 503 with a ``Retry-After`` header —
    the SLO-aware refusal the multi-core NPU serving study frames: under
    overload, shedding one request early beats queueing it into a timeout.
    """

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Aggregated serving-engine knobs (one object the CLI/API layers build).

    ``kv_mode="paged"`` swaps the default local backend for the paged KV pool
    (runtime/batch_backend.PagedLocalBackend) and switches admission/join
    accounting from fixed lanes to free pages: a request is admitted iff
    ``ceil(prompt / page_size) + page_reserve`` pages are free, decode
    allocates pages incrementally at page boundaries, and finished streams
    return their pages to the pool. ``max_pages`` sizes the pool — set it
    BELOW ``max_batch * pages_per_seq`` to serve more concurrent short
    requests than the dense footprint admits at the same HBM (the capacity
    win pinned in tests/test_paged_serving.py); None keeps the dense-
    equivalent footprint (pure-parity mode).
    """

    max_batch: int = 8
    decode_chunk_size: int = 8
    admission_window: float = 0.01
    # Scheduler shape (README "Continuous scheduling"):
    #   * "epoch"      — the lockstep epoch: admission groups land together,
    #     joins at chunk boundaries, starved streams force-finish "length".
    #   * "continuous" — the per-step scheduler: no admission-window sleep,
    #     queued requests join the moment lanes/pages free (bounded by the
    #     SLO-aware per-step prefill budget), finished lanes retire
    #     immediately, and page pressure PREEMPTS the lowest-priority lane
    #     (its page chain spills host-side as history + sampling state and
    #     re-attaches later through the suffix-prefill arithmetic,
    #     bit-identical) instead of force-finishing it. Streams are
    #     bit-identical to epoch mode given the same admission order.
    scheduler: str = "epoch"  # "epoch" | "continuous"
    # Continuous mode: prompt tokens of join/restore prefill work one step
    # may dispatch before decode resumes. 0 = auto (runtime/admission.py
    # StepBudget: a base grant scaled UP while TTFT burn says the queue is
    # missing its objective and DOWN while a live stream's deadline slack
    # is inside a few chunks).
    step_prefill_tokens: int = 0
    # Prefer grouping queued requests that extend the SAME cached prefix
    # radix path into one epoch/step (prefix cache only): the shared chain
    # is forked while it is hot instead of being evicted between epochs.
    # Candidates outside the head's radix group stay queued for the next
    # epoch — a bounded deferral inside the DRR walk, never starvation.
    cache_aware_order: bool = True
    kv_mode: str = "dense"  # "dense" | "paged"
    page_size: int = 128
    max_pages: int | None = None
    page_reserve: int = 1
    # Decode hot-path op fusion (ops/fuse.py parse_fusion_spec): "none", or
    # "<set>[@impl]" with set ⊆ {norm, tail} (or "all") and impl ∈
    # {auto, pallas, xla}. Applied to the engine's model config
    # (LlamaConfig.fusion_impl) when the engine builds its own backend; an
    # explicit backend= keeps whatever its config says (README "Decode
    # fusion").
    fusion_impl: str = "none"
    # ---- failure semantics (README "Failure semantics") ----
    # Per-op wire deadline + idempotent-resend budget for TCP backends
    # (runtime/client.py), and reconnect attempts/backoff after a dead
    # socket. These thread into StageClient via the CLI / master kwargs.
    op_deadline_s: float = 30.0
    op_retries: int = 2
    reconnect_attempts: int = 3
    reconnect_backoff_s: float = 0.5
    # Heartbeat probing of workers (runtime/client.HeartbeatMonitor);
    # 0 = no probe threads.
    heartbeat_interval_s: float = 0.0
    heartbeat_deadline_s: float = 2.0
    # Admission load shedding: refuse (HTTP 503 + Retry-After) instead of
    # queueing without bound. 0 disables each gate. Gates scale with the
    # request's priority class (0 = low, 1 = normal, 2 = high): low sheds
    # first (at half the depth / twice the page floor) and waits longer
    # (Retry-After doubles); high tolerates twice the depth.
    shed_queue_depth: int = 0       # shed when the queue is this deep
    shed_min_free_pages: int = 0    # paged only: shed when the pool is this dry
    retry_after_s: float = 1.0      # hint returned with a shed
    default_priority: int = 1      # requests without an explicit class
    # ---- replica failover (README "Failover") ----
    # When a worker dies mid-epoch (BackendWorkerError) and a healthy
    # replica exists (runtime/router.py), the engine MIGRATES live streams:
    # re-prefills each stream's accumulated tokens through the new route and
    # resumes decode — greedy streams stay bit-identical to a fault-free
    # run. Bounded: at most ``max_failovers`` migrations per epoch within
    # ``failover_budget_s`` of cumulative migration wall time; past either
    # bound (or with no healthy replica) the epoch falls back to PR 6's
    # ``finish_reason="error"`` isolation. ``failover_local`` opts
    # replica-less (local/tp/mesh) backends into migration-in-place for
    # transient faults; ``failover_cooldown_s`` is the router's standby
    # rejoin probation (0 = none: an ejected member is immediately
    # eligible again, so a permanently dead worker is re-probed — and
    # re-ejected — every epoch; keep a real cooldown in production).
    max_failovers: int = 2
    failover_budget_s: float = 30.0
    failover_local: bool = False
    failover_cooldown_s: float = 5.0
    # SSE streaming backpressure: a consumer that stops reading leaves its
    # tokens queued in the stream handle; past this many buffered tokens the
    # stream is cancelled (the PR 6 cancel path — pages freed, lane
    # recycled) instead of growing memory without bound. 0 = unbounded.
    stream_buffer_tokens: int = 0
    # ---- persistent prefix cache (README "Prefix caching") ----
    # kv_mode="paged" only: finished prompts leave their prefix KV page
    # chains in a radix cache (runtime/prefix_cache.py); a later request
    # sharing the prefix forks the chain into its lane (refcounted CoW) and
    # prefills only the uncached suffix — admission charges only that
    # suffix, and the shed gate counts evictable cache pages as available.
    prefix_cache: bool = False
    # Cache budget in pages; 0 = auto (half the pool). Inserts evict LRU
    # unpinned chains past it; pool pressure evicts on demand.
    prefix_cache_pages: int = 0
    # Don't cache or serve prefixes shorter than this many tokens (churn
    # guard); 0 = any full page's worth qualifies.
    prefix_min_tokens: int = 0
    # ---- per-tenant admission & SLOs (README "Admission control & SLOs",
    # runtime/admission.py) ----
    # Token-bucket rate limit per tenant, in work tokens (prompt +
    # max_tokens) per second; refusal = HTTP 429 + Retry-After (distinct
    # from the 503 shed). 0 = unlimited.
    tenant_rate: float = 0.0
    # Bucket capacity in work tokens; 0 = auto (2x tenant_rate).
    tenant_burst: float = 0.0
    # Concurrent (queued + live) streams per tenant; 0 = uncapped.
    tenant_streams: int = 0
    # Deficit-weighted round-robin across tenant subqueues — a burst from
    # one tenant cannot starve another's admissions/joins. False = the old
    # global FIFO (the A/B the overload-storm chaos gate measures). With a
    # single tenant both schedules are identical.
    fair_queue: bool = True
    # DRR quantum in cost tokens per scheduling visit (cost = (prompt +
    # max_tokens) scaled down by the priority factor).
    fair_quantum: int = 256
    # End-to-end deadline applied to requests that carry none; 0 = none.
    # Queued requests expire BEFORE admission (no lane, no pages), running
    # streams expire at chunk boundaries (finish_reason="deadline", pages
    # freed); submissions whose deadline is already smaller than the
    # estimated queue wait are shed immediately (503).
    default_deadline_s: float = 0.0
    # Stuck-epoch watchdog: a backend dispatch making no progress within
    # this bound is abandoned and isolated through the failover/"error"
    # path (runtime/admission.StallGuard). 0 = off.
    epoch_stall_s: float = 0.0
    # ---- declared SLOs + burn tracking (README "Cluster observability &
    # SLOs", obs/slo.py) ----
    # TTFT objective in milliseconds: slo_ttft_target of accepted requests
    # must see their first token within it. 0 = no TTFT objective (the
    # tracker still records per-tenant SLIs; burn rates need an objective).
    slo_ttft_ms: float = 0.0
    slo_ttft_target: float = 0.99
    # Deadline objective: required hit rate over deadline-carrying
    # requests. 0 = off.
    slo_deadline_rate: float = 0.0
    # Burn-rate windows (fast must not exceed slow): the multiwindow rule —
    # feedback fires only while BOTH windows burn.
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0
    # Feed SLO burn back into admission (FairQueue quantum weights +
    # WaitEstimator shed scaling); False = observe/graph only.
    slo_feedback: bool = True
    # ---- black-box anomaly capture (README "Latency attribution &
    # black-box diagnostics", obs/blackbox.py) ----
    # Directory for diagnostic bundles; None/"" = capture off. A bundle is
    # written when a request breaches a declared SLO objective, lands past
    # blackbox_p99_mult x the rolling e2e p99, or dies to a watchdog stall
    # / failover / whole-epoch error — `cake-tpu doctor` renders it.
    blackbox_dir: str | None = None
    # On-disk ring bound: keep only the newest N bundles.
    blackbox_keep: int = 16
    # Global min seconds between captures (an incident storm writes one
    # bundle, not a disk full); 0 = no rate limit.
    blackbox_min_interval_s: float = 5.0
    # Rolling-p99 outlier multiplier (0 = trigger off): a finished request
    # slower than K x the rolling end-to-end p99 captures a bundle.
    blackbox_p99_mult: float = 0.0
    # ---- goodput & hardware efficiency (README "Goodput & hardware
    # efficiency", obs/efficiency.py) ----
    # Device peaks the MFU/bandwidth-utilization roofline divides by.
    # 0 = auto: the built-in table keyed by the visible device kind; on
    # CPU (no table entry) the /efficiency snapshot reports absolute
    # achieved numbers only.
    peak_tflops: float = 0.0
    peak_hbm_gbps: float = 0.0

    def __post_init__(self):
        if self.kv_mode not in ("dense", "paged"):
            raise ValueError(f"kv_mode must be dense|paged, got {self.kv_mode}")
        if self.scheduler not in ("epoch", "continuous"):
            raise ValueError(
                f"scheduler must be epoch|continuous, got {self.scheduler}"
            )
        if self.step_prefill_tokens < 0:
            raise ValueError(
                f"step_prefill_tokens must be >= 0 (0 = auto), got "
                f"{self.step_prefill_tokens}"
            )
        from cake_tpu.ops.fuse import parse_fusion_spec

        parse_fusion_spec(self.fusion_impl)  # raises on a malformed spec
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.op_deadline_s <= 0:
            raise ValueError(
                f"op_deadline_s must be positive, got {self.op_deadline_s}"
            )
        if self.op_retries < 0 or self.reconnect_attempts < 1:
            raise ValueError(
                "op_retries must be >= 0 and reconnect_attempts >= 1, got "
                f"{self.op_retries}/{self.reconnect_attempts}"
            )
        if self.shed_queue_depth < 0 or self.shed_min_free_pages < 0:
            raise ValueError("shed thresholds must be >= 0 (0 = off)")
        if self.default_priority not in (0, 1, 2):
            raise ValueError(
                f"default_priority must be 0|1|2, got {self.default_priority}"
            )
        if self.max_failovers < 0 or self.failover_budget_s <= 0:
            raise ValueError(
                "max_failovers must be >= 0 and failover_budget_s positive, "
                f"got {self.max_failovers}/{self.failover_budget_s}"
            )
        if self.failover_cooldown_s < 0 or self.stream_buffer_tokens < 0:
            raise ValueError(
                "failover_cooldown_s and stream_buffer_tokens must be >= 0"
            )
        if self.prefix_cache and self.kv_mode != "paged":
            raise ValueError(
                "prefix_cache shares physical KV pages across requests and "
                "therefore needs kv_mode='paged'"
            )
        if self.prefix_cache_pages < 0 or self.prefix_min_tokens < 0:
            raise ValueError(
                "prefix_cache_pages and prefix_min_tokens must be >= 0"
            )
        if (
            self.tenant_rate < 0
            or self.tenant_burst < 0
            or self.tenant_streams < 0
        ):
            raise ValueError(
                "tenant_rate, tenant_burst and tenant_streams must be >= 0 "
                "(0 = gate off)"
            )
        if self.fair_quantum < 1:
            raise ValueError(
                f"fair_quantum must be >= 1, got {self.fair_quantum}"
            )
        if self.default_deadline_s < 0 or self.epoch_stall_s < 0:
            raise ValueError(
                "default_deadline_s and epoch_stall_s must be >= 0 (0 = off)"
            )
        if (
            self.slo_fast_window_s <= 0
            or self.slo_slow_window_s < self.slo_fast_window_s
        ):
            raise ValueError(
                "slo windows need 0 < fast <= slow, got "
                f"{self.slo_fast_window_s}/{self.slo_slow_window_s}"
            )
        # slo_ttft_ms / targets validate in SloObjectives (obs/slo.py) —
        # constructed eagerly here so a bad flag fails at config time.
        from cake_tpu.obs.slo import SloObjectives

        SloObjectives(
            ttft_ms=self.slo_ttft_ms,
            ttft_target=self.slo_ttft_target,
            deadline_rate=self.slo_deadline_rate,
        )
        if self.blackbox_keep < 1:
            raise ValueError(
                f"blackbox_keep must be >= 1, got {self.blackbox_keep}"
            )
        if self.blackbox_min_interval_s < 0 or self.blackbox_p99_mult < 0:
            raise ValueError(
                "blackbox_min_interval_s and blackbox_p99_mult must be >= 0"
            )
        if self.peak_tflops < 0 or self.peak_hbm_gbps < 0:
            raise ValueError(
                "peak_tflops and peak_hbm_gbps must be >= 0 (0 = auto)"
            )
        if self.page_reserve < 1:
            # The admission charge is ceil(prompt/page_size) + reserve, but a
            # left-padded window straddling a page boundary can MAP one page
            # more than ceil(prompt/page_size); reserve >= 1 is what makes
            # the charge an upper bound, so epoch-start allocation can never
            # outrun what admission accounted for.
            raise ValueError(
                f"page_reserve must be >= 1, got {self.page_reserve}"
            )


@dataclasses.dataclass
class _Request:
    prompt_ids: list[int]
    max_tokens: int
    sampling: SamplingConfig
    handle: "StreamHandle"
    # Request-scoped telemetry: the trace id rides the wire frames
    # (runtime/proto.py) and keys the flight-recorder lifecycle; the
    # timestamps feed the queue-wait / TTFT / inter-token histograms.
    rid: str = ""
    t_submit: float = 0.0
    t_last_token: float = 0.0
    # Latency attribution stamps (obs/critpath.py): when the request left
    # the queue (perf_counter; 0 = not yet) and how long submit()'s
    # admission gates (quota + shed) took — both ride the request span's
    # args so GET /explain can decompose queue vs admission time.
    t_admit: float = 0.0
    admit_s: float = 0.0
    # Priority class (0 low / 1 normal / 2 high): scales the shedding
    # gates and the Retry-After hint — low sheds first under overload.
    priority: int = 1
    # Per-tenant admission (runtime/admission.py): the fair queue's
    # subqueue key and the quota-accounting label.
    tenant: str = DEFAULT_TENANT
    # Absolute end-to-end deadline (time.monotonic clock); 0.0 = none.
    # Queued past it -> expired before admission; running past it ->
    # finish_reason="deadline" at the next chunk boundary.
    deadline: float = 0.0

    def knobs(self) -> tuple:
        # Trace compatibility = batch compatibility (SamplingConfig.trace_knobs).
        return self.sampling.trace_knobs()


class StreamHandle:
    """Consumer side of one submitted request.

    ``tokens()`` yields Token objects as the engine produces them and returns
    once the stream finishes; ``text()`` blocks to completion. An engine-side
    failure re-raises here.
    """

    def __init__(self, n_prompt: int, request_id: str = ""):
        self.prompt_tokens = n_prompt
        self.completion_tokens = 0
        self.finish_reason: str = "length"
        self.request_id = request_id
        self._events: deque = deque()
        self._cv = threading.Condition()
        # Fired exactly once when the stream terminates (a _DONE or an
        # exception lands) — the ONE choke point every finish path funnels
        # through, which is what lets the tenant meter release the stream's
        # quota slot without every caller remembering to.
        self._on_close = None

    def buffered(self) -> int:
        """Events produced but not yet consumed — the per-client output
        buffer the streaming backpressure watermark bounds."""
        with self._cv:
            return len(self._events)

    # -- engine side -------------------------------------------------------
    def _emit(self, item) -> None:
        cb = None
        with self._cv:
            self._events.append(item)
            self._cv.notify()
            if item is _DONE or isinstance(item, Exception):
                cb, self._on_close = self._on_close, None
        if cb is not None:
            cb()

    # -- consumer side -----------------------------------------------------
    def tokens(self) -> Iterator[Token]:
        while True:
            with self._cv:
                while not self._events:
                    # Deliberately unbounded: the CONSUMER blocks on the
                    # engine, whose own liveness is what the stall watchdog
                    # and deadline machinery bound — a timeout here would
                    # turn backpressure into spurious stream errors.
                    self._cv.wait()  # cake-lint: disable=unbounded-wait
                item = self._events.popleft()
            if item is _DONE:
                return
            if isinstance(item, Exception):
                raise item
            yield item

    def text(self) -> str:
        return "".join(t.text for t in self.tokens())


class BatchEngine:
    """One device-owning thread serving many concurrent requests.

    Device execution goes through a batch backend (runtime/batch_backend.py):
    local single-device by default, or tensor-parallel / in-mesh pipelined —
    continuous batching composes with the model-parallel deployment modes
    instead of falling back to the serialized generator path.
    """

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params | None,
        tokenizer: Tokenizer,
        *,
        max_seq_len: int | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
        decode_chunk_size: int = 8,
        max_batch: int = 8,
        admission_window: float = 0.01,
        backend=None,
        speculative_k: int = 0,
        proposer_factory=None,
        serve: "ServeConfig | None" = None,
    ):
        if (
            serve is not None
            and serve.fusion_impl != getattr(config, "fusion_impl", "none")
            and serve.fusion_impl != "none"
        ):
            # The aggregate knob surface wins (as for the other ServeConfig
            # fields): thread the fusion spec onto the model config BEFORE
            # any backend closes over it. Only effective when the engine
            # builds its own (local/paged) backend below — an explicit
            # backend= already baked its config at construction.
            config = dataclasses.replace(config, fusion_impl=serve.fusion_impl)
        self.config = config
        self.tokenizer = tokenizer
        self.max_seq_len = int(max_seq_len or config.max_position_embeddings)
        self.cache_dtype = cache_dtype
        if serve is not None:
            # The aggregate knob object wins over the individual kwargs it
            # covers (callers pass one or the other, not both).
            decode_chunk_size = serve.decode_chunk_size
            max_batch = serve.max_batch
            admission_window = serve.admission_window
        kv_mode = serve.kv_mode if serve is not None else "dense"
        # Scheduler shape (README "Continuous scheduling"): "epoch" keeps
        # the lockstep epoch; "continuous" admits per step, retires lanes
        # immediately, and preempts (spills) instead of force-finishing.
        self.scheduler = serve.scheduler if serve is not None else "epoch"
        self.cache_aware_order = (
            serve.cache_aware_order if serve is not None else True
        )
        from cake_tpu.runtime.admission import StepBudget

        self._step_budget = StepBudget(
            serve.step_prefill_tokens if serve is not None else 0
        )
        # Host-side spill table (continuous mode): rid -> _SpilledLane. A
        # preempted lane's pages are gone; its history + sampling state
        # wait here until pages free and a restore re-attaches them. Listed
        # in _STEP_STATE: every mutation holds the engine cv (the
        # step-state-unlocked lint rule) — submit/cancel/deadline threads
        # and the engine thread all reach it.
        self._spilled: dict[str, "_SpilledLane"] = {}
        # Admission load shedding (ServeConfig): 0 = each gate off.
        self.shed_queue_depth = serve.shed_queue_depth if serve else 0
        self.shed_min_free_pages = serve.shed_min_free_pages if serve else 0
        self.retry_after_s = serve.retry_after_s if serve else 1.0
        self.default_priority = serve.default_priority if serve else 1
        # Replica failover bounds + streaming backpressure (ServeConfig).
        self.max_failovers = serve.max_failovers if serve else 2
        self.failover_budget_s = serve.failover_budget_s if serve else 30.0
        self.failover_local = serve.failover_local if serve else False
        self.stream_buffer_tokens = serve.stream_buffer_tokens if serve else 0
        # Per-epoch failover accounting (engine thread only; reset per epoch).
        self._fo_count = 0
        self._fo_spent_s = 0.0
        # ``capability.REFUSED`` again, with the facts of a programmatic
        # engine (the CLI asked before it read a weight).
        from cake_tpu.models.llama.capability import refuse_unsupported

        refuse_unsupported(
            config,
            kv_mode_dense=kv_mode != "paged",
            prefix_cache=bool(serve and serve.prefix_cache),
            speculative_k=bool(speculative_k),
            draft_model=proposer_factory is not None,
            other_backend=backend is not None
            and getattr(backend, "cache_kind", "kv") != config.cache_kind,
        )
        if config.block_length and max_batch < 2:
            refuse_unsupported(config, single_stream=True)
        if backend is None:
            if params is None:
                # Fail here, not later inside a jitted prefill with an opaque
                # tracer error: params may be None only when an explicit
                # backend already owns the placed weights.
                raise ValueError(
                    "BatchEngine needs either params (for the default local "
                    "backend) or an explicit backend="
                )
            if kv_mode == "paged":
                from cake_tpu.runtime.batch_backend import paged_backend

                pages_per_seq = -(-self.max_seq_len // serve.page_size)
                backend = paged_backend(
                    config, params,
                    max_seq_len=self.max_seq_len, cache_dtype=cache_dtype,
                    page_size=serve.page_size,
                    max_pages=serve.max_pages
                    or max(1, max_batch) * pages_per_seq,
                    page_reserve=serve.page_reserve,
                    lanes=max(1, max_batch),
                )
            else:
                from cake_tpu.runtime.batch_backend import LocalBatchBackend

                backend = LocalBatchBackend(
                    config, params,
                    max_seq_len=self.max_seq_len, cache_dtype=cache_dtype,
                )
        elif kv_mode == "paged" and getattr(backend, "kv_mode", "dense") != "paged":
            raise ValueError(
                "kv_mode='paged' needs a paged backend "
                "(runtime/batch_backend.PagedLocalBackend); the "
                f"provided {type(backend).__name__} is dense"
            )
        self.backend = backend
        # Which programs get compiled (runtime/shapes.py): the backend owns
        # the answer, every width and capacity below is asked of it.
        self.shapes = backend.shapes
        # Thread the wire-resilience knobs into a TCP backend's live
        # clients (ServeConfig is the ONE config surface; without this the
        # fields would validate and then silently do nothing for
        # programmatic engines — the CLI threads the same values into
        # DistributedForwardStep at construction, so this is idempotent).
        self._hb_clients = getattr(
            getattr(backend, "step", None), "clients", {}
        )
        if serve is not None:
            for c in self._hb_clients.values():
                if hasattr(c, "configure"):
                    c.configure(
                        op_deadline_s=serve.op_deadline_s,
                        op_retries=serve.op_retries,
                        reconnect_attempts=serve.reconnect_attempts,
                        reconnect_backoff_s=serve.reconnect_backoff_s,
                    )
        self.heartbeat_interval_s = serve.heartbeat_interval_s if serve else 0.0
        self.heartbeat_deadline_s = serve.heartbeat_deadline_s if serve else 2.0
        self.monitor = None  # HeartbeatMonitor, started with the engine
        # Replica router (TCP backends only): owns per-epoch route choice,
        # ejection, and standby rejoin (runtime/router.py); the engine
        # threads its cooldown knob and heartbeat monitor into it.
        self._router = getattr(
            getattr(backend, "step", None), "router", None
        )
        if self._router is not None and serve is not None:
            self._router.cooldown_s = serve.failover_cooldown_s
        # Paged accounting seam: the allocator (when the backend has one)
        # drives admission, page growth, and release; None = dense lanes.
        self._alloc = getattr(backend, "allocator", None)
        # A pool a kind of attention layer (``paged_cache.PagePools``) frees
        # a windowed kind's pages behind the shared slot, once a period where
        # the lanes' pages are extended; None for every other allocator,
        # whose per-period path has no such call.
        self._free_behind = (
            self._alloc.free_behind if hasattr(self._alloc, "kinds") else None
        )
        self.kv_mode = getattr(backend, "kv_mode", "dense")
        # Layers whose recurrent state a join overwrites (0 = none: every
        # family but the hybrid ones); rides the ``join`` span.
        self._state_layers = len(config.layers_of("state"))
        # Persistent prefix cache (runtime/prefix_cache.py): fork shared
        # prompt-prefix page chains at admission, prefill only the uncached
        # suffix, insert/refresh chains on finish. Paged local backend only
        # — the cache IS pool pages, and the suffix path needs the paged
        # cached-chunk prefill.
        self._prefix = None
        if serve is not None and serve.prefix_cache:
            if self._alloc is None or not hasattr(backend, "suffix_prefill"):
                raise ValueError(
                    "prefix_cache needs a paged backend with suffix-prefill "
                    "support (runtime/batch_backend.PagedLocalBackend); "
                    f"{type(backend).__name__} has neither"
                )
            from cake_tpu.runtime.prefix_cache import PrefixCache

            self._prefix = PrefixCache(
                self._alloc,
                max_pages=serve.prefix_cache_pages
                or max(1, self._alloc.pages_total // 2),
                min_tokens=serve.prefix_min_tokens,
            )
            backend.attach_prefix_cache(self._prefix)
        # Per-lane chain pins for the CURRENT epoch (engine thread only):
        # released when the lane's pages return to the pool. ``_lane_info``
        # remembers each real lane's (request, pad) so insert-on-release can
        # adopt the prompt-prefix chain without the _RowState (which is gone
        # by the time the pages actually free).
        self._lane_leases: dict[int, object] = {}
        self._lane_info: dict[int, tuple[_Request, int]] = {}
        # True once the current epoch reached its clean end and retained the
        # pool buffer; a failed epoch leaves it False and the finally path
        # clears the cache (chains must never outlive their bytes).
        self._epoch_kv_retained = False
        self.decode_chunk_size = max(1, decode_chunk_size)
        # How a lane's tokens come out of a step (``config.generation``):
        # everything the step loop does differently for a model that generates
        # by diffusion over blocks is asked of this object
        # (runtime/generation.py); a dispatch is then whole blocks.
        self._gen = generation.of(config)
        self.decode_chunk_size = self._gen.chunk(self.decode_chunk_size)
        self.max_batch = max(1, max_batch)
        self.admission_window = admission_window
        # > 0 enables batched prompt-lookup speculative decoding: every row
        # drafts K tokens from ITS OWN history, one shared cached-chunk
        # forward verifies all rows, and the epoch advances by the MINIMUM
        # accepted length across live rows (models/llama/batch.py speculative
        # section). Greedy rows stay byte-identical; sampled rows keep the
        # exact plain-decode distribution. Requires repeat_penalty == 1.0 and
        # a backend exposing verify_greedy/verify_sampled.
        self.speculative_k = max(0, speculative_k)
        # Optional drafting seam: a zero-arg callable building one proposer
        # PER LANE (models/llama/speculative.py — LookupProposer,
        # DraftModelProposer). Lane proposers persist across row joins: a
        # DraftModelProposer resyncs to the new row's history by common
        # prefix, so no invalidation protocol is needed. None = prompt
        # lookup, the stateless default.
        self.proposer_factory = proposer_factory
        self._lane_proposers: dict[int, object] = {}
        # Resolved lazily from the first factory product: an object with
        # ``propose_batch`` drafts EVERY lane in one pair of batched
        # dispatches (BatchedDraftModelProposer); otherwise one per-lane
        # proposer per lane (2 dispatches per lane per round).
        self._batched_proposer = None
        self._proposer_mode: str | None = None
        self._spare_proposer = None
        # Per-tenant admission (runtime/admission.py): quota meter (429s),
        # fair queue (DRR across tenant subqueues — the old global FIFO
        # when fair_queue=False or a single tenant), queue-wait estimator
        # (deadline-aware shedding), stuck-epoch watchdog.
        self.tenant_meter = TenantMeter(
            rate=serve.tenant_rate if serve else 0.0,
            burst=serve.tenant_burst if serve else 0.0,
            max_streams=serve.tenant_streams if serve else 0,
        )
        self._queue: FairQueue = FairQueue(
            fair=serve.fair_queue if serve else True,
            quantum=serve.fair_quantum if serve else 256,
            cost=self._req_cost,
        )
        self._wait_est = WaitEstimator()
        # Per-tenant SLO tracking + burn-rate feedback (obs/slo.py, README
        # "Cluster observability & SLOs"): SLIs record unconditionally;
        # burn rates need declared objectives (slo_ttft_ms /
        # slo_deadline_rate), and feedback (fair-queue quantum weights +
        # shed-estimate scaling) applies about once a second from the
        # scheduler loop.
        from cake_tpu.obs.slo import SloObjectives, SloTracker

        self.slo = SloTracker(
            SloObjectives(
                ttft_ms=serve.slo_ttft_ms if serve else 0.0,
                ttft_target=serve.slo_ttft_target if serve else 0.99,
                deadline_rate=serve.slo_deadline_rate if serve else 0.0,
            ),
            fast_window_s=serve.slo_fast_window_s if serve else 60.0,
            slow_window_s=serve.slo_slow_window_s if serve else 600.0,
        )
        self.slo_feedback = serve.slo_feedback if serve else True
        # tenant -> shed-estimate scale (>= 1). Replaced wholesale by
        # _apply_slo_feedback (atomic rebind; read lock-free in submit).
        self._slo_shed_scale: dict[str, float] = {}
        # Tenants currently holding a fair-queue quantum weight > 1: a
        # tenant the tracker LRU-evicts while weighted must still be
        # reset, or it would keep its boosted share forever.
        self._slo_weighted: set[str] = set()
        self._slo_next_feedback = 0.0
        self.default_deadline_s = serve.default_deadline_s if serve else 0.0
        self.epoch_stall_s = serve.epoch_stall_s if serve else 0.0
        self._guard = (
            StallGuard(self.epoch_stall_s, on_stall=self._on_epoch_stall)
            if self.epoch_stall_s > 0
            else None
        )
        self._cv = threading.Condition()
        self._stop = False
        self._thread: threading.Thread | None = None
        # Cancellation bookkeeping (all under _cv): rids of requests live in
        # the CURRENT epoch, and rids whose cancel is pending a chunk
        # boundary. Queued requests cancel immediately in cancel().
        self._live_rids: set[str] = set()
        self._cancel_ids: set[str] = set()
        # Observability (also lets tests assert real batching happened).
        self.stats = {
            "batches": 0, "rows": 0, "max_rows": 0, "joins": 0,
            "spec_rounds": 0, "spec_tokens": 0,
            # Paged mode only: streams force-finished ("length") because the
            # page pool had no free page at a decode page boundary.
            "page_truncations": 0,
            # Failure-semantics taxonomy (README): streams finished "error"
            # after a worker failure, streams cancelled, submissions shed;
            # failovers = migrations performed, recovered = live streams
            # carried through one, backpressured = streams cancelled at the
            # output-buffer watermark.
            "stream_errors": 0, "cancelled": 0, "shed": 0,
            "failovers": 0, "recovered": 0, "backpressured": 0,
            # Prefix cache: admissions/joins served a cached chain vs not
            # (cache disabled counts nothing).
            "prefix_hits": 0, "prefix_misses": 0,
            # Admission SLOs (runtime/admission.py): quota 429s, requests
            # expired past their deadline (queued or running), and backend
            # dispatches abandoned by the stuck-epoch watchdog.
            "quota_refusals": 0, "deadline_expired": 0, "epoch_stalls": 0,
            # Continuous scheduler (README "Continuous scheduling"): lanes
            # preempted under page pressure (spilled host-side) and spilled
            # lanes re-attached (bit-identical resume).
            "preemptions": 0, "restores": 0,
        }
        # The step loop's cumulative account (obs/period.py: ``engine.period`` / ``engine.segment``).
        self.periods = PeriodAccount(self.max_batch)
        self._profiled = {"sessions": 0, "open": None, "close": None}  # ``engine.profiled``
        timeline.listen(self._profiler_edge)  # the accounts at a profiler's edges: the class's end
        self._segment_args: dict = {}
        # Device values the step loop has enqueued and not read yet, oldest
        # first (``_settle``): joiners' first tokens and at most one decode
        # chunk behind the one the host waits for. ``_serial_why`` says why
        # the last chunk was read before the next was enqueued.
        self._unread: deque[_Unread] = deque()
        self._serial_why = "segment-start"
        self._t_chunk_read = 0.0
        # Latency attribution (README "Latency attribution & black-box
        # diagnostics"): live per-phase accounting — the engine knows each
        # dispatch's wall time and how many of its tokens every row
        # consumed, so the aggregate cake_phase_seconds{phase} histograms
        # and the per-epoch convoy meter cost a few float adds per chunk.
        # Engine-thread writes; /stats reads a copy (same discipline as
        # ``stats`` above).
        # One small lock: the engine thread inserts phase keys while the
        # /stats HTTP thread snapshots them (a lock-free sorted() over a
        # growing dict can raise mid-iteration).
        self._phase_lock = threading.Lock()
        self.phase_totals: dict[str, dict] = {}
        self.convoy_stats = {
            "epochs": 0, "seconds_total": 0.0, "frac_last": 0.0,
            "frac_sum": 0.0,
        }
        # Per-epoch scratch (engine thread only; reset in _run_batch).
        self._epoch_rows: list[_RowState] = []
        self._epoch_t0 = 0.0
        self._epoch_head_rid = ""
        self._epoch_stalled = False
        # Black-box anomaly capture (obs/blackbox.py): None = off.
        self.blackbox = None
        if serve is not None and serve.blackbox_dir:
            from cake_tpu.obs.blackbox import BlackBox

            self.blackbox = BlackBox(
                serve.blackbox_dir,
                keep=serve.blackbox_keep,
                min_interval_s=serve.blackbox_min_interval_s,
                p99_mult=serve.blackbox_p99_mult,
            )
        # Goodput & hardware-efficiency ledger + scheduler decision audit
        # (README "Goodput & hardware efficiency", obs/efficiency.py):
        # every dispatch's wall lands in one taxonomy bucket, every
        # emitted token in a goodput class, and every admission verdict
        # records a structured cause /explain can retrieve.
        from cake_tpu.obs.efficiency import DecisionAudit, EfficiencyLedger

        self.audit = DecisionAudit()
        self.efficiency = EfficiencyLedger(
            config=self.config,
            peak_tflops=serve.peak_tflops if serve else 0.0,
            peak_hbm_gbps=serve.peak_hbm_gbps if serve else 0.0,
            audit=self.audit,
        )
        # Traffic observatory (README "Traffic observatory"): the canonical
        # per-request completion record — every terminal outcome, refusals
        # included, lands in the bounded ring behind GET /requests and the
        # optional --request-log JSONL sink (obs/requestlog.py; the loadgen
        # replay trace format) — and the rolling SLI time-series behind
        # GET /timeseries (obs/timeseries.py; `cake-tpu top` sparklines).
        from cake_tpu.obs.requestlog import RequestLog
        from cake_tpu.obs.timeseries import SliTimeseries

        self.requestlog = RequestLog()
        self.timeseries = SliTimeseries()

    def _req_cost(self, req: "_Request") -> float:
        """DRR cost of one request: its requested work (prompt + budget),
        scaled DOWN by the priority factor so a high-priority request
        consumes half the fair-share budget and low twice — priorities bias
        service inside a tenant's share without breaking cross-tenant
        isolation."""
        return (
            len(req.prompt_ids) + req.max_tokens
        ) / self._PRIORITY_FACTOR[req.priority]

    def _on_epoch_stall(self, op: str) -> None:
        self.stats["epoch_stalls"] += 1
        # The abandoned dispatch's wall is the watchdog bound — the
        # device (or its wire path) produced nothing for it.
        self.efficiency.note_stall(self.epoch_stall_s)
        # Capture the moment, not the aftermath: the abandoned dispatch is
        # about to unwind the epoch through the error path, and the
        # timeline slice still holds the stalled chunk (StallGuard already
        # recorded the epoch-stall instant this bundle's attribution
        # subtracts from the dispatch span).
        self._epoch_stalled = True
        self._capture("stall", self._epoch_head_rid or None)

    # ------------------------------------------- latency attribution plane

    def phase_stats(self) -> dict:
        """The ``/stats`` phases block (rendered by ``cake-tpu stats``):
        aggregate per-phase seconds over finished requests plus the
        per-epoch convoy meter — the lockstep tax, visible without pulling
        a trace."""
        with self._phase_lock:
            totals = {
                p: dict(d) for p, d in self.phase_totals.items()
            }
            cv = dict(self.convoy_stats)
        return {
            "phases": {
                p: {
                    "seconds": round(d["seconds"], 6),
                    "requests": d["requests"],
                }
                for p, d in sorted(totals.items())
            },
            "convoy": {
                "epochs": cv["epochs"],
                "seconds_total": round(cv["seconds_total"], 6),
                "frac_last": round(cv["frac_last"], 4),
                "frac_mean": round(
                    cv["frac_sum"] / cv["epochs"], 4
                ) if cv["epochs"] else 0.0,
            },
        }

    def _phase_observe(self, phase: str, seconds: float) -> None:
        if seconds <= 1e-9:
            return
        metrics.registry.histogram(
            "cake_phase_seconds",
            "Per-request latency attribution by canonical phase "
            "(obs/critpath.py taxonomy; convoy = lockstep epoch tax).",
        ).observe(seconds, phase=phase)
        with self._phase_lock:
            agg = self.phase_totals.setdefault(
                phase, {"seconds": 0.0, "requests": 0}
            )
            agg["seconds"] += seconds
            agg["requests"] += 1

    def _observe_request(self, row: "_RowState") -> None:
        """Finish-time attribution for one stream: fold its measured
        phases into the aggregate histograms, then run the black-box
        triggers (SLO breach / p99 outlier)."""
        req = row.req
        # t_submit is stamped AFTER submit()'s tokenize/gate work, so the
        # queue wait already excludes it — admission is its OWN additive
        # slice, never subtracted from queue.
        queue_s = max(
            0.0, (req.t_admit or row.t_open or req.t_submit) - req.t_submit
        )
        self._phase_observe("queue", queue_s)
        self._phase_observe("admission", req.admit_s)
        for phase, v in row.phase.items():
            self._phase_observe(phase, v)
        bb = self.blackbox
        if bb is None:
            return
        e2e = req.admit_s + max(
            0.0, (row.t_close or time.perf_counter()) - req.t_submit
        )
        outlier = bb.observe_latency(e2e)
        obj = self.slo.objectives
        reason = None
        if (
            req.handle.finish_reason == "deadline"
            and obj.deadline_rate > 0
        ):
            reason = "slo-deadline"
        elif (
            obj.ttft_ms > 0
            and row.ttft_s is not None
            and row.ttft_s * 1e3 > obj.ttft_ms
        ):
            reason = "slo-ttft"
        elif outlier:
            reason = "latency-outlier"
        if reason is not None:
            self._capture(reason, req.rid)

    # Backend execution shape -> the request record's ``node`` field; TCP
    # backends report the replica router's live routes instead.
    _NODE_LABELS = {
        "LocalBatchBackend": "local",
        "PagedLocalBackend": "local",
        "TPBatchBackend": "tp",
        "PipelineBatchBackend": "pipeline",
        "DistributedBatchBackend": "tcp",
    }

    def _node_label(self) -> str:
        """Routed node(s) for the request record: a TCP backend answers
        with the replica router's CURRENT routes (so a mid-run failover is
        visible in the log), in-process backends with their shape."""
        step = getattr(self.backend, "step", None)
        router = getattr(step, "router", None)
        if router is not None:
            try:
                routes = sorted(
                    set(router.snapshot().get("routes", {}).values())
                )
            except Exception:  # noqa: BLE001 — telemetry must not raise
                routes = []
            if routes:
                return "+".join(routes)
        return self._NODE_LABELS.get(
            type(self.backend).__name__, "local"
        )

    def _record_request(
        self, req: "_Request", row: "_RowState | None" = None,
        finish: str | None = None,
    ) -> None:
        """One canonical completion record per terminated request
        (obs/requestlog.py): every finish funnel — _RowState.finish for
        admitted rows, the queued cancel/expire paths, stranded joiners,
        whole-batch errors — calls through here, so the /requests ring,
        the --request-log JSONL sink, and the /timeseries outcome tallies
        always agree with the SLO tracker on what terminated how."""
        handle = req.handle
        finish = finish or handle.finish_reason
        now = time.perf_counter()
        n = handle.completion_tokens
        t_open = row.t_open if row is not None else None
        admitted = req.t_admit or t_open
        queue_s = max(0.0, (admitted or now) - req.t_submit)
        phases = {"queue": queue_s, "admission": req.admit_s}
        if row is not None:
            phases.update(row.phase)
        phases = {
            p: round(v, 6) for p, v in phases.items() if v > 1e-9
        }
        ttft = row.ttft_s if row is not None else None
        tpot = None
        if ttft is not None and n >= 2 and req.t_last_token:
            tpot = max(
                0.0, req.t_last_token - (req.t_submit + ttft)
            ) / (n - 1)
        t_close = (row.t_close if row is not None else None) or now
        wall = req.admit_s + max(0.0, t_close - req.t_submit)
        deadline_s = None
        if req.deadline:
            # Recover the request's ORIGINAL relative deadline (replay
            # re-issues it): absolute monotonic deadline minus the submit
            # instant, reconstructed from elapsed perf_counter time —
            # both clocks tick at wall rate, so the skew is negligible.
            deadline_s = round(
                req.deadline
                - (time.monotonic() - (now - req.t_submit)), 3
            )
        obj = self.slo.objectives
        if finish == "deadline":
            verdict = "deadline_miss"
        elif obj.ttft_ms > 0 and (
            ttft is None or ttft * 1e3 > obj.ttft_ms
        ):
            verdict = "ttft_miss"
        elif obj.ttft_ms > 0 or req.deadline:
            verdict = "ok"
        else:
            verdict = "none"
        decisions = [
            f"{d['action']}:{d['cause']}"
            for d in self.audit.for_request(req.rid)
        ][:16]
        try:
            self.requestlog.record(
                request_id=req.rid,
                tenant=req.tenant,
                priority=req.priority,
                prompt_tokens=handle.prompt_tokens,
                max_tokens=int(req.max_tokens),
                completion_tokens=n,
                queue_s=round(queue_s, 6),
                admit_s=round(req.admit_s, 6),
                ttft_s=None if ttft is None else round(ttft, 6),
                tpot_s=None if tpot is None else round(tpot, 6),
                wall_s=round(wall, 6),
                finish_reason=finish,
                slo=verdict,
                phases=phases,
                decisions=decisions,
                node=self._node_label(),
                deadline_s=deadline_s,
                # Arrival wall time (replay preserves the gaps): now minus
                # the elapsed stream wall minus the admission slice that
                # ran before t_submit was stamped.
                t_wall=round(
                    time.time() - (now - req.t_submit) - req.admit_s, 3
                ),
            )
        except ValueError:
            # Schema drift is a bug the tests/lint catch; a finishing
            # stream must never die to its own telemetry.
            log.exception("request-log record failed for %s", req.rid)
        self.timeseries.observe_finish(finish)

    def _record_refusal(
        self, rid: str, tenant: str, priority: int, kind: str,
        prompt_tokens: int, max_tokens: int, deadline_s: "float | None",
        admit_s: float,
    ) -> None:
        """Refusal record (quota 429 / shed 503): never admitted, but part
        of the replayable trace — offered traffic is not a hole in the
        capture just because the server turned it away."""
        try:
            self.requestlog.record(
                request_id=rid,
                tenant=tenant,
                priority=priority,
                prompt_tokens=prompt_tokens,
                max_tokens=int(max_tokens),
                completion_tokens=0,
                queue_s=0.0,
                admit_s=round(admit_s, 6),
                ttft_s=None,
                tpot_s=None,
                wall_s=round(admit_s, 6),
                finish_reason=kind,
                slo="refused",
                phases=(
                    {"admission": round(admit_s, 6)}
                    if admit_s > 1e-9 else {}
                ),
                decisions=[],
                node=self._node_label(),
                deadline_s=deadline_s,
            )
        except ValueError:
            log.exception("request-log refusal record failed for %s", rid)
        self.timeseries.observe_finish(kind)

    def _capture(self, reason: str, rid: str | None) -> None:
        """Snapshot one diagnostic bundle (rate-limited inside BlackBox).
        Never raises: diagnostics must not take the engine down."""
        bb = self.blackbox
        if bb is None:
            return
        try:
            from cake_tpu.obs import critpath

            events = timeline.snapshot()
            exp = critpath.explain(events, rid) if rid else None
            tl_slice = (
                timeline.snapshot(rid) if rid else events[-200:]
            )
            extra: dict = {
                "engine": dict(self.stats),
                "phase_stats": self.phase_stats(),
                "slo": self.slo.snapshot(),
                "metrics": metrics.registry.snapshot(),
                "efficiency": self.efficiency.snapshot(),
                "decisions": self.audit.snapshot(limit=50),
            }
            if self._alloc is not None:
                extra["pool"] = {
                    "pages_total": self._alloc.pages_total,
                    "pages_free": self._alloc.pages_free,
                }
            if self._prefix is not None:
                extra["prefix"] = self._prefix.stats()
            bb.capture(
                reason, rid, explain=exp, timeline=tl_slice,
                events=metrics.flight.snapshot()[-200:], extra=extra,
            )
        except Exception:  # noqa: BLE001 — diagnostics never hurt serving
            log.exception("blackbox capture failed")

    def _finish_epoch_convoy(self) -> None:
        """Per-epoch convoy meter, finalized in _run_batch's finally: the
        rows' accumulated convoy shares (padding + unconsumed chunk
        fractions) plus lane idle time (a lane that sat finished or empty
        while the epoch kept serving co-batched streams).
        ``convoy_frac`` normalizes by served-lane-seconds, so 0 = no tax
        and 1 = the epoch spent ALL its lane time on convoy."""
        rows = self._epoch_rows
        if not rows or self._epoch_t0 <= 0.0:
            return
        now = time.perf_counter()
        dur = max(1e-9, now - self._epoch_t0)
        lane_occ: dict[int, float] = {}
        convoy = 0.0
        for row in rows:
            if row.t_open:
                occ = max(0.0, (row.t_close or now) - row.t_open)
                lane_occ[row.lane] = lane_occ.get(row.lane, 0.0) + occ
            convoy += row.phase.get("convoy", 0.0)
        idle = 0.0
        if self.scheduler != "continuous":
            # Epoch-mode tax only: a lockstep epoch keeps a lane
            # occupied-shaped while unable to serve the queue. Under the
            # continuous scheduler an empty lane is admission HEADROOM —
            # anything admissible would have joined this very step, so the
            # meter bills only the real per-row convoy shares (padding +
            # unconsumed chunk fractions), which go to ~0 by construction.
            idle = sum(
                max(0.0, dur - min(occ, dur)) for occ in lane_occ.values()
            )
        total = convoy + idle
        frac = min(1.0, total / (dur * max(1, len(lane_occ))))
        metrics.registry.histogram(
            "cake_convoy_seconds",
            "Per-epoch lockstep convoy tax: lane-seconds spent on "
            "co-batched streams' work + finished/idle lane time.",
        ).observe(total)
        metrics.registry.gauge(
            "cake_convoy_frac",
            "Last epoch's convoy fraction of served-lane-seconds "
            "(0 = no lockstep tax).",
        ).set(frac)
        with self._phase_lock:
            cv = self.convoy_stats
            cv["epochs"] += 1
            cv["seconds_total"] += total
            cv["frac_last"] = frac
            cv["frac_sum"] += frac

    def tenant_stats(self) -> dict:
        """Per-tenant view for ``/stats``: quota accounting (meter) plus
        the fair queue's current depths."""
        out = self.tenant_meter.snapshot()
        with self._cv:
            queued = self._queue.queued_by_tenant()
        for tenant, n in queued.items():
            out.setdefault(tenant, {})["queued"] = n
        return out

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        if self._thread is not None:
            return
        if self.heartbeat_interval_s > 0 and self._hb_clients:
            # Engine-owned worker liveness (ServeConfig heartbeat knobs):
            # one PING prober per worker of the TCP backend's step.
            from cake_tpu.runtime.client import HeartbeatMonitor

            self.monitor = HeartbeatMonitor(
                {n: c.host for n, c in self._hb_clients.items()},
                interval_s=self.heartbeat_interval_s,
                deadline_s=self.heartbeat_deadline_s,
            ).start()
            if self._router is not None:
                # Routing consumes the liveness view: an unhealthy member
                # leaves rotation at the next refresh, and its recovery
                # (plus cooldown) readmits it — standby rejoin.
                self._router.attach_monitor(self.monitor)
        self._thread = threading.Thread(
            target=self._loop, name="batch-engine", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._guard is not None:
            # BEFORE joining the engine thread: it may be parked inside the
            # guard's bounded wait on a genuinely stalled dispatch — the
            # guard's stop wakes it immediately (as a worker-error, not a
            # counted stall) instead of stop() riding out the full bound.
            self._guard.stop()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if self.monitor is not None:
            self.monitor.stop()
            self.monitor = None

    # ------------------------------------------------------------ submission

    def submit(
        self,
        messages: list[Message],
        max_tokens: int,
        sampling: SamplingConfig,
        request_id: str | None = None,
        priority: int | None = None,
        tenant: str | None = None,
        deadline_s: float | None = None,
    ) -> StreamHandle:
        """Queue one chat completion; returns immediately with its stream.

        ``request_id`` (the API's chatcmpl id, or a fresh one) keys this
        request's flight-recorder lifecycle and wire-frame trace attribution.
        ``priority`` (0 low / 1 normal / 2 high; ServeConfig
        ``default_priority`` otherwise) scales the load-shedding gates — low
        priority sheds first and is told to retry later. ``tenant`` keys the
        per-tenant quota gates and the fair queue (runtime/admission.py;
        ``QuotaExceeded`` -> HTTP 429 + Retry-After); ``deadline_s``
        (ServeConfig ``default_deadline_s`` otherwise; 0/None = none) is the
        end-to-end SLO — queued past it the request expires unadmitted,
        running past it the stream finishes ``"deadline"`` at the next chunk
        boundary, and a deadline the estimated queue wait already exceeds is
        shed immediately. Raises ValueError for over-length prompts and bad
        deadlines (the server maps both to 400 BEFORE any streaming headers
        go out).
        """
        t_enter = time.perf_counter()
        ids = self.tokenizer.encode(
            encode_dialog(messages, self.config.dialog_template)
        )
        self._gen.check(sampling)
        # Left-pad bucket rounding can add slots ahead of the prompt; require
        # room for the bucket plus at least one generated token. Same helper
        # as the actual layout (models/llama/batch.py) so they cannot drift.
        bucket_ceiling = self.shapes.prompt_width(len(ids), self.max_seq_len)
        if bucket_ceiling >= self.max_seq_len:
            raise ValueError(
                f"prompt is {len(ids)} tokens but the context window "
                f"is {self.max_seq_len}"
            )
        if self._alloc is not None:
            # A prompt needing more pages than the whole pool can NEVER be
            # admitted — refuse here (maps to 400) rather than queueing it
            # forever behind the free-page admission gate.
            need = self._alloc.pages_needed(len(ids)) + self._alloc.reserve_pages
            if need > self._alloc.pages_total:
                raise ValueError(
                    f"prompt needs {need} KV pages (page_size "
                    f"{self._alloc.page_size}) but the pool holds "
                    f"{self._alloc.pages_total}"
                )
        if priority is None:
            priority = self.default_priority
        priority = max(0, min(2, int(priority)))
        tenant = (str(tenant).strip() if tenant else "") or DEFAULT_TENANT
        if deadline_s is None and self.default_deadline_s > 0:
            deadline_s = self.default_deadline_s
        if deadline_s is not None and (
            not isinstance(deadline_s, (int, float)) or deadline_s <= 0
        ):
            raise ValueError(
                f"deadline_s must be a positive number, got {deadline_s!r}"
            )
        rid = request_id or metrics.new_request_id()
        # Quota gates first (429 beats 503: a refusal the caller can fix by
        # backing off is more actionable than "server busy"); admit()
        # registers the stream atomically, so any later refusal must
        # close() it again.
        try:
            self.tenant_meter.admit(
                tenant, rid, len(ids) + int(max_tokens)
            )
        except QuotaExceeded:
            self.stats["quota_refusals"] += 1
            self.slo.observe_refusal(tenant, "quota")
            self._record_refusal(
                rid, tenant, priority, "quota", len(ids), max_tokens,
                deadline_s, time.perf_counter() - t_enter,
            )
            raise
        try:
            self._maybe_shed(
                len(ids), priority, deadline_s=deadline_s, tenant=tenant
            )
        except EngineOverloaded:
            # Refund: the quota grant above charged the caller's bucket,
            # but a shed is SERVER saturation — without the credit back,
            # 503-hinted retries would drain the tenant's own budget on
            # zero-work submissions and surface as spurious 429s.
            self.tenant_meter.close(rid, refund=True)
            self._record_refusal(
                rid, tenant, priority, "shed", len(ids), max_tokens,
                deadline_s, time.perf_counter() - t_enter,
            )
            raise
        handle = StreamHandle(n_prompt=len(ids), request_id=rid)
        handle._on_close = lambda: self.tenant_meter.close(rid)
        req = _Request(
            ids, max_tokens, sampling, handle,
            rid=rid, t_submit=time.perf_counter(), priority=priority,
            tenant=tenant,
            deadline=(
                time.monotonic() + deadline_s if deadline_s else 0.0
            ),
            # Tokenize + quota + shed wall time: the "admission" slice of
            # the queue phase in the /explain decomposition.
            admit_s=time.perf_counter() - t_enter,
        )
        # Record BEFORE enqueueing: once the queue holds the request the
        # scheduler may admit it immediately, and an 'admitted' flight event
        # must never precede its 'submitted'. (A stopped-engine raise below
        # leaves a lone 'submitted' event — an honest timeline for a refusal.)
        metrics.registry.counter(
            "cake_engine_submitted_total", "Requests accepted into the queue."
        ).inc()
        metrics.flight.record(
            "submitted", rid,
            prompt_tokens=len(ids), max_tokens=int(max_tokens),
        )
        with self._cv:
            if self._stop:
                self.tenant_meter.close(rid, refund=True)
                raise RuntimeError("engine is stopped")
            self._queue.append(req)
            self._cv.notify_all()
        return handle

    # Priority classes scale the shedding gates: low (0) sheds at half the
    # depth / double the page floor and is told to retry twice as late;
    # high (2) tolerates double the depth — so under overload low-priority
    # traffic degrades first (the first slice of per-tenant fairness).
    _PRIORITY_FACTOR = {0: 0.5, 1: 1.0, 2: 2.0}

    # Per-step scheduler state shared between the engine thread and the
    # submit/cancel/API threads under the continuous scheduler's
    # admit-anytime model. Declaring it here is the step-state-unlocked
    # lint contract (cake_tpu/analysis/rules/scheduler.py): every mutation
    # of these attributes must hold the engine cv — unlike
    # unlocked-shared-mutation, which only fires once SOME site is
    # guarded, the declaration enforces the invariant even before the
    # first correct site exists.
    _STEP_STATE = ("_spilled",)

    def _maybe_shed(
        self, n_prompt: int, priority: int = 1,
        deadline_s: float | None = None, tenant: str = DEFAULT_TENANT,
    ) -> None:
        """Admission load shedding: refuse NOW (503 + Retry-After at the API)
        rather than queueing into a timeout. Three gates: queue depth and
        paged-pool pressure (each off at 0, both scaled by the request's
        priority class), plus the deadline-aware gate — when the request
        carries a deadline the ESTIMATED queue wait (EWMA of observed
        waits, scaled by depth) already exceeds, queueing it is a
        guaranteed timeout that would still pin pages when it finally ran;
        refusing is strictly kinder."""
        factor = self._PRIORITY_FACTOR[priority]
        reason = None
        with self._cv:
            depth = len(self._queue)
        est = (
            self._wait_est.estimate(
                depth, self.max_batch,
                # SLO burn feedback: a tenant already missing objectives
                # gets an inflated estimate — its doomed-deadline
                # submissions shed earlier instead of queueing work that
                # would miss anyway (obs/slo.py adjustments).
                scale=self._slo_shed_scale.get(tenant, 1.0),
            )
            if deadline_s
            else 0.0
        )
        cause = ""
        if deadline_s and est > deadline_s:
            cause = "deadline_doomed"
            reason = (
                f"estimated queue wait {est:.2f}s already exceeds the "
                f"request deadline {deadline_s:.2f}s"
            )
        elif self.shed_queue_depth and depth >= self.shed_queue_depth * factor:
            cause = "queue_depth"
            reason = (
                f"queue depth {depth} >= {self.shed_queue_depth * factor:g} "
                f"(priority {priority})"
            )
        elif self.shed_min_free_pages and self._alloc is not None:
            # Pages reclaimable by prefix-cache eviction count as available:
            # admission evicts before mapping, so a full-but-COLD cache is
            # capacity, not pressure — without this a cache that grew to the
            # pool floor would shed forever (shed-after-evict ordering is
            # pinned in tests/test_prefix_serving.py).
            free_eff = self._alloc.pages_free + (
                self._prefix.reclaimable() if self._prefix is not None else 0
            )
            if free_eff < self.shed_min_free_pages / factor:
                cause = "page_pressure"
                reason = (
                    f"{free_eff} free+reclaimable KV pages < floor "
                    f"{self.shed_min_free_pages / factor:g} "
                    f"(priority {priority})"
                )
        if reason is None:
            return
        self.audit.record("shed", cause, tenant=tenant, detail=reason[:120])
        self.stats["shed"] += 1
        self.slo.observe_refusal(tenant, "shed")
        metrics.registry.counter(
            "cake_shed_total",
            "Submissions refused by admission load shedding "
            "(queue-depth / free-page gates; HTTP 503 + Retry-After).",
        ).inc()
        metrics.flight.record(
            "shed", prompt_tokens=n_prompt, reason=reason,
            priority=priority,
        )
        raise EngineOverloaded(
            f"engine overloaded: {reason}",
            retry_after_s=self.retry_after_s / factor,
        )

    # ---------------------------------------------------------- cancellation

    def cancel(self, request_id: str) -> bool:
        """Cancel one request by id (the chat response id).

        Queued: removed and finished immediately with
        ``finish_reason="cancelled"``. Running: finished at the next chunk
        boundary — its lane's pages return to the pool mid-epoch and the
        lane frees up for joins, so an abandoned stream stops burning decode
        steps. Returns False for ids that are not queued or live (already
        finished, or never existed) — cancel is idempotent.
        """
        sp = None
        with self._cv:
            for r in self._queue:
                if r.rid == request_id:
                    self._queue.remove(r)
                    self._finish_cancelled_locked(r)
                    return True
            sp = self._spilled.pop(request_id, None)
            if sp is None and request_id in self._live_rids:
                self._cancel_ids.add(request_id)
                return True
        if sp is not None:
            # A spilled lane holds no pages and no device state: cancel is
            # immediate — finish the stream here, off the engine thread
            # (same taxonomy as a mid-epoch cancel, zero pages to free).
            self._note_cancelled(sp.row, "spilled")
            sp.row.cancel()
            return True
        return False

    def quiesce(self, timeout: float = 30.0) -> bool:
        """Block until the page pool is idle: every lane's pages returned,
        only the prefix cache (if any) still holding pages.

        A stream CLOSES (its last token and end-of-stream are emitted) at
        the chunk boundary, BEFORE the epoch's insert-on-finish/release
        bookkeeping runs on the engine thread — so a caller that read
        end-of-stream and immediately inspects pool state or clears the
        cache races live allocator mutation (and a ``clear()`` that loses
        the race leaves the just-finished prompts' chains behind). Polling
        here is the one supported way to wait that race out; the bench and
        the chaos/prefix tests all come through this method. Returns False
        on timeout; dense engines are always idle."""
        if self._alloc is None:
            return True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            held = (
                self._prefix.stats()["pages"]
                if self._prefix is not None
                else 0
            )
            if self._alloc.pages_free == self._alloc.pages_total - held:
                return True
            time.sleep(0.01)
        return False

    def _finish_cancelled_locked(self, req: _Request) -> None:
        """Close a never-admitted request as cancelled (queue removal)."""
        req.handle.finish_reason = "cancelled"
        self.stats["cancelled"] += 1
        metrics.registry.counter(
            "cake_cancelled_total", "Requests cancelled (queued or live)."
        ).inc()
        metrics.flight.record("cancelled", req.rid, where="queued")
        metrics.flight.record(
            "finished", req.rid, finish_reason="cancelled",
            completion_tokens=0,
        )
        self._record_request(req)
        req.handle._emit(_DONE)

    def _fail_spilled_locked(self, error: str) -> None:
        """Close every spilled stream with a raised error (caller holds the
        cv — the stop path): a parked lane must never outlive the engine."""
        for sp in self._spilled.values():
            sp.row.req.handle._emit(RuntimeError(error))
            sp.row.req.handle._emit(_DONE)
            sp.row.close_span(error=error)
        # The _locked suffix is the contract: every caller already holds
        # the engine cv around this call (the stop and epoch-error paths).
        # cake-lint: disable-next-line=step-state-unlocked, unlocked-shared-mutation
        self._spilled.clear()

    def _expire_queued(self, req: _Request) -> None:
        """Close a queued request whose end-to-end deadline passed before
        admission: it never occupies a lane or maps a page — the whole
        point of expiring BEFORE admission instead of discovering the
        deadline mid-decode (caller removes it from the queue)."""
        req.handle.finish_reason = "deadline"
        self.stats["deadline_expired"] += 1
        self.audit.record(
            "expire", "deadline_expired", rid=req.rid, tenant=req.tenant,
            detail="queued",
        )
        metrics.registry.counter(
            "cake_deadline_expired_total",
            "Requests past their end-to-end deadline (where=queued expired "
            "before admission; where=running at a chunk boundary).",
        ).inc(where="queued")
        metrics.flight.record("deadline-expired", req.rid, where="queued")
        metrics.flight.record(
            "finished", req.rid, finish_reason="deadline",
            completion_tokens=0,
        )
        timeline.instant(
            "deadline-expired", rid=req.rid, track="engine",
            args={"where": "queued"},
        )
        # SLO view: a deadline miss AND (by definition — no first token
        # within any bound) a TTFT miss for this tenant (obs/slo.py).
        self.slo.observe_finish(
            req.tenant, "deadline",
            had_deadline=True, got_first_token=False,
        )
        self._record_request(req)
        req.handle._emit(_DONE)

    def _apply_deadlines(self, rows: list) -> None:
        """Chunk-boundary deadline sweep: running streams past their
        deadline finish ``"deadline"`` NOW (their lanes free this very
        round, pages release in the caller's _release_finished pass), and
        queued requests past theirs expire without ever admitting."""
        now = time.monotonic()
        for lane, row in enumerate(rows):
            if (
                row is not None
                and row.req.deadline
                and now > row.req.deadline
            ):
                self.stats["deadline_expired"] += 1
                self.audit.record(
                    "expire", "deadline_expired", rid=row.req.rid,
                    tenant=row.req.tenant, detail="running",
                )
                row.expire()
                rows[lane] = None
        expired_spills = []
        with self._cv:
            for rid, sp in list(self._spilled.items()):
                if sp.row.req.deadline and now > sp.row.req.deadline:
                    del self._spilled[rid]
                    expired_spills.append(sp)
        for sp in expired_spills:
            # A spilled lane past its deadline never restores: no pages to
            # free, the stream's delivered tokens stand (row.expire counts
            # the where=running metric — the stream WAS running when
            # preempted, the spill just parked it).
            self.stats["deadline_expired"] += 1
            sp.row.expire()
        if self._queue.deadline_count:
            expired = []
            with self._cv:
                for r in self._queue:
                    if r.deadline and now > r.deadline:
                        self._queue.remove(r)
                        expired.append(r)
            for r in expired:
                self._expire_queued(r)

    def _shed_backpressure(self, row: "_RowState") -> None:
        """Streaming backpressure: a consumer that stopped draining its
        stream handle has ``stream_buffer_tokens`` tokens parked in the
        per-client output buffer — treat it like a gone client
        (runtime/api.py ``_client_gone``) and route the stream into the
        cancel path: it finishes ``"cancelled"`` at this chunk boundary,
        returning its pages and lane, instead of growing memory without
        bound."""
        self.stats["backpressured"] += 1
        metrics.registry.counter(
            "cake_stream_backpressure_total",
            "Streams cancelled at the output-buffer high watermark "
            "(consumer stopped reading).",
        ).inc()
        metrics.flight.record(
            "stream-backpressure", row.req.rid,
            buffered=row.req.handle.buffered(),
            watermark=self.stream_buffer_tokens,
        )
        log.warning(
            "stream %s backpressured (%d tokens buffered >= %d); cancelling",
            row.req.rid, row.req.handle.buffered(), self.stream_buffer_tokens,
        )
        with self._cv:
            if row.req.rid in self._live_rids:
                self._cancel_ids.add(row.req.rid)

    def _row_finished(self, rid: str) -> None:
        """Row lifecycle hook (called by _RowState.finish): drop the rid
        from the live/cancel sets so cancel() answers honestly."""
        with self._cv:
            self._live_rids.discard(rid)
            self._cancel_ids.discard(rid)

    def _apply_cancels(self, rows: list) -> None:
        """Chunk-boundary cancellation sweep: finish flagged rows as
        "cancelled" and free their lanes (pages release in the caller's
        _release_finished pass)."""
        with self._cv:
            if not self._cancel_ids:
                return
            pending = set(self._cancel_ids)
        for lane, row in enumerate(rows):
            if row is not None and row.req.rid in pending:
                self.stats["cancelled"] += 1
                metrics.registry.counter(
                    "cake_cancelled_total",
                    "Requests cancelled (queued or live).",
                ).inc()
                metrics.flight.record(
                    "cancelled", row.req.rid, where="epoch",
                    completion_tokens=row.n,
                )
                row.cancel()
                rows[lane] = None

    # ------------------------------------------------------------ scheduler

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._spilled and not self._stop:
                    # Deliberately unbounded: the idle scheduler park;
                    # submit(), cancel-of-spilled, and stop() all notify
                    # under this cv (spills themselves are created by this
                    # thread, never while it parks here).
                    self._cv.wait()  # cake-lint: disable=unbounded-wait
                if self._stop:
                    for r in self._queue:
                        r.handle._emit(RuntimeError("engine stopped"))
                    self._queue.clear()
                    self._fail_spilled_locked("engine stopped")
                    return
            self.periods.work_seen()
            # Admission window: let a burst of concurrent submissions land so
            # they batch together instead of trickling into 1-row batches.
            # The continuous scheduler skips it — requests admit the moment
            # the step loop sees them; batching happens per step, not per
            # admission decision.
            if self.admission_window > 0 and self.scheduler != "continuous":
                time.sleep(self.admission_window)
            self._apply_slo_feedback()
            # Pending spills run FIRST: they are previously admitted work —
            # a spill-seeded segment restores them as its seed rows, and
            # queued requests with the same knobs join it per step.
            with self._cv:
                spill_seed = self.scheduler == "continuous" and bool(
                    self._spilled
                )
            batch = [] if spill_seed else self._admit()
            if not batch and not spill_seed:
                continue
            if batch:
                # Spill-seeded segments account themselves in _run_epoch
                # once the seed size is known (and a seed that dissolves —
                # every spill cancelled/doomed first — counts nothing).
                self._note_batch_started(len(batch))
            try:
                self._run_batch(batch)
            except Exception as e:  # noqa: BLE001 — surface to every consumer
                log.exception("batch failed")
                for r in batch:
                    if r.handle._on_close is not None:
                        # Stream not yet terminated (a closed handle's
                        # _on_close has fired and cleared): this consumer
                        # is about to see the raised error — count it in
                        # the tenant's error SLI, once (already-finished
                        # co-batched rows were observed at their finish).
                        self.slo.observe_finish(
                            r.tenant, "error",
                            had_deadline=bool(r.deadline),
                            got_first_token=r.handle.completion_tokens > 0,
                        )
                        self._record_request(r, finish="error")
                    r.handle._emit(e)
                    r.handle._emit(_DONE)

    def _note_batch_started(self, n_rows: int) -> None:
        """Epoch/segment-start accounting, shared by queue admissions
        (_loop) and spill-seeded segments (_run_epoch, once the seed size
        is known)."""
        self.stats["batches"] += 1
        self.stats["rows"] += n_rows
        self.stats["max_rows"] = max(self.stats["max_rows"], n_rows)
        metrics.registry.counter(
            "cake_engine_batches_total", "Decode epochs started."
        ).inc()
        metrics.registry.histogram(
            "cake_batch_rows",
            "Requests admitted per epoch at epoch start.",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        ).observe(n_rows)

    def _apply_slo_feedback(self, force: bool = False) -> None:
        """Feed per-tenant burn rates back into admission (obs/slo.py):
        burning tenants get a FairQueue quantum weight > 1 (their queue
        drains ahead) and an inflated WaitEstimator shed scale (their
        doomed-deadline submissions refuse earlier). Rate-limited to about
        once a second — the windows move on second granularity, and the
        scheduler loop calls this every iteration."""
        if not self.slo_feedback:
            return
        now = time.monotonic()
        if not force and now < self._slo_next_feedback:
            return
        self._slo_next_feedback = now + 1.0
        adj = self.slo.adjustments()
        if not adj and not self._slo_shed_scale and not self._slo_weighted:
            return
        self._slo_shed_scale = {
            t: a["shed_scale"]
            for t, a in adj.items()
            if a["shed_scale"] > 1.0
        }
        with self._cv:
            for t, a in adj.items():
                self._queue.set_weight(t, a["quantum_weight"])
            # A weighted tenant the tracker evicted (LRU past its tenant
            # cap) no longer appears in adjustments — reset it here, or
            # its boosted share would outlive the burn that earned it.
            for t in self._slo_weighted - set(adj):
                self._queue.set_weight(t, 1.0)
        self._slo_weighted = {
            t for t, a in adj.items() if a["quantum_weight"] > 1.0
        }

    def _backend_guard(self, op: str) -> None:
        """Fault checkpoint in front of a backend dispatch (runtime/faults.py
        ``backend.*`` sites): ``stall`` sleeps, ``kill``/``crash`` raise the
        same typed failure a dead worker produces — so the engine's isolation
        path is testable on ANY backend, not just live TCP clusters."""
        spec = faults.check(f"backend.{op}")
        if spec is None:
            return
        if spec.kind == "stall":
            faults.sleep(spec)
        elif spec.kind in ("kill", "crash"):
            from cake_tpu.runtime.batch_backend import BackendWorkerError

            raise BackendWorkerError("<fault-plan>", op)

    def _dispatch(self, op: str, fn, readback=None):
        """Run one backend dispatch (fault checkpoint included) under the
        stuck-epoch watchdog. With ``epoch_stall_s`` off this is exactly
        the old inline guard+call; with it on, the dispatch runs on the
        guard's watchdog thread — MATERIALIZED (block_until_ready) so a
        device that accepts the async dispatch but hangs at readback is
        caught too — and a stall (a backend that neither returns nor
        raises — the PR 6 ``stall`` fault kind, a wedged device) is
        abandoned within the bound and surfaced as the same
        ``BackendWorkerError`` a dead worker produces, so it flows through
        failover/error isolation instead of parking the engine forever.

        Abandonment contract: the stalled dispatch keeps running on its
        (disposable, daemon) thread. That is SAFE on the in-process
        backends — jax arrays are immutable, the late result is discarded,
        and the failed epoch's pool buffer is replaced wholesale by the
        next epoch's ``init_kv`` (a failed prefix-cache epoch also clears
        its chains) — so the stale computation can only ever read dead
        bytes, never write live ones. On the TCP backends the wire layer's
        own per-op deadlines/retries (``op_deadline_s``) already convert a
        hung worker into BackendWorkerError without the watchdog, so the
        guard is the local/device half of the same bound, not a substitute
        for wire deadlines.

        Without the watchdog the call ENQUEUES (a ``dispatch`` span) and
        returns device values nobody has waited for: the step loop reads a
        decode chunk's tokens and a joiner's first token later, through
        ``_settle``, after it has enqueued the next program. ``readback``
        is for a caller that needs the result at once (a speculative round:
        the host accepts the drafts): the wait is a ``readback`` span and
        the call returns ``(out, readback(out))``. Under the watchdog the
        engine thread only waits, so the whole call is ``readback`` and
        nothing is ever in flight behind it (``_run_epoch`` keeps such an
        engine serial)."""
        if self._guard is None:
            self._backend_guard(op)
            with self._phase("dispatch"):
                out = fn()
            if readback is None:
                return out
            with self._phase("readback"):
                return out, readback(out)

        def job():
            self._backend_guard(op)
            # Block on EVERY output leaf while still on the watchdog
            # thread: dispatch-accepted-but-readback-hung is the wedged-
            # device shape the watchdog exists for.
            out = jax.block_until_ready(fn())
            return out if readback is None else (out, readback(out))

        with self._phase("readback"):
            return self._guard.call(job, op=op)

    def _read(self, entry: "_Unread") -> np.ndarray:
        """The one place the step loop waits for the device: the host copy
        of an enqueued value, a ``readback`` span. Read once; ``_settle``
        finds the copy on the entry."""
        if entry.host is None:
            args: dict = {}
            with self._phase("readback", args=args) as wait:
                entry.host = np.asarray(entry.value)
                if entry.counters is not None:
                    # The same program's: ready when its tokens are.
                    args.update(self.backend.absorb_chunk_counters(
                        entry.counters, decode=bool(entry.n)
                    ))
            entry.t_read = time.perf_counter()
            if not entry.n:
                self.periods.note_join_wait(wait.seconds)
        return entry.host

    def _take_counters(self):
        """What the program just enqueued returned beside its tokens, still
        on the device (a latent model's account of its expert layer; None
        from every other backend). Read with the tokens, in ``_read``."""
        take = getattr(self.backend, "take_chunk_counters", None)
        return take() if take is not None else None

    def _settle(self, rows: list, keep: int = 0, why: str = "") -> None:
        """Read and emit, oldest first, all that is enqueued but the newest
        ``keep`` values (1: the chunk just enqueued stays in flight while the
        host does the boundary's work under it; 0: a boundary that must see
        every token first). ``why`` names that reason for the period
        account: the next chunk will not have been enqueued ahead."""
        if why and any(entry.n for entry in self._unread):
            self._serial_why = why
        while len(self._unread) > keep:
            entry = self._unread[0]
            host = self._read(entry)
            self._unread.popleft()
            with self._phase("emit"):
                if entry.n:
                    self._emit_chunk(rows, entry, host)
                else:
                    self._emit_first(rows, entry, self._gen.first_token(host, entry))

    def _emit_chunk(self, rows: list, entry: "_Unread", toks_np) -> None:
        """A decode chunk's tokens reach their streams: the rows are the
        chunk's own, as they stood when it was enqueued (a row that was
        cancelled since takes nothing; one whose budget the chunk fills
        has left ``rows`` already and finishes here)."""
        # The chunk's wall as the host sees it: from when the device could
        # start it (its enqueue, or the end of the chunk before it) to its
        # tokens. Feeds the step-budget clock (continuous): deadline slack
        # is measured in recent chunk walls.
        dt_chunk = entry.t_read - max(entry.t0, self._t_chunk_read)
        self._t_chunk_read = entry.t_read
        self._step_budget.observe_chunk(dt_chunk)
        n = entry.n
        # (a first block's known prompt tokens are nobody's to stream)
        known = entry.known
        consumed = {
            lane: row.peek_consumed(toks_np[lane][known.get(lane, 0):])
            for lane, row in entry.rows
        }
        self._gen.note(self.backend, consumed, known)
        # Hardware ledger: the chunk computed B x n positions — consumed
        # ones are decode goodput, live-but-unconsumed tails are convoy,
        # dead lanes are pad. Noted BEFORE the pushes for the same
        # flush-ordering reason as account_decode below.
        self.efficiency.note_decode(
            dt_chunk, len(rows), n, len(entry.rows),
            sum(consumed.values()), slot=entry.slot,
        )
        for lane, row in entry.rows:
            # Account BEFORE pushing: a row that finishes mid-chunk flushes
            # its attribution from inside push() -> finish(), so the final
            # chunk's decode share (and its unconsumed-tail convoy — the
            # very number the convoy meter exists for) must already be on
            # the row by then.
            row.inflight -= n - known.get(lane, 0)
            row.account_decode(dt_chunk, n, consumed[lane])
            for t in toks_np[lane][known.get(lane, 0):]:
                row.push(int(t))
                if row.done:
                    break
            if row.done and rows[lane] is row:
                rows[lane] = None
        self._release_finished(rows)

    def _emit_first(self, rows: list, entry: "_Unread", first: int | None) -> None:
        """A joiner's first token, read with its boundary's other values
        (None: a block-diffusion joiner has none, its join is accounted
        alone and its first tokens come with its first block)."""
        (lane, row), = entry.rows
        dt_join = entry.t_read - entry.t0
        row.account_join(dt_join)
        # Hardware ledger: one lane x W window, the prompt's share is
        # useful prefill, the left-padding is pad.
        self.efficiency.note_prefill(
            dt_join, 1, entry.width,
            min(len(row.req.prompt_ids), entry.width),
        )
        if first is None:
            return
        row.inflight -= 1
        row.push(first)
        if row.done and rows[lane] is row:
            rows[lane] = None

    def _open_rows(self, rows: list) -> list["_RowState"]:
        """Every row of the epoch whose stream is still open: the lanes'
        rows, and the rows that left their lane by count (the budget ends
        inside a chunk not read yet) and wait in ``_unread`` for it."""
        out = [row for row in rows if row is not None]
        for entry in self._unread:
            for _, row in entry.rows:
                if not row._finished and row not in out:
                    out.append(row)
        return out

    @contextlib.contextmanager
    def _phase(self, name: str, *, rid: str | None = None,
               args: dict | None = None):
        """One span of the step loop, recorded twice from one clock: on the
        timeline's engine track (and so in an open profiler window) and, as
        self time by name, in the cumulative period account. Yields the
        span; ``seconds`` is its duration once it has closed."""
        span = types.SimpleNamespace(seconds=0.0)
        with timeline.span(name, rid=rid, track=PROFILED_TRACK, args=args):
            self.periods.push(name)
            try:
                yield span
            finally:
                span.seconds = self.periods.pop()

    @contextlib.contextmanager
    def _period(self, slot: int):
        """Root span of one iteration of the step loop. The body sets
        ``dispatched``, ``live``, ``steps`` and ``order`` (``"ahead"``, or why
        the chunk was not enqueued ahead) on the yielded arguments once it has
        dispatched a chunk or a round; an iteration that did not is no
        period (obs/period.py)."""
        with self._cv:
            queued = bool(self._queue) or bool(self._spilled)
        args = {"slot": int(slot), "queued": queued, "dispatched": False}
        self.periods.begin(queued)
        with timeline.span("period", track=PROFILED_TRACK, args=args):
            try:
                yield args
            finally:
                self.periods.end(
                    args["live"] if args["dispatched"] else None,
                    args.get("order", ""), args.get("cached", 0), args.get("steps", 0),
                )

    # ------------------------------------------------- replica failover
    # Transparent recovery (README "Failover"): when a worker dies after
    # the wire retry budget (BackendWorkerError) and a healthy replica
    # exists, the epoch's live streams MIGRATE instead of finishing
    # "error" — each stream's accumulated tokens (prompt + generated so
    # far) re-prefill through the new route as one batched windowed
    # prefill, and decode resumes at the same slot with the same sampling
    # state. Greedy streams are bit-identical to a fault-free run.

    def _failover_or_raise(self, e) -> None:
        """Gate one failover attempt; re-raises ``e`` when migration is not
        possible (no healthy replica, budget burned, or too many attempts
        this epoch) so the caller degrades to PR 6's error isolation."""
        if self._fo_count >= self.max_failovers:
            log.warning("failover limit reached (%d); degrading", self._fo_count)
            raise e
        if self._fo_spent_s >= self.failover_budget_s:
            log.warning(
                "failover budget burned (%.2fs >= %.2fs); degrading",
                self._fo_spent_s, self.failover_budget_s,
            )
            raise e
        failover = getattr(self.backend, "failover", None)
        if failover is not None:
            # TCP: eject the dead member and re-route its replica group
            # (runtime/router.py records cake_failover_total + the event).
            if not failover(e.node):
                raise e  # no healthy replica left for that span
        elif not self.failover_local:
            raise e  # replica-less backend without the in-place opt-in
        else:
            # In-place retry on a local/tp/mesh backend (transient fault):
            # same observability the router gives the TCP path.
            metrics.registry.counter(
                "cake_failover_total",
                "Failovers away from a worker (labelled by the FAILED "
                "node).",
            ).inc(node=e.node)
            metrics.flight.record("failover", node=e.node, to=e.node)
        self._fo_count += 1
        self.stats["failovers"] += 1
        # Post-mortem bundle at the migration decision (rate-limited): the
        # flight tail still holds the worker-death breadcrumbs.
        self._capture("failover", self._epoch_head_rid or None)

    def _migrate_kv(self, rows: list, B: int, slot: int):
        """Rebuild every live stream's KV on the (re-routed) backend.

        At a chunk boundary the invariant is: ``row.history`` holds prompt +
        all emitted tokens, KV covers slots ``[pad, slot)`` =
        ``history[:-1]``, and ``history[-1]`` is the pending token at
        ``slot``. So migration is ONE batched prefill of each live row's
        ``history[:-1]`` into a window ending at the shared slot — the same
        per-row ``ends`` arithmetic as a continuous-batching join — after a
        fresh ``init_kv`` (new replay session on the new route; paged: pool
        reset + per-lane remap). Sampling state (keys/rings) is host/master
        state and rides through untouched.
        """
        t0 = time.perf_counter()
        live = [(lane, row) for lane, row in enumerate(rows) if row is not None]
        with timeline.span(
            "failover-migrate", track="router",
            args={"slot": int(slot), "live": len(live)},
        ):
            # The re-prefill window rides the SAME epoch capacity as every
            # other dispatch (one-capacity rule): W >= slot still holds
            # because the capacity always covers the epoch's slot ceiling.
            capw = self.max_seq_len
            if hasattr(self.backend, "capacity_slots"):
                capw = min(capw, self.backend.capacity_slots())
            _, W = self.shapes.window(0, slot, capw)
            tokens = np.zeros((B, W), np.int32)
            pads = np.full((B,), slot - 1, np.int32)
            # Dummy/finished lanes carry a 1-token bos window: garbage
            # nobody reads, exactly like epoch-start dummy lanes.
            tokens[:, slot - 1] = self.config.bos_token_id
            ends = np.full((B,), slot, np.int32)
            for lane, row in live:
                hist = row.history[:-1]  # KV prefix; history[-1] is pending
                tokens[lane, slot - len(hist): slot] = hist
                pads[lane] = slot - len(hist)
            if self._prefix is not None:
                # Migration rebuilds the pool from ZERO on the new route:
                # every cached chain's bytes die with the old pool, so the
                # chains, their pins, and the stale retained buffer go too.
                # Live lanes' prefixes re-prefill below and re-insert on
                # finish (their _lane_info pads are the original pads —
                # history only ever grows to the right of the prompt).
                self._prefix.clear(reason="failover-migrate")
                self.backend.drop_retained_kv()
                self._lane_leases.clear()
            kv = self.backend.init_kv(B)
            if self._alloc is not None:
                for lane, _ in live:
                    self._alloc.map_range(lane, int(pads[lane]), slot)
                self._pool_counter()
            if self._prefix is not None:
                # Cache-enabled epochs were prefilled through the cached-
                # chunk arithmetic; the rebuilt KV must be too, or the
                # resumed decode reads ulp-different bytes and greedy
                # streams stop being bit-identical to the fault-free run.
                # Thresholds at the pads = all-fresh; the dead tail past
                # ``slot`` writes nothing (those slots are unmapped).
                _, kv = self._dispatch(
                    "prefill",
                    lambda: self.backend.suffix_prefill(
                        tokens, kv, jnp.asarray(pads),
                        np.asarray(pads, np.int32), 0,
                    ),
                )
            else:
                _, kv = self._dispatch(
                    "prefill",
                    lambda: self.backend.prefill(
                        tokens, kv, jnp.asarray(pads), ends=jnp.asarray(ends)
                    ),
                )
        dt = time.perf_counter() - t0
        self._fo_spent_s += dt
        # Hardware ledger: the migration's re-prefill is redone work a
        # worker death cost the device.
        self.efficiency.note_failover(dt)
        self.stats["recovered"] += len(live)
        metrics.registry.histogram(
            "cake_failover_seconds",
            "Wall seconds per live-stream migration (re-prefill through "
            "the failed-over route).",
        ).observe(dt)
        metrics.registry.counter(
            "cake_streams_recovered_total",
            "Live streams carried through a failover migration (vs "
            "cake_stream_errors_total when no replica could take over).",
        ).inc(len(live))
        metrics.flight.record(
            "failover-migrated", live=len(live), slot=int(slot),
            seconds=round(dt, 6),
        )
        log.warning(
            "failover migration: %d live stream(s) re-prefilled at slot %d "
            "in %.3fs", len(live), slot, dt,
        )
        return kv

    def _pages_for(self, req: _Request, end_slot: int | None = None) -> int:
        """Admission price of one request: prompt pages + the reserve, LESS
        the cached-prefix discount — a warm request pays pages only for its
        uncached suffix (forked chain pages are already allocated and merely
        gain a reference).

        The discount depends on the lane's pad alignment. A JOIN knows it
        exactly (``end_slot`` = the epoch's shared slot); epoch-start
        admission estimates it from the request's solo bucket — exact for
        the homogeneous traffic that hits most (a shared system prompt with
        same-shape suffixes), conservative-or-optimistic otherwise, which is
        safe: the epoch-start mapping degrades a mispriced row to a cold
        prefill (or a page-truncated finish) instead of failing the epoch.
        """
        n = len(req.prompt_ids)
        served = 0
        if self._prefix is not None:
            end = (
                end_slot
                if end_slot is not None
                else self.shapes.prompt_width(n, self.max_seq_len)
            )
            served = self._prefix.match_tokens(
                req.prompt_ids, (end - n) % self._alloc.page_size
            )
        return self._alloc.pages_needed(n - served) + self._alloc.reserve_pages

    # ------------------------------------------------- prefix-cache wiring
    # Fork-at-admission / insert-on-release (runtime/prefix_cache.py): a
    # lane whose prompt extends a cached chain splices the chain's pages
    # into its block table (+1 ref each, pinned by a lease) and computes
    # only the uncached tail; when its pages return to the pool the prompt-
    # prefix chain is adopted back into the cache instead of freed.

    def _fork_lane(
        self, lane: int, req: _Request, pad: int, end: int,
        ids: list[int] | None = None,
    ):
        """Fork the longest cached chain under one lane, split the boundary
        page when the fresh region starts mid-page (make_private — the
        first divergent write must never scribble a shared page), and map
        the uncached tail [fresh, end). ``ids`` overrides the matched token
        sequence (a spilled lane's restore matches its HISTORY — which
        starts with the prompt, so the cached prompt chain still serves its
        head); default is the request's prompt.

        Returns (fresh, cow_pair): the first slot the lane must compute AND
        the first it may write (the write_starts threshold), plus the
        (src, dst) physical pages of a boundary split the CALLER must
        copy_pages before any write lands (None when the chain ends on a
        page boundary) — returned, not applied, so an epoch's splits batch
        into ONE device copy. Raises PageExhausted only when even on-demand
        cache eviction cannot supply the tail's pages (the admission
        estimate priced a different alignment class)."""
        from cake_tpu.models.llama.paged_cache import PageExhausted

        fresh = pad
        pair = None
        plan = self._prefix.fork(
            lane, ids if ids is not None else req.prompt_ids, pad,
            rid=req.rid,
        )
        if plan is None:
            self.stats["prefix_misses"] += 1
        else:
            self.stats["prefix_hits"] += 1
            self._lane_leases[lane] = plan.lease
            fresh = pad + plan.served
            if plan.cow_logical is not None:
                try:
                    pair = self._alloc.make_private(lane, plan.cow_logical)
                except PageExhausted:
                    if self._prefix.reclaim(1, rid=req.rid):
                        pair = self._alloc.make_private(
                            lane, plan.cow_logical
                        )
                    else:
                        # Degraded split: give the shared page back and
                        # recompute its tokens into a fresh page map_range
                        # allocates below — never write a shared page.
                        self._alloc.unmap_page(lane, plan.cow_logical)
                        fresh = max(
                            pad, plan.cow_logical * self._alloc.page_size
                        )
                        pair = None
        try:
            self._alloc.map_range(lane, fresh, end)
        except PageExhausted:
            # Cold cache pages are reclaimable capacity, not pressure:
            # evict enough for the tail and retry once.
            self._prefix.reclaim(
                self._alloc.pages_needed(end - fresh) + 1, rid=req.rid
            )
            self._alloc.map_range(lane, fresh, end)
        self._lane_info[lane] = (req, pad)
        return fresh, pair

    def _prefix_layout(
        self, reqs: list, rows: list, pads, bucket: int, kv,
        ids_list: list | None = None,
    ):
        """Epoch-start lane layout under the prefix cache: fork every real
        lane's longest cached chain and map only its uncached tail.
        ``ids_list`` overrides the per-lane matched tokens (spill-seeded
        segments lay out histories, not prompts).

        Returns (kv, write_starts [B] int32) — the caller dispatches the
        windowed suffix prefill with these per-lane fresh thresholds (cold
        lanes' thresholds sit at their pads: full compute, every write
        lands). A lane that cannot get its pages even after on-demand
        eviction force-finishes as "length": pool pressure degrades one
        stream, never the epoch."""
        ws = np.asarray(pads, np.int32).copy()
        cow_src: list[int] = []
        cow_dst: list[int] = []
        # The fork pass is its own (nested) span so /explain can report
        # prefix-cache fork time apart from the prefill compute around it;
        # the finally below keeps it closed on the worker-death paths too
        # (the span-leak rule's own discipline).
        fork_span = timeline.begin(
            "prefix-fork", track="engine", args={"lanes": len(reqs)},
        )
        try:
            return self._prefix_layout_inner(
                reqs, rows, pads, bucket, kv, ws, cow_src, cow_dst, ids_list
            )
        finally:
            timeline.end(fork_span)

    def _prefix_layout_inner(
        self, reqs, rows, pads, bucket, kv, ws, cow_src, cow_dst,
        ids_list=None,
    ):
        from cake_tpu.models.llama.paged_cache import PageExhausted

        for lane, r in enumerate(reqs):
            if r is None:
                # Dummy lanes hold no pages; park their threshold at the
                # window tail so they never stretch the suffix window.
                ws[lane] = bucket - 1
                continue
            try:
                fresh, pair = self._fork_lane(
                    lane, r, int(pads[lane]), bucket,
                    ids=ids_list[lane] if ids_list is not None else None,
                )
            except PageExhausted:
                row = rows[lane]
                self.stats["page_truncations"] += 1
                row.req.handle.finish_reason = "length"
                metrics.flight.record(
                    "page-truncated", r.rid, slot=int(pads[lane]),
                    where="admission", completion_tokens=0,
                )
                row.finish()
                rows[lane] = None
                reqs[lane] = None
                if self._alloc.lane_mapped(lane):
                    self._lane_recycle(lane, insert=False)
                else:
                    self._prefix.release(self._lane_leases.pop(lane, None))
                    self._lane_info.pop(lane, None)
                ws[lane] = bucket - 1
                continue
            ws[lane] = fresh
            if pair is not None:
                cow_src.append(pair[0])
                cow_dst.append(pair[1])
        if cow_src:
            # One batched device copy for every lane's boundary split (a
            # per-lane copy would rewrite the whole pool buffer B times).
            kv = self.backend.cow_copy(kv, cow_src, cow_dst)
        self._pool_counter()
        return kv, ws

    def _admit(self) -> list[_Request]:
        """Take the fair-order head request plus every queued request with
        the same sampling knobs, up to max_batch. Others stay queued.

        The scan order is the fair queue's deficit-weighted round-robin
        across tenants (runtime/admission.py) — per-tenant FIFO inside each
        subqueue, the old global FIFO when a single tenant (or
        ``fair_queue=False``) is in play. Expired-deadline requests are
        dropped here, BEFORE they can occupy a lane or map pages.

        Paged mode admits by FREE-PAGE accounting on top of the knob/lane
        rules: each candidate charges ``ceil(prompt / page_size) + reserve``
        pages against the pool (fresh at epoch start — the previous epoch
        released every lane); candidates that do not fit stay queued while
        smaller later ones may still land, which is exactly how a page pool
        beats slot accounting under short/variable-length load."""
        now = time.monotonic()
        state = {"knobs": None, "avail": None, "ckey": None}

        def radix_key(r: _Request):
            # The request's cached-prefix radix group at its solo-bucket
            # alignment (the same estimate _pages_for prices admission
            # with): requests extending the same cached chain share a key.
            n = len(r.prompt_ids)
            align = (
                self.shapes.prompt_width(n, self.max_seq_len) - n
            ) % self._alloc.page_size
            return self._prefix.radix_key(r.prompt_ids, align)

        def defer(r: _Request, cause: str) -> str:
            # Decision audit (obs/efficiency.py): the verdict AND its
            # structured cause, so /explain answers "why was this queued".
            self.audit.record("defer", cause, rid=r.rid, tenant=r.tenant)
            return "skip"

        def accept(r: _Request) -> str:
            if r.deadline and now > r.deadline:
                self._expire_queued(r)
                return "drop"
            if state["knobs"] is None:
                # Fair-order head: defines the epoch's knobs: always taken
                # (submit() refused prompts over pool size, and the pool is
                # fresh — only cold prefix-cache pages can sit on the free
                # list, reclaimed on demand before charging).
                state["knobs"] = r.knobs()
                if self._prefix is not None and self.cache_aware_order:
                    # Cache-aware ordering (ROADMAP): the head's radix
                    # group defines the epoch's; candidates outside it
                    # defer one epoch so the head's chain is forked while
                    # hot — grouped traffic stops thrashing the cache
                    # between epochs (hit-rate pin in
                    # tests/test_prefix_serving.py). DRR bounds hold: the
                    # deferral is a "skip" inside the fair walk, and the
                    # next epoch's head is taken unconditionally.
                    state["ckey"] = radix_key(r)
                if self._alloc is not None:
                    need = self._pages_for(r)
                    free = self._alloc.pages_free
                    if need > free and self._prefix is not None:
                        free += self._prefix.reclaim(need - free, rid=r.rid)
                    state["avail"] = free - need
                self.audit.record(
                    "admit", "fair_order", rid=r.rid, tenant=r.tenant
                )
                return "take"
            if r.knobs() != state["knobs"]:
                return defer(r, "knob_incompatible")
            if state["ckey"] is not None and radix_key(r) != state["ckey"]:
                return defer(r, "cache_group")
            if state["avail"] is not None:
                need = self._pages_for(r)
                if need > state["avail"] and self._prefix is not None:
                    state["avail"] += self._prefix.reclaim(
                        need - state["avail"], rid=r.rid
                    )
                if need > state["avail"]:
                    return defer(r, "page_pressure")
                state["avail"] -= need
            self.audit.record(
                "admit", "fair_order", rid=r.rid, tenant=r.tenant
            )
            return "take"

        with self._cv:
            if not self._queue:
                return []
            group = self._queue.take(self.max_batch, accept)
            if not group:
                return []
            # Register as live while STILL under the lock that popped them:
            # cancel() must never observe a request as neither queued nor
            # live while it is on its way into an epoch.
            self._live_rids.update(r.rid for r in group)
        t_admit = time.perf_counter()
        for r in group:
            r.t_admit = t_admit  # queue-phase boundary for /explain
        self._record_admissions(group, "admitted")
        return group

    def _record_admissions(
        self, reqs: list[_Request], event: str, **fields
    ) -> None:
        """Queue-wait histogram + lifecycle event for requests leaving the
        queue — epoch admissions and continuous joins share the telemetry."""
        now = time.perf_counter()
        wait_h = metrics.registry.histogram(
            "cake_queue_wait_seconds",
            "Seconds a request waited in the queue before admission.",
        )
        counter = metrics.registry.counter(
            "cake_engine_admitted_total",
            "Requests admitted into a decode epoch (initial or join).",
        )
        for r in reqs:
            wait = now - r.t_submit
            wait_h.observe(wait)
            # Feed the deadline-aware shed estimator (admission.py): the
            # EWMA of these waits is what "estimated queue wait" means.
            self._wait_est.observe(wait)
            counter.inc()
            metrics.flight.record(
                event, r.rid, queue_wait_s=round(wait, 6), **fields
            )

    # -------------------------------------------------- execution (epochs)
    # Continuous batching: see the module docstring. An epoch = fixed lanes +
    # one shared slot counter; joins happen at chunk boundaries.

    def _run_batch(self, batch: list[_Request]) -> None:
        """One epoch, with failure ISOLATION (the taxonomy README documents):

        * ``BackendWorkerError`` (a worker died after the retry/replay budget
          — or an injected fault standing in for one) finishes only the
          epoch's LIVE streams with ``finish_reason="error"``; streams that
          already finished are untouched (their output was bit-identical to
          a fault-free run), pages return to the pool, and the engine keeps
          draining the queue.
        * Any OTHER exception is a bug: it reaches EVERY row admitted so far
          — including continuous-batching joiners that are no longer in
          ``batch`` or the queue — as a raised error, so no consumer can
          hang on a lost request."""
        from cake_tpu.runtime.batch_backend import BackendWorkerError

        rows: list[_RowState | None] = []
        # Fresh failover budget per epoch (count + cumulative migration
        # wall time); _run_epoch's dispatch sites consume it.
        self._fo_count = 0
        self._fo_spent_s = 0.0
        self._epoch_kv_retained = False
        # Fresh attribution scratch: the convoy meter and the blackbox's
        # stall/error captures are per-epoch.
        self._epoch_rows = []
        self._epoch_t0 = time.perf_counter()
        with self._cv:
            head_rid = batch[0].rid if batch else next(
                iter(self._spilled), ""
            )
        self._epoch_head_rid = head_rid
        self._epoch_stalled = False
        # The root span's arguments are filled as the segment learns them
        # (_run_epoch: lanes, prompt bucket, capacity, its prefill's seconds,
        # why it ended) and serialize when it closes.
        with self._cv:
            queue_depth = len(self._queue)
        self._segment_args = seg_args = {
            "rows": len(batch),
            "queue_depth": queue_depth,
            "kv_mode": self.kv_mode,
            "scheduler": self.scheduler,
            # Kernel vs fallback choice, resolved exactly as the batched
            # forward resolves it at trace time — so a trace captured on
            # CPU says "xla" and one on TPU says "pallas" without reading
            # configs.
            "attention_impl": M.resolve_attention_impl(
                self.config.attention_impl
            ),
            "fusion_impl": self.config.fusion_impl,
            "prefill_s": 0.0,
            "ended": "error",
        }
        t_segment = time.perf_counter()
        try:
            # The epoch span roots this epoch's timeline tree: prefill /
            # decode-chunk / join / page-extend spans nest under it, lane
            # tracks carry each request from admission to finish, and the
            # head request's id keys GET /trace?request_id=... retrieval.
            # Continuous mode calls the same structure a SEGMENT (one
            # contiguous shared-slot run) and nests a `step` span per
            # scheduler iteration inside it.
            with timeline.span(
                "epoch" if self.scheduler != "continuous" else "segment",
                rid=head_rid, track="engine", args=seg_args,
            ):
                self._run_epoch(batch, rows)
        except BackendWorkerError as e:
            # Failure isolation: degrade the affected streams, not the fleet.
            log.warning("epoch lost its worker: %s", e)
            if not self._epoch_stalled and not self._stop:
                # A stall already captured its own bundle a moment ago (and
                # the rate limit would fold this one into it anyway); a
                # plain stop() mid-epoch is an operator action, not an
                # anomaly worth a bundle.
                self._capture("epoch-error", self._epoch_head_rid or None)
            for row in self._open_rows(rows):
                row.fail(str(e))
            rows[:] = [None] * len(rows)
        except Exception as e:  # noqa: BLE001 — surface to every consumer
            log.exception("epoch failed")
            for row in self._open_rows(rows):
                row.req.handle._emit(e)
                row.req.handle._emit(_DONE)
                row.close_span(error=str(e))
            # A non-worker exception is a bug: spilled streams must not
            # retry a deterministically failing seed forever — close them
            # with the same error every other consumer sees.
            with self._cv:
                self._fail_spilled_locked(str(e))
            # _loop's handler covers rows that never made it into `rows`.
            raise
        finally:
            self.periods.segment_done(
                time.perf_counter() - t_segment, seg_args["prefill_s"]
            )
            # Paged: the epoch is over — EVERY lane's pages go back to the
            # pool (also on the error path, so _admit always sees the whole
            # pool free at the next epoch start). A CLEAN epoch end first
            # adopts each lane's prompt-prefix chain into the prefix cache
            # (insert-on-finish); a failed one must not — its pool bytes are
            # suspect and its buffer was not retained, so the whole cache is
            # cleared instead (chains never outlive their bytes).
            if self._alloc is not None:
                for lane in range(len(rows)):
                    if self._alloc.lane_mapped(lane):
                        self._lane_recycle(lane, insert=self._epoch_kv_retained)
            if self._prefix is not None and not self._epoch_kv_retained:
                self._prefix.clear(reason="epoch-failed")
                self.backend.drop_retained_kv()
            self._lane_leases.clear()
            self._lane_info.clear()
            if hasattr(self.backend, "set_epoch_capacity"):
                # The capacity dies with its epoch: direct backend use
                # between epochs (tests, drains) sees the full table again.
                self.backend.set_epoch_capacity(None)
            # The lockstep tax, measured: rows' convoy shares + lane idle
            # (also on error paths — a failed epoch's tax is still real).
            self._finish_epoch_convoy()
            # Whatever path ended the epoch, nothing in it is live anymore:
            # cancel() must answer False for these rids from here on.
            with self._cv:
                self._live_rids.difference_update(r.rid for r in batch)
                self._cancel_ids.difference_update(r.rid for r in batch)
                for row in self._open_rows(rows):
                    self._live_rids.discard(row.req.rid)
                    self._cancel_ids.discard(row.req.rid)
            self._unread.clear()  # and the device values it held

    def _run_epoch(self, batch: list[_Request], rows: list) -> None:
        from cake_tpu.models.llama.batch import (
            first_sample,
            layout_prompts,
            seed_rings,
        )

        seed_spills: list[_SpilledLane] = []
        if not batch:
            # Spill-seeded segment (continuous scheduler): the oldest
            # spill's knob group restores as the seed rows — their page
            # chains rebuild through the prefill arithmetic below, their
            # sampling state rides back from the host copies — and queued
            # requests with the same knobs join per step as usual.
            seed_spills = self._pop_spill_seed()
            if not seed_spills:
                self._segment_args["ended"] = "empty"
                return
            self._note_batch_started(len(seed_spills))
            head = seed_spills[0].row.req
            s, knobs = head.sampling, head.knobs()
        else:
            s, knobs = batch[0].sampling, batch[0].knobs()
        eos = set(self.config.eos_token_ids)
        if hasattr(self.backend, "trace_id"):
            # Wire-frame trace attribution (runtime/proto.py): remote hops of
            # this epoch carry the head request's id. An epoch serves many
            # rows; the head id identifies the epoch in worker-side logs.
            self.backend.trace_id = self._epoch_head_rid
        n_seed = len(batch) or len(seed_spills)
        B = self.shapes.lanes(n_seed, self.max_batch)
        window = s.repeat_last_n

        # Lay out the initial group over B fixed lanes; spare lanes carry a
        # 1-token dummy prompt (bos) and are immediately free for joins.
        # A spill-seeded segment lays out each restored row's
        # ``history[:-1]`` instead (the KV the suffix arithmetic rebuilds;
        # ``history[-1]`` is the pending token at the shared slot — the
        # _migrate_kv invariant).
        if seed_spills:
            reqs: list[_Request | None] = [
                sp.row.req for sp in seed_spills
            ] + [None] * (B - n_seed)
            ids_list = [
                sp.row.history[:-1] for sp in seed_spills
            ] + [[self.config.bos_token_id]] * (B - n_seed)
            for lane, sp in enumerate(seed_spills):
                sp.row.lane = lane
                sp.row.t_close = 0.0
                rows.append(sp.row)
            rows.extend([None] * (B - n_seed))
            # (registered live by _pop_spill_seed, under its table lock)
        else:
            reqs = list(batch) + [None] * (B - len(batch))
            ids_list = [
                self._gen.prefilled(r) if r is not None else self._gen.dead_row()
                for r in reqs
            ]
            rows.extend(
                _RowState(r, eos, self.tokenizer, lane=lane, engine=self)
                if r is not None
                else None
                for lane, r in enumerate(reqs)
            )  # (already registered live by _admit, under its queue lock)
        # One timeline track per lane: the request span opens at admission
        # and closes at finish, so a Perfetto row shows the lane's occupancy
        # from prefill through its last token.
        for row in rows:
            if row is not None:
                row.open_span(slot=None)
        from cake_tpu.runtime.batch_backend import BackendWorkerError

        tokens, pads, bucket = layout_prompts(ids_list, self.max_seq_len, self._gen.block)
        # ONE bounded attention capacity for the whole epoch (paged backends
        # only; why, in their class docstring): enough slots for every
        # admitted row's full token budget. ``cap`` (the epoch's slot
        # ceiling) clamps to it below, so joins (_take_joins gates budgets on
        # cap), spec verify (slot + K + 1 < cap), decode chunks, and failover
        # re-prefills all stay inside the ONE capacity.
        cap = self.max_seq_len
        if self._alloc is not None and hasattr(
            self.backend, "set_epoch_capacity"
        ):
            budgets = (
                [max(1, sp.row.req.max_tokens - sp.row.n)
                 for sp in seed_spills]
                if seed_spills
                else [self._gen.slots_for(r) for r in batch]
            )
            reach = bucket + max(
                min(t, self.max_seq_len - bucket) for t in budgets
            )
            self.backend.set_epoch_capacity(
                self.shapes.capacity(reach, self.max_seq_len)
            )
            cap = min(self.max_seq_len, self.backend.capacity_slots())
        t_prefill = time.perf_counter()
        while True:
            # The epoch-start prefill has no generated state to migrate: a
            # worker death here retries the whole block through the
            # failed-over route (init_kv refreshes sessions + pool).
            try:
                with timeline.span(
                    "prefill", rid=self._epoch_head_rid, track="engine",
                    args={
                        "bucket": int(bucket), "lanes": B,
                        "restored": len(seed_spills),
                    },
                ):
                    kv = self.backend.init_kv(B)  # paged: resets allocator
                    write_starts = None
                    if self._alloc is not None:
                        if self._prefix is not None:
                            kv, write_starts = self._prefix_layout(
                                reqs, rows, pads, bucket, kv, ids_list
                            )
                        else:
                            # Map each REAL lane's pages over its live window
                            # [pad, bucket); dummy lanes hold no pages (their
                            # writes drop, their reads are garbage nobody
                            # consumes). _admit's reserve accounting
                            # guarantees this cannot exhaust the fresh pool.
                            for lane, r in enumerate(reqs):
                                if r is not None:
                                    self._alloc.map_range(
                                        lane, int(pads[lane]), bucket
                                    )
                    pads_j = jnp.asarray(pads)
                    if write_starts is not None:
                        # Prefix-cache path (cold epochs included): prefill
                        # ONLY the window [start, bucket) covering every
                        # lane's uncached tail (``shapes.window`` bounds
                        # the widths); writes below each lane's
                        # threshold drop, so forked shared pages stay
                        # byte-stable. Cold lanes' thresholds are their
                        # pads — full compute through the SAME cached-chunk
                        # arithmetic warm lanes use, which is what makes
                        # warm streams bit-identical to cold ones (the
                        # plain fresh-chunk path reduces in a different
                        # order at the ulp level). Logits land at
                        # bucket - 1, exactly where the cold path reads
                        # them.
                        start, _ = self.shapes.window(
                            int(write_starts.min()), bucket, bucket,
                            reads_pool=True,
                        )
                        logits, kv = self._dispatch(
                            "prefill",
                            lambda: self.backend.suffix_prefill(
                                tokens[:, start:], kv, pads_j,
                                write_starts, start,
                            ),
                        )
                    else:
                        logits, kv = self._dispatch(
                            "prefill",
                            lambda: self.backend.prefill(tokens, kv, pads_j),
                        )
                break
            except BackendWorkerError as e:
                self._failover_or_raise(e)
                if self._prefix is not None:
                    # The retry rebuilds the pool from zero (init_kv above):
                    # cached chains would outlive their bytes — drop them,
                    # their pins, and the stale retained buffer first.
                    self._prefix.clear(reason="prefill-retry")
                    self.backend.drop_retained_kv()
                    self._lane_leases.clear()
                    self._lane_info.clear()
        # Attribution: the shared left-padded prefill computes `bucket`
        # positions for every lane — a lane's own share scales with its
        # prompt, the rest is convoy (the padding half of the lockstep tax).
        dt_prefill = time.perf_counter() - t_prefill
        self._segment_args.update(
            lanes=B, bucket=int(bucket), capacity=int(cap),
            prefill_s=round(dt_prefill, 6),
        )
        own_tok = 0
        for row in rows:
            if row is not None:
                if seed_spills:
                    own_tok += len(row.history) - 1
                    row.account_restore(dt_prefill, bucket)
                else:
                    own_tok += len(row.req.prompt_ids)
                    row.account_prefill(dt_prefill, bucket)
        # Hardware ledger: the shared window computed B x bucket
        # positions; only the live prompts (or restored histories) were
        # anyone's own work — the rest is pad. A spill-seeded segment's
        # prefill is REDONE work (restore_prefill), the preemption's price.
        self.efficiency.note_prefill(
            dt_prefill, B, bucket, own_tok, restore=bool(seed_spills)
        )
        ring, ring_idx = seed_rings(ids_list, window)
        if seed_spills:
            # Bit-identical resume: the pending token and the sampling
            # state (per-row key, penalty ring) come back from the host
            # copies taken at the spill boundary — nothing is re-sampled,
            # so the restored stream continues the exact token sequence
            # the uninterrupted run would have produced.
            for lane, sp in enumerate(seed_spills):
                if sp.ring is not None and window > 0:
                    ring[lane] = sp.ring
                    ring_idx[lane] = sp.ring_idx
            key0 = np.asarray(jax.random.PRNGKey(0))
            keys = jnp.asarray(
                np.stack(
                    [sp.key for sp in seed_spills]
                    + [key0] * (B - n_seed)
                )
            )
            first = np.asarray(
                [sp.row.history[-1] for sp in seed_spills]
                + [0] * (B - n_seed),
                np.int32,
            )
            for sp in seed_spills:
                sp.row.n_at_restore = sp.row.n
                self._note_restore(sp.row)
        elif self._gen.block:
            # No first token: a block's tokens come from its own passes.
            first, keys = self._gen.seat(reqs, rows)
        else:
            # The ``prefill`` span above closed after the ENQUEUE: the wait
            # for the device is here, where ``first_sample`` reads the
            # sampled tokens (and compiles, for a batch size it has not
            # seen), behind the rows' keys, a handful of small programs
            # each. No period is open: a plain span, no ``_phase``.
            with timeline.span(
                "epoch-first-sample", rid=self._epoch_head_rid,
                track=PROFILED_TRACK, args={"lanes": B, "bucket": int(bucket)},
            ):
                keys = jnp.stack(
                    [
                        jax.random.PRNGKey(
                            r.sampling.seed if r is not None else 0
                        )
                        for r in reqs
                    ]
                )
                first, keys, ring, ring_idx = first_sample(
                    logits, s, ring, ring_idx, keys
                )
            for lane, row in enumerate(rows):
                if row is not None:
                    row.push(int(first[lane]))
                    if row.done:
                        rows[lane] = None
        self._release_finished(rows)
        memwatch.sample("prefill")

        tok = jnp.asarray(first)
        ring_j = jnp.asarray(ring)
        ring_idx_j = jnp.asarray(ring_idx)
        slot = bucket  # slot of the most recent token, shared by all lanes
        # ``cap`` was fixed above: max_seq_len, or the epoch's bounded
        # capacity — which covers every admitted row's full budget, so the
        # clamp never truncates a stream below what max_seq_len would give.

        # THE ORDER OF THE LOOP. The host never reads a value back from the
        # device before it has enqueued the next program that does not
        # depend on that read: with chunk k enqueued and unread, an
        # iteration does chunk k's boundary work from what it can COUNT
        # (a budget that ends inside chunk k frees its lane now; an EOS id
        # is seen one chunk late), enqueues the joins' prefills and chunk
        # k+1 behind chunk k, and only then waits for chunk k's tokens and
        # the joiners' first ones (``_settle``). One chunk of look-ahead,
        # by what the code can observe: a backend that states it
        # (``lookahead``: a paged single-device backend never redoes a
        # chunk from pre-chunk state), no watchdog thread in the dispatch,
        # no speculative round (the host accepts the drafts). A boundary
        # that needs every token first — a restore, a pool too short for
        # the next chunk (a spill snapshots host and device state), a lost
        # worker, stop() — reads what is in flight and goes on serially.
        backend_ahead = (
            getattr(self.backend, "lookahead", 0) > 0 and self._guard is None
        )
        self._serial_why = "segment-start"
        self._t_chunk_read = 0.0
        ended = "capacity"  # the loop's own end: the slot reached the cap
        while self.shapes.more(cap, slot):
            if self._stop:
                # stop() must not wait out a long epoch: close every live
                # stream now (consumers see the error, not a hang).
                err = RuntimeError("engine stopped")
                for row in self._open_rows(rows):
                    row.req.handle._emit(err)
                    row.req.handle._emit(_DONE)
                    row.close_span(error="engine stopped")
                    self._row_finished(row.req.rid)
                rows[:] = [None] * len(rows)
                self._segment_args["ended"] = "stopped"
                return
            spec = self._spec_applicable(s, slot, cap)
            look = int(backend_ahead and not spec)
            serial = "" if look else ("spec" if backend_ahead else "backend")
            with self._period(slot) as period:
                budget = None
                with self._phase("sweep"):
                    # Cancellation + deadline sweeps at the chunk boundary:
                    # flagged rows finish "cancelled" and over-deadline rows
                    # finish "deadline" NOW — their pages return to the pool
                    # (release just below) and their lanes are joinable this
                    # very round; queued requests past their deadline expire
                    # without ever admitting. (A chunk in flight still
                    # computes such a row's lane: its tokens are dropped.)
                    self._apply_cancels(rows)
                    self._apply_deadlines(rows)
                    self._release_finished(rows)
                    if self.scheduler == "continuous":
                        # A segment under sustained joins may never drain,
                        # so the SLO feedback (fair-queue weights, shed
                        # scales — and the burning signal the step budget
                        # reads) must apply HERE, not only between
                        # segments. Rate-limited internally to ~1/s; epoch
                        # mode keeps its between-epoch cadence.
                        self._apply_slo_feedback()
                        budget = {"left": self._grant_step_budget(rows)}
                # Per-step scheduling (continuous): this step's prefill
                # budget (SLO-aware, runtime/admission.StepBudget) was
                # granted above; restore spilled lanes FIRST (previously
                # admitted work beats new admissions), then admit queued
                # joins the moment lanes and pages are free. Epoch mode
                # keeps the unbudgeted join path. A join failure must not
                # strand the popped requests: anything not yet admitted
                # into `rows` gets the error directly (rows themselves are
                # covered by _run_batch).
                join_args: list = []
                step_args: dict = {}
                if budget is not None:
                    step_args = {
                        "slot": int(slot),
                        "live": sum(r is not None for r in rows),
                        "budget": budget["left"],
                    }
                with (
                    self._phase("step", args=step_args)
                    if budget is not None
                    else contextlib.nullcontext()
                ):
                    try:
                        if budget is not None:
                            (
                                tok, kv, keys, ring_j, ring_idx_j, pads_j
                            ) = self._take_restores(
                                knobs, rows, slot, cap, budget, tok, kv,
                                keys, ring_j, ring_idx_j, pads_j, s,
                            )
                        join_args = self._take_joins(
                            knobs, rows, slot, cap, budget
                        )
                        joined: set[int] = set()
                        try:
                            for group in self._join_groups(join_args, slot):
                                while True:
                                    try:
                                        (
                                            tok, kv, keys, ring_j, ring_idx_j
                                        ) = self._join_group(
                                            group, rows, slot, tok, kv,
                                            keys, ring_j, ring_idx_j, s,
                                        )
                                        break
                                    except BackendWorkerError as e:
                                        # A join prefill lost its worker:
                                        # migrate the epoch's live rows to
                                        # the new route (from their whole
                                        # histories: what is in flight is
                                        # read first), then retry the join
                                        # there (the joiner saw no side
                                        # effects — its first token samples
                                        # only after backend.join returns).
                                        self._settle(rows, 0, "join")
                                        self._failover_or_raise(e)
                                        kv = self._migrate_kv(rows, B, slot)
                                joined.update(id(req) for _, req in group)
                                # (each lane's pad: one lane's as ever, a
                                # group's rows' in one program)
                                pads_j = self._set_pads(pads_j, group, slot)
                                # (a group is one program: ``_join_group``)
                                if not look:
                                    # Serial: the joiner's first token is
                                    # read before anything else is enqueued.
                                    self._settle(rows, 0, serial)
                        except Exception as e:
                            for _, req2 in join_args:
                                if id(req2) not in joined:
                                    if isinstance(e, BackendWorkerError):
                                        # Same isolation as admitted rows:
                                        # a graceful "error" finish, not a
                                        # raised exception.
                                        _fail_request(
                                            req2, str(e), engine=self
                                        )
                                    else:
                                        req2.handle._emit(e)
                                        req2.handle._emit(_DONE)
                                    # Popped-but-never-joined: finish()
                                    # never runs for these, so deregister
                                    # here or cancel() would claim them
                                    # live forever.
                                    self._row_finished(req2.rid)
                            raise
                    finally:
                        step_args["joins"] = len(join_args)
                n = self.shapes.decode_steps(
                    self.decode_chunk_size, cap, slot
                )
                if self._unread and (
                    not look or self._pages_short(rows, slot, n)
                ):
                    # _extend_pages would spill or truncate (or the round
                    # is the host's): with every token read, as the serial
                    # order had them. An EOS in the chunk that was in
                    # flight may free the very pages that were short.
                    self._settle(rows, 0, serial or "pages")
                live = sum(r is not None for r in rows)
                metrics.registry.gauge(
                    "cake_batch_occupancy",
                    "Live lockstep lanes at the current chunk boundary.",
                ).set(live)
                if not live:
                    ended = "drained"
                    break
                self.periods.first_dispatch()
                if spec:
                    # The verify chunk WRITES slots [slot, slot + K + 1)
                    # through the block table — map those pages first (an
                    # unmapped slot silently drops the chunk's KV). Dense
                    # backends skip this; a page-truncated row degrades
                    # exactly like the decode path.
                    if self._alloc is not None and not self._extend_pages(
                        rows, slot, self.speculative_k + 1,
                        spill_ctx=(keys, ring_j, ring_idx_j),
                    ):
                        ended = "pages"
                        break  # every remaining row truncated or spilled
                    try:
                        # Mutable span args: _spec_round stamps the round's
                        # accepted advance + K before the span serializes
                        # at exit, so /explain can split accepted vs
                        # wasted time.
                        sargs = {"slot": int(slot)}
                        with self._phase("spec-round", args=sargs):
                            res = self._spec_round(
                                rows, kv, tok, slot, pads_j, keys, s,
                                span_args=sargs,
                            )
                    except BackendWorkerError as e:
                        # Verify-round worker death: migrate the live
                        # streams, then take this round as a plain decode
                        # chunk (the half-written verify tail on the dead
                        # route is gone with it; sampling state never
                        # advanced).
                        self._failover_or_raise(e)
                        kv = self._migrate_kv(rows, B, slot)
                        res = None
                    if res is not None:
                        tok, kv, keys, slot = res
                        period.update(dispatched=True, live=live, order="spec", steps=self.speculative_k + 1)
                        continue
                if self._alloc is not None and not self._extend_pages(
                    rows, slot, n,
                    spill_ctx=self._gen.spill_ctx(keys, ring_j, ring_idx_j),
                ):
                    ended = "pages"
                    break  # every remaining row was truncated or spilled
                ahead = any(entry.n for entry in self._unread)
                order = "ahead" if ahead else self._serial_why
                try:
                    # The program's key rides the span: lanes x capacity x
                    # n name the compiled decode program, slot and live
                    # what it was run on; ``ahead``: enqueued before the
                    # chunk in front of it was read. The span holds this
                    # chunk's enqueue and the wait for the OLDEST unread
                    # chunk (this one, where the order is serial): one
                    # chunk's wall either way.
                    with self._phase(
                        "decode-chunk",
                        args={
                            "lanes": B, "capacity": int(cap),
                            "slot": int(slot), "n": int(n), "live": live,
                            "ahead": ahead, **self._gen.phase_args(n),
                        },
                    ):
                        t0 = time.perf_counter()
                        # Under the watchdog the call blocks on the device
                        # too: a device-level hang surfaces here, not just
                        # a stuck dispatch.
                        toks, kv, keys, ring_j, ring_idx_j = self._dispatch(
                            "decode",
                            lambda: self.backend.decode(
                                kv, tok, slot, pads_j, keys, ring_j,
                                ring_idx_j, n, s,
                            ),
                        )
                        chunk = _Unread(
                            toks,
                            [
                                (lane, row) for lane, row in enumerate(rows)
                                if row is not None
                            ],
                            slot, n, t0,
                            counters=self._take_counters(),
                        )
                        self._unread.append(chunk)
                        # Tokens the live lanes hold in the cache at this
                        # dispatch (each lane's position).
                        cached = sum(
                            len(row.history) - 1 + row.inflight
                            for _, row in chunk.rows
                        ) + self._gen.cached_more(chunk)
                        for lane, row in chunk.rows:
                            # What the host can count does not lag: a
                            # budget that ends inside this chunk frees the
                            # lane at the next boundary, read or not. The
                            # row takes its last tokens from ``_unread``.
                            row.inflight += n - chunk.known.get(lane, 0)
                            if row.n + row.inflight >= row.req.max_tokens:
                                rows[lane] = None
                        tok = self._gen.next_operand(toks, B)
                        slot += n
                        if not look or self._unread[0] is not chunk:
                            self._read(self._unread[0])
                except BackendWorkerError as e:
                    # Transparent recovery: a worker died and a healthy
                    # replica exists — rebuild every live stream's KV on
                    # the new route and REDO this chunk. The failed chunk's
                    # partial steps are discarded with the dead route;
                    # tok/keys/rings still hold the pre-chunk state, so the
                    # redone chunk samples the exact same tokens (greedy
                    # streams stay bit-identical).
                    self._settle(rows, 0, "backend")
                    self._failover_or_raise(e)
                    kv = self._migrate_kv(rows, B, slot)
                    continue
                self._settle(rows, look, serial)
                period.update(
                    dispatched=True, live=live, order=order, cached=cached, steps=n
                )
        self._settle(rows, 0)
        self._segment_args["ended"] = ended

        for row in rows:
            if row is not None:
                row.finish()  # cache edge: stream closes with finish "length"
        memwatch.sample("epoch-end")
        if self._prefix is not None:
            # Persistent pool: the final buffer carries every cached chain's
            # bytes into the next epoch's init_kv.
            self.backend.retain_kv(kv)
        self._epoch_kv_retained = True  # clean end: the finally path inserts
        # (_run_batch's finally returns every lane's pages to the pool.)

    # ------------------------------------------------- paged-pool accounting

    def _release_finished(self, rows: list) -> None:
        """Return every finished (or never-real) lane's pages to the pool —
        AND unmap them, so the lane's continuing lockstep garbage writes drop
        instead of landing in pages a later join may recycle."""
        if self._alloc is None:
            return
        released = False
        for lane, row in enumerate(rows):
            if row is None and self._alloc.lane_mapped(lane):
                self._lane_recycle(lane)
                released = True
        if released:
            self._pool_counter()

    def _lane_recycle(self, lane: int, insert: bool = True) -> None:
        """One lane's pages go back to the pool — in prefix-cache order:
        FIRST adopt the lane's prompt-prefix chain into the cache (the pages
        gain cache references while still alive), THEN unpin the chain the
        lane forked at admission, THEN drop the lane's own mappings. A
        cancelled stream still inserts (its prompt prefill completed and its
        prefix KV is exact); failed epochs pass ``insert=False`` — their
        bytes are suspect and the cache is cleared right after."""
        if self._prefix is not None:
            info = self._lane_info.pop(lane, None)
            if insert and info is not None:
                req, pad = info
                self._prefix.insert(lane, req.prompt_ids, pad, rid=req.rid)
            self._prefix.release(self._lane_leases.pop(lane, None))
        self._alloc.release(lane)

    def _pool_counter(self) -> None:
        """Pool occupancy onto the timeline's counter track — the same view
        as the cake_kv_pages_* gauges, but on the span clock, so page churn
        lines up with the decode/extend spans that caused it."""
        timeline.counter(
            "kv_pages",
            {
                "in_use": float(
                    self._alloc.pages_total - self._alloc.pages_free
                ),
                "free": float(self._alloc.pages_free),
            },
            track="mem",
        )

    def _pages_short(self, rows: list, slot: int, n: int) -> bool:
        """Whether the free list cannot cover the next chunk's slots for
        every live lane: ``_extend_pages`` would have to reclaim, spill or
        truncate, which it does from host and device state that holds
        every token (the step loop reads what is in flight first)."""
        if self._alloc is None:
            return False
        live = [lane for lane, row in enumerate(rows) if row is not None]
        need = self._alloc.pages_missing(live, slot, slot + n)
        return need > self._alloc.pages_free

    def _extend_pages(
        self, rows: list, slot: int, n: int, spill_ctx: tuple | None = None,
    ) -> bool:
        """Grow every live lane's mapping to cover the next decode chunk
        (slots [slot, slot + n)); only page-boundary crossings allocate.

        Pool pressure escalates in order: (1) reclaim cold prefix-cache
        pages, retrying as long as a pass makes progress — a single
        under-freeing pass must never strand a stream the next pass could
        save; (2) under the CONTINUOUS scheduler, PREEMPT — spill the
        lowest-priority lane host-side (history + sampling state; restored
        bit-identically when pages free) rather than killing anything;
        (3) only then force-finish as "length" (epoch mode, or a lane no
        pool state can serve). Degradation costs one stream a pause or a
        truncation, never the epoch. Returns False when no live row
        survived (the epoch has nothing left to decode this step).
        """
        from cake_tpu.models.llama.paged_cache import PageExhausted

        any_live = grew = False
        free0 = self._alloc.pages_free
        with self._phase("page-extend", args={"slot": int(slot), "n": int(n)}):
            if self._free_behind is not None:
                self._free_behind(slot)
            for lane, row in enumerate(rows):
                if row is None:
                    continue
                try:
                    try:
                        self._alloc.map_range(lane, slot, slot + n)
                    except PageExhausted:
                        # Evict-then-retry until a reclaim pass frees
                        # nothing new: pool pressure reclaims COLD
                        # prefix-cache pages before degrading a live
                        # stream, and a pass that under-frees (pages still
                        # lane-shared, pins releasing between passes) gets
                        # another chance instead of force-finishing a
                        # stream reclaimable pages could have served.
                        self._reclaim_and_map(lane, slot, n, row.req.rid)
                    any_live = True
                except PageExhausted:
                    if (
                        self.scheduler == "continuous"
                        and spill_ctx is not None
                    ):
                        if self._preempt_for(rows, lane, slot, n, spill_ctx):
                            any_live = True
                        grew = True
                        continue
                    self.stats["page_truncations"] += 1
                    row.req.handle.finish_reason = "length"
                    metrics.flight.record(
                        "page-truncated", row.req.rid, slot=slot,
                        completion_tokens=row.n,
                    )
                    timeline.instant(
                        "page-truncated", rid=row.req.rid,
                        track=f"lane{lane}", args={"slot": int(slot)},
                    )
                    row.finish()
                    rows[lane] = None
                    self._lane_recycle(lane)
                    grew = True
            grew = grew or self._alloc.pages_free != free0
        if grew:
            self._pool_counter()
        return any_live

    def _reclaim_and_map(
        self, lane: int, slot: int, n: int, rid: str
    ) -> None:
        """Map [slot, slot + n) for ``lane``, evicting prefix-cache pages
        between attempts for as long as eviction makes progress. Raises
        PageExhausted only when a whole reclaim pass freed nothing."""
        from cake_tpu.models.llama.paged_cache import PageExhausted

        if self._prefix is None:
            raise PageExhausted(
                f"lane {lane} needs pages for [{slot}, {slot + n}) and no "
                "prefix cache exists to reclaim from"
            )
        while True:
            freed = self._prefix.reclaim(
                self._alloc.pages_needed(n) + 1, rid=rid
            )
            try:
                self._alloc.map_range(lane, slot, slot + n)
                return
            except PageExhausted:
                if not freed:
                    raise

    # ------------------------------------------- preemption (spill/restore)
    # Continuous scheduler only (README "Continuous scheduling"): page
    # pressure PREEMPTS instead of force-finishing. A spilled lane's pages
    # return to the pool; its host-side record (history + per-row PRNG key
    # + penalty ring — everything the chunk-boundary invariant needs) waits
    # in ``_spilled`` until pages free, then a restore re-prefills
    # ``history[:-1]`` into a window ending at the shared slot through the
    # SAME join/suffix arithmetic a continuous-batching join uses — the
    # _migrate_kv proof pattern, so resumed streams are bit-identical to
    # uninterrupted ones (greedy AND sampled; pinned in
    # tests/test_continuous_serving.py).

    def _pick_victim(self, rows: list, lane: int) -> int | None:
        """The lane to preempt so ``lane`` can extend: lowest priority
        first (never a HIGHER priority than the starving lane), then the
        one holding the most pages (maximum relief per spill), then the
        youngest. None = no other lane qualifies (the starving lane spills
        itself — it parks, it does not die)."""
        me = rows[lane].req.priority
        best = None
        best_key = None
        for i, row in enumerate(rows):
            if row is None or i == lane or row.req.priority > me:
                continue
            key = (
                row.req.priority,
                -self._alloc.lane_pages(i),
                -row.t_open,
            )
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _preempt_for(
        self, rows: list, lane: int, slot: int, n: int, spill_ctx: tuple
    ) -> bool:
        """Spill victims until ``lane``'s next chunk maps (True), or spill
        ``lane`` itself when nothing lower-priority is left to take pages
        from (False — the lane parked; its stream resumes bit-identically
        once a restore finds room)."""
        from cake_tpu.models.llama.paged_cache import PageExhausted

        while True:
            victim = self._pick_victim(rows, lane)
            if victim is None:
                self._spill_lane(rows, lane, slot, spill_ctx, reason="self")
                return False
            self._spill_lane(
                rows, victim, slot, spill_ctx, reason="preempted"
            )
            try:
                try:
                    self._alloc.map_range(lane, slot, slot + n)
                except PageExhausted:
                    # A victim's prompt-prefix pages were adopted by the
                    # prefix cache on recycle — reclaim them (and any other
                    # cold chains) before trying the next victim.
                    self._reclaim_and_map(lane, slot, n, rows[lane].req.rid)
                return True
            except PageExhausted:
                continue

    def _note_cancelled(self, row: "_RowState", where: str) -> None:
        """The one cancellation-bookkeeping sequence (stats + counter +
        flight event), shared by the spilled and raced-preemption paths;
        the caller still owns the row.cancel()/_emit that closes the
        stream. ``stats`` keeps the engine-wide convention — best-effort
        unguarded writes, /stats reads a copy — so this stays consistent
        with every other site instead of making one counter look
        lock-protected."""
        self.stats["cancelled"] += 1
        metrics.registry.counter(
            "cake_cancelled_total", "Requests cancelled (queued or live)."
        ).inc()
        metrics.flight.record(
            "cancelled", row.req.rid, where=where, completion_tokens=row.n,
        )

    def _spill_lane(
        self, rows: list, lane: int, slot: int, spill_ctx: tuple,
        reason: str,
    ) -> None:
        """Preempt one lane at the chunk boundary: host-copy its sampling
        state, return its pages (prompt-prefix chain adopted by the prefix
        cache — the restore may fork it right back), and park it in the
        spill table. A cancel that raced the preemption wins: the stream
        finishes cancelled instead of parking. A lane that made ZERO
        progress since its last restore and is spilling ITSELF again can
        never advance on this pool (its very next chunk needs pages the
        pool cannot supply even fully drained) — it force-finishes
        "length" instead of livelocking through zero-progress
        respill/reseed cycles."""
        assert not self._unread, "a spill copies settled state"
        keys, ring_j, ring_idx_j = spill_ctx
        row = rows[lane]
        rid = row.req.rid
        if reason == "self" and row.n == row.n_at_restore:
            # The restore re-prefilled the whole history and the first
            # chunk still could not map: re-parking would reseed the
            # IDENTICAL segment forever. Same honest degradation as epoch
            # mode, discovered one re-prefill later.
            self.stats["page_truncations"] += 1
            row.req.handle.finish_reason = "length"
            metrics.flight.record(
                "page-truncated", rid, slot=int(slot), where="respill",
                completion_tokens=row.n,
            )
            rows[lane] = None
            row.finish()
            self._lane_recycle(lane)
            return
        window = int(ring_j.shape[1]) if ring_j.ndim == 2 else 0
        sp = _SpilledLane(
            row=row,
            key=np.asarray(keys[lane]),
            ring=np.asarray(ring_j[lane]) if window > 0 else None,
            ring_idx=int(np.asarray(ring_idx_j[lane])) if window > 0 else 0,
        )
        rows[lane] = None
        cancelled = False
        with self._cv:
            if rid in self._cancel_ids:
                self._cancel_ids.discard(rid)
                cancelled = True
            else:
                self._spilled[rid] = sp
                self._live_rids.discard(rid)
        if cancelled:
            self._note_cancelled(row, "epoch")
            row.cancel()
            self._lane_recycle(lane)
            return
        self.stats["preemptions"] += 1
        # Decision audit: a victim spilled for someone else's pages is a
        # PREEMPT; a starving lane parking itself is a SPILL — both
        # caused by page pressure (the victim choice itself is the
        # priority policy, carried in the detail).
        self.audit.record(
            "preempt" if reason == "preempted" else "spill",
            "page_pressure", rid=rid, tenant=row.req.tenant,
            detail=reason,
        )
        metrics.registry.counter(
            "cake_preemptions_total",
            "Lanes preempted under page pressure (continuous scheduler): "
            "page chain spilled host-side, stream parked for a "
            "bit-identical restore.",
        ).inc()
        metrics.flight.record(
            "preempted", rid, slot=int(slot), reason=reason,
            completion_tokens=row.n, priority=row.req.priority,
        )
        timeline.instant(
            "preempted", rid=rid, track=f"lane{lane}",
            args={"slot": int(slot), "reason": reason},
        )
        row.close_span()
        self._lane_recycle(lane, insert=True)

    def _pop_spill_seed(self) -> list["_SpilledLane"]:
        """Seed rows for a spill-seeded segment: the oldest spill's knob
        group, oldest first, as many as fit the lanes and the (fully free)
        pool. Spills whose history can NEVER be served again — the window
        or the whole pool is too small for it — force-finish "length" here
        instead of parking forever."""
        doomed: list[_SpilledLane] = []
        out: list[_SpilledLane] = []
        with self._cv:
            if not self._spilled:
                return []
            order = sorted(self._spilled.values(), key=lambda e: e.t)
            knobs = order[0].row.req.knobs()
            claimed = 0
            for sp in order:
                if len(out) >= self.max_batch:
                    break
                row = sp.row
                if row.req.knobs() != knobs:
                    continue
                hist = len(row.history) - 1
                if (
                    self.shapes.prompt_width(hist, self.max_seq_len)
                    >= self.max_seq_len
                ):
                    del self._spilled[row.req.rid]
                    doomed.append(sp)
                    continue
                if self._alloc is not None:
                    need = (
                        self._alloc.pages_needed(hist)
                        + self._alloc.reserve_pages
                    )
                    if need + claimed > self._alloc.pages_total:
                        if need > self._alloc.pages_total:
                            del self._spilled[row.req.rid]
                            doomed.append(sp)
                        continue
                    claimed += need
                del self._spilled[row.req.rid]
                # Live the moment it leaves the spill table, under the SAME
                # lock — cancel() must never observe a request as neither
                # queued, nor spilled, nor live (the _admit no-gap rule).
                self._live_rids.add(row.req.rid)
                out.append(sp)
        for sp in doomed:
            self.stats["page_truncations"] += 1
            sp.row.req.handle.finish_reason = "length"
            metrics.flight.record(
                "page-truncated", sp.row.req.rid, where="spilled",
                completion_tokens=sp.row.n,
            )
            sp.row.finish()
        return out

    def _take_restores(
        self, knobs, rows, slot, cap, budget, tok, kv, keys, ring_j,
        ring_idx_j, pads_j, s,
    ):
        """Step-boundary restores: re-attach spilled lanes (oldest first,
        same knobs) into free lanes while pages and the step's prefill
        budget allow. Restores run BEFORE joins — previously admitted work
        outranks new admissions — and charge the same budget, so a restore
        storm cannot starve decode any more than a join storm can."""
        with self._cv:
            empty = not self._spilled
        if empty:
            return tok, kv, keys, ring_j, ring_idx_j, pads_j
        # A restore puts a lane back from its whole history, and competes
        # for the lanes and pages an EOS in flight may be about to free.
        self._settle(rows, 0, "restore")
        free = [i for i, r in enumerate(rows) if r is None]
        if not free:
            return tok, kv, keys, ring_j, ring_idx_j, pads_j
        picks: list[tuple[int, _SpilledLane]] = []
        claimed = 0
        with self._cv:
            for sp in sorted(self._spilled.values(), key=lambda e: e.t):
                if not free:
                    break
                row = sp.row
                req = row.req
                hist = len(row.history) - 1
                if req.knobs() != knobs:
                    self.audit.record(
                        "defer", "knob_incompatible", rid=req.rid,
                        tenant=req.tenant, detail="spilled",
                    )
                    continue  # wrong trace for this segment
                if hist > slot or cap - 1 - slot < req.max_tokens - row.n:
                    # needs a taller segment, or restoring here would
                    # truncate below what a solo segment delivers
                    self.audit.record(
                        "defer", "capacity", rid=req.rid,
                        tenant=req.tenant, detail="spilled",
                    )
                    continue
                if budget is not None and budget["left"] < hist:
                    self.audit.record(
                        "defer", "step_budget", rid=req.rid,
                        tenant=req.tenant, detail="spilled",
                    )
                    continue
                if self._alloc is not None:
                    need = (
                        self._alloc.pages_needed(hist)
                        + self._alloc.reserve_pages
                    )
                    avail = self._alloc.pages_free - claimed + (
                        self._prefix.reclaimable()
                        if self._prefix is not None
                        else 0
                    )
                    if need > avail:
                        self.audit.record(
                            "defer", "page_pressure", rid=req.rid,
                            tenant=req.tenant, detail="spilled",
                        )
                        continue
                    claimed += need
                if budget is not None:
                    budget["left"] -= hist
                del self._spilled[req.rid]
                self._live_rids.add(req.rid)
                self.audit.record(
                    "restore", "fair_order", rid=req.rid, tenant=req.tenant
                )
                picks.append((free.pop(0), sp))
        from cake_tpu.models.llama.paged_cache import PageExhausted

        for lane, sp in picks:
            try:
                (
                    tok, kv, keys, ring_j, ring_idx_j, pads_j
                ) = self._restore_lane(
                    sp, lane, rows, slot, tok, kv, keys, ring_j,
                    ring_idx_j, pads_j,
                )
            except PageExhausted:
                # The accounting above raced an eviction estimate: put the
                # spill back (it retries next step) — never fail the step.
                self._unwind_restore(lane, sp)
            except BaseException:
                # Worker death mid-restore: re-park the spill (the next
                # segment retries through the failed-over route) and let
                # the epoch-level isolation handle the live rows.
                self._unwind_restore(lane, sp)
                raise
        return tok, kv, keys, ring_j, ring_idx_j, pads_j

    def _unwind_restore(self, lane: int, sp: "_SpilledLane") -> None:
        rid = sp.row.req.rid
        sp.row.close_span()
        sp.row.t_close = 0.0
        cancelled = False
        with self._cv:
            if rid in self._cancel_ids:
                # A cancel landed while the rid was transiently live for
                # the failed restore: honor it NOW (the documented
                # cancels-reach-spilled-lanes-immediately contract) instead
                # of deferring it to an unboundedly-later restore.
                self._cancel_ids.discard(rid)
                self._live_rids.discard(rid)
                cancelled = True
            else:
                self._spilled[rid] = sp
                self._live_rids.discard(rid)
        if self._alloc is not None and self._alloc.lane_mapped(lane):
            self._lane_recycle(lane, insert=False)
        elif self._prefix is not None:
            self._prefix.release(self._lane_leases.pop(lane, None))
            self._lane_info.pop(lane, None)
        if cancelled:
            self._note_cancelled(sp.row, "spilled")
            sp.row.cancel()

    def _restore_lane(
        self, sp: "_SpilledLane", lane: int, rows, slot, tok, kv, keys,
        ring_j, ring_idx_j, pads_j,
    ):
        """Re-attach one spilled lane at the shared slot: re-prefill
        ``history[:-1]`` into a window ending at ``slot`` (suffix-join
        arithmetic under a prefix cache — the restore may fork the very
        chain its spill inserted — plain join otherwise), then put the
        host-saved sampling state back. The pending token ``history[-1]``
        was already delivered before the spill; nothing is re-sampled."""
        row = sp.row
        req = row.req
        hist = row.history[:-1]
        pad = slot - len(hist)
        row.lane = lane
        row.t_close = 0.0
        row.open_span(slot=slot)
        t0 = time.perf_counter()
        try:
            with self._phase(
                "restore", rid=req.rid,
                args={"lane": lane, "slot": int(slot), "tokens": len(hist)},
            ):
                fork = None
                if self._prefix is not None:
                    fork = self._fork_lane(lane, req, pad, slot, ids=hist)
                _, kv, W = self._row_prefill(kv, lane, hist, pad, slot, fork)
        except BaseException as e:
            row.close_span(error=str(e)[:200])
            raise
        dt_restore = time.perf_counter() - t0
        row.phase["restore"] += dt_restore
        # Hardware ledger: a restore's re-prefill is REDONE work — the
        # preemption's device price, booked to its own bucket.
        self.efficiency.note_prefill(
            dt_restore, 1, W, min(len(hist), W), restore=True
        )
        window = int(ring_j.shape[1]) if ring_j.ndim == 2 else 0
        if window > 0 and sp.ring is not None:
            ring_j, ring_idx_j = _set_lane(
                (ring_j, ring_idx_j), lane, (sp.ring, int(sp.ring_idx))
            )
        keys, tok, pads_j = _set_lane(
            (keys, tok, pads_j), lane,
            (sp.key, int(row.history[-1]), pad),
        )
        rows[lane] = row
        row.n_at_restore = row.n
        if self._alloc is not None:
            self._pool_counter()
        self._note_restore(row)
        return tok, kv, keys, ring_j, ring_idx_j, pads_j

    def _note_restore(self, row: "_RowState") -> None:
        self.stats["restores"] += 1
        metrics.registry.counter(
            "cake_restores_total",
            "Spilled lanes re-attached to a running segment "
            "(bit-identical resume).",
        ).inc()
        metrics.flight.record(
            "restored", row.req.rid, completion_tokens=row.n,
            lane=row.lane,
        )
        timeline.instant(
            "restored", rid=row.req.rid, track=f"lane{row.lane}",
        )

    def _grant_step_budget(self, rows: list) -> int:
        """This step's prefill grant in prompt tokens (StepBudget,
        runtime/admission.py): scaled UP while the SLO tracker says some
        tenant is burning (queue waits are missing the TTFT objective —
        drain admissions faster) and DOWN while a live stream's deadline
        slack is inside a few chunk walls (protect running deadlines from
        prefill stalls)."""
        now = time.monotonic()
        slack = None
        for row in rows:
            if row is not None and row.req.deadline:
                left = row.req.deadline - now
                if slack is None or left < slack:
                    slack = left
        burning = bool(self._slo_shed_scale)
        grant = self._step_budget.grant(
            burning=burning, tightest_slack_s=slack,
        )
        if burning or slack is not None:
            # SLO feedback moved this step's prefill-vs-decode split; the
            # audit keeps only state CHANGES (consecutive-dedupe), so a
            # long burning run is one ring entry, not one per step.
            self.audit.record(
                "budget", "slo_feedback",
                detail="burning" if burning else "deadline_slack",
            )
        return grant

    # ------------------------------------------------- batched speculative

    def _spec_applicable(self, s, slot: int, cap: int) -> bool:
        sampled = s.temperature is not None and s.temperature > 0.0
        return (
            self.speculative_k > 0
            # A repeat penalty makes the in-chunk target history-dependent;
            # both acceptance modes gate on it (generator does the same).
            and s.repeat_penalty == 1.0
            # Gate on the method THIS round will call — a backend may grow
            # greedy verify before sampled verify, and the TCP backend
            # shadows both with None when a worker lacks the capability.
            and callable(
                getattr(
                    self.backend,
                    "verify_sampled" if sampled else "verify_greedy",
                    None,
                )
            )
            # The verify chunk writes slots [slot, slot + K].
            and slot + self.speculative_k + 1 < cap
        )

    def _spec_round(self, rows, kv, tok, slot, pads_j, keys, s,
                    span_args: dict | None = None):
        """One batched verify round: every live row drafts K tokens from its
        own history (prompt lookup), one shared cached-chunk forward verifies
        all rows, the epoch advances by the MINIMUM accepted length across
        live rows (rows' surplus accepted tokens are re-verified next round —
        correctness never depends on the drafts, see models/llama/batch.py).

        Returns (tok, kv, keys, slot) or None when NO live row produced a
        draft (the caller falls back to a plain decode chunk). Rows without
        a draft still ride the shared verify (``n_drafts = 0``): the chunk's
        first position scores exactly their plain-decode next token, so a
        non-repetitive co-batched row costs the round its surplus (the
        cross-row MIN advance) but never disables speculation for the rows
        that DO draft — the per-round efficiency stays visible as
        ``spec_tokens / spec_rounds``.
        """
        from cake_tpu.models.llama.speculative import (
            greedy_accept,
            propose_lookup,
        )

        K = self.speculative_k
        B = len(rows)
        t_round = time.perf_counter()
        tok_np = np.asarray(tok)
        drafts = np.zeros((B, K), np.int32)
        n_drafts = np.zeros((B,), np.int32)
        if self.proposer_factory is not None and self._proposer_mode is None:
            probe = self.proposer_factory()
            if hasattr(probe, "propose_batch"):
                self._batched_proposer = probe
                self._proposer_mode = "batched"
            else:
                self._spare_proposer = probe  # first lane claims it below
                self._proposer_mode = "per-lane"
        if self._proposer_mode == "batched":
            bp = self._batched_proposer
            can = getattr(bp, "can_propose", None)
            # Lanes the proposer cannot serve ride the round draft-less
            # (history None skips them) instead of aborting it for everyone.
            lane_drafts = bp.propose_batch(
                [
                    row.history
                    if row is not None
                    and (can is None or can(len(row.history), K))
                    else None
                    for row in rows
                ],
                K,
            )
        else:
            lane_drafts = []
            for lane, row in enumerate(rows):
                if row is None:
                    lane_drafts.append(None)
                    continue
                if self.proposer_factory is not None:
                    if lane not in self._lane_proposers:
                        self._lane_proposers[lane] = (
                            self._spare_proposer or self.proposer_factory()
                        )
                        self._spare_proposer = None
                    prop = self._lane_proposers[lane]
                    can = getattr(prop, "can_propose", None)
                    if can is not None and not can(len(row.history), K):
                        lane_drafts.append(None)  # rides draft-less
                        continue
                    lane_drafts.append(prop.propose(row.history, K) or None)
                else:
                    lane_drafts.append(propose_lookup(row.history, K) or None)
        n_drafting = 0
        for lane, row in enumerate(rows):
            if row is None:
                continue
            d = lane_drafts[lane]
            if d:
                drafts[lane, : len(d)] = d
                n_drafts[lane] = len(d)
                n_drafting += 1
        if n_drafting == 0:
            return None  # nobody drafted: plain decode is strictly cheaper
        tokens = np.concatenate([tok_np[:, None], drafts], axis=1)  # [B, K+1]

        sampled = s.temperature is not None and s.temperature > 0.0
        if sampled:
            (n_accs, nxts, kv, keys), (n_accs, nxts) = self._dispatch(
                "verify",
                lambda: self.backend.verify_sampled(
                    kv, tokens, slot, pads_j, drafts, n_drafts, keys, s
                ),
                readback=lambda out: (np.asarray(out[0]), np.asarray(out[1])),
            )
            cand = [
                [*drafts[l, : n_accs[l]].tolist(), int(nxts[l])]
                for l in range(B)
            ]
        else:
            (_, kv), ids = self._dispatch(
                "verify",
                lambda: self.backend.verify_greedy(kv, tokens, slot, pads_j),
                readback=lambda out: np.asarray(out[0]),
            )
            cand = []
            for l in range(B):
                n, nxt = greedy_accept(drafts[l], ids[l])
                cand.append([*drafts[l][:n].tolist(), nxt])

        # Shared-slot advance: the minimum candidate length over LIVE rows
        # (dead/dummy lanes are excluded — joins replace their KV wholesale).
        a = min(len(cand[l]) for l, row in enumerate(rows) if row is not None)
        dt_round = time.perf_counter() - t_round
        if span_args is not None:
            span_args["accepted"] = int(a)
            span_args["k"] = int(K)
        with self._phase("emit"):
            live_rows = [
                (lane, row) for lane, row in enumerate(rows) if row is not None
            ]
            used_map = {
                lane: row.peek_consumed(cand[lane][:a]) for lane, row in live_rows
            }
            # Hardware ledger: the verify chunk computed B x (K+1) positions;
            # accepted ones are spec goodput, the live remainder is the wasted
            # half of the speculative split, dead lanes are pad.
            self.efficiency.note_spec(
                dt_round, B, K, len(live_rows), sum(used_map.values()),
                slot=int(slot),
            )
            for lane, row in live_rows:
                # The verify chunk computed K+1 positions; the row consumes
                # `used` of them — the accepted/wasted split of the round.
                # Accounted BEFORE the pushes (a finishing row flushes its
                # attribution from inside push() -> finish()).
                row.account_spec(dt_round, K, used_map[lane])
                for t in cand[lane][:a]:
                    row.push(int(t))
                    if row.done:
                        rows[lane] = None
                        break
            new_tok = np.asarray(
                [c[a - 1] if len(c) >= a else 0 for c in cand], np.int32
            )
        self.stats["spec_rounds"] += 1
        self.stats["spec_tokens"] += a
        return jnp.asarray(new_tok), kv, keys, slot + a

    def _take_joins(
        self, knobs: tuple, rows: list, slot: int, cap: int,
        budget: dict | None = None,
    ) -> list[tuple[int, _Request]]:
        """Pop queued requests that can join NOW: same sampling knobs, prompt
        short enough to end at the shared slot, a free lane, and enough
        decode budget left that joining is not worse than waiting.
        ``budget`` (continuous scheduler) caps this step's cumulative join
        prefill work in prompt tokens — the SLO-aware prefill-vs-decode
        split; candidates over it stay queued for the next step.

        Candidates walk in the fair queue's DRR order. Two fairness rules
        compose: within a TENANT, scanning stops at its first request with
        DIFFERENT knobs (per-tenant FIFO — a tenant's own requests never
        jump each other); across the EPOCH, no joins are taken at all while
        the OLDEST queued request is knob-incompatible with it, so a
        waiting different-knob request still bounds the epoch (the old
        global-FIFO guarantee) instead of starving behind endless same-knob
        joins from other tenants.
        """
        free = [i for i, r in enumerate(rows) if r is None]
        if not free:
            return []
        now = time.monotonic()
        # Paged: joiners charge prompt pages + reserve against the pool,
        # cumulatively across this round's joins (allocation happens in
        # _join, after this accounting admits them).
        state = {
            "avail": self._alloc.pages_free if self._alloc is not None else None
        }

        def defer(req: _Request, cause: str, verdict: str = "skip") -> str:
            self.audit.record(
                "defer", cause, rid=req.rid, tenant=req.tenant
            )
            return verdict

        def accept(req: _Request) -> str:
            if req.deadline and now > req.deadline:
                self._expire_queued(req)
                return "drop"
            if req.knobs() != knobs:
                # per-tenant FIFO: nothing jumps this request
                return defer(req, "knob_incompatible", verdict="next")
            n_ids = len(req.prompt_ids)
            # A solo epoch would give the request
            # min(max_tokens, max_seq - bucket) tokens — it sizes its
            # OWN bounded capacity from its own max_tokens, NOT this
            # epoch's (possibly much smaller) cap. Join only when the
            # epoch's remaining budget matches that, so joining never
            # truncates below what waiting would deliver. A joiner gets
            # cap - slot tokens: 1 at the join + cap - 1 - slot decoded.
            solo_budget = min(
                self._gen.slots_for(req),
                self.max_seq_len
                - self.shapes.prompt_width(n_ids, self.max_seq_len),
            )
            fits = n_ids <= slot and cap - slot >= solo_budget
            if not fits:
                return defer(req, "capacity")
            if budget is not None and budget["left"] < n_ids:
                # over this step's prefill grant: next step
                return defer(req, "step_budget")
            # A join knows its pad exactly (prompt ends at the shared
            # slot), so the cached-prefix discount is exact here — and
            # cold prefix-cache pages reclaim on demand before the
            # free-page accounting refuses the join.
            avail = state["avail"]
            need = (
                self._pages_for(req, end_slot=slot)
                if avail is not None
                else 0
            )
            if avail is not None and need > avail and (
                self._prefix is not None
            ):
                avail = state["avail"] = avail + self._prefix.reclaim(
                    need - avail, rid=req.rid
                )
            if avail is None or need <= avail:
                if avail is not None:
                    state["avail"] = avail - need
                if budget is not None:
                    budget["left"] -= n_ids
                self.audit.record(
                    "join", "fair_order", rid=req.rid, tenant=req.tenant
                )
                return "take"
            return defer(req, "page_pressure")

        with self._cv:
            head = self._queue.oldest_head()
            if (
                head is not None
                and head.knobs() != knobs
                and not (head.deadline and now > head.deadline)
            ):
                # The epoch-bounding rule: the oldest queued request wants a
                # DIFFERENT trace — stop extending this epoch so it gets
                # its own, instead of waiting out other tenants' joins.
                self.audit.record(
                    "defer", "fairness_skip", rid=head.rid,
                    tenant=head.tenant, detail="epoch_bound",
                )
                return []
            taken = self._queue.take(len(free), accept)
            out = [(free[i], req) for i, req in enumerate(taken)]
            # Same no-gap rule as _admit: live the moment they leave the
            # queue, so cancel() always finds them somewhere.
            self._live_rids.update(req.rid for _, req in out)
        t_admit = time.perf_counter()
        for _, req in out:
            req.t_admit = t_admit  # join prefill is lane time, not queue
        return out

    def _row_prefill(self, kv, lane: int, ids, pad: int, slot: int, fork):
        """Re-prefill one row so that it ends at the shared slot: ``ids`` (a
        joiner's prompt, a restored lane's history) into slots [pad, slot)
        of lane ``lane``, in the window ``shapes.window`` cuts. ``fork`` is
        ``_fork_lane``'s answer under a prefix cache: only the window over
        the uncached tail runs, hit or miss through the one cached-chunk
        arithmetic (``suffix_join``), so a warm row is bit-identical to a
        cold one. Without a cache (None) the lane's pages, charged by
        ``_take_joins``, are mapped over the row and the backend's join
        runs. Returns (logits, kv, window width)."""
        if fork is not None:
            bound, pair = fork  # writes below ``bound`` drop
            if pair is not None:
                kv = self.backend.cow_copy(kv, [pair[0]], [pair[1]])
            start, W = self.shapes.window(bound, slot, slot, reads_pool=True)
            run = self.backend.suffix_join
        else:
            bound = slot  # the row ends here
            start, W = self.shapes.window(pad, slot, self.max_seq_len)
            if self._alloc is not None:
                self._alloc.map_range(lane, pad, slot)
            run = self.backend.join
        row_tokens = np.zeros((1, W), np.int32)
        lo = max(pad, start)
        row_tokens[0, lo - start : slot - start] = ids[lo - pad :]
        logits, kv = self._dispatch(
            "join",
            lambda: run(
                kv, row_tokens, np.asarray([pad], np.int32),
                np.asarray([bound], np.int32), lane, start,
            ),
        )
        return logits, kv, W

    def _join(self, req, lane, rows, slot, tok, kv, keys, ring_j, ring_idx_j, s):
        """Prefill one request into a free lane of the RUNNING epoch.

        The prompt is left-padded to end exactly at the epoch's shared slot;
        its KV row (computed in a fresh single-row cache) replaces the lane's
        row wholesale. The first token samples from the row's own fresh PRNG
        stream — identical to what a solo run would produce.
        """
        row = _RowState(
            req, set(self.config.eos_token_ids), self.tokenizer, lane=lane,
            engine=self,
        )
        # Open the lane-track span BEFORE the join prefill: the prefill IS
        # lane time (the /explain decomposition attributes it to the
        # joiner), and every failure path below still closes the span —
        # finish() on the page-truncated return, the except on a re-raise.
        row.open_span(slot=slot)
        t_join = time.perf_counter()
        try:
            return self._join_inner(
                req, row, lane, rows, slot, tok, kv, keys, ring_j,
                ring_idx_j, s, t_join,
            )
        except BaseException as e:
            # The caller retries (worker death) or strands the request —
            # either way THIS _RowState's span will never finish; close it
            # so the ring holds no orphan B for a lane that never served.
            row.close_span(error=str(e)[:200])
            raise

    def _join_inner(
        self, req, row, lane, rows, slot, tok, kv, keys, ring_j, ring_idx_j,
        s, t_join,
    ):
        from cake_tpu.models.llama.batch import _first_sample_fn, seed_rings

        ids = self._gen.prefilled(req)
        with self._phase(
            "join", rid=req.rid,
            args={
                "lane": lane, "slot": int(slot),
                "state_layers": self._state_layers,
            },
        ) as join:
            pad = slot - len(ids)
            fork = None
            if self._prefix is not None:
                from cake_tpu.models.llama.paged_cache import PageExhausted

                try:
                    with self._phase(
                        "prefix-fork", args={"lane": lane, "slot": int(slot)}
                    ):
                        fork = self._fork_lane(lane, req, pad, slot)
                except PageExhausted:
                    # _take_joins priced this join exactly, but the chain it
                    # was priced against can be reclaimed by an earlier
                    # joiner's own eviction before this fork runs — the same
                    # stale-estimate degradation as _prefix_layout: pool
                    # pressure costs this one stream, never the epoch.
                    self.stats["page_truncations"] += 1
                    req.handle.finish_reason = "length"
                    metrics.flight.record(
                        "page-truncated", req.rid, slot=int(slot),
                        where="join", completion_tokens=0,
                    )
                    if self._alloc.lane_mapped(lane):
                        self._lane_recycle(lane, insert=False)
                    else:
                        self._prefix.release(self._lane_leases.pop(lane, None))
                        self._lane_info.pop(lane, None)
                    row.finish()
                    self._pool_counter()
                    return tok, kv, keys, ring_j, ring_idx_j
            logits, kv, W = self._row_prefill(kv, lane, ids, pad, slot, fork)
            if self._gen.block:
                # No first token: the joiner's first block and its key.
                tok, keys = _set_lane(
                    (tok, keys), lane,
                    (self._gen.known_block(req), jax.random.PRNGKey(req.sampling.seed)),
                )
            else:
                # Same first-token arithmetic as every entry point (batch.py's
                # ``first_sample``), left ON THE DEVICE: the sample, the ring's
                # update and the lane's writes are programs enqueued behind the
                # prefill, and the token itself is read with this boundary's
                # other values (``_settle``), after the next decode chunk has
                # been enqueued behind them.
                row_ring, row_ring_idx = seed_rings([ids], s.repeat_last_n)
                key0 = jax.random.PRNGKey(req.sampling.seed)
                first, key_next = _first_sample_fn(
                    s.temperature, s.top_k, s.top_p, s.repeat_penalty, True
                )(logits, jnp.asarray(row_ring), key0[None])
                tok, keys, ring_j, ring_idx_j = _seat_joiner(
                    tok, keys, ring_j, ring_idx_j, lane, first, key_next,
                    row_ring, row_ring_idx,
                )
        self.periods.note_join(join.seconds)
        counters = self._take_counters()
        if self._gen.block:
            # nothing of the row's is in flight; the entry carries the join's
            # account (its counts, ready when the join is: what is read)
            row.known = self._gen.tail(req)
            first = counters[0] if counters is not None else tok
        else:
            row.inflight = 1
        self._unread.append(_Unread(
            first, [(lane, row)], slot, 0, t_join, W, counters=counters,
        ))
        # A budget of one token ends with the token in flight: the lane
        # never becomes the row's (its pages go at the next release).
        rows[lane] = row if self._gen.keeps_lane(req) else None
        self._record_admissions([req], "joined", lane=lane, slot=slot)
        metrics.registry.counter(
            "cake_engine_joins_total",
            "Requests that joined a RUNNING epoch at a chunk boundary.",
        ).inc()
        self.stats["joins"] += 1
        self.stats["rows"] += 1
        return tok, kv, keys, ring_j, ring_idx_j

    # The joiners of one step as one program (``shapes.join_rows`` > 1: a
    # model whose join re-reads routed experts the chip holds whole). Below
    # the step loop and ``_join``: a Mosaic kernel's payload carries its
    # callers' line numbers, and a line that moves above a dispatch is a new
    # compile-cache key for every cell's programs (PERF.md section 7, row 17).

    def _join_groups(self, join_args: list, slot: int) -> list[list]:
        """What ``_take_joins`` accepted, as the programs it goes as: lists
        of (lane, request), in the order accepted. One a program unless the
        backend's shapes group a step's joiners (``shapes.join_groups``, by
        each row's own window width)."""
        if self.shapes.join_rows < 2 or len(join_args) < 2:
            return [[pair] for pair in join_args]
        widths = [
            self.shapes.window(slot - len(req.prompt_ids), slot, self.max_seq_len)[1]
            for _, req in join_args
        ]
        return [
            [join_args[i] for i in program]
            for program in self.shapes.join_groups(widths)
        ]

    def _set_pads(self, pads_j, group: list, slot: int):
        """The joiners' pads into their lanes' rows of ``pads_j``."""
        pads = [slot - len(self._gen.prefilled(req)) for _, req in group]
        # (whole blocks: what lets the block-causal mask compare slots)
        assert not any(p % (self._gen.block or 1) for p in pads), pads
        if len(group) == 1:
            return _set_lane((pads_j,), group[0][0], (pads[0],))[0]
        lanes = self._row_lanes(group, dead=int(pads_j.shape[0]))
        pads = np.asarray(pads + [0] * (len(lanes) - len(pads)), np.int32)
        return _set_lanes((pads_j,), lanes, (pads,))[0]

    def _row_lanes(self, group: list, dead: int) -> np.ndarray:
        """The lanes of a group program's ``shapes.join_rows`` rows: the
        group's own, then ``dead`` for the rows the step did not fill (-1 to
        the backend; past the last lane to a write, which then drops)."""
        spare = self.shapes.join_rows - len(group)
        return np.asarray([lane for lane, _ in group] + [dead] * spare, np.int32)

    def _join_group(
        self, group: list, rows, slot, tok, kv, keys, ring_j, ring_idx_j, s
    ):
        """``_join`` for one program's joiners: the request alone through
        ``_join`` as ever, a group through ONE join program of
        ``shapes.join_rows`` rows (``backend.join_rows``), one first sample
        and one write of the lanes' rows: what every joiner's stream holds
        is what its own join gives it (each row its own pad, lane, table row
        and PRNG stream)."""
        if len(group) == 1:
            (lane, req), = group
            return self._join(
                req, lane, rows, slot, tok, kv, keys, ring_j, ring_idx_j, s
            )
        states = [
            _RowState(
                req, set(self.config.eos_token_ids), self.tokenizer,
                lane=lane, engine=self,
            )
            for lane, req in group
        ]
        for row in states:
            row.open_span(slot=slot)  # the prefill is lane time (``_join``)
        t_join = time.perf_counter()
        try:
            return self._join_rows(
                group, states, rows, slot, tok, kv, keys, ring_j,
                ring_idx_j, s, t_join,
            )
        except BaseException as e:
            for row in states:
                row.close_span(error=str(e)[:200])
            raise

    def _join_rows(
        self, group, states, rows, slot, tok, kv, keys, ring_j, ring_idx_j,
        s, t_join,
    ):
        from cake_tpu.models.llama.batch import _first_sample_fn, seed_rings

        n_rows = self.shapes.join_rows
        prompts = [req.prompt_ids for _, req in group]
        pads = [slot - len(ids) for ids in prompts]
        # every row ends at the shared slot, in the group program's width
        W = self.shapes.join_width([
            self.shapes.window(pad, slot, self.max_seq_len)[1] for pad in pads
        ])
        start = max(0, slot - W)
        spare = n_rows - len(group)
        with self._phase(
            "join", rid=group[0][1].rid,
            args={
                "lanes": [lane for lane, _ in group], "slot": int(slot),
                "rids": [req.rid for _, req in group],
                "rows": n_rows, "state_layers": self._state_layers,
            },
        ) as join:
            tokens = np.zeros((n_rows, W), np.int32)
            for r, ((lane, _), ids, pad) in enumerate(zip(group, prompts, pads)):
                lo = max(pad, start)
                tokens[r, lo - start : slot - start] = ids[lo - pad :]
                if self._alloc is not None:
                    self._alloc.map_range(lane, pad, slot)
            logits, kv = self._dispatch(
                "join",
                lambda: self.backend.join_rows(
                    kv, tokens, pads + [slot] * spare, [slot] * n_rows,
                    self._row_lanes(group, dead=-1), start,
                ),
            )
            # ``_join_inner``'s first-token arithmetic at ``n_rows`` rows,
            # each row its own ring and its own key; a dead row's sample is
            # seated nowhere.
            row_ring, row_ring_idx = seed_rings(
                prompts + [[]] * spare, s.repeat_last_n
            )
            key0 = jnp.stack([
                jax.random.PRNGKey(req.sampling.seed) for _, req in group
            ] + [jax.random.PRNGKey(0)] * spare)
            first, key_next = _first_sample_fn(
                s.temperature, s.top_k, s.top_p, s.repeat_penalty, True
            )(logits, jnp.asarray(row_ring), key0)
            tok, keys, ring_j, ring_idx_j = _seat_joiners(
                tok, keys, ring_j, ring_idx_j,
                self._row_lanes(group, dead=len(rows)), first, key_next,
                row_ring, row_ring_idx,
            )
        self.periods.note_join(join.seconds, joiners=len(group), rows=n_rows)
        counters = self._take_counters()
        for r, ((lane, req), row) in enumerate(zip(group, states)):
            row.inflight = 1
            # one device value, a row an entry: read once, emitted a joiner
            self._unread.append(_Unread(
                first, [(lane, row)], slot, 0, t_join, W,
                counters=counters if r == 0 else None, row=r,
            ))
            rows[lane] = row if req.max_tokens > 1 else None
            self._record_admissions([req], "joined", lane=lane, slot=slot)
            metrics.registry.counter(
                "cake_engine_joins_total",
                "Requests that joined a RUNNING epoch at a chunk boundary.",
            ).inc()
        self.stats["joins"] += len(group)
        self.stats["rows"] += len(group)
        return tok, kv, keys, ring_j, ring_idx_j

    # ------------------------------------- the accounts at a profiler's edges
    # A device trace's times are of the dispatches a profiler recorded; what
    # they are divided by has to be of the same dispatches. The engine's
    # accounts are cumulative, so one copy where the recorder starts and one
    # where it stops (obs/timeline.py ``recording``: the instant
    # ``stop_trace`` is CALLED, not the end of its closing) give, by
    # difference, the periods that ended under the recorder.

    def accounts(self, now: float | None = None) -> dict:
        """What ``GET /stats`` serves under ``engine``, less ``profiled``:
        the counters, the step loop's ``period`` and ``segment``
        (obs/period.py), the backend's ``cache`` / ``moe`` / ``sparse`` /
        ``state`` facts where its kind keeps them (a section's PRESENCE is
        read: runtime/batch_backend.py), the scheduler's shape and the
        spill table's depth. All cumulative: difference two reads
        (``period.open_seconds`` is of the iteration open at ``now``, this
        read's clock by default). Any thread's to call, and under no lock of
        the engine's."""
        out = dict(self.stats)
        out.update(self.periods.snapshot(now))
        for key in ("cache", "moe", "sparse", "state", "diffusion"):
            facts = getattr(self.backend, f"{key}_facts", None)
            if facts is not None:
                out[key] = facts()
        out["scheduler"] = self.scheduler
        out["spilled"] = len(self._spilled)
        return out

    def _profiler_edge(self, recording: bool) -> None:
        """The timeline saw ``recording()`` flip (one flip at a time: its
        lock): keep the accounts as they stand, with the clock the
        benchmark's ``trace_start`` / ``trace_stop`` answer with.
        ``_profiled`` is of the LAST session (``close`` None while it
        records) and is replaced whole: a reader holds one or the other."""
        now = time.perf_counter()
        edge = {"mono": now, "engine": self.accounts(now)}
        was = self._profiled
        if recording:
            self._profiled = {**was, "open": edge, "close": None}
        elif was["open"] is not None:
            self._profiled = {**was, "sessions": was["sessions"] + 1, "close": edge}

    def profiled(self) -> dict:
        """``GET /stats`` engine.profiled: {``sessions`` closed so far,
        ``open`` and ``close``: {``mono``, ``engine``: ``accounts()``} where
        the engine noticed the recorder start and stop}. The step loop
        notices at its spans' boundaries; an engine that idles when the
        recorder stops passes none, so the reader looks too: no period has
        ended since, and only ``mono`` is the later for it. A period's wall
        enters ``period.seconds`` at its END: each copy's
        ``period.open_seconds`` is what the period open at that notice had
        run, so ``close - open`` of ``seconds + open_seconds`` is the time in
        periods BETWEEN the notices and no more than ``mono``'s."""
        timeline.notice()
        return self._profiled


def _set_lanes_rows(arrays, lanes, values):
    return tuple(
        a.at[lanes].set(v, mode="drop") for a, v in zip(arrays, values)
    )


# ``_set_lane`` for a group program's rows: ``lanes`` [R], a dead row's past
# the last lane, where a write drops. One shape whatever the group holds.
_set_lanes = tracked_jit(_set_lanes_rows, name="engine.set_lanes")


def _seat_joiners_rows(
    tok, keys, ring, ring_idx, lanes, first, key, row_ring, row_ring_idx
):
    window = ring.shape[1]
    if window > 0:
        pushed = row_ring.at[jnp.arange(first.shape[0]), row_ring_idx].set(first)
        ring = ring.at[lanes].set(pushed, mode="drop")
        ring_idx = ring_idx.at[lanes].set(
            (row_ring_idx + 1) % window, mode="drop"
        )
    return (
        tok.at[lanes].set(first, mode="drop"),
        keys.at[lanes].set(key, mode="drop"), ring, ring_idx,
    )


# ``_seat_joiner`` for a group program's rows, a dead row seated nowhere.
_seat_joiners = tracked_jit(_seat_joiners_rows, name="engine.seat_joiners")


def _fail_request(
    req: _Request, error: str, engine: "BatchEngine | None" = None
) -> None:
    """Finish a never-admitted request gracefully as ``"error"`` (a joiner
    stranded by a worker failure): same taxonomy as admitted rows, without
    raising into the consumer."""
    req.handle.finish_reason = "error"
    metrics.registry.counter(
        "cake_stream_errors_total",
        "Streams finished with finish_reason=error after a worker failure.",
    ).inc()
    metrics.flight.record("stream-error", req.rid, error=error[:200])
    metrics.flight.record(
        "finished", req.rid, finish_reason="error", completion_tokens=0
    )
    if engine is not None:
        # SLO view (obs/slo.py): an error death with zero tokens — counts
        # against the tenant's error rate AND (no first token within any
        # bound) its TTFT objective, same as the _RowState.finish path.
        engine.slo.observe_finish(
            req.tenant, "error",
            had_deadline=bool(req.deadline), got_first_token=False,
        )
        engine._record_request(req, finish="error")
    req.handle._emit(_DONE)


@dataclasses.dataclass
class _Unread:
    """A device value the step loop has enqueued and not read yet: a decode
    chunk's tokens ``[lanes, n]`` or, with ``n`` 0, a joiner's first token
    ``[1]`` (``width``: its prefill's window). ``rows`` are the (lane, row)
    pairs that take the tokens, as they stood at the enqueue at ``t0``;
    ``slot`` is the shared slot then. ``host`` is the copy once read.
    ``counters``: what the backend's decode program returned beside the
    tokens (``take_chunk_counters``; None from most), read with them."""

    value: jax.Array
    rows: list
    slot: int
    n: int
    t0: float
    width: int = 0
    host: np.ndarray | None = None
    t_read: float = 0.0
    counters: jax.Array | None = None
    row: int = 0  # a joiner's row of ``value``: a group's joiners share one
    # lane -> prompt tokens its FIRST block carries unmasked (a block-diffusion
    # chunk's; they lead the lane's tokens and are not streamed)
    known: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _SpilledLane:
    """Host-side record of a preempted lane (continuous scheduler): the
    full chunk-boundary state a bit-identical restore needs. ``row`` keeps
    history / budget / phase accounting; ``key``/``ring``/``ring_idx`` are
    the device sampling state copied out at the spill boundary. No device
    memory, no pages — a spilled lane costs a few KB of host RAM."""

    row: "_RowState"
    key: np.ndarray
    ring: np.ndarray | None
    ring_idx: int
    t: float = dataclasses.field(default_factory=time.perf_counter)


class _RowState:
    """Engine-side per-row bookkeeping: budget, EOS, incremental detok, events."""

    def __init__(
        self, req: _Request, eos: set[int], tokenizer: Tokenizer,
        lane: int = 0, engine: "BatchEngine | None" = None,
    ):
        self.req = req
        self._eos = eos
        self._engine = engine
        self._tokenizer = tokenizer
        self._ids: list[int] = []
        # Full prompt+output history, grown incrementally by push() — the
        # speculative drafter reads it every round, so rebuilding it by
        # concatenation there would be O(history) per round.
        self.history: list[int] = list(req.prompt_ids)
        self._decoded_len = 0
        self.n = 0
        # Tokens of this row that are enqueued on the device and unread:
        # ``n + inflight`` is what the host can count of the budget.
        self.inflight = 0
        self.done = False
        self._finished = False
        self._backpressured = False
        self.lane = lane
        self._span: int | None = None
        # Latency attribution (obs/critpath.py taxonomy): per-phase wall
        # seconds accumulated by the engine's dispatch accounting. The
        # convoy bucket is the lockstep tax — epoch work this row rode
        # along for but did not need.
        self.phase: dict[str, float] = {
            "prefill": 0.0, "decode": 0.0, "spec_accepted": 0.0,
            "spec_wasted": 0.0, "convoy": 0.0, "restore": 0.0,
        }
        self.t_open = 0.0
        self.t_close = 0.0
        self.ttft_s: float | None = None
        # Token count at the last restore (-1 = never restored): a lane
        # that self-spills again at the SAME count made zero progress —
        # its next chunk can never map on this pool, so re-parking would
        # livelock (the respill doom check in _spill_lane).
        self.n_at_restore = -1
        # Prompt tokens this row's first block carries unmasked, until the
        # chunk that holds that block is enqueued (a block-diffusion row).
        self.known = 0

    # ---- lane-track timeline span (admission -> finish) ------------------

    def open_span(self, slot: int | None) -> None:
        """Open this request's lane-track span: one Perfetto row per lane,
        occupied from admission (or join) until the stream finishes. The
        queue/admission stamps ride the B args so GET /explain can
        decompose submit-to-lane time without the flight recorder."""
        self.t_open = time.perf_counter()
        queue_wait = max(
            0.0, (self.req.t_admit or self.t_open) - self.req.t_submit
        )
        args: dict = {
            "prompt_tokens": len(self.req.prompt_ids),
            "queue_wait_s": round(queue_wait, 6),
            "admit_s": round(self.req.admit_s, 6),
        }
        if slot is not None:
            args["join_slot"] = int(slot)
        self._span = timeline.begin(
            "request", rid=self.req.rid, track=f"lane{self.lane}", args=args,
            parent=None,  # lane-track root: not a child of the epoch span
        )
        if self._engine is not None and self not in self._engine._epoch_rows:
            # Epoch convoy meter input: lane occupancy intervals (a
            # restored row re-opens its span in the same segment; one
            # entry keeps its occupancy from double-counting).
            self._engine._epoch_rows.append(self)

    def close_span(self, error: str | None = None) -> None:
        if self.t_close == 0.0:
            self.t_close = time.perf_counter()
        if self._span is None:
            return
        args: dict = {
            "finish_reason": self.req.handle.finish_reason,
            "completion_tokens": self.n,
        }
        if error is not None:
            args["error"] = error[:200]
        timeline.end(self._span, args=args)
        self._span = None

    # ---- dispatch-time attribution (engine thread) -----------------------

    def account_prefill(self, dt: float, bucket: int) -> None:
        """Epoch-start prefill: own share scales with the prompt's fraction
        of the shared left-padded bucket; the padding's compute is convoy."""
        share = min(1.0, len(self.req.prompt_ids) / max(1, bucket))
        self.phase["prefill"] += dt * share
        self.phase["convoy"] += dt * (1.0 - share)

    def account_join(self, dt: float) -> None:
        """A join prefill computes exactly this row's window: all own."""
        self.phase["prefill"] += dt

    def account_restore(self, dt: float, bucket: int) -> None:
        """A spill-seeded restore prefill: redone work the preemption
        cost this stream — its own phase (so /explain can price the
        preemption), the shared bucket's padding split like prefill."""
        share = min(1.0, (len(self.history) - 1) / max(1, bucket))
        self.phase["restore"] += dt * share
        self.phase["convoy"] += dt * (1.0 - share)

    def account_decode(self, dt: float, n: int, used: int) -> None:
        """One decode chunk: n tokens computed, ``used`` consumed; the
        unconsumed tail (EOS/budget mid-chunk) is convoy."""
        frac = min(1.0, used / max(1, n))
        self.phase["decode"] += dt * frac
        self.phase["convoy"] += dt * (1.0 - frac)

    def account_spec(self, dt: float, k: int, used: int) -> None:
        """One verify round: K+1 positions computed, ``used`` accepted into
        this row's stream; the rest (rejected drafts + co-batched shape)
        is the wasted half of the speculative split."""
        frac = min(1.0, used / (k + 1))
        self.phase["spec_accepted"] += dt * frac
        self.phase["spec_wasted"] += dt * (1.0 - frac)

    def peek_consumed(self, toks) -> int:
        """How many of ``toks`` push() will consume before this row
        finishes — mirrors push()'s termination exactly (EOS token, or
        the budget filling on a non-EOS append), so dispatch accounting
        can run BEFORE the pushes that may finish the row."""
        if self.done:
            return 0
        used = 0
        n = self.n
        for t in toks:
            used += 1
            if int(t) in self._eos:
                break
            n += 1
            if n >= self.req.max_tokens:
                break
        return used

    def push(self, tid: int) -> None:
        """Accept one decoded id; emits a Token event unless already done.

        The moment a row is done (EOS or budget) its stream is CLOSED — the
        consumer unblocks immediately even though the row's lockstep lane keeps
        computing until the whole batch drains.
        """
        if self.done:
            return
        self._ids.append(tid)
        self.history.append(tid)
        self.n += 1
        now = time.perf_counter()
        if self.n == 1:
            ttft = now - self.req.t_submit
            self.ttft_s = ttft
            metrics.registry.histogram(
                "cake_ttft_seconds",
                "Submit-to-first-token latency (queue wait + prefill).",
            ).observe(ttft)
            metrics.flight.record(
                "first-token", self.req.rid, ttft_s=round(ttft, 6)
            )
            timeline.instant(
                "first-token", rid=self.req.rid, track=f"lane{self.lane}",
                args={"ttft_s": round(ttft, 6)},
            )
            if self._engine is not None:
                # Per-tenant TTFT SLI (obs/slo.py): the burn-rate input
                # for the declared --slo-ttft-ms objective. The rolling
                # time-series (obs/timeseries.py) takes the same sample
                # for the /timeseries p50/p99 window points.
                self._engine.slo.observe_ttft(self.req.tenant, ttft)
                self._engine.timeseries.observe_ttft(ttft)
        else:
            metrics.registry.histogram(
                "cake_inter_token_seconds",
                "Wall-clock gap between consecutive tokens of one stream.",
            ).observe(now - self.req.t_last_token)
        self.req.t_last_token = now
        if self._engine is not None:
            # Window tok/s (obs/timeseries.py): one tally per emitted
            # token — same cost class as the inter-token histogram above.
            self._engine.timeseries.observe_tokens()
        # Streaming backpressure watermark: a consumer that stopped
        # draining the handle gets the stream cancelled (next chunk
        # boundary) instead of an unbounded buffer. Checked before this
        # token's emit so the flagged stream still delivers it.
        eng = self._engine
        if (
            eng is not None
            and eng.stream_buffer_tokens
            and not self._backpressured
            and self.req.handle.buffered() >= eng.stream_buffer_tokens
        ):
            self._backpressured = True
            eng._shed_backpressure(self)
        is_eos = tid in self._eos
        if is_eos:
            self.req.handle.finish_reason = "stop"
            self.done = True
            text = ""
        else:
            text = self._delta()
        self.req.handle.completion_tokens = self.n
        self.req.handle._emit(Token(id=tid, text=text, is_end_of_stream=is_eos))
        if not is_eos and self.n >= self.req.max_tokens:
            self.req.handle.finish_reason = "length"
            self.done = True
        if self.done:
            self.finish()

    def _delta(self) -> str:
        delta, self._decoded_len = decode_delta(
            self._tokenizer, self._ids, self._decoded_len
        )
        return delta

    def fail(self, error: str) -> None:
        """Worker-failure isolation: finish this stream with
        ``finish_reason="error"`` — the consumer sees a clean end-of-stream
        with the error reason, NOT a raised exception (the tokens already
        delivered were bit-identical to a fault-free run's prefix)."""
        if self._finished:
            return
        self.done = True
        self.req.handle.finish_reason = "error"
        if self._engine is not None:
            self._engine.stats["stream_errors"] += 1
        metrics.registry.counter(
            "cake_stream_errors_total",
            "Streams finished with finish_reason=error after a worker "
            "failure.",
        ).inc()
        metrics.flight.record(
            "stream-error", self.req.rid,
            error=error[:200], completion_tokens=self.n,
        )
        timeline.instant(
            "stream-error", rid=self.req.rid, track=f"lane{self.lane}",
        )
        self.close_span(error=error)
        self.finish()

    def cancel(self) -> None:
        """Mid-epoch cancellation (engine.cancel): clean finish with
        ``finish_reason="cancelled"``; the lane and its pages recycle at
        this chunk boundary."""
        if self._finished:
            return
        self.done = True
        self.req.handle.finish_reason = "cancelled"
        timeline.instant(
            "cancelled", rid=self.req.rid, track=f"lane{self.lane}",
        )
        self.finish()

    def expire(self) -> None:
        """End-to-end deadline passed mid-decode (engine._apply_deadlines):
        clean finish with ``finish_reason="deadline"`` at this chunk
        boundary — the tokens already streamed stand, the lane and its
        pages recycle, and the consumer learns the SLO verdict instead of
        a silently late completion."""
        if self._finished:
            return
        self.done = True
        self.req.handle.finish_reason = "deadline"
        metrics.registry.counter(
            "cake_deadline_expired_total",
            "Requests past their end-to-end deadline (where=queued expired "
            "before admission; where=running at a chunk boundary).",
        ).inc(where="running")
        metrics.flight.record(
            "deadline-expired", self.req.rid, where="running",
            completion_tokens=self.n,
        )
        timeline.instant(
            "deadline-expired", rid=self.req.rid, track=f"lane{self.lane}",
        )
        self.finish()

    def finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        metrics.registry.counter(
            "cake_engine_completed_total", "Streams closed (any finish reason)."
        ).inc()
        metrics.flight.record(
            "finished", self.req.rid,
            finish_reason=self.req.handle.finish_reason,
            completion_tokens=self.n,
        )
        self.close_span()
        if self._engine is not None:
            # Per-tenant SLO SLIs (obs/slo.py): deadline hit/miss, error
            # and goodput accounting — a zero-token deadline/error finish
            # also counts as a TTFT miss (no first token within any bound).
            self._engine.slo.observe_finish(
                self.req.tenant, self.req.handle.finish_reason,
                tokens=self.n,
                had_deadline=bool(self.req.deadline),
                got_first_token=self.n > 0,
            )
            # Goodput ledger (obs/efficiency.py): class every emitted
            # token next to the SLO tracker's per-tenant goodput SLI —
            # same finish event, so the two views always agree.
            self._engine.efficiency.note_finish(
                self.req.tenant, self.req.handle.finish_reason, self.n
            )
            # Latency attribution: fold the row's measured phases into the
            # aggregate histograms and run the blackbox triggers.
            self._engine._observe_request(self)
            # Traffic observatory: the canonical completion record
            # (obs/requestlog.py) — same finish event as the SLO/goodput
            # observations above, so all three views always agree.
            self._engine._record_request(self.req, row=self)
        self.req.handle._emit(_DONE)
        if self._engine is not None:
            self._engine._row_finished(self.req.rid)
