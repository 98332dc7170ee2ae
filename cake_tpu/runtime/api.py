"""OpenAI-compatible REST API.

Covers the reference's API layer (cake-core/src/cake/api/mod.rs): a single
``POST /api/v1/chat/completions`` route (api/mod.rs:123) whose response carries
``{id, object: "chat.completion", created, model, choices:[{index, message}]}``
(api/mod.rs:26-62), resetting the model per request (api/mod.rs:78).

Beyond reference parity (its quirks are documented, not contracts — SURVEY.md §2.6):
  * SSE streaming (``"stream": true`` -> ``chat.completion.chunk`` events) — the
    reference is non-streaming only.
  * ``usage`` token counts in the response.
  * Per-request sampling overrides (temperature, top_p, max_tokens, seed).
  * A ``GET /health`` probe and an observability surface: ``GET /stats``
    (span timers + host/device memory + metric percentiles — what the
    ``cake-tpu stats`` CLI renders), ``GET /metrics`` (full Prometheus text
    exposition: latency histograms with cumulative buckets, counters, gauges,
    build info + uptime — utils/metrics.py), ``GET /events`` (the flight
    recorder's ring of request lifecycle events, filterable by request id;
    ``events_jsonl`` additionally streams every event to a JSONL file), and
    ``GET /trace`` (the timeline profiler's span-tree ring rendered as
    Perfetto-loadable Chrome trace-event JSON, filterable by request id;
    ``trace_jsonl`` streams the raw events — cake_tpu/obs/timeline.py), and
    ``GET /slo`` (per-tenant rolling SLIs + error-budget burn rates —
    cake_tpu/obs/slo.py), and ``GET /explain?request_id=`` (per-request
    critical-path latency attribution: queue / prefill / decode / convoy /
    stall / wire phase decomposition — cake_tpu/obs/critpath.py). On a TCP
    cluster with worker telemetry reports
    (obs/cluster.py), /metrics becomes ONE merged exposition with every
    node's series under a ``node`` label, /events interleaves cluster-wide
    events by clock-aligned time, and ``/trace?cluster=1`` exports ONE
    merged Perfetto trace with worker spans aligned onto the master clock.

Concurrency: with a ``BatchEngine`` (runtime/serving.py, ``--api-batch``),
requests are queued and decoded in lockstep batches — N concurrent clients
stream simultaneously at near-single-request speed each. Without an engine,
requests serialize behind a lock around the single generator (the reference
holds a global write lock the same way, api/mod.rs:76). Streaming sends tokens
as they decode, and a per-write socket timeout (``stream_write_timeout``) aborts
the stream if the client stops reading, so one stalled consumer can't wedge the
server for everyone. Built on http.server's ThreadingHTTPServer: the framework
runs with zero third-party server dependencies.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import logging
import math
import os
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.generator import LlamaGenerator, SamplingConfig, Token
from cake_tpu.runtime import faults

log = logging.getLogger("cake_tpu.api")

CHAT_ROUTE = "/api/v1/chat/completions"
CANCEL_ROUTE = "/api/v1/cancel"
PROFILE_ROUTE = "/profile"
MAX_PROFILE_SECONDS = 30.0

# Tenant ids key metric labels, quota buckets, and fair-queue subqueues;
# bounding their length keeps a hostile header from being a label-
# cardinality / memory vector (runtime/admission.py bounds the COUNT via
# MAX_TENANTS the same way).
MAX_TENANT_ID_LEN = 64


@dataclasses.dataclass
class ApiServer:
    generator: LlamaGenerator
    model_name: str = "llama3"
    default_max_tokens: int = 256
    # Max seconds a single SSE write may block on a non-reading client before
    # the stream is aborted (the generator lock is held while streaming).
    stream_write_timeout: float = 30.0
    # Optional concurrent-serving engine (runtime/serving.py). When set, chat
    # requests bypass the generator lock entirely: they queue into the engine
    # and decode as lockstep batches, streaming concurrently.
    engine: "object | None" = None
    # Flight-recorder JSONL dump hook: when set, every lifecycle event
    # (utils/metrics.py FlightRecorder) is appended to this path as one JSON
    # line — the durable counterpart of the bounded GET /events ring.
    events_jsonl: "str | None" = None
    # Timeline JSONL stream (--trace-jsonl): every profiling event
    # (cake_tpu/obs/timeline.py — spans, instants, counters, flow arrows) is
    # appended as one JSON line; ``cake_tpu.obs.load_jsonl`` +
    # ``export_events`` turn the file into a Perfetto-loadable trace, and the
    # bounded ring stays live at GET /trace either way.
    trace_jsonl: "str | None" = None
    # Request-log JSONL sink (--request-log): every per-request completion
    # record (obs/requestlog.py — tenant, token counts, timing ladder,
    # finish/SLO verdict, phase digest, decision causes) is appended as one
    # JSON line; the bounded ring stays live at GET /requests either way,
    # and the file IS the loadgen replay trace (cake_tpu/loadgen/replay.py).
    request_log: "str | None" = None
    # Where start-up went, as the entry point measured it (cli.main):
    # ``t_main`` (perf_counter at its entry), ``load_s`` with ``load`` by
    # stage, ``engine_init_s``. ``serve_forever`` adds ``main_to_ready_s``
    # when the socket listens; GET /stats carries the block as ``startup``.
    startup: "dict | None" = None
    # Where ``POST /profile?seconds=S`` writes its profiler windows
    # (--trace-dir); None = the route answers 404.
    profile_dir: "str | None" = None

    def __post_init__(self) -> None:
        self._lock = threading.Lock()
        with self._lock:
            self._profiling = False  # a POST /profile window is open
        self._started = int(time.time())
        if self.events_jsonl:
            from cake_tpu.utils import metrics

            metrics.flight.attach_jsonl(self.events_jsonl)
        if self.trace_jsonl:
            from cake_tpu.obs.timeline import timeline

            timeline.attach_jsonl(self.trace_jsonl)
        if self.request_log:
            reqlog = getattr(self.engine, "requestlog", None)
            if reqlog is not None:
                reqlog.attach_jsonl(self.request_log)
            else:
                log.warning(
                    "--request-log needs the batch engine (--api-batch "
                    "> 1); no request records will be written"
                )
        # Every backend compile in the process, tracked jit or not: /stats
        # reports the total so a caller can tell a cold start from a warm one.
        from cake_tpu.obs import jitwatch

        jitwatch.install_compile_listener()
        if self.engine is not None:
            self.engine.start()

    def startup_stats(self) -> dict:
        """The ``startup`` block of GET /stats (empty when the server was
        not started through the CLI)."""
        return {
            k: v for k, v in (self.startup or {}).items() if k != "t_main"
        }

    def profile(self, seconds: float) -> dict:
        """Record one profiler window of ``seconds`` into ``profile_dir``
        and return where it went. The window holds the device's operations
        and, on the host plane, the engine's spans (obs/timeline.py) and
        jit dispatches. Python's own tracer stays off: it slows the host it
        is meant to watch. One window at a time (409 meanwhile): the
        profiler is process-wide."""
        if self.profile_dir is None:
            raise ApiError(404, "profiling needs --trace-dir DIR at start-up")
        if not 0 < seconds <= MAX_PROFILE_SECONDS:
            raise ApiError(
                400, f"seconds must be in (0, {MAX_PROFILE_SECONDS:g}]"
            )
        with self._lock:
            if self._profiling:
                raise ApiError(409, "a profiler window is already open")
            self._profiling = True
        try:
            from cake_tpu.utils import trace

            with trace.jax_profile(self.profile_dir, host_python=False):
                t0 = time.perf_counter()
                time.sleep(seconds)
                t1 = time.perf_counter()
            files = glob.glob(
                f"{self.profile_dir}/plugins/profile/*/*.xplane.pb"
            )
            return {
                "path": max(files, key=os.path.getmtime) if files else None,
                "seconds": round(t1 - t0, 3),
                "close_seconds": round(time.perf_counter() - t1, 3),
            }
        finally:
            with self._lock:
                self._profiling = False

    def device_info(self) -> dict:
        """What is serving: the device as JAX reports it and the attention
        implementation the engine resolved. /health and the start-up log
        carry it, so a client reads the device instead of guessing."""
        from cake_tpu.models.llama.model import resolve_attention_impl
        from cake_tpu.utils.device import describe_devices

        backend = getattr(self.engine, "backend", None)
        if hasattr(backend, "kernel_impl"):
            impl = backend.kernel_impl()
        else:
            impl = resolve_attention_impl(self.generator.config.attention_impl)
        return {**describe_devices(), "attention_impl": impl}

    # ------------------------------------------------------------- handlers

    def handle_chat(self, body: dict, handler: BaseHTTPRequestHandler) -> dict | None:
        """Run one chat completion; returns a JSON response, or None if the
        reply was streamed directly to ``handler``. The whole request — including
        streaming — runs under the generator lock."""
        def opt(key, default, cast):
            """Request field with JSON-null treated as unset; bad types -> 400."""
            v = body.get(key)
            if v is None:
                return default
            try:
                return cast(v)
            except (TypeError, ValueError) as e:
                raise ApiError(400, f"invalid {key!r}: {e}") from e

        raw_messages = body.get("messages", [])
        if not isinstance(raw_messages, list) or not raw_messages:
            raise ApiError(400, "messages must be a non-empty list")
        try:
            messages = [Message.from_dict(m) for m in raw_messages]
        except (KeyError, ValueError, TypeError, AttributeError) as e:
            raise ApiError(400, f"invalid message: {e}") from e
        max_tokens = opt("max_tokens", None, int)
        if max_tokens is None:
            max_tokens = opt("max_completion_tokens", None, int)
        if max_tokens is None:
            max_tokens = self.default_max_tokens
        elif max_tokens < 1:
            raise ApiError(400, f"max_tokens must be >= 1, got {max_tokens}")
        stream = bool(body.get("stream", False))
        # OpenAI stream_options: {"include_usage": true} appends one final
        # usage chunk (empty choices) after the finish chunk, before
        # [DONE] — the only way a streaming client gets exact token
        # counts (tokens with empty text emit no content chunk, so
        # client-side chunk counting undercounts).
        stream_options = body.get("stream_options")
        if stream_options is not None and not isinstance(stream_options, dict):
            raise ApiError(400, "stream_options must be an object")
        include_usage = bool((stream_options or {}).get("include_usage"))

        if self.engine is not None:
            return self._handle_chat_batched(
                body, messages, max_tokens, stream, include_usage, opt, handler
            )

        from cake_tpu.utils import metrics

        with self._lock:
            gen = self.generator
            base = gen.sampling
            # Per-request sampling overrides; generator-level defaults otherwise.
            gen.sampling = self._request_sampling(opt, base)
            try:
                gen.reset()  # per-request reset, api/mod.rs:78
                for m in messages:
                    gen.add_message(m)
                n_prompt = gen.prompt_token_count()
                if n_prompt >= gen.step.max_seq_len:
                    # Context-length overflow is a client error (4xx), caught
                    # BEFORE streaming headers go out.
                    raise ApiError(
                        400,
                        f"prompt is {n_prompt} tokens but the context window "
                        f"is {gen.step.max_seq_len}",
                    )
                rid = f"chatcmpl-{uuid.uuid4()}"
                created = int(time.time())
                # Request-scoped wire attribution: distributed steps stamp
                # this id on every FORWARD frame (runtime/master.py).
                if hasattr(gen.step, "trace_id"):
                    gen.step.trace_id = rid
                metrics.flight.record(
                    "submitted", rid, prompt_tokens=n_prompt, path="serialized"
                )
                if stream:

                    def produce(on_token) -> str:
                        gen.generate(max_tokens, on_token=on_token)
                        return gen.last_finish_reason

                    _SseStream(
                        self, produce, rid, created,
                        usage_fn=(
                            (lambda: (gen._n_prompt, gen.generated_count))
                            if include_usage else None
                        ),
                    ).run(handler)
                    metrics.flight.record(
                        "finished", rid,
                        finish_reason=gen.last_finish_reason,
                        completion_tokens=gen.generated_count,
                    )
                    return None
                text = gen.generate(max_tokens)
                metrics.flight.record(
                    "finished", rid,
                    finish_reason=gen.last_finish_reason,
                    completion_tokens=gen.generated_count,
                )
                return self._completion_response(
                    rid,
                    created,
                    text,
                    gen.last_finish_reason,
                    gen._n_prompt,
                    gen.generated_count,
                )
            finally:
                gen.sampling = base
                if hasattr(gen.step, "trace_id"):
                    gen.step.trace_id = None

    def _handle_chat_batched(
        self, body, messages, max_tokens: int, stream: bool,
        include_usage: bool, opt, handler
    ) -> dict | None:
        """Engine path: no generator lock — submit and consume a stream handle.

        Requests admitted together decode as one lockstep batch; per-request
        sampling/seed stay exact (per-row PRNG keys, runtime/serving.py).
        """
        from cake_tpu.runtime.admission import QuotaExceeded
        from cake_tpu.runtime.serving import EngineOverloaded

        sampling = self._request_sampling(opt, self.generator.sampling)
        # Priority class (0 low / 1 normal / 2 high; engine default
        # otherwise): scales the load-shedding gates and the 503
        # Retry-After — low-priority traffic degrades first under overload.
        priority = opt("priority", None, int)
        if priority is not None and priority not in (0, 1, 2):
            raise ApiError(400, f"priority must be 0, 1 or 2, got {priority}")
        # Tenant identity (README "Admission control & SLOs"): the explicit
        # body field wins over the X-Cake-Tenant header; absent both, the
        # engine books everything to the default tenant. Keys the
        # per-tenant quota gates (429s) and the fair queue's subqueues.
        tenant = body.get("tenant")
        if tenant is not None and (
            not isinstance(tenant, str) or not tenant.strip()
        ):
            raise ApiError(400, "tenant must be a non-empty string")
        if tenant is None:
            tenant = handler.headers.get("X-Cake-Tenant") or None
        if tenant is not None and len(tenant) > MAX_TENANT_ID_LEN:
            # Tenant ids become metric labels and queue keys; an
            # attacker-chosen unbounded string is a cardinality/memory
            # vector, so the length is a hard 400 — not a truncation,
            # which would silently merge distinct tenants' quotas.
            raise ApiError(
                400,
                f"tenant id longer than {MAX_TENANT_ID_LEN} characters",
            )
        # End-to-end deadline in seconds (submit -> last token). Queued
        # past it the request expires unadmitted; running past it the
        # stream finishes with finish_reason="deadline".
        deadline_s = opt("deadline_s", None, float)
        if deadline_s is not None and deadline_s <= 0:
            raise ApiError(
                400, f"deadline_s must be > 0 seconds, got {deadline_s}"
            )
        rid = f"chatcmpl-{uuid.uuid4()}"
        try:
            # The response id doubles as the request/trace id: the engine's
            # flight-recorder lifecycle and wire-frame attribution use the
            # same string the client sees, so GET /events?request_id=<id>
            # resolves straight from a client-side response.
            h = self.engine.submit(
                messages, max_tokens, sampling, request_id=rid,
                priority=priority, tenant=tenant, deadline_s=deadline_s,
            )
        except QuotaExceeded as e:
            # Per-tenant quota refusal: 429 (the CALLER is over budget; the
            # hint is their own bucket arithmetic) — deliberately distinct
            # from the 503 below, which means the SERVER is saturated.
            raise ApiError(
                429, str(e),
                headers={"Retry-After": str(max(1, math.ceil(e.retry_after_s)))},
            ) from e
        except EngineOverloaded as e:
            # Load shedding: an honest 503 with a retry hint beats queueing
            # the request into a client-side timeout.
            raise ApiError(
                503, str(e),
                headers={"Retry-After": str(max(1, int(e.retry_after_s)))},
            ) from e
        except ValueError as e:  # over-length prompt — 4xx before any headers
            raise ApiError(400, str(e)) from e
        created = int(time.time())
        if stream:

            def produce(on_token) -> str:
                for tok in h.tokens():
                    on_token(tok)
                return h.finish_reason

            _SseStream(
                self, produce, rid, created,
                usage_fn=(
                    (lambda: (h.prompt_tokens, h.completion_tokens))
                    if include_usage else None
                ),
            ).run(handler)
            return None
        text = h.text()
        return self._completion_response(
            rid, created, text, h.finish_reason, h.prompt_tokens, h.completion_tokens
        )

    def _refresh_cluster(self) -> None:
        """Keep the cluster observability plane fresh for a merged surface
        read (/metrics, /events, /trace?cluster=1, /stats cluster block).

        With heartbeat probing on, the monitor's STATS pulls feed the
        observer continuously and this is a no-op; without it, a TCP
        master pulls on demand (runtime/master.py ``pull_cluster_stats``)
        — rate-limited to one refresh per few seconds so a burst of
        scrapes (or a worker whose connect must time out) costs one pull,
        not one per request."""
        monitor = getattr(self.engine, "monitor", None)
        if monitor is not None:
            return  # probe threads keep the observer live
        step = getattr(self.generator, "step", None)
        pull = getattr(step, "pull_cluster_stats", None)
        if pull is None:
            return
        now = time.monotonic()
        last = getattr(self, "_cluster_last_pull", 0.0)
        if now - last < 5.0:
            return  # fresh enough: serve the cached reports
        self._cluster_last_pull = now
        try:
            pull()
        except Exception:  # noqa: BLE001 — a scrape must not 500
            log.exception("cluster stats pull failed")

    def _client_gone(self, rid: str) -> None:
        """Client-disconnect/stall hook (the SSE error path): with a batch
        engine, cancel the abandoned request so its lane stops decoding and
        its pages free up; always leave a flight-recorder breadcrumb."""
        from cake_tpu.utils import metrics

        cancelled = False
        if self.engine is not None:
            try:
                cancelled = bool(self.engine.cancel(rid))
            except Exception:  # noqa: BLE001 — a dying stream must not 500
                log.exception("cancel-on-disconnect failed for %s", rid)
        metrics.flight.record("client-gone", rid, cancelled=cancelled)

    @staticmethod
    def _request_sampling(opt, base: SamplingConfig) -> SamplingConfig:
        """Per-request overrides over the server's base sampling — the ONE
        list of knobs the API exposes, shared by both serving paths."""
        return SamplingConfig(
            temperature=opt("temperature", base.temperature, float),
            top_k=opt("top_k", base.top_k, int),
            top_p=opt("top_p", base.top_p, float),
            repeat_penalty=base.repeat_penalty,
            repeat_last_n=base.repeat_last_n,
            seed=opt("seed", base.seed, int),
        )

    def _completion_response(
        self, rid, created, text, finish_reason, n_prompt, n_generated
    ) -> dict:
        """The reference's response shape (api/mod.rs:26-62) + usage."""
        return {
            "id": rid,
            "object": "chat.completion",
            "created": created,
            "model": self.model_name,
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish_reason,
                }
            ],
            "usage": {
                "prompt_tokens": n_prompt,
                "completion_tokens": n_generated,
                "total_tokens": n_prompt + n_generated,
            },
        }

    # ------------------------------------------------------------- serving

    def make_server(self, host: str, port: int) -> ThreadingHTTPServer:
        api = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):  # route through logging
                log.debug("%s " + fmt, self.client_address[0], *args)

            def _json(self, code: int, obj: dict,
                      headers: dict[str, str] | None = None) -> None:
                data = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                from urllib.parse import parse_qs, urlparse

                parsed = urlparse(self.path)
                route, query = parsed.path, parse_qs(parsed.query)
                if route == "/health":
                    self._json(
                        200,
                        {
                            "status": "ok",
                            "model": api.model_name,
                            **api.device_info(),
                        },
                    )
                elif route == "/metrics":
                    # Prometheus text exposition: the metrics registry
                    # (histograms with cumulative buckets, counters, gauges —
                    # utils/metrics.py) plus span timers as count/total pairs
                    # (the standard summary shape) and the batch engine's
                    # admission counters. # HELP lines ride along so scrapes
                    # are self-describing. Scrapers point at the serving port.
                    from cake_tpu import __version__
                    from cake_tpu.utils import metrics, trace

                    # Refreshed at scrape time (not construction): a registry
                    # clear() between test modules must not lose them.
                    metrics.registry.gauge(
                        "cake_build_info",
                        "Constant 1; the labels carry model and version.",
                    ).set(1, model=api.model_name, version=__version__)
                    if hasattr(api.engine, "slo"):
                        # cake_slo_* gauges reflect the live rolling
                        # windows; set at scrape time, not per observation.
                        api.engine.slo.refresh_metrics()
                    if hasattr(api.engine, "efficiency"):
                        # cake_goodput_frac / cake_mfu / cake_mbu follow
                        # the same scrape-time gauge pattern.
                        api.engine.efficiency.refresh_metrics()
                    metrics.registry.gauge(
                        "cake_uptime_seconds",
                        "Seconds since the API server started.",
                    ).set(round(time.time() - api._started, 3))
                    lines = [
                        "# HELP cake_span_seconds Accumulated span timers "
                        "(utils/trace.py), as count/sum pairs.",
                        "# TYPE cake_span_seconds summary",
                    ]
                    for name, d in sorted(trace.spans.snapshot().items()):
                        # Prometheus label-value escaping (\ " and newline):
                        # dropped characters would silently collide series,
                        # and a raw newline fails the whole scrape.
                        label = metrics.escape_label_value(name)
                        lines.append(
                            f'cake_span_seconds_count{{span="{label}"}} '
                            f"{d['count']}"
                        )
                        lines.append(
                            f'cake_span_seconds_sum{{span="{label}"}} '
                            f"{d['total_s']:.6f}"
                        )
                    if api.engine is not None:
                        # High-water marks are gauges — rate()/increase()
                        # over a non-monotonic stat is meaningless, and the
                        # wrong TYPE hint poisons the scraper's view.
                        _GAUGES = {"max_rows"}
                        _HELP = {
                            "batches": "Lockstep decode batches started.",
                            "rows": "Rows ever admitted (initial + joins).",
                            "max_rows": "High-water mark of rows per batch.",
                            "joins": "Continuous-batching joins.",
                            "spec_rounds": "Batched speculative rounds.",
                            "spec_tokens": "Tokens advanced speculatively.",
                            "page_truncations": "Streams force-finished "
                            "at page exhaustion.",
                            "stream_errors": "Streams finished "
                            "finish_reason=error (worker failure).",
                            "cancelled": "Requests cancelled.",
                            "shed": "Submissions refused by load shedding.",
                            "quota_refusals": "Submissions refused by "
                            "per-tenant quotas (HTTP 429).",
                            "deadline_expired": "Requests past their "
                            "end-to-end deadline (queued or running).",
                            "epoch_stalls": "Backend dispatches abandoned "
                            "by the stuck-epoch watchdog.",
                            "prefix_hits": "Admissions/joins served a "
                            "cached prefix chain (--prefix-cache).",
                            "prefix_misses": "Admissions/joins with no "
                            "usable cached prefix (--prefix-cache).",
                        }
                        for k, v in sorted(api.engine.stats.items()):
                            kind = "gauge" if k in _GAUGES else "counter"
                            lines.append(
                                f"# HELP cake_engine_{k} "
                                f"{_HELP.get(k, 'Engine counter.')}"
                            )
                            lines.append(f"# TYPE cake_engine_{k} {kind}")
                            lines.append(f"cake_engine_{k} {v}")
                    # Cluster federation (obs/cluster.py): when workers have
                    # reported telemetry, the registry block becomes ONE
                    # merged exposition — every node's series under a
                    # ``node`` label (the master's own injected as
                    # node="master"). Single-process servers expose the
                    # local registry exactly as before.
                    from cake_tpu.obs.cluster import cluster

                    api._refresh_cluster()
                    if cluster.nodes():
                        registry_text = cluster.merged_exposition(
                            metrics.registry.dump()
                        )
                    else:
                        registry_text = metrics.registry.expose()
                    body = (
                        "\n".join(lines) + "\n" + registry_text
                    ).encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/plain; version=0.0.4"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif route == "/events":
                    # Flight recorder: the bounded ring of request lifecycle
                    # events (submitted/admitted/joined/first-token/finished/
                    # worker-reconnect). ?request_id=<id> filters to one
                    # request's timeline — the id is the chat response id.
                    from cake_tpu.obs.cluster import cluster
                    from cake_tpu.utils import metrics

                    rid = query.get("request_id", [None])[0]
                    api._refresh_cluster()
                    if cluster.nodes():
                        # Cluster-wide interleave by ALIGNED time: worker
                        # event timestamps are shifted onto the master
                        # clock by each node's estimated offset.
                        events = cluster.merged_events(
                            metrics.flight.snapshot()
                        )
                        if rid is not None:
                            events = [
                                e for e in events
                                if e.get("request_id") == rid
                            ]
                    else:
                        events = metrics.flight.snapshot(request_id=rid)
                    self._json(
                        200,
                        {
                            "events": events,
                            "count": len(events),
                            "capacity": metrics.flight.capacity,
                            "cluster": cluster.nodes(),
                        },
                    )
                elif route == "/trace":
                    # Timeline profiler: the bounded span-tree ring rendered
                    # as Chrome trace-event JSON — save the body to a file
                    # and load it in Perfetto / chrome://tracing (lane
                    # tracks, engine spans, flow arrows, HBM counters).
                    # ?request_id=chatcmpl-... narrows to one request's
                    # spans; `cake-tpu trace --out t.json` wraps this route.
                    from cake_tpu.obs.timeline import timeline

                    rid = query.get("request_id", [None])[0]
                    if query.get("cluster", ["0"])[0] in ("1", "true"):
                        # ONE merged export: every reporting worker's
                        # timeline slice, clock-shifted onto the master's
                        # wall, so op spans nest inside the wire.<node>
                        # spans that caused them and flow arrows connect
                        # across process tracks (obs/cluster.py;
                        # `cake-tpu trace --cluster` wraps this).
                        from cake_tpu.obs.cluster import cluster

                        api._refresh_cluster()
                        self._json(
                            200,
                            cluster.merged_trace(
                                timeline.snapshot(rid), request_id=rid
                            ),
                        )
                    else:
                        self._json(200, timeline.export(rid))
                elif route == "/explain":
                    # Critical-path attribution (obs/critpath.py): where
                    # did this request's latency go — queue / prefill /
                    # decode / convoy / stall / wire — straight from the
                    # timeline ring. 400 without a request_id, 404 when
                    # the id has no spans left in the ring (evicted, shed
                    # before admission, or never existed);
                    # `cake-tpu explain` wraps this route.
                    from cake_tpu.obs import critpath
                    from cake_tpu.obs.timeline import timeline

                    rid = query.get("request_id", [None])[0]
                    if not rid:
                        self._json(
                            400,
                            {"error": "explain needs a request_id query "
                             "parameter (the chatcmpl-... response id)"},
                        )
                    else:
                        res = critpath.explain(timeline.snapshot(), rid)
                        if res is None:
                            self._json(
                                404,
                                {"error": f"no timeline spans for request "
                                 f"{rid!r}: evicted from the ring, refused "
                                 "before admission, or unknown"},
                            )
                        else:
                            audit = getattr(api.engine, "audit", None)
                            if audit is not None:
                                # Scheduler decision audit (obs/
                                # efficiency.py): WHY the scheduler
                                # queued/deferred/preempted this request,
                                # next to critpath's "how long".
                                res["decisions"] = audit.for_request(rid)
                            self._json(200, res)
                elif route == "/efficiency":
                    # Goodput & hardware-efficiency ledger
                    # (obs/efficiency.py): device-time buckets (sum to the
                    # measured device wall by construction), token goodput
                    # classes, per-tenant attribution, the analytic
                    # FLOPs/HBM roofline (MFU/MBU when device peaks are
                    # known), plus the scheduler decision-audit ring.
                    # `cake-tpu top` polls this next to /stats and /slo.
                    eff = getattr(api.engine, "efficiency", None)
                    if eff is None:
                        self._json(
                            404,
                            {"error": "efficiency ledger needs the batch "
                             "engine (--api-batch > 1)"},
                        )
                    else:
                        body = eff.snapshot()
                        audit = getattr(api.engine, "audit", None)
                        if audit is not None:
                            body["decision_ring"] = audit.snapshot(
                                limit=200
                            )
                        self._json(200, body)
                elif route == "/slo":
                    # Per-tenant SLO view (obs/slo.py): declared objectives,
                    # rolling fast/slow-window SLIs (TTFT p99, deadline hit
                    # rate, error/shed rates, goodput tok/s) and error-
                    # budget burn rates per tenant.
                    slo = getattr(api.engine, "slo", None)
                    if slo is None:
                        self._json(
                            404,
                            {"error": "SLO tracking needs the batch "
                             "engine (--api-batch > 1)"},
                        )
                    else:
                        self._json(200, slo.snapshot())
                elif route == "/requests":
                    # Traffic observatory (obs/requestlog.py): the bounded
                    # ring of per-request completion records — tenant,
                    # token counts, queue/TTFT/TPOT timing ladder, finish
                    # reason, SLO verdict, phase digest, decision causes.
                    # ?tenant= / ?finish= filter, ?since=<seq> is the tail
                    # cursor (`cake-tpu requests --follow` wraps it),
                    # ?limit= keeps the newest N. --request-log streams the
                    # same records to JSONL, the loadgen replay format.
                    reqlog = getattr(api.engine, "requestlog", None)
                    if reqlog is None:
                        self._json(
                            404,
                            {"error": "request log needs the batch "
                             "engine (--api-batch > 1)"},
                        )
                    else:
                        def _int_q(key):
                            raw = query.get(key, [None])[0]
                            if raw is None:
                                return None
                            try:
                                return int(raw)
                            except ValueError:
                                return None
                        recs = reqlog.snapshot(
                            tenant=query.get("tenant", [None])[0],
                            finish=query.get("finish", [None])[0],
                            since=_int_q("since"),
                            limit=_int_q("limit") or 0,
                        )
                        self._json(
                            200,
                            {
                                "requests": recs,
                                "count": len(recs),
                                **reqlog.stats(),
                            },
                        )
                elif route == "/timeseries":
                    # Rolling SLI time-series (obs/timeseries.py): the
                    # sliding window of per-bucket points (p50/p99 TTFT,
                    # tok/s, shed/429 rate) `cake-tpu top` renders as
                    # sparkline columns.
                    ts = getattr(api.engine, "timeseries", None)
                    if ts is None:
                        self._json(
                            404,
                            {"error": "SLI time-series needs the batch "
                             "engine (--api-batch > 1)"},
                        )
                    else:
                        self._json(200, ts.series())
                elif route == "/api/v1/models":
                    # OpenAI SDK model discovery (client.models.list()): the
                    # one loaded model, in the list-envelope shape.
                    self._json(
                        200,
                        {
                            "object": "list",
                            "data": [
                                {
                                    "id": api.model_name,
                                    "object": "model",
                                    "created": api._started,
                                    "owned_by": "cake-tpu",
                                }
                            ],
                        },
                    )
                elif route == "/stats":
                    # Observability: span timers (per-hop TCP latencies, local
                    # stage times) + host/device memory (utils/trace.py) +
                    # the metrics registry snapshot (histogram percentiles,
                    # counters, gauges — what `cake-tpu stats` renders) + the
                    # batch engine's admission counters under --api-batch.
                    from cake_tpu.obs import jitwatch, memwatch
                    from cake_tpu.obs.timeline import timeline
                    from cake_tpu.utils import metrics, trace

                    body = {
                        "model": api.model_name,
                        "uptime_s": round(time.time() - api._started, 3),
                        # Process-wide backend compiles since start (with a
                        # warm persistent cache: the time to fetch them),
                        # what they stalled, and which tracked family paid.
                        "compile": jitwatch.compile_stats(),
                        # Where start-up went (cli.main: load by stage,
                        # engine construction, entry to listening).
                        "startup": api.startup_stats(),
                        "spans": trace.spans.snapshot(),
                        # Structured span tree aggregate (total vs SELF time
                        # per span name) over the timeline ring — what
                        # `cake-tpu stats --spans` renders.
                        "timeline": timeline.aggregate(),
                        "memory": trace.memory_report(),
                        # Allocator-truth watermarks (obs/memwatch.py):
                        # host RSS + per-device HBM in-use/peak/limit, so
                        # `cake-tpu top`/`stats` see memory pressure next
                        # to pool occupancy without scraping /metrics.
                        "memwatch": {
                            "host_rss_bytes": memwatch.host_rss_bytes(),
                            "devices": memwatch.device_memory(),
                        },
                        "metrics": metrics.registry.snapshot(),
                    }
                    from cake_tpu.obs.cluster import cluster

                    api._refresh_cluster()
                    if cluster.nodes():
                        # Per-node federation summary (obs/cluster.py):
                        # clock offset + error bound, probe RTT, report
                        # freshness, headline op/byte telemetry — what
                        # `cake-tpu stats` renders as the per-node table.
                        body["cluster"] = cluster.snapshot()
                    if api.engine is not None:
                        # The engine's cumulative accounts (counters,
                        # ``period`` / ``segment``, the backend's ``cache`` /
                        # ``moe`` / ``sparse`` / ``state``, the scheduler's
                        # shape) and the copies of them it kept where a
                        # profiler last started and stopped recording.
                        body["engine"] = {
                            **api.engine.accounts(),
                            "profiled": api.engine.profiled(),
                        }
                        if hasattr(api.engine, "phase_stats"):
                            # Latency attribution aggregate + per-epoch
                            # convoy meter (the lockstep tax) — rendered
                            # by `cake-tpu stats` next to the tenant
                            # table; per-request detail at GET /explain.
                            body["phases"] = api.engine.phase_stats()
                        if hasattr(api.engine, "blackbox") and (
                            api.engine.blackbox is not None
                        ):
                            body["blackbox"] = api.engine.blackbox.stats()
                        if hasattr(api.engine, "slo"):
                            # Per-tenant SLO burn view (obs/slo.py; the
                            # full window detail lives at GET /slo).
                            body["slo"] = api.engine.slo.snapshot()
                        if hasattr(api.engine, "efficiency"):
                            # Goodput & hardware-efficiency headline
                            # (obs/efficiency.py; full bucket detail and
                            # the decision ring live at GET /efficiency).
                            body["efficiency"] = (
                                api.engine.efficiency.snapshot()
                            )
                        if hasattr(api.engine, "tenant_stats"):
                            # Per-tenant admission view (runtime/
                            # admission.py): queue depth, active streams,
                            # admitted work tokens, quota refusals, and the
                            # current token-bucket level per tenant.
                            body["tenants"] = api.engine.tenant_stats()
                        prefix = getattr(api.engine, "_prefix", None)
                        if prefix is not None:
                            # Persistent prefix cache (--prefix-cache):
                            # footprint, radix shape, hit/miss/eviction
                            # counters, and how many pages eviction could
                            # free right now (runtime/prefix_cache.py).
                            body["prefix"] = prefix.stats()
                    self._json(200, body)
                else:
                    self._json(404, {"error": "not found"})

            def do_POST(self):
                from urllib.parse import parse_qs, urlparse

                parsed = urlparse(self.path)
                if parsed.path == PROFILE_ROUTE:
                    try:
                        seconds = float(
                            parse_qs(parsed.query).get("seconds", [""])[0]
                        )
                        self._json(200, api.profile(seconds))
                    except ValueError:
                        self._json(
                            400, {"error": "POST /profile?seconds=S"}
                        )
                    except ApiError as e:
                        self._json(e.code, {"error": str(e)})
                    return
                if self.path not in (CHAT_ROUTE, CANCEL_ROUTE):
                    # Reference returns a default 404 for everything else
                    # (api/mod.rs:105-107).
                    self._json(404, {"error": "not found"})
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = json.loads(self.rfile.read(length) or b"{}")
                except (ValueError, json.JSONDecodeError) as e:
                    self._json(400, {"error": f"bad request body: {e}"})
                    return
                if self.path == CANCEL_ROUTE:
                    # Request cancellation: frees the lane's KV pages
                    # mid-epoch and stops its decode steps (runtime/
                    # serving.py cancel). The id is the chat response id.
                    rid = body.get("id") or body.get("request_id")
                    if not isinstance(rid, str) or not rid:
                        self._json(
                            400, {"error": "body needs a request 'id'"}
                        )
                        return
                    if api.engine is None:
                        self._json(
                            400,
                            {"error": "cancellation needs the batch "
                             "engine (--api-batch > 1)"},
                        )
                        return
                    self._json(
                        200, {"id": rid, "cancelled": api.engine.cancel(rid)}
                    )
                    return
                try:
                    response = api.handle_chat(body, self)
                except ApiError as e:
                    self._json(e.code, {"error": str(e)}, headers=e.headers)
                    return
                except Exception as e:  # noqa: BLE001 - surface as 500
                    log.exception("chat handler failed")
                    self._json(500, {"error": str(e)})
                    return
                if response is not None:
                    self._json(200, response)

        server = ThreadingHTTPServer((host, port), Handler)
        server.daemon_threads = True
        return server

    def serve_forever(self, host: str, port: int) -> None:
        server = self.make_server(host, port)
        if self.startup and "t_main" in self.startup:
            self.startup["main_to_ready_s"] = round(
                time.perf_counter() - self.startup["t_main"], 3
            )
        log.info("API listening on http://%s:%d%s", host, port, CHAT_ROUTE)
        log.info(
            "serving on %s",
            " ".join(f"{k}={v}" for k, v in self.device_info().items()),
        )
        server.serve_forever()


class ApiError(Exception):
    def __init__(self, code: int, message: str,
                 headers: dict[str, str] | None = None):
        super().__init__(message)
        self.code = code
        self.headers = headers or {}


class _SseStream:
    """SSE emitter for chat.completion.chunk events.

    ``produce(on_token) -> finish_reason`` drives generation — a locked
    LlamaGenerator.generate or a BatchEngine stream handle — and the emitter
    owns only the wire format.
    """

    def __init__(self, api: ApiServer, produce, rid: str, created: int,
                 usage_fn=None):
        self.api = api
        self.produce = produce
        self.rid = rid
        self.created = created
        # stream_options {"include_usage": true}: () -> (prompt_tokens,
        # completion_tokens), read AFTER produce() returns so the counts
        # are final.
        self.usage_fn = usage_fn

    def _chunk(self, delta: dict, finish: str | None = None) -> bytes:
        payload = {
            "id": self.rid,
            "object": "chat.completion.chunk",
            "created": self.created,
            "model": self.api.model_name,
            "choices": [
                {"index": 0, "delta": delta, "finish_reason": finish}
            ],
        }
        return f"data: {json.dumps(payload)}\n\n".encode()

    def run(self, handler: BaseHTTPRequestHandler) -> None:
        """Stream the completion. Once headers are sent, errors are reported as
        an SSE error event (never a second HTTP response into the open chunked
        stream) and the stream is terminated cleanly.

        Writes run under a socket timeout: a client that stops reading raises
        socket.timeout once the TCP send buffer fills, aborting the stream
        instead of blocking forever while holding the generator lock. The
        original timeout is restored afterwards so keep-alive reuse of the
        connection is unaffected."""
        prev_timeout = handler.connection.gettimeout()
        handler.connection.settimeout(self.api.stream_write_timeout)
        try:
            self._run_stream(handler)
        finally:
            try:
                handler.connection.settimeout(prev_timeout)
            except OSError:
                pass

    def _run_stream(self, handler: BaseHTTPRequestHandler) -> None:
        handler.send_response(200)
        handler.send_header("Content-Type", "text/event-stream")
        handler.send_header("Cache-Control", "no-cache")
        handler.send_header("Transfer-Encoding", "chunked")
        handler.end_headers()

        def write(data: bytes) -> None:
            spec = faults.check("api.stream")
            if spec is not None and spec.kind == "stall":
                faults.sleep(spec)  # a consumer that stopped reading
            handler.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")

        try:
            write(self._chunk({"role": "assistant", "content": ""}))

            def on_token(tok: Token) -> None:
                if tok.text:
                    write(self._chunk({"content": tok.text}))

            finish = self.produce(on_token)
            write(self._chunk({}, finish=finish))
            if self.usage_fn is not None:
                # OpenAI shape: the usage chunk carries empty choices and
                # sits between the finish chunk and [DONE].
                n_prompt, n_completion = self.usage_fn()
                payload = {
                    "id": self.rid,
                    "object": "chat.completion.chunk",
                    "created": self.created,
                    "model": self.api.model_name,
                    "choices": [],
                    "usage": {
                        "prompt_tokens": n_prompt,
                        "completion_tokens": n_completion,
                        "total_tokens": n_prompt + n_completion,
                    },
                }
                write(f"data: {json.dumps(payload)}\n\n".encode())
        except (BrokenPipeError, ConnectionResetError, TimeoutError):
            # Client went away or stopped reading mid-stream; abandon it. The
            # chunked stream was never terminated, so the connection cannot be
            # reused — without close_connection the keep-alive loop would block
            # in readline() on the dead socket forever. With a batch engine,
            # also CANCEL the request so the abandoned stream stops burning
            # decode steps and returns its KV pages mid-epoch.
            log.warning("client %s stalled or disconnected mid-stream",
                        handler.client_address)
            self.api._client_gone(self.rid)
            handler.close_connection = True
            return
        except Exception as e:  # noqa: BLE001 - surface in-band
            log.exception("generation failed mid-stream")
            try:
                write(f"data: {json.dumps({'error': str(e)})}\n\n".encode())
            except (BrokenPipeError, ConnectionResetError, TimeoutError, OSError):
                # Client is gone too; never let this propagate to do_POST,
                # which would inject a second HTTP response into the open
                # chunked stream.
                handler.close_connection = True
                return
        try:
            write(b"data: [DONE]\n\n")
            handler.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError, TimeoutError):
            # Terminator never reached the client; drop the connection rather
            # than reuse a stream with no final chunk.
            handler.close_connection = True
