"""Command-line entry point: ``python -m cake_tpu.cli``.

Covers the reference CLI's flag surface (cake-core/src/lib.rs:13-70 and
cake-cli/src/main.rs): ``--mode master|worker``, ``--name``, ``--address``,
``--api``, ``--model``, ``--topology``, ``--prompt``/``--system-prompt``,
sampling flags (seed / sample-len / temperature / top-p / top-k /
repeat-penalty / repeat-last-n), ``--dtype``, ``--cpu``.

Subcommands: ``cake-tpu stats`` polls a serving master's ``/stats`` endpoint
and renders a live observability table (latency percentiles, counters, spans;
``--spans`` switches to the timeline span tree with total/self time).
``cake-tpu trace`` exports the timeline profiler (GET /trace, or an offline
``--trace-jsonl`` stream) as Perfetto-loadable Chrome trace-event JSON.
``cake-tpu explain`` decomposes one request's end-to-end latency into the
critical-path phase taxonomy (GET /explain, or offline over ``--trace-jsonl``
— cake_tpu/obs/critpath.py). ``cake-tpu doctor`` renders a black-box anomaly
bundle (``--blackbox-dir``) as a human report naming the likely cause.
``cake-tpu benchdiff`` compares two bench JSON records with noise-aware
thresholds and exits 1 on regression (cake_tpu/obs/perf_ledger.py).
``cake-tpu lint`` runs the JAX-aware static analysis pass (cake_tpu/analysis)
over the tree: jit discipline, lock discipline, wire-frame symmetry, hygiene.
``cake-tpu locks`` renders the project lock graph from the interprocedural
lock-set analysis — identities, held->acquired order edges with witness
paths, cycles (``--check`` exits 1 on any cycle; ``--dot`` for Graphviz).

Execution-mode selection (TPU-first addition): with ``--topology``, the master
chooses between
  * ``--backend mesh`` (explicit opt-in): treat the topology's stages as an
    in-slice shard_map pipeline over LOCAL mesh devices — one compiled step,
    ICI hops. The topology's hosts are ignored; all weights load locally.
  * ``--backend tcp`` (default when the topology names workers): heterogeneous
    master/worker deployment over the wire protocol (the reference's only mode).
Without a topology everything runs locally (llama.rs:210-217's fallback,
generalized).
"""

from __future__ import annotations

import argparse
import logging
import sys
import time

from cake_tpu.utils import parse_address

DTYPES = ("bf16", "f16", "f32")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cake-tpu",
        description="TPU-native distributed pipeline-parallel LLM inference",
    )
    p.add_argument("--model", required=True, help="checkpoint directory")
    p.add_argument(
        "--mode",
        choices=("master", "worker"),
        default="master",
        help="run as generation master or block-serving worker",
    )
    p.add_argument("--name", default="", help="this node's name in the topology")
    p.add_argument(
        "--address",
        default="127.0.0.1:10128",
        help="worker bind address host:port",
    )
    p.add_argument(
        "--api",
        default=None,
        metavar="HOST:PORT",
        help="serve the OpenAI-compatible REST API instead of one-shot generation",
    )
    p.add_argument("--topology", default=None, help="topology YAML path")
    p.add_argument(
        "--backend",
        choices=("mesh", "tcp", "local"),
        default=None,
        help="master execution backend (default: tcp when the topology names "
        "workers; mesh runs all stages on local mesh devices, ignoring hosts)",
    )
    p.add_argument("--prompt", default="Why can't cats taste sweetness?")
    p.add_argument("--system-prompt", default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("-n", "--sample-len", type=int, default=100)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--repeat-penalty", type=float, default=1.1)
    p.add_argument("--repeat-last-n", type=int, default=128)
    p.add_argument("--dtype", choices=DTYPES, default="bf16")
    p.add_argument(
        "--kv-dtype",
        choices=("auto", "bf16", "f16", "f32", "f8"),
        default="auto",
        help="KV-cache storage dtype (auto = --dtype). f8 (float8_e4m3fn) "
        "halves KV memory and per-token cache bandwidth — the long-context "
        "lever; attention computes in --dtype after an on-read upcast. "
        "Applies to every backend (local/tp/sp/mesh masters, workers, the "
        "--api-batch engine)",
    )
    p.add_argument("--max-seq-len", type=int, default=None)
    p.add_argument(
        "--attention-impl",
        choices=("auto", "pallas", "xla"),
        default="auto",
        help="attention kernels: Pallas (TPU default) or the XLA einsum path",
    )
    p.add_argument(
        "--fusion",
        default="none",
        metavar="SPEC",
        help="decode hot-path op fusion (README 'Decode fusion'): 'none', "
        "or '<set>[@impl]' with set ⊆ {norm,tail} (or 'all') — "
        "norm folds RMSNorm into the projection it feeds, tail fuses the "
        "repeat-penalty/temperature/top-k/draw chain; impl ∈ "
        "{auto,pallas,xla} picks the Pallas kernels vs their XLA twins "
        "(auto = pallas on TPU). Kernel and twin agree to rounding; top-p "
        "keeps the XLA sort path behind a kernel-fallback flight event",
    )
    p.add_argument(
        "--chat-template",
        choices=("llama3", "llama2", "chatml", "qwen3", "mistral", "gemma", "phi3"),
        default=None,
        help="override the chat template (default: by model family from "
        "config.json). Needed for Llama-2-chat checkpoints, whose config "
        "is indistinguishable from base Llama",
    )
    p.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel width over local mesh devices: shards each layer's "
        "heads/intermediate. Composes with --backend mesh (stages x tp) or "
        "runs width-only without a topology",
    )
    p.add_argument(
        "--decode-chunk",
        type=int,
        default=8,
        help="fused decode granularity: N tokens per device dispatch on the "
        "local, mesh, and tp backends (tcp falls back to per-token decode); "
        "1 = per-token. Streaming emits in bursts of N",
    )
    p.add_argument(
        "--sp",
        type=int,
        default=1,
        help="sequence-parallel width over local mesh devices: ring-attention "
        "prefill, chunked-prefill continuation, and 1/N-sharded KV cache with "
        "distributed decode attention. Long-context mode; composes with --tp "
        "(2-D sp x tp mesh); exclusive with --backend mesh",
    )
    p.add_argument(
        "--prefill-chunk",
        type=int,
        default=None,
        help="prefill long prompts in chunks of at most N tokens (cache-prefix "
        "attention per chunk) instead of one shot; bounds compile shapes and "
        "score memory for long contexts",
    )
    p.add_argument(
        "--quantize",
        choices=("int8", "int4"),
        default=None,
        help="weight-only quantization: int8 per-channel (halves weight HBM "
        "traffic) or int4 group-128 (quarters it; MoE expert stacks stay "
        "int8); activations stay --dtype. Local, --tp, --sp, and "
        "--backend mesh masters; workers quantize their own ranges",
    )
    p.add_argument(
        "--denoise-steps", type=int, default=None,
        help="block-diffusion models (sdar_moe): denoising passes a block "
        "before its commit, 1..block_length (default: the checkpoint's, the "
        "block length)",
    )
    p.add_argument(
        "--remask", default=None,
        choices=("sequential", "low_confidence_static", "low_confidence_dynamic"),
        help="block-diffusion models: which masked slots a pass reveals: the "
        "first, the most confident, or every one past --confidence-threshold",
    )
    p.add_argument(
        "--confidence-threshold", type=float, default=None,
        help="block-diffusion models, --remask low_confidence_dynamic: a "
        "masked slot whose token's probability passes this is revealed",
    )
    p.add_argument(
        "--speculative-k",
        type=int,
        default=0,
        help="prompt-lookup speculative decoding: draft K tokens from n-gram "
        "matches in the context and verify them in one chunked forward "
        "(local and tcp backends — on tcp the chunk is one worker round "
        "trip per span instead of K+1). Greedy configs only "
        "(--temperature 0 --repeat-penalty 1.0); exact — affects speed, "
        "never output",
    )
    p.add_argument(
        "--draft-model",
        default=None,
        metavar="DIR",
        help="draft-model speculative decoding: a small checkpoint proposes "
        "the K tokens (--speculative-k) instead of prompt lookup — wins "
        "on free-generation text where the history has no n-gram signal. "
        "Exact like lookup: the target's verify forward re-derives the "
        "stream, drafts affect only speed",
    )
    p.add_argument(
        "--draft-quantize",
        choices=("int8", "int4"),
        default=None,
        help="weight-only quantization for the --draft-model weights",
    )
    p.add_argument(
        "--prefix-cache",
        choices=("on", "off", "auto"),
        default="auto",
        help="KV prefix reuse across API requests. Serialized path "
        "(--api-batch 1): a new dialog sharing a token prefix with the "
        "previous one (multi-turn chat) prefills only the new suffix; "
        "auto = on for --api. Batch engine under --kv-mode paged: the "
        "persistent prefix cache (runtime/prefix_cache.py) — finished "
        "prompts leave their prefix KV page chains in a radix cache, a "
        "later request sharing the prefix forks the chain (refcounted "
        "CoW) and prefills only the uncached suffix, so a shared system "
        "prompt is prefilled once; auto = on. Token streams are "
        "unchanged either way",
    )
    p.add_argument(
        "--api-batch",
        type=int,
        default=1,
        help="serve up to N API requests as one lockstep decode batch with "
        "continuous admission (runtime/serving.py): concurrent clients "
        "stream simultaneously, and new requests join the running batch at "
        "chunk boundaries instead of waiting for it to drain. Composes with "
        "local, --tp, --backend mesh, and --backend tcp masters (--sp keeps "
        "the serialized path); 1 = serialized (reference behavior)",
    )
    p.add_argument(
        "--scheduler",
        choices=("epoch", "continuous"),
        default="epoch",
        help="batch-engine scheduler (--api-batch > 1): epoch = the "
        "lockstep epoch (admission groups land together; page pressure "
        "force-finishes); continuous = the per-step scheduler (README "
        "'Continuous scheduling') — no admission-window sleep, queued "
        "requests join the moment lanes/pages free under an SLO-aware "
        "per-step prefill budget, finished lanes retire immediately, and "
        "page pressure PREEMPTS the lowest-priority lane (spilled "
        "host-side, restored bit-identically) instead of truncating it. "
        "Streams are bit-identical across both schedulers",
    )
    p.add_argument(
        "--step-prefill",
        type=int,
        default=0,
        metavar="TOKENS",
        help="continuous scheduler: prompt tokens of join/restore prefill "
        "work one engine step may dispatch before decode resumes; 0 = "
        "auto (SLO-aware: doubled under TTFT burn, quartered while a "
        "running stream's deadline slack is inside a few chunk walls)",
    )
    p.add_argument(
        "--kv-mode",
        choices=("dense", "paged"),
        default="dense",
        help="KV storage for the --api-batch engine: dense preallocates a "
        "[max_seq] strip per lane; paged commits HBM per live page from a "
        "shared pool (models/llama/paged_cache.py), admits by free pages, "
        "and serves more concurrent short requests at the same HBM. "
        "Prefill, warm suffix prefill, speculative verify, and decode all "
        "have paged Pallas kernels when --page-size is a multiple of 128 "
        "(README 'Kernel paths'; other sizes use the XLA gather twin and "
        "surface a kernel-fallback flight event). Local backend only",
    )
    p.add_argument(
        "--page-size",
        type=int,
        default=128,
        help="tokens per KV page under --kv-mode paged (a multiple of the "
        "128-lane tile on TPU)",
    )
    p.add_argument(
        "--max-pages",
        type=int,
        default=None,
        help="KV pool size in pages under --kv-mode paged; default = the "
        "dense-equivalent footprint (api-batch lanes x pages per sequence). "
        "Size it DOWN to trade per-request max length for concurrency",
    )
    p.add_argument(
        "--prefix-cache-pages",
        type=int,
        default=0,
        metavar="N",
        help="prefix-cache budget in KV pages; inserts evict LRU unpinned "
        "chains past it and pool pressure evicts on demand. 0 = auto "
        "(half the pool)",
    )
    p.add_argument(
        "--prefix-min-tokens",
        type=int,
        default=0,
        metavar="N",
        help="do not cache or serve prefixes shorter than N tokens (churn "
        "guard); 0 = any cached page's worth qualifies",
    )
    p.add_argument(
        "--op-deadline",
        type=float,
        default=30.0,
        metavar="S",
        help="per-op wire deadline in seconds for worker round trips: a hop "
        "that neither replies nor fails within it is retried (tcp backends)",
    )
    p.add_argument(
        "--op-retries",
        type=int,
        default=2,
        metavar="N",
        help="idempotent resends of a failed worker op before giving up "
        "(session replay, runtime/client.py); 0 = fail fast",
    )
    p.add_argument(
        "--reconnect-attempts",
        type=int,
        default=3,
        metavar="N",
        help="re-dial attempts after a worker connection dies (exponential "
        "backoff between attempts, none after the last)",
    )
    p.add_argument(
        "--reconnect-backoff",
        type=float,
        default=0.5,
        metavar="S",
        help="base reconnect backoff in seconds (doubles per attempt)",
    )
    p.add_argument(
        "--heartbeat-interval",
        type=float,
        default=0.0,
        metavar="S",
        help="ping every worker over a dedicated connection at this cadence "
        "(cake_worker_healthy gauge + cake_worker_unhealthy_total); "
        "0 = no heartbeat threads. TCP masters only",
    )
    p.add_argument(
        "--heartbeat-deadline",
        type=float,
        default=2.0,
        metavar="S",
        help="a heartbeat PING unanswered for this long marks the worker "
        "unhealthy",
    )
    p.add_argument(
        "--shed-queue-depth",
        type=int,
        default=0,
        metavar="N",
        help="admission load shedding: refuse new requests (HTTP 503 + "
        "Retry-After) once the engine queue is N deep; 0 = off",
    )
    p.add_argument(
        "--shed-free-pages",
        type=int,
        default=0,
        metavar="N",
        help="paged mode: shed new requests while fewer than N KV pages are "
        "free; 0 = off",
    )
    p.add_argument(
        "--default-priority",
        type=int,
        choices=(0, 1, 2),
        default=1,
        help="priority class for requests that carry none (0 low / 1 "
        "normal / 2 high): low sheds first under overload and its 503 "
        "Retry-After doubles; high tolerates twice the shed thresholds",
    )
    p.add_argument(
        "--tenant-rate",
        type=float,
        default=0.0,
        metavar="TOK_S",
        help="per-tenant token-bucket rate limit in work tokens (prompt + "
        "max_tokens) per second; over it a submission is refused with "
        "HTTP 429 + Retry-After (the tenant rides the request's 'tenant' "
        "field or X-Cake-Tenant header). 0 = unlimited (--api-batch)",
    )
    p.add_argument(
        "--tenant-burst",
        type=float,
        default=0.0,
        metavar="TOKENS",
        help="per-tenant token-bucket capacity in work tokens; "
        "0 = auto (2x --tenant-rate)",
    )
    p.add_argument(
        "--tenant-streams",
        type=int,
        default=0,
        metavar="N",
        help="per-tenant concurrent-stream cap (queued + live); over it a "
        "submission is refused with HTTP 429. 0 = uncapped",
    )
    p.add_argument(
        "--no-fair-queue",
        action="store_true",
        help="disable the deficit-weighted round-robin fair queue across "
        "tenants and fall back to one global FIFO (an abusive tenant can "
        "then starve everyone else — A/B knob for the overload benches)",
    )
    p.add_argument(
        "--default-deadline",
        type=float,
        default=0.0,
        metavar="S",
        help="end-to-end deadline applied to requests that carry no "
        "'deadline_s' field: queued past it a request expires before "
        "admission (no lane, no pages), running past it the stream "
        "finishes with finish_reason=deadline at the next chunk boundary, "
        "and a deadline the estimated queue wait already exceeds is shed "
        "immediately (503). 0 = none",
    )
    p.add_argument(
        "--slo-ttft-ms",
        type=float,
        default=0.0,
        metavar="MS",
        help="declared TTFT objective: --slo-ttft-target of accepted "
        "requests must see their first token within MS milliseconds. "
        "Per-tenant burn rates (fast/slow windows) surface at GET /slo "
        "and as cake_slo_* metrics; a burning tenant's fair-queue "
        "quantum is boosted and its doomed-deadline submissions shed "
        "earlier (obs/slo.py). 0 = no TTFT objective (--api-batch)",
    )
    p.add_argument(
        "--slo-ttft-target",
        type=float,
        default=0.99,
        metavar="FRAC",
        help="required fraction of requests meeting --slo-ttft-ms "
        "(error budget = 1 - FRAC)",
    )
    p.add_argument(
        "--slo-deadline-rate",
        type=float,
        default=0.0,
        metavar="FRAC",
        help="declared deadline objective: required hit rate over "
        "deadline-carrying requests; burn tracked per tenant at GET /slo. "
        "0 = off (--api-batch)",
    )
    p.add_argument(
        "--epoch-stall",
        type=float,
        default=0.0,
        metavar="S",
        help="stuck-epoch watchdog: a backend dispatch making no progress "
        "within S seconds is abandoned and isolated through the failover/"
        "finish_reason=error path (a silently hung backend costs one "
        "epoch, not the engine). 0 = off",
    )
    p.add_argument(
        "--stream-buffer",
        type=int,
        default=8192,
        metavar="TOKENS",
        help="streaming backpressure watermark: a client that stops reading "
        "its SSE stream is cancelled (pages freed, lane recycled) once this "
        "many undelivered tokens buffer up; 0 = unbounded (--api-batch)",
    )
    p.add_argument(
        "--failover-max",
        type=int,
        default=2,
        metavar="N",
        help="replica failover: at most N live-stream migrations per epoch "
        "after a worker death before degrading to finish_reason=error; "
        "0 disables migration (PR 6 error isolation only)",
    )
    p.add_argument(
        "--failover-budget",
        type=float,
        default=30.0,
        metavar="S",
        help="replica failover: cumulative migration wall-time budget per "
        "epoch; past it the epoch degrades to finish_reason=error",
    )
    p.add_argument(
        "--failover-cooldown",
        type=float,
        default=5.0,
        metavar="S",
        help="standby rejoin probation: an ejected replica re-enters the "
        "routing rotation after this long (and, with heartbeats on, only "
        "once the monitor sees it healthy again)",
    )
    p.add_argument(
        "--failover-local",
        action="store_true",
        help="opt replica-less backends (local/tp/mesh) into migration-in-"
        "place: a transient backend fault re-prefills live streams instead "
        "of finishing them with finish_reason=error",
    )
    p.add_argument(
        "--blackbox-dir",
        default=None,
        metavar="DIR",
        help="black-box anomaly capture (README 'Latency attribution & "
        "black-box diagnostics'): when a request breaches a declared SLO "
        "objective, lands past --blackbox-p99-mult x the rolling e2e p99, "
        "or dies to a watchdog stall / failover / whole-epoch error, a "
        "diagnostic bundle (attribution, timeline slice, flight tail, "
        "engine/pool/prefix snapshots) is written here for `cake-tpu "
        "doctor`. Unset = capture off (--api-batch)",
    )
    p.add_argument(
        "--blackbox-keep",
        type=int,
        default=16,
        metavar="N",
        help="bound the on-disk bundle ring to the newest N bundles",
    )
    p.add_argument(
        "--blackbox-interval",
        type=float,
        default=5.0,
        metavar="S",
        help="min seconds between bundle captures (an incident storm "
        "writes one bundle, not a disk full); 0 = no rate limit",
    )
    p.add_argument(
        "--blackbox-p99-mult",
        type=float,
        default=0.0,
        metavar="K",
        help="capture a bundle when a request finishes slower than K x "
        "the rolling end-to-end p99 (needs a warm window); 0 = off",
    )
    p.add_argument(
        "--peak-tflops",
        type=float,
        default=0.0,
        metavar="TF",
        help="device peak dense TFLOP/s for the MFU estimate at "
        "GET /efficiency (obs/efficiency.py); 0 = look up the built-in "
        "table by device kind, absolute numbers only when unknown (CPU)",
    )
    p.add_argument(
        "--peak-hbm-gbps",
        type=float,
        default=0.0,
        metavar="GB",
        help="device peak HBM bandwidth (GB/s) for the memory-bandwidth-"
        "utilization estimate at GET /efficiency; 0 = built-in table",
    )
    p.add_argument(
        "--faults",
        default=None,
        metavar="PLAN",
        help="install a deterministic fault plan (runtime/faults.py DSL, "
        "e.g. 'seed=7;kill@worker.op:after=5') — chaos testing; also "
        "settable via the CAKE_FAULTS environment variable",
    )
    p.add_argument(
        "--trace-dir",
        default=None,
        help="directory for JAX/XLA profiler traces (xplane, for TensorBoard/XProf): "
        "the one-shot CLI and a worker trace their whole run; an --api "
        "server records one window per POST /profile?seconds=S (S <= 30)",
    )
    p.add_argument(
        "--events-jsonl",
        default=None,
        metavar="PATH",
        help="append every flight-recorder lifecycle event (submitted/"
        "admitted/joined/first-token/finished/worker-reconnect) to this "
        "JSONL file; the bounded in-memory ring stays available at "
        "GET /events either way (--api only)",
    )
    p.add_argument(
        "--trace-jsonl",
        default=None,
        metavar="PATH",
        help="stream every timeline-profiler event (spans, lane tracks, "
        "flow arrows, HBM counters — cake_tpu/obs/timeline.py) to this "
        "JSONL file; `cake-tpu trace --jsonl PATH --out t.json` renders it "
        "Perfetto-loadable, and the bounded ring stays live at GET /trace "
        "(--api only)",
    )
    p.add_argument(
        "--request-log",
        default=None,
        metavar="PATH",
        help="append every per-request completion record (tenant, token "
        "counts, queue/TTFT/TPOT timings, finish reason, SLO verdict — "
        "obs/requestlog.py) to this JSONL file; the bounded ring stays "
        "live at GET /requests either way, and the file replays with "
        "`python -m cake_tpu.loadgen --replay PATH` "
        "(--api with --api-batch > 1 only)",
    )
    p.add_argument("--cpu", action="store_true", help="force the CPU backend")
    p.add_argument(
        "--distributed",
        default=None,
        metavar="COORD:PORT,N,I",
        help="join a multi-host jax.distributed cluster before building the "
        "step: coordinator address, process count, this process's id. "
        "Requires --backend mesh; process 0 serves (CLI/API), others replay "
        "its steps over the global device mesh (parallel/multihost.py)",
    )
    p.add_argument(
        "--device",
        type=int,
        default=None,
        metavar="N",
        help="device ordinal: pin single-device compute (local master, worker) "
        "to jax.devices()[N] on a multi-chip host (lib.rs:14-16, "
        "utils/mod.rs:15-30 parity). Mesh/tp/sp backends span all local "
        "devices and ignore this",
    )
    p.add_argument("-v", "--verbose", action="store_true")
    return p




def _fmt_ms(v: float) -> str:
    return f"{v * 1e3:10.2f}"


def _render_stats(stats: dict) -> str:
    """One poll of /stats -> a fixed-width terminal table."""
    lines = [
        f"model={stats.get('model', '?')}  "
        f"uptime={stats.get('uptime_s', 0):.1f}s"
    ]
    m = stats.get("metrics", {})
    hists = m.get("histograms", [])
    # Only *_seconds families belong in a milliseconds table; other
    # histograms (e.g. batch-size distributions) render in raw units.
    latency = [h for h in hists if h["name"].endswith("_seconds")]
    other = [h for h in hists if not h["name"].endswith("_seconds")]

    def _label(h):
        return h["name"] + (
            "{%s}" % ",".join(f"{k}={v}" for k, v in h["labels"].items())
            if h["labels"]
            else ""
        )

    if latency:
        lines.append("")
        lines.append(
            f"{'latency':40} {'count':>8} {'mean_ms':>10} {'p50_ms':>10} "
            f"{'p90_ms':>10} {'p99_ms':>10}"
        )
        for h in latency:
            lines.append(
                f"{_label(h):40} {h['count']:>8} {_fmt_ms(h['mean'])} "
                f"{_fmt_ms(h['p50'])} {_fmt_ms(h['p90'])} {_fmt_ms(h['p99'])}"
            )
    if other:
        lines.append("")
        lines.append(
            f"{'distribution':40} {'count':>8} {'mean':>10} {'p50':>10} "
            f"{'p90':>10} {'p99':>10}"
        )
        for h in other:
            lines.append(
                f"{_label(h):40} {h['count']:>8} {h['mean']:>10.2f} "
                f"{h['p50']:>10.2f} {h['p90']:>10.2f} {h['p99']:>10.2f}"
            )
    scalars = m.get("counters", []) + m.get("gauges", [])
    if scalars:
        lines.append("")
        lines.append(f"{'counter/gauge':56} {'value':>14}")
        for c in scalars:
            v = c["value"]
            lines.append(
                f"{_label(c):56} {v:>14.3f}"
                if isinstance(v, float) and v != int(v)
                else f"{_label(c):56} {int(v):>14}"
            )
    if stats.get("engine"):
        lines.append("")
        lines.append(
            "engine: "
            + "  ".join(f"{k}={v}" for k, v in sorted(stats["engine"].items()))
        )
    mw = stats.get("memwatch") or {}
    if mw.get("host_rss_bytes") is not None or mw.get("devices"):
        # Allocator-truth watermarks (obs/memwatch.py): host RSS next to
        # per-device HBM in-use/peak/limit, beside pool occupancy above.
        rss = mw.get("host_rss_bytes")
        lines.append("")
        lines.append(
            "memwatch: host_rss="
            + ("-" if rss is None else f"{rss / 2**30:.2f}GiB")
        )
        for d in mw.get("devices") or []:
            used = d.get("bytes_in_use", 0)
            peak = d.get("peak_bytes_in_use", 0)
            limit = d.get("bytes_limit")
            line = (
                f"  {d.get('device', '?'):24} hbm={used / 2**30:.2f}GiB "
                f"peak={peak / 2**30:.2f}GiB"
            )
            if limit:
                line += (
                    f" limit={limit / 2**30:.2f}GiB"
                    f" ({used / limit * 100:.0f}%)"
                )
            lines.append(line)
    eff = stats.get("efficiency") or {}
    if eff.get("dispatches"):
        # Goodput headline (obs/efficiency.py; bucket detail at
        # GET /efficiency and in `cake-tpu top`).
        roof = eff.get("roofline") or {}
        line = (
            f"efficiency: goodput_frac={eff.get('goodput_frac', 0.0):.3f} "
            f"device_s={eff.get('device_s', 0.0):.2f} "
            f"goodput_tokens={eff.get('goodput_tokens', 0)}"
        )
        if roof.get("mfu") is not None:
            line += f" mfu={roof['mfu']:.3f}"
        if roof.get("mbu") is not None:
            line += f" mbu={roof['mbu']:.3f}"
        lines.append("")
        lines.append(line)
    cluster = stats.get("cluster")
    if cluster:
        # Per-node federation table (obs/cluster.py snapshot): clock
        # offset + bound, probe RTT, report freshness, op/byte headline.
        lines.append("")
        lines.append(
            f"{'node':16} {'offset_ms':>10} {'±bound_ms':>10} "
            f"{'rtt_ms':>8} {'age_s':>7} {'ops':>8} {'op_mean_ms':>11} "
            f"{'rx_kib':>9} {'tx_kib':>9}"
        )
        for node, d in sorted(cluster.items()):
            age = d.get("report_age_s")
            lines.append(
                f"{node:16} {d.get('offset_s', 0.0) * 1e3:>10.3f} "
                f"{d.get('offset_error_bound_s', 0.0) * 1e3:>10.3f} "
                f"{d.get('rtt_ms', 0.0):>8.2f} "
                f"{('-' if age is None else f'{age:.1f}'):>7} "
                f"{d.get('ops', 0):>8} {d.get('op_mean_ms', 0.0):>11.2f} "
                f"{d.get('bytes_rx', 0) / 1024:>9.1f} "
                f"{d.get('bytes_tx', 0) / 1024:>9.1f}"
            )
    slo = stats.get("slo")
    if slo and slo.get("tenants"):
        # Per-tenant SLO burn table (obs/slo.py; full detail at GET /slo).
        lines.append("")
        lines.append(
            f"{'tenant':24} {'burn':>7} {'p99_ttft_ms':>12} "
            f"{'dl_hit':>7} {'good_tok_s':>11} {'shed%':>7}"
        )
        for tenant, d in sorted(slo["tenants"].items()):
            fast = d.get("fast", {})
            hit = fast.get("deadline_hit_rate")
            lines.append(
                f"{tenant:24} {d.get('burn_rate', 0.0):>7.2f} "
                f"{fast.get('ttft_p99_s', 0.0) * 1e3:>12.2f} "
                f"{('-' if hit is None else f'{hit:.2f}'):>7} "
                f"{fast.get('goodput_tok_s', 0.0):>11.1f} "
                f"{fast.get('shed_rate', 0.0) * 100:>6.1f}%"
            )
    phases = stats.get("phases") or {}
    if phases.get("phases"):
        # Latency attribution aggregate (obs/critpath.py taxonomy) + the
        # per-epoch convoy meter: the lockstep tax, visible without a trace.
        total = sum(
            d.get("seconds", 0.0) for d in phases["phases"].values()
        ) or 1.0
        lines.append("")
        lines.append(f"{'phase':24} {'seconds':>12} {'share':>7} {'reqs':>8}")
        for name, d in sorted(
            phases["phases"].items(),
            key=lambda kv: kv[1].get("seconds", 0.0),
            reverse=True,
        ):
            lines.append(
                f"{name:24} {d.get('seconds', 0.0):>12.3f} "
                f"{d.get('seconds', 0.0) / total * 100:>6.1f}% "
                f"{d.get('requests', 0):>8}"
            )
        cv = phases.get("convoy") or {}
        if cv.get("epochs"):
            lines.append(
                f"convoy: epochs={cv['epochs']} "
                f"seconds={cv.get('seconds_total', 0.0):.3f} "
                f"frac_last={cv.get('frac_last', 0.0):.3f} "
                f"frac_mean={cv.get('frac_mean', 0.0):.3f}"
            )
    spans = stats.get("spans", {})
    if spans:
        lines.append("")
        lines.append(
            f"{'span':40} {'count':>8} {'mean_ms':>10} {'last_ms':>10}"
        )
        for name, d in sorted(spans.items()):
            lines.append(
                f"{name:40} {d['count']:>8} {_fmt_ms(d['mean_s'])} "
                f"{_fmt_ms(d['last_s'])}"
            )
    return "\n".join(lines)


def _render_span_tree(stats: dict, top: int = 30) -> str:
    """``cake-tpu stats --spans``: top spans by total/self time from the
    timeline aggregate (falls back to the flat accumulator registry when the
    server predates the timeline)."""
    agg = stats.get("timeline") or {}
    lines = [
        f"model={stats.get('model', '?')}  "
        f"uptime={stats.get('uptime_s', 0):.1f}s",
        "",
        f"{'span':44} {'count':>8} {'total_ms':>12} {'self_ms':>12} "
        f"{'self%':>6}",
    ]
    if agg:
        rows = sorted(
            agg.items(), key=lambda kv: kv[1]["total_s"], reverse=True
        )
        for name, d in rows[:top]:
            total, self_s = d["total_s"], d["self_s"]
            pct = 100.0 * self_s / total if total > 0 else 0.0
            lines.append(
                f"{name:44} {d['count']:>8} {total * 1e3:>12.2f} "
                f"{self_s * 1e3:>12.2f} {pct:>5.1f}%"
            )
        return "\n".join(lines)
    rows = sorted(
        stats.get("spans", {}).items(),
        key=lambda kv: kv[1]["total_s"],
        reverse=True,
    )
    for name, d in rows[:top]:
        lines.append(
            f"{name:44} {d['count']:>8} {d['total_s'] * 1e3:>12.2f} "
            f"{'-':>12} {'-':>6}"
        )
    return "\n".join(lines)


def _stats_main(argv: list[str]) -> int:
    """``cake-tpu stats``: poll /stats and render a live table."""
    import json
    import time
    import urllib.request

    p = argparse.ArgumentParser(
        prog="cake-tpu stats",
        description="poll a serving master's /stats and render a live table",
    )
    p.add_argument(
        "--url",
        default="http://127.0.0.1:8000",
        help="API base URL (the --api address of the serving master)",
    )
    p.add_argument(
        "--interval", type=float, default=2.0, help="seconds between polls"
    )
    p.add_argument(
        "--count",
        type=int,
        default=0,
        help="number of polls before exiting (0 = poll forever)",
    )
    p.add_argument(
        "--no-clear",
        action="store_true",
        help="append polls instead of redrawing in place",
    )
    p.add_argument(
        "--spans",
        action="store_true",
        help="render the timeline span tree (top spans by total/self time) "
        "instead of the metrics table",
    )
    args = p.parse_args(argv)
    base = args.url.rstrip("/")
    n = 0
    while True:
        try:
            try:
                with urllib.request.urlopen(base + "/stats", timeout=10) as r:
                    stats = json.load(r)
            except (OSError, ValueError) as e:
                print(f"cake-tpu stats: poll of {base}/stats failed: {e}",
                      file=sys.stderr)
                return 1
            if n > 0 and not args.no_clear and sys.stdout.isatty():
                print("\x1b[2J\x1b[H", end="")
            print(
                _render_span_tree(stats) if args.spans
                else _render_stats(stats),
                flush=True,
            )
            n += 1
            if args.count and n >= args.count:
                return 0
            time.sleep(args.interval)
        except KeyboardInterrupt:
            # Ctrl-C anywhere in the poll (a hung urlopen included) is a
            # clean exit, not a traceback.
            return 0


def _sparkline(values: list, width: int = 32) -> str:
    """Unicode block sparkline (▁..█), newest value rightmost; scaled to
    the series max so shape, not magnitude, is what reads at a glance."""
    blocks = "▁▂▃▄▅▆▇█"
    vals = [max(0.0, float(v)) for v in values][-width:]
    if not vals:
        return ""
    top = max(vals)
    if top <= 0:
        return blocks[0] * len(vals)
    return "".join(
        blocks[min(len(blocks) - 1, int(v / top * (len(blocks) - 1) + 0.5))]
        for v in vals
    )


def _render_top(stats: dict, eff: dict, slo: dict, ts: dict | None = None) -> str:
    """One poll of /stats + /efficiency + /slo (+ /timeseries) -> the
    `cake-tpu top` dashboard. Pure (dicts in, string out) so the render
    is testable without a server."""
    engine = stats.get("engine") or {}
    lines = [
        f"cake-tpu top — model={stats.get('model', '?')}  "
        f"uptime={stats.get('uptime_s', 0):.1f}s  "
        f"scheduler={engine.get('scheduler', '?')}"
    ]
    roof = eff.get("roofline") or {}
    head = (
        f"goodput {eff.get('goodput_frac', 0.0) * 100:5.1f}%   "
        f"device {eff.get('device_s', 0.0):.2f}s / "
        f"{eff.get('accounted_s', 0.0):.2f}s accounted   "
        f"dispatches {eff.get('dispatches', 0)}"
    )
    if roof.get("mfu") is not None:
        head += f"   mfu {roof['mfu']:.3f}"
    if roof.get("mbu") is not None:
        head += f"   mbu {roof['mbu']:.3f}"
    if roof.get("source") == "none":
        # CPU / unknown device: absolute achieved numbers, no peaks.
        model = eff.get("model") or {}
        if model.get("achieved_tflops") is not None:
            head += (
                f"   achieved {model['achieved_tflops']:.4f} TF/s "
                f"(no device peak known)"
            )
    lines.append(head)
    buckets = eff.get("buckets") or {}
    frac = eff.get("bucket_frac") or {}
    if buckets:
        lines.append("")
        lines.append(f"{'bucket':18} {'seconds':>10} {'share':>7}")
        for name, secs in sorted(
            buckets.items(), key=lambda kv: kv[1], reverse=True
        ):
            share = frac.get(name, 0.0)
            bar = "#" * int(round(share * 40))
            lines.append(
                f"{name:18} {secs:>10.3f} {share * 100:>6.1f}%  {bar}"
            )
    tokens = eff.get("tokens") or {}
    if tokens:
        lines.append("")
        lines.append(
            "tokens: "
            + "  ".join(f"{k}={v}" for k, v in sorted(tokens.items()))
        )
    tenants = eff.get("tenants") or {}
    slo_tenants = (slo or {}).get("tenants") or {}
    if tenants or slo_tenants:
        lines.append("")
        lines.append(
            f"{'tenant':24} {'good_tok':>9} {'waste_tok':>10} {'burn':>7} "
            f"{'p99_ttft_ms':>12}"
        )
        for tenant in sorted(set(tenants) | set(slo_tenants)):
            t = tenants.get(tenant, {})
            s = slo_tenants.get(tenant, {})
            fast = s.get("fast", {})
            burn = s.get("burn_rate")
            lines.append(
                f"{tenant:24} {t.get('goodput_tokens', 0):>9} "
                f"{t.get('wasted_tokens', 0):>10} "
                f"{('-' if burn is None else f'{burn:.2f}'):>7} "
                f"{fast.get('ttft_p99_s', 0.0) * 1e3:>12.2f}"
            )
    decisions = eff.get("decisions") or {}
    if decisions:
        lines.append("")
        lines.append(
            "decisions: "
            + "  ".join(f"{k}={v}" for k, v in sorted(decisions.items()))
        )
    mw = stats.get("memwatch") or {}
    rss = mw.get("host_rss_bytes")
    mem_parts = [] if rss is None else [f"host_rss={rss / 2**30:.2f}GiB"]
    for d in mw.get("devices") or []:
        used, limit = d.get("bytes_in_use", 0), d.get("bytes_limit")
        part = f"{d.get('device', '?')}={used / 2**30:.2f}GiB"
        if limit:
            part += f"/{limit / 2**30:.2f}GiB"
        mem_parts.append(part)
    if mem_parts:
        lines.append("")
        lines.append("memory: " + "  ".join(mem_parts))
    if engine:
        keep = (
            "queued", "rows", "joins", "preemptions", "restores", "shed",
            "deadline_expired", "spilled", "prefix_hits",
        )
        parts = [f"{k}={engine[k]}" for k in keep if k in engine]
        if parts:
            lines.append("")
            lines.append("engine: " + "  ".join(parts))
    points = (ts or {}).get("points") or []
    if points:
        # Rolling SLI sparklines (GET /timeseries, obs/timeseries.py):
        # one column per bucket, newest rightmost; the number after each
        # line is the newest bucket's value.
        last = points[-1]
        lines.append("")
        lines.append(
            f"sli window — {ts.get('bucket_s', 0):.0f}s buckets, "
            f"newest right:"
        )
        for label, key, fmt in (
            ("ttft_p99_ms", "ttft_p99_ms", "{:.1f}"),
            ("tok/s", "tok_s", "{:.1f}"),
            ("shed_frac", "shed_frac", "{:.3f}"),
        ):
            spark = _sparkline([p.get(key, 0.0) for p in points])
            lines.append(
                f"{label:>12} {spark} {fmt.format(last.get(key, 0.0))}"
            )
    return "\n".join(lines)


def _top_main(argv: list[str]) -> int:
    """``cake-tpu top``: live goodput/utilization dashboard — polls
    /stats, /efficiency, and /slo on a serving master."""
    import json
    import time
    import urllib.error
    import urllib.request

    p = argparse.ArgumentParser(
        prog="cake-tpu top",
        description="live goodput & hardware-efficiency dashboard: device-"
        "time buckets, MFU/MBU roofline estimates, token goodput classes, "
        "per-tenant attribution, and scheduler decision counts "
        "(polls /stats, /efficiency, /slo)",
    )
    p.add_argument(
        "--url",
        default="http://127.0.0.1:8000",
        help="API base URL (the --api address of the serving master)",
    )
    p.add_argument(
        "--interval", type=float, default=2.0, help="seconds between polls"
    )
    p.add_argument(
        "--once",
        action="store_true",
        help="render one poll and exit (CI / scripting)",
    )
    p.add_argument(
        "--no-clear",
        action="store_true",
        help="append polls instead of redrawing in place",
    )
    args = p.parse_args(argv)
    base = args.url.rstrip("/")

    def _fetch(route: str) -> dict:
        # /efficiency and /slo 404 on engines without batching — top
        # degrades to the /stats view instead of dying.
        try:
            with urllib.request.urlopen(base + route, timeout=10) as r:
                return json.load(r)
        except urllib.error.HTTPError as e:
            if e.code == 404:
                return {}
            raise
    n = 0
    while True:
        try:
            try:
                stats = _fetch("/stats")
                eff = _fetch("/efficiency")
                slo = _fetch("/slo")
                ts = _fetch("/timeseries")
            except (OSError, ValueError) as e:
                print(f"cake-tpu top: poll of {base} failed: {e}",
                      file=sys.stderr)
                return 1
            if n > 0 and not args.no_clear and sys.stdout.isatty():
                print("\x1b[2J\x1b[H", end="")
            print(_render_top(stats, eff, slo, ts), flush=True)
            n += 1
            if args.once:
                return 0
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def _render_requests(recs: list[dict]) -> str:
    """Request-log records -> a tail-style table (pure: testable without
    a server). One line per record, newest last."""
    lines = [
        f"{'seq':>5} {'time':8} {'request_id':30} {'tenant':12} "
        f"{'pri':>3} {'fin':9} {'slo':13} {'ptok':>5} {'ctok':>5} "
        f"{'queue_ms':>8} {'ttft_ms':>8}"
    ]
    import datetime

    for r in recs:
        t = r.get("t_wall")
        hhmmss = (
            datetime.datetime.fromtimestamp(t).strftime("%H:%M:%S")
            if isinstance(t, (int, float)) else "?"
        )
        ttft = r.get("ttft_s")
        queue = r.get("queue_s")
        lines.append(
            f"{r.get('seq', 0):>5} {hhmmss:8} "
            f"{str(r.get('request_id', '?'))[:30]:30} "
            f"{str(r.get('tenant', '?'))[:12]:12} "
            f"{str(r.get('priority', '-')):>3} "
            f"{str(r.get('finish_reason', '?')):9} "
            f"{str(r.get('slo', '?')):13} "
            f"{r.get('prompt_tokens', 0):>5} "
            f"{r.get('completion_tokens', 0):>5} "
            f"{('-' if queue is None else f'{queue * 1e3:.1f}'):>8} "
            f"{('-' if ttft is None else f'{ttft * 1e3:.1f}'):>8}"
        )
    return "\n".join(lines)


def _requests_main(argv: list[str]) -> int:
    """``cake-tpu requests``: tail the structured request log — the
    per-request completion records at GET /requests (obs/requestlog.py).
    Same thin-HTTP-poller shape as `stats`/`top`: no --model, no jax."""
    import json
    import time
    import urllib.parse
    import urllib.request

    p = argparse.ArgumentParser(
        prog="cake-tpu requests",
        description="tail the traffic observatory's request log: one "
        "completion record per terminated request — tenant, token counts, "
        "queue/TTFT timings, finish reason, SLO verdict (GET /requests)",
    )
    p.add_argument(
        "--url",
        default="http://127.0.0.1:8000",
        help="API base URL (the --api address of the serving master)",
    )
    p.add_argument("--tenant", default=None, help="filter by tenant id")
    p.add_argument(
        "--finish", default=None,
        help="filter by finish_reason (stop/length/error/cancelled/"
        "deadline/quota/shed)",
    )
    p.add_argument(
        "-n", "--limit", type=int, default=20,
        help="show the newest N records (0 = the whole ring)",
    )
    p.add_argument(
        "-f", "--follow", action="store_true",
        help="keep polling, printing only records newer than the last "
        "seen seq (tail -f)",
    )
    p.add_argument(
        "--interval", type=float, default=1.0,
        help="seconds between --follow polls",
    )
    p.add_argument(
        "--json", action="store_true",
        help="emit raw record JSON lines instead of the table",
    )
    args = p.parse_args(argv)
    base = args.url.rstrip("/")

    def _fetch(since: int | None) -> dict:
        q = {}
        if args.tenant:
            q["tenant"] = args.tenant
        if args.finish:
            q["finish"] = args.finish
        if since is not None:
            q["since"] = str(since)
        elif args.limit:
            q["limit"] = str(args.limit)
        url = base + "/requests"
        if q:
            url += "?" + urllib.parse.urlencode(q)
        with urllib.request.urlopen(url, timeout=10) as r:
            return json.load(r)

    since: int | None = None
    header_done = False
    while True:
        try:
            try:
                body = _fetch(since)
            except (OSError, ValueError) as e:
                print(f"cake-tpu requests: poll of {base}/requests "
                      f"failed: {e}", file=sys.stderr)
                return 1
            recs = body.get("requests", [])
            if args.json:
                for r in recs:
                    print(json.dumps(r))
            elif recs or not header_done:
                out = _render_requests(recs)
                # --follow reprints only rows after the first poll.
                print(out if not header_done
                      else "\n".join(out.splitlines()[1:]), flush=True)
                header_done = True
            if not args.follow:
                return 0
            since = body.get("last_seq", since)
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0


def _trace_main(argv: list[str]) -> int:
    """``cake-tpu trace``: fetch a server's timeline (or render a
    --trace-jsonl stream) into a Perfetto-loadable trace file."""
    import json
    import urllib.request

    p = argparse.ArgumentParser(
        prog="cake-tpu trace",
        description="export the timeline profiler as Chrome trace-event "
        "JSON (open in Perfetto or chrome://tracing)",
    )
    p.add_argument(
        "--url",
        default="http://127.0.0.1:8000",
        help="API base URL of the serving master (GET /trace)",
    )
    p.add_argument(
        "--jsonl",
        default=None,
        metavar="PATH",
        help="render a --trace-jsonl stream file instead of polling a "
        "server (offline mode)",
    )
    p.add_argument(
        "--request-id",
        default=None,
        help="narrow the export to one request's spans (chatcmpl-... id)",
    )
    p.add_argument(
        "--cluster",
        action="store_true",
        help="merged cluster export (GET /trace?cluster=1): every "
        "reporting worker's timeline slice clock-aligned onto the master "
        "and rendered as ONE trace — worker op spans nest inside the "
        "master's wire.<node> spans, flow arrows cross process tracks",
    )
    p.add_argument(
        "--out", default="trace.json", help="output trace file path"
    )
    p.add_argument(
        "--validate",
        action="store_true",
        help="run the trace-event schema checker on the export; exit "
        "nonzero on problems",
    )
    args = p.parse_args(argv)

    from cake_tpu.obs.timeline import (
        export_events,
        load_jsonl,
        validate_export,
    )

    if args.jsonl:
        events = load_jsonl(args.jsonl)
        if args.request_id:
            keep = {
                e.get("id") for e in events
                if e.get("rid") == args.request_id and "id" in e
            }
            events = [
                e for e in events
                if e.get("rid") == args.request_id or e.get("id") in keep
            ]
        trace = export_events(events)
    else:
        url = args.url.rstrip("/") + "/trace"
        params = []
        if args.request_id:
            from urllib.parse import quote

            params.append("request_id=" + quote(args.request_id))
        if args.cluster:
            params.append("cluster=1")
        if params:
            url += "?" + "&".join(params)
        try:
            with urllib.request.urlopen(url, timeout=30) as r:
                trace = json.load(r)
        except (OSError, ValueError) as e:
            print(f"cake-tpu trace: fetch of {url} failed: {e}",
                  file=sys.stderr)
            return 1
    with open(args.out, "w") as f:
        json.dump(trace, f)
    n = len(trace.get("traceEvents", []))
    print(f"wrote {n} trace events to {args.out} (load in Perfetto or "
          "chrome://tracing)")
    if args.validate:
        problems = validate_export(trace)
        for prob in problems:
            print(f"cake-tpu trace: INVALID: {prob}", file=sys.stderr)
        return 1 if problems else 0
    return 0


def _explain_main(argv: list[str]) -> int:
    """``cake-tpu explain``: fetch GET /explain (or decompose an offline
    --trace-jsonl stream) and render the phase breakdown."""
    import json
    import urllib.error
    import urllib.request

    p = argparse.ArgumentParser(
        prog="cake-tpu explain",
        description="per-request critical-path latency attribution "
        "(queue / prefill / decode / convoy / stall / wire phases)",
    )
    p.add_argument(
        "--url",
        default="http://127.0.0.1:8000",
        help="API base URL of the serving master (GET /explain)",
    )
    p.add_argument(
        "--jsonl",
        default=None,
        metavar="PATH",
        help="decompose a --trace-jsonl stream file instead of polling a "
        "server (offline mode); without --request-id, every request in "
        "the stream is summarized",
    )
    p.add_argument(
        "--request-id",
        default=None,
        help="the chatcmpl-... response id to explain (required online)",
    )
    p.add_argument(
        "--json",
        action="store_true",
        help="print the raw attribution JSON instead of the table",
    )
    args = p.parse_args(argv)

    from cake_tpu.obs import critpath

    if args.jsonl:
        from cake_tpu.obs.timeline import load_jsonl

        events = load_jsonl(args.jsonl)
        if args.request_id:
            results = [critpath.explain(events, args.request_id)]
            if results[0] is None:
                print(
                    f"cake-tpu explain: no spans for {args.request_id!r} "
                    f"in {args.jsonl}",
                    file=sys.stderr,
                )
                return 1
        else:
            results = critpath.explain_all(events)
            if not results:
                print(
                    f"cake-tpu explain: no request spans in {args.jsonl}",
                    file=sys.stderr,
                )
                return 1
    else:
        if not args.request_id:
            print(
                "cake-tpu explain: --request-id is required when polling "
                "a server (use --jsonl for the offline sweep)",
                file=sys.stderr,
            )
            return 2
        from urllib.parse import quote

        url = (
            args.url.rstrip("/") + "/explain?request_id="
            + quote(args.request_id)
        )
        try:
            with urllib.request.urlopen(url, timeout=30) as r:
                results = [json.load(r)]
        except urllib.error.HTTPError as e:
            body = e.read().decode(errors="replace")[:300]
            print(
                f"cake-tpu explain: {url} -> HTTP {e.code}: {body}",
                file=sys.stderr,
            )
            return 1
        except (OSError, ValueError) as e:
            print(f"cake-tpu explain: fetch of {url} failed: {e}",
                  file=sys.stderr)
            return 1
    for res in results:
        print(json.dumps(res) if args.json else critpath.render(res))
        if not args.json and res.get("decisions"):
            # Scheduler decision audit (obs/efficiency.py, attached by
            # GET /explain): WHY this request was deferred / preempted /
            # restored, under the critpath's "how long".
            print("decisions:")
            for d in res["decisions"]:
                detail = f"  ({d['detail']})" if d.get("detail") else ""
                print(f"  {d['action']:8} cause={d['cause']}{detail}")
        print()
    return 0


def _doctor_main(argv: list[str]) -> int:
    """``cake-tpu doctor``: render a blackbox bundle as a human report
    naming the dominant phase and likely cause."""
    p = argparse.ArgumentParser(
        prog="cake-tpu doctor",
        description="diagnose a black-box anomaly bundle (--blackbox-dir): "
        "names the dominant latency phase and the likely cause "
        "(convoy / queue / stall / wire / compute / shed)",
    )
    p.add_argument(
        "path",
        help="a bundle-*.json file, or a --blackbox-dir (newest bundle)",
    )
    p.add_argument(
        "--json",
        action="store_true",
        help="print the diagnosis JSON instead of the report",
    )
    args = p.parse_args(argv)

    import json

    from cake_tpu.obs import blackbox

    try:
        bundle = blackbox.load_bundle(args.path)
    except (OSError, ValueError) as e:
        print(f"cake-tpu doctor: cannot load {args.path}: {e}",
              file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(blackbox.diagnose(bundle)))
    else:
        print(blackbox.render_report(bundle))
    return 0


def _benchdiff_main(argv: list[str]) -> int:
    """``cake-tpu benchdiff``: noise-aware comparison of two bench JSON
    records; exit 1 on regression — the one-command perf gate."""
    p = argparse.ArgumentParser(
        prog="cake-tpu benchdiff",
        description="compare two benchmark JSON records (or ledger JSONL "
        "files) with noise-aware thresholds; exit 1 on regression",
    )
    p.add_argument("old", help="baseline bench JSON (or a history JSONL)")
    p.add_argument("new", help="candidate bench JSON (or ledger JSONL)")
    p.add_argument(
        "--pct",
        type=float,
        default=0.10,
        help="relative regression threshold (default 0.10 = 10%%); a key "
        "must also move past its class's absolute floor to gate",
    )
    p.add_argument(
        "--json",
        action="store_true",
        help="print the diff JSON instead of the table",
    )
    args = p.parse_args(argv)

    import json

    from cake_tpu.obs import perf_ledger

    try:
        old = perf_ledger.load_record(args.old)
        new = perf_ledger.load_record(args.new)
    except (OSError, ValueError, IndexError) as e:
        print(f"cake-tpu benchdiff: cannot load records: {e}",
              file=sys.stderr)
        return 2
    diff = perf_ledger.diff_records(old, new, pct=args.pct)
    print(
        json.dumps(diff) if args.json
        else perf_ledger.render_diff(diff, pct=args.pct)
    )
    return 1 if diff["regressions"] else 0


def main(argv: list[str] | None = None) -> int:
    # Where start-up goes (GET /stats ``startup``): filled as it happens.
    startup: dict = {"t_main": time.perf_counter()}
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "stats":
        # Subcommand dispatch ahead of the flag parser: `stats` is a thin
        # HTTP poller and must not demand --model or import jax.
        return _stats_main(argv[1:])
    if argv and argv[0] == "top":
        # The goodput/utilization dashboard is the same thin HTTP poller
        # shape as `stats`: no --model, no jax.
        return _top_main(argv[1:])
    if argv and argv[0] == "requests":
        # Tailing the request log is the same thin HTTP poller shape:
        # no --model, no jax.
        return _requests_main(argv[1:])
    if argv and argv[0] == "loadgen":
        # Open-loop load generator / trace replayer (cake_tpu/loadgen):
        # an HTTP client + stdlib arithmetic — no --model, no jax.
        from cake_tpu.loadgen.__main__ import main as loadgen_main

        return loadgen_main(argv[1:])
    if argv and argv[0] == "trace":
        # Same rationale: exporting/validating a timeline is HTTP + stdlib
        # JSON shuffling; no --model, no jax.
        return _trace_main(argv[1:])
    if argv and argv[0] == "explain":
        # Attribution is ring-event arithmetic (obs/critpath.py): HTTP +
        # stdlib JSON, no --model, no jax.
        return _explain_main(argv[1:])
    if argv and argv[0] == "doctor":
        # Bundle rendering is pure JSON shuffling (obs/blackbox.py).
        return _doctor_main(argv[1:])
    if argv and argv[0] == "benchdiff":
        # The perf gate compares two JSON records (obs/perf_ledger.py).
        return _benchdiff_main(argv[1:])
    if argv and argv[0] == "lint":
        # Same rationale: the linter is pure stdlib AST analysis and must
        # run (fast) without --model or a jax install.
        from cake_tpu.analysis.cli import lint_main

        return lint_main(argv[1:])
    if argv and argv[0] == "locks":
        # The lock-graph view rides the same stdlib-only analysis package:
        # no --model, no jax, safe to run anywhere the repo checks out.
        from cake_tpu.analysis.cli import locks_main

        return locks_main(argv[1:])
    if argv and argv[0] == "resources":
        # Resource-ownership view: same stdlib-only analysis package as
        # lint/locks — no --model, no jax, safe anywhere the repo checks out.
        from cake_tpu.analysis.cli import resources_main

        return resources_main(argv[1:])
    args = build_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="[%(asctime)s %(levelname)s %(name)s] %(message)s",
    )
    if args.faults:
        # Chaos mode: install the deterministic fault plan before any
        # sockets/engines exist (CAKE_FAULTS does the same at import).
        from cake_tpu.runtime import faults as _faults

        _faults.install(_faults.parse(args.faults))
    if args.cpu:
        import os

        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from cake_tpu.utils.device import (
        cpu_requested,
        describe_devices,
        setup_compile_cache,
    )

    if args.cpu:
        # jax reads the variable when it is first imported; main() also runs
        # in processes that imported jax earlier, where only the config wins.
        jax.config.update("jax_platforms", "cpu")
    setup_compile_cache()

    dist = None
    if args.distributed:
        try:
            coord, n_str, i_str = args.distributed.rsplit(",", 2)
            dist = (coord, int(n_str), int(i_str))
        except ValueError:
            print(
                "--distributed expects COORDINATOR:PORT,NUM_PROCESSES,PROCESS_ID",
                file=sys.stderr,
            )
            return 2
        if args.backend != "mesh" or args.mode != "master":
            print(
                "--distributed requires --mode master --backend mesh "
                "(the TCP worker protocol is the heterogeneous path)",
                file=sys.stderr,
            )
            return 2
        from cake_tpu.parallel import multihost

        # Must run before anything queries devices: after this,
        # jax.devices() spans every process in the cluster.
        multihost.initialize(*dist)

    device = describe_devices()
    if device["platform"] != "tpu" and not cpu_requested():
        # Without this JAX drops to the CPU when the TPU fails to initialise
        # and the server answers from there as if nothing had happened.
        print(
            f"no TPU: JAX initialised platform {device['platform']!r} "
            f"({device['device_kind']}, {device['device_count']} device(s)). "
            "Pass --cpu or set JAX_PLATFORMS=cpu to run on the CPU on purpose.",
            file=sys.stderr,
        )
        return 3

    if args.device is not None:
        devices = jax.devices()
        if not 0 <= args.device < len(devices):
            print(
                f"--device {args.device} out of range: host has "
                f"{len(devices)} device(s)",
                file=sys.stderr,
            )
            return 2
        # Pins every un-sharded computation (local step, worker block ranges)
        # to chip N; mesh/tp/sp paths build explicit device meshes and are
        # unaffected.
        jax.config.update("jax_default_device", devices[args.device])

    import jax.numpy as jnp

    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import LlamaGenerator, SamplingConfig
    from cake_tpu.models.llama.tokenizer import load_tokenizer
    from cake_tpu.parallel.topology import Topology

    dtype = {"bf16": jnp.bfloat16, "f16": jnp.float16, "f32": jnp.float32}[
        args.dtype
    ]
    kv_dtype = _resolve_kv_dtype(args, dtype)
    topology = Topology.from_path(args.topology) if args.topology else None

    if args.mode == "worker":
        from cake_tpu.runtime.worker import Worker

        if topology is None:
            print("worker mode requires --topology", file=sys.stderr)
            return 2
        if args.tp > 1:
            print("--tp is a master-side (mesh/local) option", file=sys.stderr)
            return 2
        worker = Worker(
            args.name,
            args.model,
            topology,
            parse_address(args.address),
            dtype=dtype,
            kv_dtype=kv_dtype,
            max_seq_len=args.max_seq_len,
            attention_impl=args.attention_impl,
            fusion_impl=args.fusion,
            quantize=args.quantize,
        )
        from cake_tpu.utils import trace

        try:
            # Trace covers the serving session (stopped cleanly on Ctrl-C).
            with trace.jax_profile(args.trace_dir):
                worker.serve_forever()
        except KeyboardInterrupt:
            worker.stop()
        return 0

    # ----------------------------------------------------------------- master
    sampling = SamplingConfig(
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        repeat_penalty=args.repeat_penalty,
        repeat_last_n=args.repeat_last_n,
        **({"seed": args.seed} if args.seed is not None else {}),
    )
    config = LlamaConfig.from_model_dir(
        args.model, attention_impl=args.attention_impl
    )
    # What a cache that is not plain K and V refuses
    # (``capability.REFUSED``), asked before any weight is read and any
    # backend chosen.
    from cake_tpu.models.llama.capability import (
        UnsupportedForCacheKind,
        refuse_unsupported,
    )

    try:
        refuse_unsupported(
            config,
            single_stream=not (args.api and args.api_batch > 1),
            kv_mode_dense=args.kv_mode != "paged",
            prefix_cache=args.prefix_cache == "on",
            draft_model=args.draft_model is not None,
            speculative_k=bool(args.speculative_k),
            tp=args.tp > 1,
            sp=args.sp > 1,
            topology=topology is not None or args.backend is not None,
            distributed=bool(args.distributed),
            quantize=bool(args.quantize),
            kv_dtype_narrow=jnp.dtype(kv_dtype).itemsize < jnp.dtype(dtype).itemsize,
            repeat_penalty=args.repeat_penalty != 1.0,
        )
        config = _generation_flags(args, config)
    except (UnsupportedForCacheKind, ValueError) as e:
        print(f"cake-tpu: {e}", file=sys.stderr)
        return 2
    if args.fusion != "none":
        import dataclasses

        from cake_tpu.ops.fuse import parse_fusion_spec

        try:
            parse_fusion_spec(args.fusion)
        except ValueError as e:
            print(f"--fusion: {e}", file=sys.stderr)
            return 2
        # On the config BEFORE any backend/step construction, so every
        # serving mode (local, --tp, --backend mesh, --api-batch engines)
        # closes over the fused config.
        config = dataclasses.replace(config, fusion_impl=args.fusion)
    if args.chat_template is not None:
        import dataclasses

        config = dataclasses.replace(config, chat_template=args.chat_template)
    t_load = time.perf_counter()
    startup["load"] = {}
    step = _build_master_step(
        args, config, topology, dtype, kv_dtype, startup["load"]
    )
    startup["load_s"] = round(time.perf_counter() - t_load, 3)
    if dist is not None:
        from cake_tpu.parallel.multihost import MultiHostStep

        if args.decode_chunk > 1 or args.speculative_k:
            # The lockstep wrapper broadcasts per-step calls only; the fused
            # scan's on-device sampling state is not broadcast.
            logging.getLogger("cake_tpu.cli").warning(
                "--distributed decodes per-token: --decode-chunk/"
                "--speculative-k are ignored on the multi-host path"
            )
        step = MultiHostStep(step)
        if not step.leader:
            # Followers replay the leader's steps until it broadcasts STOP.
            logging.getLogger("cake_tpu.cli").info(
                "follower process %d joined; replaying leader steps",
                jax.process_index(),
            )
            step.follow()
            return 0
        # EVERY leader exit — clean return, SystemExit from a flag check,
        # tokenizer/model errors, Ctrl-C — must release the followers, or
        # they stay parked in the broadcast collective. stop() is idempotent.
        try:
            return _run_leader(
                args, step, config, sampling, dtype, kv_dtype, startup
            )
        finally:
            step.stop()
    return _run_leader(args, step, config, sampling, dtype, kv_dtype, startup)


def _generation_flags(args, config):
    """``--denoise-steps`` / ``--remask`` / ``--confidence-threshold`` onto the
    config of a model that generates by diffusion over blocks (where they
    override the checkpoint's defaults); given for any other model they are a
    mistake, said by name."""
    import dataclasses

    given = {
        "denoising_steps": args.denoise_steps, "remask": args.remask,
        "confidence_threshold": args.confidence_threshold,
    }
    given = {k: v for k, v in given.items() if v is not None}
    if not given:
        return config
    if not config.block_length:
        raise ValueError(
            "--denoise-steps / --remask / --confidence-threshold are for a "
            "model that generates by diffusion over blocks; model_type "
            f"{config.model_type!r} generates one token a step"
        )
    steps = given.get("denoising_steps", config.denoising_steps)
    if not 1 <= steps <= config.block_length:
        raise ValueError(
            f"--denoise-steps {steps} must lie in 1..{config.block_length} "
            "(the block length): a pass reveals at least one slot"
        )
    return dataclasses.replace(config, **given)


def _resolve_kv_dtype(args, dtype):
    """--kv-dtype -> jnp dtype (auto = the activation --dtype)."""
    import jax.numpy as jnp

    return {
        "auto": dtype,
        "bf16": jnp.bfloat16,
        "f16": jnp.float16,
        "f32": jnp.float32,
        "f8": jnp.float8_e4m3fn,
    }[args.kv_dtype]


def _run_leader(
    args, step, config, sampling, dtype, kv_dtype, startup: dict
) -> int:
    """The master-side tail of main(): generator + API server or one-shot."""
    from cake_tpu.models.llama.config import CACHE_KV
    from cake_tpu.models.llama.generator import LlamaGenerator
    from cake_tpu.models.llama.tokenizer import load_tokenizer

    if args.prefix_cache == "auto":
        # On for --api, but for a cache that is not plain K and V: a reused
        # prefix restores K and V only ("on" is refused outright, cli.main).
        prefix_cache = (
            bool(args.api) and config.cache_kind == CACHE_KV and not config.block_length
        )
    else:
        prefix_cache = args.prefix_cache == "on"
    # With a batch engine attached, the API path bypasses the generator for
    # chat requests — a generator-side proposer would be dead weight (a full
    # draft KV cache held for nothing).
    engine_serves = bool(args.api) and args.api_batch > 1
    proposer_factory = None
    if args.draft_model is not None:
        if not args.speculative_k:
            raise SystemExit("--draft-model needs --speculative-k > 0")
        from cake_tpu.io.safetensors_io import load_params as _lp
        from cake_tpu.models.llama.config import LlamaConfig
        from cake_tpu.models.llama.speculative import (
            BatchedDraftModelProposer,
            DraftModelProposer,
        )

        # Load the draft weights ONCE — shared by whatever proposer objects
        # get built. The engine gets the BATCHED proposer (one ingest + one
        # scan per round for all lanes); the serialized generator gets the
        # single-stream one.
        draft_cfg = LlamaConfig.from_model_dir(args.draft_model)
        draft_params = _lp(args.draft_model, draft_cfg, dtype)
        if args.draft_quantize is not None:
            from cake_tpu.ops.quant import quantize_params as _qp

            draft_params = _qp(draft_params, args.draft_quantize)
        _draft_cls = (
            BatchedDraftModelProposer if engine_serves else DraftModelProposer
        )

        def proposer_factory():
            return _draft_cls(
                draft_cfg,
                draft_params,
                max_seq_len=step.max_seq_len,
                cache_dtype=kv_dtype,
            )
    generator = LlamaGenerator(
        config,
        step,
        load_tokenizer(args.model),
        sampling,
        decode_chunk_size=args.decode_chunk,
        prefill_chunk=args.prefill_chunk,
        speculative_k=args.speculative_k,
        prefix_cache=prefix_cache,
        proposer=(
            proposer_factory()
            if proposer_factory is not None and not engine_serves
            else None
        ),
    )

    if args.api:
        from cake_tpu.models.llama.generator import LocalForwardStep
        from cake_tpu.runtime.api import ApiServer

        engine = None
        if args.api_batch > 1:
            from cake_tpu.parallel.pipeline import PipelineRunner
            from cake_tpu.parallel.tensor import TensorParallelRunner
            from cake_tpu.runtime.serving import BatchEngine

            backend_obj = None
            engine_params = None
            if isinstance(step, LocalForwardStep):
                engine_params = step.params
            elif isinstance(step, TensorParallelRunner):
                from cake_tpu.runtime.batch_backend import TPBatchBackend

                backend_obj = TPBatchBackend.from_runner(
                    step, max_seq_len=step.max_seq_len, cache_dtype=kv_dtype
                )
            elif isinstance(step, PipelineRunner):
                from cake_tpu.runtime.batch_backend import PipelineBatchBackend

                backend_obj = PipelineBatchBackend.from_runner(
                    step, max_seq_len=step.max_seq_len, cache_dtype=kv_dtype
                )
            else:
                from cake_tpu.runtime.master import DistributedForwardStep

                if isinstance(step, DistributedForwardStep):
                    # Continuous batching over the TCP topology: B concurrent
                    # rows share every worker round trip (the reference
                    # serves one request at a time here, api/mod.rs:76).
                    from cake_tpu.runtime.batch_backend import (
                        DistributedBatchBackend,
                    )

                    backend_obj = DistributedBatchBackend(
                        step, max_seq_len=step.max_seq_len, cache_dtype=kv_dtype
                    )
                else:
                    raise SystemExit(
                        "--api-batch runs on the local, --tp, --backend mesh, "
                        "and --backend tcp masters (--sp keeps the serialized "
                        "path)"
                    )
            if args.kv_mode == "paged" and backend_obj is not None:
                raise SystemExit(
                    "--kv-mode paged runs on the local --api-batch master "
                    "only (the tp/mesh/tcp backends keep the dense cache)"
                )
            # One flag, two layers: the engine reading of --prefix-cache.
            # "auto" means on exactly when the paged pool exists to share;
            # an EXPLICIT "on" without paged is a contradiction worth
            # refusing loudly rather than silently serving dense.
            if args.prefix_cache == "on" and args.kv_mode != "paged":
                raise SystemExit(
                    "--prefix-cache on shares physical KV pages across "
                    "requests and therefore needs --kv-mode paged"
                )
            engine_prefix_cache = (
                args.kv_mode == "paged" and args.prefix_cache != "off"
                and config.cache_kind == CACHE_KV  # "auto" only: see above
            )
            from cake_tpu.runtime.serving import ServeConfig

            serve_cfg = ServeConfig(
                max_batch=args.api_batch,
                decode_chunk_size=args.decode_chunk,
                scheduler=args.scheduler,
                step_prefill_tokens=args.step_prefill,
                kv_mode=args.kv_mode,
                page_size=args.page_size,
                max_pages=args.max_pages,
                fusion_impl=args.fusion,
                op_deadline_s=args.op_deadline,
                op_retries=args.op_retries,
                reconnect_attempts=args.reconnect_attempts,
                reconnect_backoff_s=args.reconnect_backoff,
                heartbeat_interval_s=args.heartbeat_interval,
                heartbeat_deadline_s=args.heartbeat_deadline,
                shed_queue_depth=args.shed_queue_depth,
                shed_min_free_pages=args.shed_free_pages,
                default_priority=args.default_priority,
                tenant_rate=args.tenant_rate,
                tenant_burst=args.tenant_burst,
                tenant_streams=args.tenant_streams,
                fair_queue=not args.no_fair_queue,
                default_deadline_s=args.default_deadline,
                epoch_stall_s=args.epoch_stall,
                slo_ttft_ms=args.slo_ttft_ms,
                slo_ttft_target=args.slo_ttft_target,
                slo_deadline_rate=args.slo_deadline_rate,
                stream_buffer_tokens=args.stream_buffer,
                max_failovers=args.failover_max,
                failover_budget_s=args.failover_budget,
                failover_cooldown_s=args.failover_cooldown,
                failover_local=args.failover_local,
                prefix_cache=engine_prefix_cache,
                prefix_cache_pages=args.prefix_cache_pages,
                prefix_min_tokens=args.prefix_min_tokens,
                blackbox_dir=args.blackbox_dir,
                blackbox_keep=args.blackbox_keep,
                blackbox_min_interval_s=args.blackbox_interval,
                blackbox_p99_mult=args.blackbox_p99_mult,
                peak_tflops=args.peak_tflops,
                peak_hbm_gbps=args.peak_hbm_gbps,
            )
            t_engine = time.perf_counter()
            engine = BatchEngine(
                config,
                engine_params,
                generator.tokenizer,
                max_seq_len=step.max_seq_len,
                cache_dtype=kv_dtype,
                backend=backend_obj,
                speculative_k=args.speculative_k,
                proposer_factory=proposer_factory,
                serve=serve_cfg,
            )
            startup["engine_init_s"] = round(
                time.perf_counter() - t_engine, 3
            )
            from cake_tpu.utils.device import cpu_requested

            if (
                engine.backend.shapes.programs(args.api_batch)
                and not cpu_requested()
            ):
                # The backend's programs are a closed set (runtime/shapes.py):
                # run each once now, so that none is traced while streams are
                # live (``warm_programs``; ``GET /stats`` startup).
                startup["warm"] = engine.backend.warm_programs(
                    args.api_batch, sampling, args.decode_chunk
                )
            if args.speculative_k and not hasattr(
                engine.backend, "verify_greedy"
            ):
                print(
                    "warning: --speculative-k is ignored by this --api-batch "
                    "backend (it exposes no batched verify ops; the engine "
                    "falls back to plain decode)",
                    file=sys.stderr,
                )
        if args.heartbeat_interval > 0 and engine is None:
            # Liveness probing over dedicated PING connections (daemon
            # threads; they die with the server). TCP masters only — the
            # in-process backends have no workers to lose. The batch engine
            # starts its OWN monitor from ServeConfig, so this covers the
            # serialized (--api-batch 1) path.
            from cake_tpu.runtime.master import DistributedForwardStep

            if isinstance(step, DistributedForwardStep) and step.clients:
                from cake_tpu.runtime.client import HeartbeatMonitor

                HeartbeatMonitor(
                    {n: c.host for n, c in step.clients.items()},
                    interval_s=args.heartbeat_interval,
                    deadline_s=args.heartbeat_deadline,
                ).start()
        host, port = parse_address(args.api)
        # --trace-dir here is where POST /profile?seconds=S records its
        # windows; a server's whole life is not a window anyone can read.
        ApiServer(
            generator, engine=engine, events_jsonl=args.events_jsonl,
            trace_jsonl=args.trace_jsonl, request_log=args.request_log,
            startup=startup, profile_dir=args.trace_dir,
        ).serve_forever(host, port)
        return 0

    from cake_tpu.models.llama.chat import Message
    from cake_tpu.runtime.master import Master

    from cake_tpu.utils import trace

    trace.log_memory("master.loaded")
    if args.system_prompt:
        generator.add_message(Message.system(args.system_prompt))
    generator.add_message(Message.user(args.prompt))
    master = Master(generator, sample_len=args.sample_len)
    with trace.jax_profile(args.trace_dir):
        master.generate(
            on_token=lambda t: (print(t.text, end="", flush=True))
        )
    print()
    trace.log_memory("master.done")
    if args.verbose and trace.spans.snapshot():
        print(trace.spans.report(), file=sys.stderr)
    return 0


def _load_params(args, config, dtype, *, host: bool, times: dict):
    """The checkpoint as a param tree, quantized if asked: on the default
    device, or with ``host`` in host memory (parallel.tensor.host_staging)
    for a runner that shards it. ``times`` receives the load's stages
    (io/safetensors_io.load_params)."""
    import contextlib

    from cake_tpu.io.safetensors_io import load_params
    from cake_tpu.parallel.tensor import host_staging

    with host_staging() if host else contextlib.nullcontext():
        params = load_params(args.model, config, dtype, times=times)
        if args.quantize:
            from cake_tpu.ops.quant import quantize_params

            params = quantize_params(params, args.quantize)
    return params


def _build_master_step(
    args, config, topology, dtype, kv_dtype, load: dict | None = None
):
    """Pick mesh / tcp / local execution for the master. ``load``, when
    given, receives the seconds of the load's stages (where this process
    loads weights)."""
    if load is None:
        load = {}
    import jax

    from cake_tpu.models.llama.generator import LocalForwardStep

    backend = args.backend
    if topology is None:
        if backend in ("mesh", "tcp"):
            raise SystemExit(f"--backend {backend} requires --topology")
        backend = "local"

    if backend == "local" or (
        backend is None and not topology.nodes
    ):
        # A tree that TensorParallelRunner is about to shard is built in
        # host memory, and the runner places each chip's shard once.
        params = _load_params(
            args, config, dtype, host=args.tp > 1 and args.sp <= 1, times=load
        )
        if args.sp > 1:
            from cake_tpu.parallel.sequence import SequenceParallelRunner

            return SequenceParallelRunner(
                config, params, sp=args.sp, tp=args.tp,
                max_seq_len=args.max_seq_len, cache_dtype=kv_dtype,
            )
        if args.tp > 1:
            from cake_tpu.parallel.tensor import TensorParallelRunner

            return TensorParallelRunner(
                config, params, tp=args.tp,
                max_seq_len=args.max_seq_len, cache_dtype=kv_dtype,
            )
        # Sliding-window models with chunked prefill get the rolling cache:
        # KV memory bounded by window + chunk instead of max_seq_len
        # (models/llama/cache.py). Speculative decoding verifies chunks
        # through the dense layout, so it keeps the full cache.
        rolling_budget = None
        if (
            config.sliding_window is not None
            # gemma2/gemma3: their full-attention layers need ALL keys — a
            # ring bounded by the window would evict history those layers
            # must still attend (win_flag only masks, it cannot resurrect
            # evicted keys).
            and not config.alt_sliding_window
            and config.sliding_pattern is None
            and args.prefill_chunk
            and not args.speculative_k
        ):
            rolling_budget = max(args.prefill_chunk, args.decode_chunk)
        t_fuse = time.perf_counter()
        step = LocalForwardStep(
            config, params, max_seq_len=args.max_seq_len, cache_dtype=kv_dtype,
            rolling_budget=rolling_budget,
        )
        # The step's constructor builds the fused QKV and gate|up copies.
        jax.block_until_ready(step.params)
        load["fuse_s"] = round(time.perf_counter() - t_fuse, 3)
        return step

    if args.sp > 1:
        raise SystemExit("--sp requires local execution (no topology backend)")
    if args.quantize and backend != "mesh":
        # The TCP master's own local stages stay full precision; workers
        # quantize their ranges with their OWN --quantize flag.
        raise SystemExit(
            "--quantize on a master runs on the local/--tp/--sp/mesh "
            "backends (give workers their own --quantize for the tcp path)"
        )
    plan = topology.stage_plan(config.num_hidden_layers)
    if backend is None:
        # A topology that names workers means the model is deployed across
        # hosts; silently loading everything locally (mesh) could OOM the
        # master or bypass the cluster — mesh stays an explicit opt-in.
        backend = "tcp"

    if backend == "mesh":
        if len(plan) * args.tp > len(jax.devices()):
            raise SystemExit(
                f"--backend mesh needs one local device per stage x tp "
                f"({len(plan)} stages x tp={args.tp}, "
                f"{len(jax.devices())} devices)"
            )
        from cake_tpu.parallel.pipeline import PipelineRunner

        # In host memory: PipelineRunner places each stage's layers on its
        # own chip, and nothing whole ever sits on the first.
        return PipelineRunner(
            config,
            _load_params(args, config, dtype, host=True, times=load),
            [(s.lo, s.hi) for s in plan],
            tp=args.tp,
            max_seq_len=args.max_seq_len,
            cache_dtype=kv_dtype,
        )

    if args.tp > 1:
        # Silent fallthrough would run tp=1 while the user believes otherwise.
        raise SystemExit("--tp requires --backend mesh or local execution")
    from cake_tpu.runtime.master import DistributedForwardStep

    return DistributedForwardStep(
        config,
        args.model,
        topology,
        dtype=dtype,
        max_seq_len=args.max_seq_len,
        kv_dtype=kv_dtype,
        op_deadline_s=args.op_deadline,
        op_retries=args.op_retries,
        reconnect_attempts=args.reconnect_attempts,
        reconnect_backoff_s=args.reconnect_backoff,
    )


if __name__ == "__main__":
    sys.exit(main())
