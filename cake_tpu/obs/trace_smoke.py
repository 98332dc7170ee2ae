"""Trace smoke gate: serve 2 concurrent streams, validate the timeline export.

``make trace-smoke`` (wired into ``make verify`` after lint) runs this on the
CPU backend with a tiny random-weight model: two concurrent requests through
the real BatchEngine with ``--trace-jsonl`` streaming, then the JSONL is read
back, rendered as Chrome trace-event JSON, and pushed through the schema
checker (cake_tpu/obs/timeline.validate_export). Exit is nonzero on malformed
output — a torn JSONL line, an unpaired B/E, a flow arrow with no start —
so the export contract that Perfetto depends on gates like a test.

Usage: ``python -m cake_tpu.obs.trace_smoke [--jsonl PATH] [--out PATH]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="cake-tpu trace-smoke")
    p.add_argument(
        "--jsonl", default=None,
        help="where to stream timeline events (default: a temp file)",
    )
    p.add_argument(
        "--out", default=None,
        help="also write the rendered Chrome trace JSON here",
    )
    p.add_argument("--tokens", type=int, default=12)
    p.add_argument(
        "--paged-pallas", action="store_true",
        help="serve through the paged Pallas kernel family (128-slot "
        "pages, attention_impl=pallas, prefix cache on) and GATE on the "
        "export showing kernel:* dispatch instants with impl=pallas — a "
        "silent fallback to the XLA gather path fails the smoke",
    )
    p.add_argument(
        "--fused-pallas", action="store_true",
        help="serve with the decode op-fusion kernels (fusion_impl="
        "all@pallas) and GATE on the export showing kernel:fused_* "
        "dispatch instants with impl=pallas — a silent fallback to the "
        "unfused path fails the smoke (mirrors --paged-pallas)",
    )
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from cake_tpu.models.llama import model as M
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.obs.timeline import (
        export_events,
        load_jsonl,
        timeline,
        validate_export,
    )
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    jsonl = args.jsonl or os.path.join(
        tempfile.mkdtemp(prefix="cake-trace-smoke-"), "trace.jsonl"
    )
    timeline.attach_jsonl(jsonl)

    if args.paged_pallas:
        # Kernel-path gate: 128-slot pages (the lane-tile minimum) and an
        # explicit pallas attention_impl; the prefix cache routes the warm
        # round through the cached-chunk kernel (suffix_prefill dispatch).
        cfg = LlamaConfig.tiny(num_hidden_layers=2, attention_impl="pallas")
        serve = ServeConfig(
            max_batch=2, decode_chunk_size=4, admission_window=0.02,
            kv_mode="paged", page_size=128, prefix_cache=True,
        )
        max_seq = 256
    elif args.fused_pallas:
        # Decode-fusion gate: fusion_impl=all@pallas over the dense local
        # backend (the fused kernels run interpret on CPU, exactly like
        # the paged round); the export must show the fused-kernel dispatch
        # instants with impl=pallas.
        cfg = LlamaConfig.tiny(num_hidden_layers=2)
        serve = ServeConfig(
            max_batch=2, decode_chunk_size=4, admission_window=0.02,
            fusion_impl="all@pallas",
        )
        max_seq = 128
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=2)
        serve = ServeConfig(
            max_batch=4, decode_chunk_size=4, admission_window=0.02,
            kv_mode="paged", page_size=16,
        )
        max_seq = 128
    params = M.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    engine = BatchEngine(
        cfg, params, ByteTokenizer(),
        max_seq_len=max_seq, cache_dtype=jnp.float32, serve=serve,
    )
    engine.start()
    try:
        greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
        if args.paged_pallas:
            # Two ROUNDS, not two streams: round 2 re-serves the same
            # prompt warm so the suffix (cached-chunk) kernel dispatches.
            counts = []
            for _ in range(2):
                h = engine.submit(
                    [Message.user("kernel smoke prompt")],
                    min(args.tokens, 8), greedy,
                )
                counts.append(sum(1 for _ in h.tokens()))
                if not engine.quiesce(30.0):
                    raise RuntimeError("paged-pallas smoke pool never settled")
        else:
            handles = [
                engine.submit([Message.user(prompt)], args.tokens, greedy)
                for prompt in (
                    "smoke stream one", "a second concurrent stream"
                )
            ]
            counts = [sum(1 for _ in h.tokens()) for h in handles]
    finally:
        engine.stop()
        timeline.attach_jsonl(None)

    events = load_jsonl(jsonl)  # malformed line -> json error -> nonzero exit
    trace = export_events(events)
    problems = validate_export(trace)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(trace, f)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] != "M"}
    required = {"epoch", "prefill", "decode-chunk", "request"}
    missing = required - names
    if missing:
        problems.append(f"expected span names absent: {sorted(missing)}")
    if args.paged_pallas:
        # The kernel-dispatch breadcrumbs (PagedLocalBackend._kernel_note):
        # every paged op of the warm serve must have resolved to the Pallas
        # family — an instant saying impl=xla means the kernel path
        # silently fell back, which is exactly what this gate exists to
        # catch before it lands.
        kernel = {
            e["name"]: e.get("args", {}).get("impl")
            for e in trace["traceEvents"]
            if e["ph"] == "i" and e["name"].startswith("kernel:")
        }
        # (Prefix-cache epochs route EVERY prefill — cold included —
        # through suffix_prefill, so kernel:prefill never fires here; the
        # fresh-chunk kernel path is pinned by tests/test_paged_prefill.py.)
        for op in ("kernel:suffix_prefill", "kernel:decode"):
            if op not in kernel:
                problems.append(f"paged kernel instant absent: {op}")
            elif kernel[op] != "pallas":
                problems.append(
                    f"{op} dispatched impl={kernel[op]!r}, wanted 'pallas' "
                    "(silent fallback to the XLA gather path)"
                )
    if args.fused_pallas:
        # The fused-kernel breadcrumbs (batch_backend._note_fusion_kernels):
        # every decode dispatch of the fused serve must have resolved the
        # fusion family to pallas — an instant saying impl=xla (or no
        # instant at all) means the fusion silently fell back to the
        # unfused path, which is exactly what this gate exists to catch.
        kernel = {
            e["name"]: e.get("args", {}).get("impl")
            for e in trace["traceEvents"]
            if e["ph"] == "i" and e["name"].startswith("kernel:fused_")
        }
        for op in (
            "kernel:fused_norm_matmul",
            "kernel:fused_sample_tail",
        ):
            if op not in kernel:
                problems.append(f"fused kernel instant absent: {op}")
            elif kernel[op] != "pallas":
                problems.append(
                    f"{op} dispatched impl={kernel[op]!r}, wanted 'pallas' "
                    "(silent fallback to the unfused path)"
                )
    if min(counts) < 1:
        problems.append(f"a stream produced no tokens: {counts}")
    for prob in problems:
        print(f"trace-smoke: FAIL: {prob}", file=sys.stderr)
    if problems:
        return 1
    print(
        f"trace-smoke: OK — {len(events)} events, {counts} tokens/stream, "
        f"jsonl={jsonl}" + (f", trace={args.out}" if args.out else "")
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
