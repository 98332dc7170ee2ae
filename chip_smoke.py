#!/usr/bin/env python3
"""chip_smoke.py: does the --api-batch server start, compile and answer on
the chip?

    python3 chip_smoke.py                  # on a machine with a TPU
    python3 chip_smoke.py --rehearse-cpu   # the same phases, tiny, on the CPU

It drives the serving main path once through the entry points a user calls
(``python -m cake_tpu.cli --model DIR --api ... --api-batch 8`` and HTTP), at
the full width of one model the repo supports: Mistral-7B-v0.1 (hidden 4096,
intermediate 14336, 32 q / 8 kv heads of 128, vocab 32000, sliding window
4096, bf16), depth cut to 8 layers, seeded random weights written as a real
sharded safetensors checkpoint and loaded as a user's would be.

This process never imports jax: a chip belongs to one process at a time, so
every phase runs in a child that has exited before the next one starts.

  probe    which device JAX sees, where the compile cache is
  native   rebuild cake_tpu/native/*.so from source (they are not in git)
  setup    write the checkpoint
  A        server at its defaults (dense KV, epoch scheduler, auto attention)
  B        server as the benchmark will run it (paged KV, continuous
           scheduler, prefix cache, Pallas attention); Bf adds --fusion
  C        every Pallas kernel at the model's shapes against its XLA twin,
           and a bf16 matmul chain held against the device's peak
  P        the paged decode chunk and a suffix join compiled (not run, no
           weights) at the benchmark cell's geometry: no copy, slice or
           update-slice of the KV pool or of a layer of it in the compiled
           program, and less than one pool of temporaries
  H        a model with state layers at jamba2-3b-chat-closed's geometry:
           the paged kernels at one KV head under a group of 20, the
           state-space mixer (ops/ssm.py) against the stepwise scan, and its
           decode chunk and join compiled: no copy of the pool or the state
  O        a model whose state layers run the gated delta rule at
           olmo-hybrid-7b-chat-closed's geometry: the paged kernels at 30 KV
           heads, the mixer (ops/delta_rule.py: the chunkwise form, the
           one-token kernel) against the rule one position at a time, both
           alone on the clock, and its decode chunk and join compiled: no
           copy of the pool or the float32 state
  L        a model with latent attention and a share of its experts at
           pangu-ultra-ep16-chat-closed's geometry: the absorbed decode
           kernel over the latent pool against its XLA twin and alone on the
           clock, one sparse layer's 16 held experts of 256 by the dense
           combine and by the grouped path, and its decode chunk and join
           compiled: no copy of the latent pool
  F        a model of gated short convolutions and routed experts, all held,
           at lfm2-8b-a1b-chat-closed's geometry: one period of the stack
           (conv, conv, attention, conv; a dense and three sparse layers) at
           the published widths through a join and a decode chunk for real,
           the kernels' programs against their XLA twins' (heads of 64 two a
           pool row of 128), and the cell's decode chunk and join compiled:
           no copy of the pool or of the convolutions' windows
  Q        a model of grouped delta-rule heads (32 value heads on 16 key
           heads, 128 x 128 states), gated attention on heads of 256 (16 on
           2 KV) and a share of softmax-routed experts (128 held of 512, ten
           a token) beside a gated shared one, at
           qwen3-next-ep4-chat-closed's geometry: one period of the stack at
           the published widths through a join and a decode chunk of 64 rows
           for real, both delta kernels and the three paged kernels against
           their XLA twins and the plain float32 reference, the experts at a
           step's 64 and a join's 1,024 rows, and the cell's decode chunk,
           join and three-row group compiled: no copy of the pool or of the
           float32 state
  M        a model that generates by diffusion over blocks of 4 over 128
           softmax-routed experts a layer, all held, at
           sdar-30b-a3b-chat-closed's geometry: the paged chunk kernel at 4
           queries a lane over 64 lanes under the block-causal mask against
           its XLA twin and alone on the clock, a sparse layer at 128 held of
           128 at a pass's 256 rows and a join's 1,024, two layers of the cut
           at the published widths through a prefill and a dispatch of two
           blocks for real (kernels' programs against their twins'), and the
           cell's decode dispatch and join compiled: no copy of the pool, no
           weight laid out again a pass
  D        four chips: the phase-A server under --tp 4 and as a four-stage
           pipeline, with per-device memory (skipped below four devices)

Any phase failing makes the exit code non-zero and suppresses the result
line. Without an accelerator the probe fails: merely lacking a chip never
selects the CPU. Timings are labelled with the device and are information,
not a benchmark. The last line of a passing run is one JSON object naming
the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")  # listed in .gitignore
PHASES = ("probe", "native", "setup", "A", "B", "Bf", "C", "P", "H", "O", "L", "S", "F", "J", "Q", "M", "D")

MISTRAL_7B = dict(  # Mistral-7B-v0.1 config.json, depth aside
    model_type="mistral", hidden_size=4096, intermediate_size=14336,
    num_attention_heads=32, num_key_value_heads=8, head_dim_override=128,
    vocab_size=32000, sliding_window=4096, rope_theta=10000.0,
    rms_norm_eps=1e-5, max_position_embeddings=32768, bos_token_id=1,
    eos_token_ids=(2,), tie_word_embeddings=False,
)
def _benchmark_model(name: str) -> dict:
    """The model's own keys of ``bench/configs/<name>.json`` (what the
    benchmark writes as ``config.json``), so that this file holds no copy
    of a catalog row's widths. ``bench.manifest`` imports no JAX."""
    from bench.manifest import model_config

    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        return model_config(json.load(f))


JAMBA2_3B = _benchmark_model("ai21-jamba2-3b")
PANGU_EP16 = _benchmark_model("openpangu-ultra-moe-718b-ep16")
OLMO_HYBRID_D16 = _benchmark_model("olmo-hybrid-7b-d16")
DEEPSEEK_V32_EP16 = _benchmark_model("deepseek-v3.2-exp-ep16-d5")
LFM2_D16 = _benchmark_model("lfm2-8b-a1b-d16")
QWEN3_NEXT_EP4 = _benchmark_model("qwen3-next-80b-a3b-ep4-d12")
SDAR_PP8 = _benchmark_model("sdar-30b-a3b-chat-pp8-d6")
PRESETS = {
    # Prompt lengths are in characters: without a tokenizer file the byte
    # tokenizer serves, one token a byte.
    "full": dict(
        name="full", model=dict(MISTRAL_7B, num_hidden_layers=8), dtype="bf16",
        max_seq_len=2048, prompts=(32, 300, 1500), shared_prefix=1000,
        new_tokens=64, page_size=128, chunk=256, int4_group=128,
        batches=(1, 8), matmul=(8192, 20),
        # the pool's write (ops/pallas/paged_write.py): its slab at the
        # widest KV layout served beside the model's 8, and alone on the
        # clock beside its scatter as (rows, KV heads, window) of a decode
        # step of each paged K/V cell and of a 512-token join
        write_kv=(30,),
        timed_write=dict(shapes=((32, 30, 1), (8, 8, 1), (32, 1, 1),
                                 (1, 30, 512))),
        # mistral7b-chat-closed (bench/configs/mistral-7b-v0.1-d16.json)
        pool=dict(layers=16, pages=256, lanes=8, table_pages=8, steps=8,
                  join_width=256),
        # jamba2-3b-chat-closed (bench/configs/ai21-jamba2-3b.json)
        hybrid=dict(
            model=dict(JAMBA2_3B), prompt=300, pages=1024, lanes=32,
            table_pages=8, steps=8, join_width=512,
        ),
        # olmo-hybrid-7b-chat-closed (bench/configs/olmo-hybrid-7b-d16.json)
        olmo=dict(
            model=dict(OLMO_HYBRID_D16), prompt=300, pages=320, lanes=32,
            table_pages=8, steps=8, join_width=512,
            # one group of an epoch's prefill: rows, slots, table pages
            epoch=(2, 2560, 20),
        ),
        # pangu-ultra-ep16-chat-closed
        # (bench/configs/openpangu-ultra-moe-718b-ep16.json)
        latent=dict(
            model=dict(PANGU_EP16), pages=2048, lanes=64, table_pages=8,
            steps=8, join_width=512, expert_tokens=(1, 8, 64, 512, 2048),
        ),
        # deepseek-v32-ep16-longdoc-closed
        # (bench/configs/deepseek-v3.2-exp-ep16-d5.json)
        sparse=dict(
            model=dict(DEEPSEEK_V32_EP16), pages=2688, lanes=16, table_pages=168,
            steps=8, join_width=8064,
        ),
        # lfm2-8b-a1b-chat-closed (bench/configs/lfm2-8b-a1b-d16.json)
        lfm2=dict(
            model=dict(LFM2_D16), pages=2048, lanes=64, table_pages=32,
            steps=8, join_width=512, prompts=(300, 190), block_lanes=8,
            expert_tokens=(16, 32, 64, 128, 256),
            # phase J: join programs alone (rows, slots, the live rows' tokens)
            joins=dict(
                rows=(2, 3, 4), slots=(256, 512, 1024),
                tokens={256: (200,), 512: (200, 300), 1024: (200, 300, 600)},
                runs=4,
            ),
        ),
        # qwen3-next-ep4-chat-closed
        # (bench/configs/qwen3-next-80b-a3b-ep4-d12.json): the period's decode
        # chunk at the cell's 64 rows, the experts at a step's and a join's rows
        qwen3next=dict(
            model=dict(QWEN3_NEXT_EP4), pages=1024, lanes=64, table_pages=32,
            steps=8, join_width=512, prompts=(300, 190), block_lanes=64,
            expert_tokens=(64, 1024),
        ),
        # sdar-30b-a3b-chat-closed (bench/configs/sdar-30b-a3b-chat-pp8-d6.json):
        # a dispatch of two blocks at the cell's 64 lanes, the experts at a
        # pass's rows (64 x 4) and a join's
        sdar=dict(
            model=dict(SDAR_PP8), pages=1024, lanes=64, table_pages=32,
            steps=8, join_width=512, prompts=(301, 190, 450), block_lanes=64,
            block_layers=2, cached=450, expert_tokens=(256, 1024),
        ),
    ),
    # The rehearsal: same family and head layout rules (tp 4 divides the
    # heads, a page is a whole lane tile), widths a CPU can interpret.
    "tiny": dict(
        name="tiny",
        model=dict(
            MISTRAL_7B, hidden_size=128, intermediate_size=256,
            num_attention_heads=8, num_key_value_heads=4,
            head_dim_override=16, vocab_size=512, num_hidden_layers=8,
        ),
        dtype="f32", max_seq_len=256, prompts=(12, 40, 150),
        shared_prefix=100, new_tokens=8, page_size=128, chunk=32,
        int4_group=64, batches=(1, 2), matmul=(256, 4),
        timed_decode=dict(table_pages=(2, 4), calls=2, repeats=1),
        write_kv=(3,),
        timed_write=dict(shapes=((2, 3, 1), (1, 3, 40)), layers=2, calls=2,
                         repeats=1),
        pool=dict(layers=3, pages=64, lanes=2, table_pages=2, steps=4,
                  join_width=64),
        hybrid=dict(
            model=dict(
                JAMBA2_3B, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, vocab_size=512, num_hidden_layers=8,
                attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4,
                mamba_dt_rank=4,
            ),
            # 4 lanes: with 2 a run's stacked A_log [2, 4, 128] has the
            # shape of one layer of the state, and the audit names it.
            prompt=37, pages=64, lanes=4, table_pages=2, steps=4,
            join_width=64,
            # the model's d_state 4 is no sublane tile: the twin's by shape
            timed_scan=dict(d_state=8, windows=((1, 64), (2, 48)), calls=2,
                            repeats=1),
        ),
        olmo=dict(
            # 3 heads of 128 values: the state tiles (the step is the
            # kernel's, interpreted here), dk != dv, H no power of two
            model=dict(
                OLMO_HYBRID_D16, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
                num_hidden_layers=4,
                layer_types=["linear_attention"] * 3 + ["full_attention"],
                linear_num_key_heads=3, linear_num_value_heads=3,
                linear_key_head_dim=8, linear_value_head_dim=128,
            ),
            prompt=70, pages=64, lanes=4, table_pages=2, steps=4,
            join_width=64, epoch=(2, 128, 2),
            timed_delta=dict(windows=((1, 70), (2, 200, 70, 130)), lanes=4,
                             calls=2, repeats=1, step_live=(2, 1)),
        ),
        latent=dict(
            model=dict(
                PANGU_EP16, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=64, num_attention_heads=8,
                num_key_value_heads=8, q_lora_rank=48, kv_lora_rank=128,
                qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=32,
                num_hidden_layers=3, n_routed_experts=2,
                n_routed_experts_total=16, num_experts_per_tok=4,
                vocab_size=512,
            ),
            pages=16, lanes=2, table_pages=2, steps=4, join_width=64,
            expert_tokens=(8, 64), timed=dict(table_pages=(2,), calls=2, repeats=1),
        ),
        sparse=dict(
            model=dict(
                DEEPSEEK_V32_EP16, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=64, num_attention_heads=8,
                num_key_value_heads=8, q_lora_rank=48, kv_lora_rank=128,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                num_hidden_layers=3, n_routed_experts=2,
                n_routed_experts_total=16, num_experts_per_tok=4, n_group=4,
                topk_group=2, index_n_heads=4, index_head_dim=128,
                index_topk=64, vocab_size=512,
            ),
            pages=16, lanes=2, table_pages=2, steps=4, join_width=256,
            timed=dict(lengths=(64, 256), calls=2, repeats=1),
        ),
        lfm2=dict(
            # heads of 64: two KV heads a pool row, as at the published widths
            model=dict(
                LFM2_D16, hidden_size=128, intermediate_size=256,
                moe_intermediate_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, num_experts=8,
                num_experts_per_tok=2, vocab_size=512,
            ),
            pages=16, lanes=4, table_pages=2, steps=4, join_width=64,
            prompts=(37, 21), block_lanes=4,
            expert_tokens=(8, 32), timed=dict(calls=2, repeats=1),
            joins=dict(rows=(3,), slots=(32,), tokens={32: (20,)}, runs=2),
        ),
        qwen3next=dict(
            # value heads of 128 in groups of 2 on the key heads, a rotary
            # term over a quarter of a head of 128, 4 held of 16 ranked
            model=dict(
                QWEN3_NEXT_EP4, hidden_size=128, moe_intermediate_size=64,
                shared_expert_intermediate_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=128, num_experts=4, num_experts_total=16,
                num_experts_per_tok=4, vocab_size=512,
            ),
            pages=16, lanes=4, table_pages=2, steps=4, join_width=64,
            prompts=(37, 21), block_lanes=4,
            expert_tokens=(8, 32), timed=dict(calls=2, repeats=1),
        ),
        sdar=dict(
            model=dict(
                SDAR_PP8, hidden_size=128, moe_intermediate_size=64,
                num_attention_heads=4, num_key_value_heads=2, head_dim=128,
                num_experts=8, num_experts_per_tok=2, vocab_size=512,
                mask_token_id=300, bos_token_id=3, eos_token_id=4, pad_token_id=3,
            ),
            pages=16, lanes=4, table_pages=2, steps=8, join_width=64,
            prompts=(37, 21), block_lanes=4, block_layers=2, cached=40,
            expert_tokens=(16, 32), timed=dict(calls=2, repeats=1),
        ),
    ),
}

_state = {"platform": "unprobed", "procs": []}


def say(msg: str) -> None:
    print(f"[chip_smoke platform={_state['platform']}] {msg}", flush=True)


class PhaseFailed(Exception):
    pass


# ------------------------------------------------------------------ children


def _child_env(cpu: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
        # Four virtual devices, so the rehearsal walks phase D too.
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
            proc.wait(timeout=20)
        except (ProcessLookupError, subprocess.TimeoutExpired):
            pass
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=20)


def _kill_all(*_args) -> None:
    for proc in _state["procs"]:
        _kill(proc)
    if _args:  # called as a signal handler
        sys.exit(128 + _args[0])


def run_child(name: str, args: argparse.Namespace, timeout: float) -> list:
    """Run ``chip_smoke.py --child NAME`` to its end; returns the JSON
    records it printed on ``RESULT`` lines. Its other output passes
    through. A child that raises, or outlives ``timeout``, fails the phase."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=_child_env(args.rehearse_cpu),
        stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    _state["procs"].append(proc)
    records = []
    timer = threading.Timer(timeout, _kill, (proc,))
    timer.start()
    try:
        for line in proc.stdout:
            if line.startswith("RESULT "):
                records.append(json.loads(line[len("RESULT "):]))
            else:
                say(f"  {name}| {line.rstrip()}")
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill(proc)
        _state["procs"].remove(proc)
    if rc != 0:
        raise PhaseFailed(f"child {name!r} exited with code {rc}")
    return records


def emit(record: dict) -> None:
    print("RESULT " + json.dumps(record), flush=True)


def child_probe(preset: dict) -> None:
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    emit({"cache_dir": setup_compile_cache(), **describe_devices()})


def child_setup(preset: dict) -> None:
    """Seeded random weights at the preset's widths, written shard by shard
    with the repo's checkpoint writer. numpy only: no backend starts."""
    from concurrent.futures import ThreadPoolExecutor

    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.io.safetensors_io import (
        ShardedCheckpointWriter,
        head_tensor_dict,
        layer_tensor_dict,
    )
    from cake_tpu.models.llama.config import LlamaConfig

    config = LlamaConfig(**preset["model"])
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]]
    model_dir = _model_dir(preset)
    h, inter, v = config.hidden_size, config.intermediate_size, config.vocab_size
    qd = config.num_attention_heads * config.head_dim
    kd = config.num_key_value_heads * config.head_dim
    shapes = {  # compute orientation [in, out], stacked over one layer
        "wq": (h, qd), "wk": (h, kd), "wv": (h, kd), "wo": (qd, h),
        "w_gate": (h, inter), "w_up": (h, inter), "w_down": (inter, h),
    }

    def normal(seed, shape):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal(shape, dtype=np.float32) * 0.02
        return x.astype(dtype)

    t0 = time.perf_counter()
    shutil.rmtree(model_dir, ignore_errors=True)
    os.makedirs(model_dir)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(config.to_hf_dict(), f, indent=2)
    with ShardedCheckpointWriter(model_dir) as writer, ThreadPoolExecutor(
        os.cpu_count() or 1
    ) as pool:
        embed, head = pool.map(normal, (1, 2), ((v, h), (h, v)))
        writer.add(head_tensor_dict(
            {"embed": embed, "ln_f": np.ones((h,), dtype), "lm_head": head},
            config, dtype,
        ))
        for i in range(config.num_hidden_layers):
            mats = pool.map(
                normal, (1000 * (i + 1) + j for j in range(len(shapes))),
                shapes.values(),
            )
            layer = {k: m[None] for k, m in zip(shapes, mats)}
            layer["ln_attn"] = layer["ln_mlp"] = np.ones((1, h), dtype)
            writer.add(layer_tensor_dict(layer, config, dtype, i, i + 1))
        paths = writer.finish()
    size = sum(os.path.getsize(p) for p in paths)
    emit({"model_dir": model_dir, "bytes": size, "shards": len(paths),
          "seconds": round(time.perf_counter() - t0, 1)})


def child_kernels(preset: dict) -> None:
    from cake_tpu.obs.efficiency import device_peaks
    from cake_tpu.ops.pallas.check import (
        Geometry,
        run_checks,
        timed_matmul_chain,
        timed_paged_decode,
        timed_pool_write,
    )
    from cake_tpu.obs import jitwatch
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    jitwatch.install_compile_listener()
    device = describe_devices()
    m = preset["model"]
    out = run_checks(Geometry(
        hidden=m["hidden_size"], intermediate=m["intermediate_size"],
        n_q=m["num_attention_heads"], n_kv=m["num_key_value_heads"],
        head_dim=m["head_dim_override"], vocab=m["vocab_size"],
        window=m["sliding_window"], page_size=preset["page_size"],
        max_seq=preset["max_seq_len"], chunk=preset["chunk"],
        int4_group=preset["int4_group"], dtype=preset["dtype"],
        batches=tuple(preset["batches"]),
        write_kv=tuple(preset["write_kv"]),
    ))
    for rec in out["results"]:
        emit({"kind": "case", **rec})
    emit({"kind": "timed", "rows": timed_paged_decode(
        m["num_attention_heads"], m["num_key_value_heads"],
        m["head_dim_override"], preset["page_size"], preset["pool"]["lanes"],
        preset["pool"]["layers"], preset["dtype"],
        **preset.get("timed_decode", {}),
    )})
    emit({"kind": "write", "rows": timed_pool_write(
        head_dim=m["head_dim_override"], page_size=preset["page_size"],
        dtype=preset["dtype"], **preset["timed_write"],
    )})
    chain = timed_matmul_chain(*preset["matmul"])
    peaks = device_peaks()  # raises on an accelerator it has no peaks for
    emit({
        "kind": "summary", **device, "interpret": sorted(set(out["interpret"])),
        "pallas_calls": len(out["interpret"]), "matmul": chain,
        "peak_tflops": peaks[0] if peaks else None,
        "compile": dict(zip(("count", "seconds"), jitwatch.compile_totals())),
    })


def child_pool(preset: dict) -> None:
    """Compile, for the device this process holds, the two programs a
    saturated paged server runs, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import pool_audit
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.utils.device import describe_devices

    device = describe_devices()
    g = preset["pool"]
    config = LlamaConfig(**dict(
        preset["model"], num_hidden_layers=g["layers"],
        attention_impl="pallas",
    ))
    # The interpreter (the rehearsal's) stages a kernel's operands through
    # copies of its own; there the XLA twins stand in for the kernels.
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=preset["page_size"],
        lanes=g["lanes"], table_pages=g["table_pages"], n_steps=g["steps"],
        width=g["join_width"],
        dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]],
        allow_pallas=jax.default_backend() != "cpu",
        only=("decode", "suffix_join"),
    )
    for name, report in reports.items():
        emit({"program": name, **device, **report})


def child_hybrid(preset: dict) -> None:
    """A model with state layers at the benchmark cell's geometry: the paged
    kernels at its head layout (one KV head under a group of 20) and the
    state-space mixer against the stepwise scan, then its decode chunk and
    join compiled for the device this process holds, from shapes alone."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import pool_audit
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.ops.pallas.check import (
        HybridGeometry,
        run_hybrid_checks,
        timed_paged_decode,
        timed_selective_scan,
        timed_selective_step,
    )
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    device = describe_devices()
    g = preset["hybrid"]
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas"
    )
    out = run_hybrid_checks(HybridGeometry(
        hidden=config.hidden_size, n_q=config.num_attention_heads,
        n_kv=config.num_key_value_heads, head_dim=config.head_dim,
        d_inner=config.mamba_d_inner, d_state=config.mamba_d_state,
        d_conv=config.mamba_d_conv, dt_rank=config.mamba_dt_rank,
        page_size=preset["page_size"], max_seq=preset["max_seq_len"],
        chunk=preset["chunk"], prompt=g["prompt"], dtype=preset["dtype"],
        batches=tuple(preset["batches"]),
    ))
    for rec in out["results"]:
        emit({"kind": "case", **rec})
    emit({"kind": "summary", **device,
          "interpret": sorted(set(out["interpret"])),
          "pallas_calls": len(out["interpret"])})
    emit({"kind": "timed", "rows": timed_paged_decode(
        config.num_attention_heads, config.num_key_value_heads,
        config.head_dim, preset["page_size"], g["lanes"],
        len(config.layers_of("attention")), preset["dtype"],
        **preset.get("timed_decode", {}),
    )})
    emit({"kind": "scan", "rows": timed_selective_scan(**{
        "d_inner": config.mamba_d_inner, "d_state": config.mamba_d_state,
        **g.get("timed_scan", {}),
    })})
    emit({"kind": "step", "rows": timed_selective_step(
        config.mamba_d_inner, config.mamba_d_state, g["lanes"],
        len(config.layers_of("state")),
    )})
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=preset["page_size"],
        lanes=g["lanes"], table_pages=g["table_pages"], n_steps=g["steps"],
        width=g["join_width"],
        dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]],
        allow_pallas=jax.default_backend() != "cpu",
    )
    for name, report in reports.items():
        emit({"kind": "program", "program": name, **report})


def child_olmo(preset: dict) -> None:
    """A model whose state layers run the gated delta rule, at the benchmark
    cell's geometry: the paged kernels at its head layout (30 KV heads, no
    grouping), the mixer against the stepwise rule, the rule alone on the
    clock, and its decode chunk and join compiled for the device this process
    holds, from shapes alone."""
    import dataclasses
    from unittest import mock

    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import pool_audit, programs
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.ops import delta_rule as D
    from cake_tpu.ops.pallas.check import (
        DeltaGeometry,
        run_delta_checks,
        timed_delta_rule,
        timed_paged_decode,
    )
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    device = describe_devices()
    g = preset["olmo"]
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas"
    )
    heads, dk, dv = (config.linear_num_value_heads, config.linear_key_head_dim,
                     config.linear_value_head_dim)
    out = run_delta_checks(DeltaGeometry(
        hidden=config.hidden_size, n_q=config.num_attention_heads,
        n_kv=config.num_key_value_heads, head_dim=config.head_dim,
        heads=heads, dk=dk, dv=dv, taps=config.linear_conv_kernel_dim,
        page_size=preset["page_size"], max_seq=preset["max_seq_len"],
        chunk=preset["chunk"], prompt=g["prompt"], dtype=preset["dtype"],
        batches=tuple(preset["batches"]),
    ))
    for rec in out["results"]:
        emit({"kind": "case", **rec})
    emit({"kind": "summary", **device,
          "interpret": sorted(set(out["interpret"])),
          "pallas_calls": len(out["interpret"])})
    emit({"kind": "timed", "rows": timed_paged_decode(
        config.num_attention_heads, config.num_key_value_heads,
        config.head_dim, preset["page_size"], g["lanes"],
        len(config.layers_of("attention")), preset["dtype"],
        **preset.get("timed_decode", {}),
    )})
    emit({"kind": "delta", "rows": timed_delta_rule(
        heads, dk, dv, **g.get("timed_delta", {}))})

    def audit(**kw):
        return pool_audit.audit_programs(
            config, n_pages=g["pages"], page_size=preset["page_size"],
            lanes=g["lanes"], n_steps=g["steps"],
            dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]],
            allow_pallas=jax.default_backend() != "cpu", **kw,
        )

    def join_and_epoch():
        # a join at its width; one group of an epoch's prefill at its own
        rows, width, pages = g["epoch"]
        return {
            **audit(table_pages=g["table_pages"], width=g["join_width"]),
            **audit(
                table_pages=pages, width=width, prefill_rows=rows,
                only=("prefill",),
            ),
        }

    # The same programs with every window's rule as the XLA twin (what a
    # window was before it had a kernel), for their sizes: the mixer asks
    # ``window_in_kernel``, and a traced program is remembered.
    with mock.patch.object(D, "window_in_kernel", lambda *a: False):
        twin = join_and_epoch()
    programs.join_program.cache_clear()
    jax.clear_caches()
    reports = join_and_epoch()
    for name in ("decode", "join"):
        emit({"kind": "program", "program": name, **reports[name]})
    emit({"kind": "sizes", "rows": [
        {"program": name, "form": form, "code_bytes": r[name]["code_bytes"],
         "temp_bytes": r[name]["temp_bytes"], "compile_s": r[name]["seconds"]}
        for name in ("join", "prefill")
        for form, r in (("twin", twin), ("kernel", reports))
    ]})


def child_latent(preset: dict) -> None:
    """A model with latent attention and a share of its experts at the
    benchmark cell's geometry: the absorbed decode kernel against its XLA
    twin and alone on the clock, one sparse layer's routed experts by the
    dense combine and by the grouped path, then its decode chunk and join
    compiled for the device this process holds, from shapes alone."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import pool_audit
    from cake_tpu.models.llama.config import SPARSE, LlamaConfig
    from cake_tpu.ops.pallas.check import (
        run_latent_checks,
        timed_expert_layer,
        timed_latent_decode,
    )
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    device = describe_devices()
    g = preset["latent"]
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas"
    )
    geometry = dict(
        n_heads=config.num_attention_heads, rank=config.kv_lora_rank,
        rope=config.qk_rope_head_dim, page_size=preset["page_size"],
        lanes=g["lanes"], dtype=preset["dtype"],
    )
    out = run_latent_checks(**geometry, table_pages=g["table_pages"])
    for rec in out["results"]:
        emit({"kind": "case", **rec})
    emit({"kind": "summary", **device,
          "interpret": sorted(set(out["interpret"])),
          "pallas_calls": len(out["interpret"])})
    emit({"kind": "timed", "rows": timed_latent_decode(
        **geometry, layers=config.num_hidden_layers, **g.get("timed", {}),
    )})
    emit({"kind": "experts", "rows": timed_expert_layer(
        config.hidden_size, config.moe_intermediate_size,
        config.num_local_experts, config.n_router_experts,
        config.num_experts_per_tok, tuple(g["expert_tokens"]),
        dtype=preset["dtype"], layers=config.ff_kinds.count(SPARSE),
        **({"calls": 2, "repeats": 1} if "timed" in g else {}),
    )})
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=preset["page_size"],
        lanes=g["lanes"], table_pages=g["table_pages"], n_steps=g["steps"],
        width=g["join_width"],
        dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]],
        allow_pallas=jax.default_backend() != "cpu",
    )
    for name, report in reports.items():
        emit({"kind": "program", "program": name, **report})


def child_sparse(preset: dict) -> None:
    """A model whose attention reads the tokens a learned index chooses, at
    the benchmark cell's geometry: one layer's index scores, choice and
    sparse attention against float32, the three alone on the clock at three
    cached lengths, then its decode chunk and a join compiled for the device
    this process holds, from shapes alone."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import pool_audit
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.ops.pallas.check import run_sparse_index_checks, timed_sparse_index
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    device = describe_devices()
    g = preset["sparse"]
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas"
    )
    geometry = dict(
        n_heads=config.num_attention_heads, rank=config.kv_lora_rank,
        rope=config.qk_rope_head_dim, index_heads=config.index_n_heads,
        index_dim=config.index_head_dim, topk=config.index_topk,
        page_size=preset["page_size"], lanes=g["lanes"],
        table_pages=g["table_pages"], dtype=preset["dtype"],
    )
    for rec in run_sparse_index_checks(**geometry):
        emit({"kind": "case", **rec})
    emit({"kind": "summary", **device})
    emit({"kind": "timed", "rows": timed_sparse_index(
        **geometry, layers=config.num_hidden_layers, **g.get("timed", {}),
    )})
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=preset["page_size"],
        lanes=g["lanes"], table_pages=g["table_pages"], n_steps=g["steps"],
        width=g["join_width"],
        dtype={"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]],
        allow_pallas=jax.default_backend() != "cpu",
    )
    for name, report in reports.items():
        emit({"kind": "program", "program": name, **report})


def child_lfm2(preset: dict) -> None:
    """Gated short convolutions beside routed experts at the benchmark cell's
    widths: one period of the stack (a dense layer and three sparse ones, all
    experts held): ``_sparse_hybrid_child``."""
    g = preset["lfm2"]
    _sparse_hybrid_child(preset, g, {
        **g["model"], "num_hidden_layers": 4, "num_dense_layers": 1,
        "layer_types": g["model"]["layer_types"][:4]}, sparse_layers=3)


def child_qwen3next(preset: dict) -> None:
    """Grouped delta-rule heads and gated attention beside a share of
    softmax-routed experts at the benchmark cell's widths: one period of the
    stack (three delta-rule layers at the cell's 64 rows, then an attention
    layer at 2 KV heads of 256 through the pool's write, the window's kernel
    and the decode kernel; four sparse layers of 128 held of 512 beside the
    gated shared expert): ``_sparse_hybrid_child``, with the group program."""
    g = preset["qwen3next"]
    _sparse_hybrid_child(
        preset, g, {**g["model"], "num_hidden_layers": 4}, sparse_layers=4,
        programs=("decode", "join", "join_rows"))


def child_sdar(preset: dict) -> None:
    """Generation by diffusion over blocks at the benchmark cell's widths
    (sdar-30b-a3b-chat-closed): the paged chunk kernel at ``B`` queries a
    lane under the block-causal mask against its gather twin, and alone on
    the clock (microseconds a call, twelve calls a program over the pool's
    layers); one sparse layer's 128 held of 128 at a pass's rows and a
    join's; ``block_layers`` layers of the cut through an epoch's prefill
    and a dispatch of two blocks FOR REAL, by the kernels' programs and by
    their twins'; then the cell's decode dispatch and a join compiled for the
    device this process holds, from shapes alone: the pool neither sliced nor
    copied (ten passes write the same slots), no stacked weight written out
    again a pass (``pool_audit.weight_ops_in_hlo``)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.models.llama import model as M
    from cake_tpu.models.llama import pool_audit
    from cake_tpu.models.llama.batch import block_pass_attention
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.ops.pallas.check import timed_expert_layer
    from cake_tpu.ops.pallas.paged_prefill import (
        paged_chunk_attention, paged_chunk_attention_xla,
    )
    from cake_tpu.runtime.batch_backend import paged_backend
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    emit({"kind": "summary", **describe_devices()})
    g = preset["sdar"]
    on_chip = jax.default_backend() != "cpu"
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]]
    page, lanes = preset["page_size"], g["block_lanes"]
    # (as the cell serves it: the one rule whose states the reference rebuilds)
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas", remask="sequential")
    width, n_q, n_kv, hd = (config.block_length, config.num_attention_heads,
                            config.num_key_value_heads, config.head_dim)

    # (1) the chunk kernel at a pass's shape: every lane ``cached`` tokens
    # behind a left pad of whole blocks, the block's own slots the last
    rng = np.random.default_rng(0)
    table_pages, layers = g["table_pages"], 4
    per_lane = -(-(g["cached"] + 64) // page)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    k_pool, v_pool = (draw(layers, lanes * per_lane, n_kv, page, hd) for _ in range(2))
    q = draw(lanes, width, n_q, hd)
    tables = np.full((lanes, table_pages), -1, np.int32)
    tables[:, :per_lane] = np.arange(lanes * per_lane).reshape(lanes, per_lane)
    pads = jnp.asarray(rng.integers(0, 8, lanes) * width, jnp.int32)
    slot = g["cached"] // width * width
    starts = jnp.full((lanes,), slot, jnp.int32)
    lengths = starts + width
    grid = jnp.arange(table_pages * page, dtype=jnp.int32)[None, :] - pads[:, None]
    k_pos = jnp.where(grid < 0, 2**30, grid)
    q_pos = slot + jnp.arange(width, dtype=jnp.int32)[None, :] - pads[:, None]
    # a pass's call: the block's queries as more heads of the paged DECODE kernel
    kernel = jax.jit(lambda q, li: block_pass_attention(
        q, k_pool, v_pool, lengths, jnp.asarray(tables), pads, layer=li,
        interpret=not on_chip))
    # the chunk kernel itself under the block-causal mask, at the same shape
    chunk = np.asarray(jax.jit(lambda q, li: paged_chunk_attention(
        q, k_pool, v_pool, starts, lengths, pads, jnp.asarray(tables), layer=li,
        block=width, interpret=not on_chip))(q, jnp.int32(1)), np.float32)
    twin = jax.jit(lambda q, li: paged_chunk_attention_xla(
        q, k_pool, v_pool, q_pos, k_pos, jnp.asarray(tables), layer=li, block=width))
    got = np.asarray(kernel(q, jnp.int32(1)), np.float32)
    want = np.asarray(twin(q, jnp.int32(1)), np.float32)
    causal = np.asarray(jax.jit(lambda q, li: paged_chunk_attention_xla(
        q, k_pool, v_pool, q_pos, k_pos, jnp.asarray(tables), layer=li))(q, jnp.int32(1)), np.float32)

    @jax.jit
    def chain(q):
        def call(i, q):
            out = block_pass_attention(
                q, k_pool, v_pool, lengths, jnp.asarray(tables), pads,
                layer=i % layers, interpret=not on_chip)
            return (q + out * 1e-3).astype(q.dtype)
        return jax.lax.fori_loop(0, 12, call, q)

    jax.block_until_ready(chain(q))
    t0 = time.perf_counter()
    for _ in range(5):
        out = chain(q)
    jax.block_until_ready(out)
    emit({"kind": "attention", "lanes": lanes, "cached": g["cached"], "queries": width,
          "err_in_spreads": float(np.abs(got - want).max() / want.std()),
          "chunk_err_in_spreads": float(np.abs(chunk - want).max() / want.std()),
          "causal_differs_by": float(np.abs(causal - want).max() / want.std()),
          "us_a_call": round(1e6 * (time.perf_counter() - t0) / (5 * 12), 1),
          "kv_bytes": int(2 * lanes * g["cached"] * n_kv * hd * jnp.dtype(dtype).itemsize)})
    del k_pool, v_pool, kernel, twin, chain

    # (2) a sparse layer's experts alone on the clock
    emit({"kind": "experts", "rows": timed_expert_layer(
        config.hidden_size, config.moe_intermediate_size,
        config.num_local_experts, config.n_router_experts,
        config.num_experts_per_tok, tuple(g["expert_tokens"]),
        dtype=preset["dtype"], dead_every=3, **g.get("timed", {}),
    )})

    # (3) a prefill and a dispatch of two blocks for real, kernels and twins
    cut = dataclasses.replace(config, num_hidden_layers=g["block_layers"])
    params = M.init_params(cut, jax.random.PRNGKey(0), dtype)
    # (the special ids' head rows zero, as the benchmark's checkpoint has them)
    params["lm_head"] = params["lm_head"].at[:, cut.mask_token_id].set(0)
    rows = [rng.integers(8, min(cut.vocab_size, 100_000), n).tolist() for n in g["prompts"]]
    heads = [p[: len(p) // width * width] for p in rows]
    bucket = -(-max(len(h) for h in heads) // page) * page
    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    steps = g["steps"]

    def served(allow_pallas: bool):
        t0 = time.perf_counter()
        be = paged_backend(
            cut, params, max_seq_len=bucket + page, cache_dtype=dtype, page_size=page,
            max_pages=lanes * (bucket // page + 1), allow_pallas=allow_pallas, lanes=lanes,
        )
        cache = be.init_kv(lanes)
        tokens = np.zeros((lanes, bucket), np.int32)
        pads = np.full((lanes,), bucket, np.int32)
        known = np.full((lanes, width), cut.mask_token_id, np.int32)
        for r, (p, h) in enumerate(zip(rows, heads)):
            pads[r] = bucket - len(h)
            tokens[r, pads[r]:] = h
            known[r, : len(p) - len(h)] = p[len(h):]
            be.allocator.map_range(r, int(pads[r]), bucket + steps)
        _, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
        toks, cache, *_ = be.decode(
            cache, jnp.asarray(known), bucket, jnp.asarray(pads), keys,
            jnp.zeros((lanes, 0), jnp.int32), jnp.zeros((lanes,), jnp.int32),
            steps, greedy,
        )
        counts = be.absorb_chunk_counters(be.take_chunk_counters())
        toks = np.asarray(toks)[:len(rows)]
        first_s = round(time.perf_counter() - t0, 1)
        be.allocator.reset(batch=lanes)  # a second dispatch, dead lanes all: its wall alone
        t0 = time.perf_counter()
        jax.block_until_ready(be.decode(
            cache, jnp.asarray(known), bucket, jnp.asarray(pads), keys,
            jnp.zeros((lanes, 0), jnp.int32), jnp.zeros((lanes,), jnp.int32),
            steps, greedy)[0])
        be.take_chunk_counters()
        return toks, counts, {**be.diffusion_facts()}, first_s, round(
            1e3 * (time.perf_counter() - t0), 1)

    kernel_toks, counts, facts, kernel_s, idle_ms = served(on_chip)
    twin_toks, *_ = served(False)
    tails = [len(p) - len(h) for p, h in zip(rows, heads)]
    # The benchmark's plain reference (float32, full forward passes over the
    # judged STATES, nothing of this program) over the same weights, on what
    # each program served: the judge's reading (``bench/reference.py``).
    from bench.manifest import architecture

    hf = cut.to_hf_dict()
    hf.update(pad_token_id=hf["bos_token_id"])
    arch = architecture(REPO, hf)
    layers = params["layers"]
    names = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
             "wo": "self_attn.o_proj", "router": "mlp.gate"}
    experts = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    norms = {"ln_attn": "input_layernorm", "ln_mlp": "post_attention_layernorm",
             "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm"}

    def reader(name: str):
        """The tree's tensors under HF's names (a plain model's: [in, out]
        matrices transposed, fused projections split by the loader's rule)."""
        if name == "model.embed_tokens.weight":
            return params["embed"]
        if name == "model.norm.weight":
            return params["ln_f"]
        if name == "lm_head.weight":
            return params["lm_head"].T
        _, _, i, rest = name.split(".", 3)
        rest = rest.removesuffix(".weight")
        for key, hf_name in norms.items():
            if rest == hf_name:
                return layers[key][int(i)]
        for key, hf_name in names.items():
            if rest == hf_name:
                return layers[key][int(i)].T
        _, _, e, which = rest.split(".")
        key = next(k for k, v in experts.items() if v == which)
        return layers[key][int(i), int(e)].T

    def deficit(toks):
        served_ids = [toks[r, t:].tolist() for r, t in enumerate(tails)]
        got = arch.state_logits(
            reader, hf, [p + s for p, s in zip(rows, served_ids)], [len(p) - 1 for p in rows])
        each = np.concatenate([arch.deficits(g, s) for g, s in zip(got, served_ids)])
        return float(each.max()), float(each.mean())

    missing = [k for k in ("wq", "wk", "wv", "wo") if k not in layers]
    kernel_deficit = twin_deficit = (None, None)
    if not missing:  # (a fused tree would need the loader's split: not a plain one's)
        kernel_deficit, twin_deficit = deficit(kernel_toks), deficit(twin_toks)
    emit({"kind": "block", "rows": len(rows), "lanes": lanes, "layers": g["block_layers"],
          "tokens_agree": float(np.mean([
              (kernel_toks[r, t:] == twin_toks[r, t:]).mean() for r, t in enumerate(tails)])),
          "known_kept": all(kernel_toks[r, :t].tolist() == rows[r][len(heads[r]):]
                            for r, t in enumerate(tails)),
          "unmasked": bool((kernel_toks != cut.mask_token_id).all()),
          "kernel_deficit": kernel_deficit, "twin_deficit": twin_deficit,
          "counts": counts, "diffusion": {k: facts[k] for k in (
              "blocks", "passes", "commit_passes", "lane_passes", "revealed")},
          "top_k": cut.num_experts_per_tok, "first_pass_s": kernel_s,
          "dead_dispatch_ms": idle_ms})
    del params

    # (4) the cell's programs compiled from shapes
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=page, lanes=g["lanes"],
        table_pages=g["table_pages"], n_steps=steps, width=g["join_width"],
        dtype=dtype, allow_pallas=on_chip, only=("decode", "join"),
        watch=("wq", "wk", "wv", "wo", "w_qkv", "w_gate", "w_up", "w_down", "router"),
    )
    for name, report in reports.items():
        emit({"kind": "program", "program": name, **report})


def _sparse_hybrid_child(preset: dict, g: dict, period_hf: dict, *, sparse_layers: int,
                         programs: tuple[str, ...] = ("decode", "join")) -> None:
    """A hybrid stack with routed experts at a benchmark cell's widths: one
    period of the stack (``period_hf``) through an epoch's prefill and a
    decode chunk FOR REAL, as
    the kernels' programs and as their XLA twins', one sparse layer's routed
    experts alone on the clock by the dense combine and by the grouped path
    (every third row dead, as a dispatch's spare lanes are: the table that
    set ``ops/moe.dispatch_path``'s two numbers), then the cell's decode
    chunk and join compiled for the device this process holds, from shapes
    alone."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cake_tpu.models.llama import hybrid, pool_audit
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.ops.pallas.check import timed_expert_layer
    from cake_tpu.runtime.batch_backend import paged_backend
    from cake_tpu.utils.device import describe_devices, setup_compile_cache

    setup_compile_cache()
    emit({"kind": "summary", **describe_devices()})
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]]
    page, lanes, steps = preset["page_size"], g["block_lanes"], g["steps"]
    period = dataclasses.replace(
        LlamaConfig.from_hf_dict(period_hf), attention_impl="pallas",
    )
    params = hybrid.init_params(period, jax.random.PRNGKey(0), dtype)
    rng = np.random.default_rng(0)
    rows = [rng.integers(8, period.vocab_size, n).tolist() for n in g["prompts"]]
    bucket = -(-max(g["prompts"]) // page) * page
    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)

    def served(allow_pallas: bool):
        """(the prefill's logits, a decode chunk's tokens, seconds) of the
        rows on ``lanes`` lanes, the spare ones dead."""
        t0 = time.perf_counter()
        be = paged_backend(
            period, params, max_seq_len=bucket + page, cache_dtype=dtype,
            page_size=page, max_pages=lanes * (bucket // page + 1),
            allow_pallas=allow_pallas, lanes=lanes,
        )
        cache = be.init_kv(lanes)
        tokens = np.zeros((lanes, bucket), np.int32)
        pads = np.full((lanes,), bucket - 1, np.int32)
        tokens[:, -1] = 1
        for r, ids in enumerate(rows):
            pads[r] = bucket - len(ids)
            tokens[r, pads[r]:] = ids
            be.allocator.map_range(r, int(pads[r]), bucket + steps)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        logits = np.asarray(logits[:len(rows)], np.float32)
        tok = jnp.asarray(np.asarray(logits.argmax(-1), np.int32).tolist()
                          + [1] * (lanes - len(rows)), jnp.int32)
        keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
        toks, cache, *_ = be.decode(
            cache, tok, bucket, jnp.asarray(pads), keys,
            jnp.zeros((lanes, 0), jnp.int32), jnp.zeros((lanes,), jnp.int32),
            steps, greedy,
        )
        counts = be.absorb_chunk_counters(be.take_chunk_counters())
        toks = np.asarray(toks)[:len(rows)]
        return logits, toks, counts, round(time.perf_counter() - t0, 1)

    kernels, kernel_toks, counts, kernel_s = served(jax.default_backend() != "cpu")
    twins, twin_toks, _, twin_s = served(False)
    spread = float(twins.std())
    # The benchmark's plain reference (float32, a full forward pass, nothing
    # of this program) over the same weights, teacher-forced on what the
    # kernels' programs served: the judge's reading (``bench/reference.py``).
    from bench.manifest import architecture
    from cake_tpu.io.safetensors_io import hybrid_tensor_dict

    hf = period.to_hf_dict()
    arch = architecture(REPO, hf)
    tensors = hybrid_tensor_dict(params, period, dtype)
    firsts = kernels.argmax(-1)
    served_ids = [[int(firsts[r]), *kernel_toks[r].tolist()] for r in range(len(rows))]
    # (without ``first_rows``: with them the rows are the judge's, in which
    # every served position reads the call's mean deficit)
    full = [lg[len(p) - 1:] for lg, p in zip(arch.forward_logits(
        tensors.__getitem__, hf, [p + s[:-1] for p, s in zip(rows, served_ids)],
    ), rows)]
    deficits = np.concatenate([
        (lg.max(-1) - lg[np.arange(len(s)), s]) / lg.std(-1)
        for lg, s in zip(full, served_ids)
    ])
    prefill_err = max(
        float(np.abs(kernels[r] - full[r][0]).max() / full[r][0].std())
        for r in range(len(rows))
    )
    emit({"kind": "block", "rows": len(rows), "lanes": lanes,
          "logit_err_in_spreads": float(np.abs(kernels - twins).max() / spread),
          "tokens_agree": float((kernel_toks == twin_toks).mean()),
          "finite": bool(np.isfinite(kernels).all()),
          "reference_deficit_worst": float(deficits.max()),
          "reference_deficit_mean": float(deficits.mean()),
          "reference_positions": int(deficits.size),
          "reference_prefill_err_in_spreads": prefill_err,
          "counts": counts, "kernel_s": kernel_s, "twin_s": twin_s,
          "sparse_layers": sparse_layers, "top_k": period.num_experts_per_tok,
          "held_share": period.num_local_experts / period.n_router_experts,
          "forms": [hybrid.window_form(period, True), hybrid.step_form(period, True)]})
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas"
    )
    del params, tensors  # the layer alone wants the memory
    emit({"kind": "experts", "rows": timed_expert_layer(
        config.hidden_size, config.moe_intermediate_size,
        config.num_local_experts, config.n_router_experts,
        config.num_experts_per_tok, tuple(g["expert_tokens"]),
        dtype=preset["dtype"], dead_every=3, **g.get("timed", {}),
    )})
    reports = pool_audit.audit_programs(
        config, n_pages=g["pages"], page_size=page, lanes=g["lanes"],
        table_pages=g["table_pages"], n_steps=steps, width=g["join_width"],
        dtype=dtype, allow_pallas=jax.default_backend() != "cpu",
        only=programs, **({"join_rows": 3} if "join_rows" in programs else {}),
    )
    for name, report in reports.items():
        emit({"kind": "program", "program": name, **report})


def child_joins(preset: dict) -> None:
    """LFM2's join programs alone on the clock at the cell's published widths
    and geometry (the 16-layer cut's weights drawn on the device, 64 lanes of
    32 pages): one row a program at each width, and a step's joiners together
    as R rows (``_PagedBackend.join_rows``) with 1, 2 and R live rows, the
    others dead. A case is compiled by its first call, then run a few times
    under ``jax.profiler``; its device ms and their parts are read from that
    trace by the benchmark's own reader (``bench/parts.part_seconds``: whole
    runs of ``jit_prefill_join*``, own time by PART scope). The table that
    sets ``runtime/shapes.py``'s ``_DEAR_JOIN_ROWS``, ``_DEAR_JOIN_64THS``
    and ``_DEAR_DEAD_SLOTS``."""
    import dataclasses
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import parts
    from cake_tpu.models.llama import hybrid
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.runtime.batch_backend import paged_backend
    from cake_tpu.utils.device import describe_devices

    # a dozen programs of 10 to 30 MB that nothing serves: they are kept out
    # of the persistent compile cache, which holds 192 MiB on the machine
    # with the chip and is the cells' (PERF.md section 7, row 17)
    jax.config.update("jax_enable_compilation_cache", False)
    emit({"kind": "summary", **describe_devices()})
    g = preset["lfm2"]
    j = g["joins"]
    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[preset["dtype"]]
    page = preset["page_size"]
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict(g["model"]), attention_impl="pallas")
    on_chip = jax.default_backend() != "cpu"
    be = paged_backend(
        config, hybrid.init_params(config, jax.random.PRNGKey(0), dtype),
        max_seq_len=page * g["table_pages"], cache_dtype=dtype, page_size=page,
        max_pages=g["pages"], allow_pallas=on_chip, lanes=g["lanes"],
    )
    cache = be.init_kv(g["lanes"])
    rng = np.random.default_rng(0)

    def timed(rows: int, slots: int, live: int, n_tokens: int):
        """One case: rows ``live`` of ``n_tokens`` tokens that end at slot
        ``slots`` in lanes 0.., the window from slot 0."""
        nonlocal cache
        tokens = np.zeros((rows, slots), np.int32)
        tokens[:live, slots - n_tokens:] = rng.integers(
            8, config.vocab_size, (live, n_tokens))
        pads = [slots - n_tokens] * live + [slots] * (rows - live)
        be.allocator.reset(batch=g["lanes"])
        for lane in range(live):
            be.allocator.map_range(lane, pads[lane], slots)

        def run():
            nonlocal cache
            if rows == 1:
                out, cache = be.join(
                    cache, tokens, jnp.asarray(pads, jnp.int32),
                    jnp.asarray([slots], jnp.int32), 0)
            else:
                lanes = list(range(live)) + [-1] * (rows - live)
                out, cache = be.join_rows(cache, tokens, pads, [slots] * rows, lanes)
            be.take_chunk_counters()
            return out

        t0 = time.perf_counter()
        jax.block_until_ready(run())
        first_s = time.perf_counter() - t0
        with tempfile.TemporaryDirectory(dir=WORK) as trace:
            # a small program at either end: a run that touches the trace's
            # first or last operation counts as cut by the window's edge
            edge = jnp.zeros((8, 128), jnp.float32)
            jax.profiler.start_trace(trace)
            jax.block_until_ready(edge + 1)
            t0 = time.perf_counter()
            for _ in range(j["runs"]):
                out = run()
            jax.block_until_ready(out)
            wall_ms = 1e3 * (time.perf_counter() - t0) / j["runs"]
            jax.block_until_ready(edge + 2)
            jax.profiler.stop_trace()
            files = glob.glob(f"{trace}/plugins/profile/*/*.xplane.pb")
            got = parts.part_seconds(files[0], "^jit_prefill_join") if files else {"runs": 0}
        own = be.shapes.program_width(n_tokens)  # the row's own window
        record = {"kind": "join", "rows": rows, "slots": slots, "live": live,
                  "tokens": n_tokens, "first_s": round(first_s, 1),
                  # whether a step's ``live`` such joiners go as THIS program
                  "rule": "group" if rows == be.shapes.join_rows and len(
                      be.shapes.join_groups([own] * live)) < live and (
                      slots == be.shapes.join_width([own] * live)) else "single",
                  "wall_ms": round(wall_ms, 3), "dev_ms": None, "parts_ms": {}}
        if got["runs"]:
            record["dev_ms"] = round(1e3 * got["program_s"] / got["runs"], 3)
            record["parts_ms"] = {
                part or "unscoped": round(1e3 * s / got["runs"], 2)
                for part, s in got["own_s"].items()}
        emit(record)

    os.makedirs(WORK, exist_ok=True)
    for slots in j["slots"]:
        for n_tokens in j["tokens"][slots]:
            timed(1, slots, 1, n_tokens)
    for rows in j["rows"]:
        for slots in j["slots"]:
            for n_tokens in j["tokens"][slots]:
                for live in sorted({1, 2, rows - 1, rows} - {0}):
                    timed(rows, slots, live, n_tokens)


CHILDREN = {"probe": child_probe, "joins": child_joins, "sparse": child_sparse, "setup": child_setup,
            "lfm2": child_lfm2, "qwen3next": child_qwen3next, "sdar": child_sdar,
            "kernels": child_kernels, "pool": child_pool,
            "hybrid": child_hybrid, "olmo": child_olmo,
            "latent": child_latent}


# ------------------------------------------------------------------- traffic


def _model_dir(preset: dict) -> str:
    return os.path.join(WORK, f"model-{preset['name']}")


def _get(base: str, route: str, timeout: float = 30.0) -> dict:
    with urllib.request.urlopen(base + route, timeout=timeout) as r:
        return json.load(r)


def _wait_quiet(base: str, timeout: float = 20.0) -> None:
    """Until the engine's loop stands still: two reads of ``GET /stats``
    0.3 s apart (two dispatch periods on the chip) count the same periods
    and segments. A server without the account is not waited for."""
    def counts():
        engine = _get(base, "/stats").get("engine") or {}
        return [(engine.get(k) or {}).get("count") for k in ("period", "segment")]

    deadline = time.monotonic() + timeout
    last = counts()
    while last != [None, None] and time.monotonic() < deadline:
        time.sleep(0.3)
        now = counts()
        if now == last:
            return
        last = now


def _post_chat(base: str, messages: list, max_tokens: int) -> dict:
    """One non-streaming completion -> {status, finish_reason, body}."""
    req = urllib.request.Request(
        base + "/api/v1/chat/completions",
        data=json.dumps({
            "messages": messages, "max_tokens": max_tokens, "stream": False,
        }).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=900) as r:
            body = json.load(r)
            return {
                "status": r.status, "body": body,
                "finish_reason": body["choices"][0]["finish_reason"],
                "completion_tokens": body["usage"]["completion_tokens"],
            }
    except urllib.error.HTTPError as e:
        return {"status": e.code, "finish_reason": "error",
                "body": e.read().decode("utf-8", "replace")[:500]}
    except (OSError, ValueError, KeyError) as e:
        return {"status": 0, "finish_reason": "error", "body": repr(e)}


def _stream_chat(base: str, prompt: str, max_tokens: int) -> dict:
    """One streaming completion through the load generator's own client."""
    from cake_tpu.loadgen.client import HttpTarget

    res = HttpTarget(base, timeout_s=900).chat(prompt, max_tokens)
    return {"status": res.status, "finish_reason": res.finish_reason,
            "completion_tokens": res.completion_tokens, "body": res.error}


def _prompt(n: int, salt: str) -> str:
    words = f"{salt} the quick brown fox jumps over the lazy dog and "
    return (words * (n // len(words) + 1))[:n]


def _comparable(body: dict) -> dict:
    return {k: v for k, v in body.items() if k not in ("id", "created")}


def drive_traffic(base: str, preset: dict, shared_prefix: bool) -> dict:
    """The smoke's traffic; returns counts. Raises PhaseFailed on the first
    response that is not a 200 finishing ``stop`` or ``length``."""
    n_new = preset["new_tokens"]
    short, mid, long_ = preset["prompts"]
    results = []

    def check(tag: str, r: dict) -> dict:
        results.append(r)
        if r["status"] != 200 or r["finish_reason"] not in ("stop", "length"):
            raise PhaseFailed(
                f"request {tag}: HTTP {r['status']} finish_reason="
                f"{r['finish_reason']!r} {r.get('body')!r:.400}"
            )
        if not r.get("completion_tokens"):
            raise PhaseFailed(f"request {tag}: no completion tokens")
        return r

    # One request alone, then the same greedy request again: equal bodies.
    alone = [{"role": "user", "content": _prompt(short, "alone")}]
    first = check("alone#1", _post_chat(base, alone, n_new))
    again = check("alone#2", _post_chat(base, alone, n_new))
    if _comparable(first["body"]) != _comparable(again["body"]):
        raise PhaseFailed(
            "the same greedy request returned two different bodies: "
            f"{first['body']['choices']} vs {again['body']['choices']}"
        )

    def concurrently(jobs: dict) -> None:
        out: dict = {}
        threads = [
            threading.Thread(target=lambda t=t, j=j: out.update({t: j()}))
            for t, j in jobs.items()
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for tag in jobs:
            if tag not in out:
                raise PhaseFailed(f"request {tag}: client thread died")
            check(tag, out[tag])

    # Eight at once: three prompt lengths, streaming and not.
    jobs = {}
    for i, n in enumerate((short, mid, long_, short, mid, long_, mid, short)):
        text = _prompt(n, f"mix{i}")
        if i % 2:
            jobs[f"mix{i}/stream/{n}"] = (
                lambda text=text: _stream_chat(base, text, n_new)
            )
        else:
            jobs[f"mix{i}/plain/{n}"] = lambda text=text: _post_chat(
                base, [{"role": "user", "content": text}], n_new
            )
    concurrently(jobs)

    if shared_prefix:
        # Three requests behind one long system prompt, one after another:
        # the first fills the prefix cache, the other two must hit it. A
        # cached chain serves lanes of its own alignment class only (left
        # pad modulo the page), so the questions are of one length and each
        # request starts its own epoch: equal prompts lengths, equal pads.
        system = {"role": "system",
                  "content": _prompt(preset["shared_prefix"], "system")}
        for i, q in enumerate(("question one?", "question two?",
                               "question six?"), start=1):
            # An answer is back before the engine has closed the segment
            # that served it; a request sent in that instant JOINS the
            # segment at another pad and misses (seen here on an idle CPU).
            _wait_quiet(base)
            check(f"prefix#{i}", _post_chat(
                base, [system, {"role": "user", "content": q}], n_new
            ))
    return {"requests": len(results),
            "tokens": sum(r["completion_tokens"] for r in results)}


# -------------------------------------------------------------- server phases


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tail(path: str, n: int = 60) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"(no log: {e})"


def serve_phase(
    name: str, args: argparse.Namespace, preset: dict, extra: list,
    *, expect_impl: str, shared_prefix: bool = False, memory_check=None,
) -> dict:
    """Start the server as a user would, wait for /health, drive the
    traffic, read /stats and /events, stop the server. Returns the phase's
    figures; raises PhaseFailed with the server log's tail."""
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    cmd = [
        sys.executable, "-m", "cake_tpu.cli", "--model", _model_dir(preset),
        "--api", f"127.0.0.1:{port}", "--api-batch", "8",
        "--max-seq-len", str(preset["max_seq_len"]),
        "--temperature", "0", "--repeat-penalty", "1.0", *extra,
    ]
    if args.rehearse_cpu:
        cmd += ["--cpu", "--dtype", preset["dtype"]]
    os.makedirs(os.path.join(WORK, "logs"), exist_ok=True)
    log_path = os.path.join(WORK, "logs", f"{name}.log")
    say(f"phase={name} starting: {' '.join(cmd[1:])}")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=_child_env(args.rehearse_cpu), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
    _state["procs"].append(proc)
    try:
        deadline = time.monotonic() + 900
        health = None
        while health is None:
            if proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited with code {proc.returncode} before "
                    "/health answered"
                )
            if time.monotonic() > deadline:
                raise PhaseFailed("server did not answer /health in 900 s")
            try:
                health = _get(base, "/health", timeout=5)
            except (OSError, ValueError):
                time.sleep(1.0)
        if health.get("platform") != _state["platform"]:
            raise PhaseFailed(
                f"/health says platform={health.get('platform')!r}, the "
                f"probe saw {_state['platform']!r}"
            )
        if health.get("attention_impl") != expect_impl:
            raise PhaseFailed(
                f"/health says attention_impl="
                f"{health.get('attention_impl')!r}, expected {expect_impl!r}"
            )
        loaded = _get(base, "/stats")["memwatch"]["devices"]
        counts = drive_traffic(base, preset, shared_prefix)
        stats = _get(base, "/stats")
        events = _get(base, "/events")["events"]
        engine = stats["engine"]
        if engine.get("stream_errors"):
            raise PhaseFailed(
                f"/stats engine.stream_errors = {engine['stream_errors']}"
            )
        fallbacks = [e for e in events if e.get("event") == "kernel-fallback"]
        if fallbacks:
            raise PhaseFailed(f"kernel-fallback events: {fallbacks}")
        if shared_prefix and engine.get("prefix_hits", 0) < 2:
            raise PhaseFailed(
                f"prefix_hits = {engine.get('prefix_hits')} after three "
                "requests behind one system prompt, expected >= 2"
            )
        if proc.poll() is not None:
            raise PhaseFailed(f"server died (code {proc.returncode})")
        devices = stats["memwatch"]["devices"]
        if memory_check is not None:
            # The peak after load is where a whole copy passing through the
            # first chip shows; in-use, where one that stayed does.
            memory_check(loaded, "peak_bytes_in_use", "at load")
            memory_check(loaded, "bytes_in_use", "after load")
            memory_check(devices, "bytes_in_use", "after traffic")
        return {
            **{k: health[k] for k in
               ("platform", "device_kind", "device_count", "jax_version")},
            "attention_impl": health["attention_impl"], **counts,
            "compile_s": stats["compile"]["seconds"],
            "compiles": stats["compile"]["count"],
            "prefix_hits": engine.get("prefix_hits"),
            "loaded_bytes_in_use": [d.get("bytes_in_use") for d in loaded],
            "loaded_peak_bytes": [d.get("peak_bytes_in_use") for d in loaded],
            "bytes_in_use": [d.get("bytes_in_use") for d in devices],
            "peak_bytes_in_use": [d.get("peak_bytes_in_use") for d in devices],
        }
    except PhaseFailed as e:
        raise PhaseFailed(
            f"{e}\n--- last lines of {log_path} ---\n{_tail(log_path)}"
        ) from None
    finally:
        _kill(proc)  # the chip is free again once it has exited
        _state["procs"].remove(proc)


def _spread_check(tolerance: float):
    """--tp: the chips hold the same, within ``tolerance`` of the fullest."""

    def check(devices: list, key: str, when: str) -> None:
        used = [d[key] for d in devices if key in d]
        if used and (max(used) - min(used)) > tolerance * max(used):
            raise PhaseFailed(
                f"{when}: {key} per device {used} differ by more than "
                f"{tolerance:.0%}: the model is not spread evenly"
            )

    return check


def _stage_check(preset: dict, n_stages: int):
    """Pipeline: no chip holds more than its stage plus embedding and head
    (a quarter over, plus the KV strips of eight lanes and the serialized
    generator's one)."""
    m = preset["model"]
    item = 2 if preset["dtype"] == "bf16" else 4
    h, inter = m["hidden_size"], m["intermediate_size"]
    qd = m["num_attention_heads"] * m["head_dim_override"]
    kd = m["num_key_value_heads"] * m["head_dim_override"]
    layer = (2 * h * qd + 2 * h * kd + 3 * h * inter + 2 * h) * item
    per_stage = m["num_hidden_layers"] // n_stages
    head = (2 * m["vocab_size"] * h + h) * item
    kv = 2 * per_stage * 9 * kd * preset["max_seq_len"] * item
    bound = int(1.25 * (per_stage * layer + head)) + kv

    def check(devices: list, key: str, when: str) -> None:
        used = [d[key] for d in devices if key in d]
        if used and max(used) > bound:
            raise PhaseFailed(
                f"{when}: {key} per device {used}: a chip holds more than "
                f"its stage plus embedding/head ({bound} bytes)"
            )

    return check


# ---------------------------------------------------------------------- main


def phase_native(args, preset) -> dict:
    """The .so files are not in git; whatever sits on disk is stale by
    definition. Rebuild from codec.cpp / embed.c, or serve without."""
    native = os.path.join(REPO, "cake_tpu", "native")
    for f in os.listdir(native):
        if f.endswith(".so"):
            os.remove(os.path.join(native, f))
    proc = subprocess.run(
        [sys.executable, "-m", "cake_tpu.native.build"], cwd=REPO,
        env=_child_env(True), capture_output=True, text=True, timeout=300,
    )
    built = sorted(f for f in os.listdir(native) if f.endswith(".so"))
    if proc.returncode != 0 or not built:
        say("native build did not produce the libraries; the Python codec "
            f"serves. {proc.stderr.strip()[-300:]}")
    return {"built": built}


def _say_timed_decode(phase: str, records: list, args) -> None:
    """``check.timed_paged_decode``'s rows; a time is a device's, so the
    rehearsal walks the path and prints none."""
    if args.rehearse_cpu:
        return
    for r in next(r for r in records if r["kind"] == "timed")["rows"]:
        say(f"phase={phase} paged_decode_attention alone, "
            f"table_pages={r['table_pages']}: {r['full_us']} us a call with "
            f"every page live, {r['live_us']} us at {r['live_tokens']} live "
            "tokens a lane")


def phase_kernels(args, preset) -> dict:
    records = run_child("kernels", args, timeout=900)
    cases = [r for r in records if r["kind"] == "case"]
    summary = next(r for r in records if r["kind"] == "summary")
    by_kernel: dict = {}
    for c in cases:
        k = by_kernel.setdefault(
            c["kernel"], {"cases": 0, "failed": [], "max_err": 0.0, "s": 0.0}
        )
        k["cases"] += 1
        k["s"] += c.get("first_call_s", 0.0)
        k["max_err"] = max(k["max_err"], c.get("max_err", 0.0))
        if not c["ok"]:
            k["failed"].append(c)
    for name, k in by_kernel.items():
        say(f"phase=C kernel={name} cases={k['cases']} "
            f"failed={len(k['failed'])} max_err_vs_twin={k['max_err']:.3g} "
            f"first_calls_s={k['s']:.1f}")
        for c in k["failed"]:
            say(f"phase=C   FAILED {name} {c['case']}: "
                f"{c.get('error') or 'max_err %.3g > tol %.3g' % (c['max_err'], c['tol'])}")
    _say_timed_decode("C", records, args)
    if not args.rehearse_cpu:  # a time is a device's
        for r in next(r for r in records if r["kind"] == "write")["rows"]:
            say(f"phase=C paged_write_pool alone, {r['rows']} rows x "
                f"{r['kv_heads']} KV heads x {r['width']} slots: kernel "
                f"{r['kernel_us']} us a call, scatter {r['twin_us']} "
                f"({r['head_rows']} [head_dim] rows a call)")
    chain = summary["matmul"]
    if summary["peak_tflops"]:  # a rate is a device's; the cpu gets none
        say(f"phase=C bf16 matmul chain on {summary['device_kind']}: "
            f"{chain['flops'] / 1e12:.1f} TFLOP in "
            f"{chain['elapsed_s'] * 1e3:.1f} ms = "
            f"{chain['flops'] / chain['elapsed_s'] / 1e12:.1f} TFLOP/s of "
            f"{summary['peak_tflops']} peak")
    problems = [f"{len(k['failed'])} case(s) of {n} failed"
                for n, k in by_kernel.items() if k["failed"]]
    want_interpret = [args.rehearse_cpu]
    if summary["interpret"] != want_interpret:
        problems.append(
            f"pallas_call was traced with interpret={summary['interpret']}, "
            f"expected {want_interpret}"
        )
    if not chain["finite"]:
        problems.append("the matmul chain produced non-finite values")
    if summary["peak_tflops"]:
        floor = chain["flops"] / (summary["peak_tflops"] * 1e12)
        if chain["elapsed_s"] < floor:
            problems.append(
                f"matmul chain took {chain['elapsed_s']:.4f} s, less than "
                f"FLOPs/peak = {floor:.4f} s: the host clock around "
                "block_until_ready cannot be trusted here"
            )
    else:
        say("phase=C no peak table entry on the cpu: clock check not run")
    if problems:
        raise PhaseFailed("; ".join(problems))
    return {
        **{k: summary[k] for k in
           ("platform", "device_kind", "device_count", "jax_version")},
        "compile_s": round(summary["compile"]["seconds"], 1),
        "compiles": summary["compile"]["count"],
        "kernels": len(by_kernel), "cases": len(cases),
    }


def phase_pool(args, preset) -> dict:
    out, problems = {}, []
    for r in run_child("pool", args, timeout=900):
        moved = r["scans"] + r["pool_ops"]
        say(f"phase=P program={r['program']} temp_bytes={r['temp_bytes']} "
            f"pool_bytes={r['pool_bytes']} pool_moving_ops={len(moved)} "
            f"compile_s={r['seconds']}")
        for m in moved:
            say(f"phase=P   {r['program']} moves the pool: {m}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move the pool")
        if r["temp_bytes"] is None:
            say(f"phase=P   {r['platform']} gives no memory analysis")
        elif r["temp_bytes"] >= r["pool_bytes"]:
            problems.append(
                f"{r['program']}: {r['temp_bytes']} B of temporaries, one "
                f"pool is {r['pool_bytes']} B"
            )
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
        out[f"{r['program']}_pool_ops"] = len(moved)
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_hybrid(args, preset) -> dict:
    """Phase H: a model with state layers at the benchmark cell's geometry
    (jamba2-3b-chat-closed): kernel cases, the prefill scan's kernel and the
    one-token update's beside their twins, then the compiled programs."""
    records = run_child("hybrid", args, timeout=1200)
    problems = _say_cases("H", records, args)
    _say_timed_decode("H", records, args)
    for r in next(r for r in records if r["kind"] == "scan")["rows"]:
        # a time is a device's: the rehearsal prints the errors alone
        times = "" if args.rehearse_cpu else (
            f": kernel {r['kernel_us']} us a call, XLA twin {r['twin_us']}")
        say(f"phase=H selective_scan alone, {r['rows']} x {r['length']} "
            f"({r['live']} live) err_y={r['err_y']:.3g} "
            f"err_s={r['err_s']:.3g}{times}")
        if max(r["err_y"], r["err_s"]) > 1e-4:
            problems.append(
                f"selective_scan {r['rows']} x {r['length']} differs from "
                "its twin")
    for r in next(r for r in records if r["kind"] == "step")["rows"]:
        times = "" if args.rehearse_cpu else (
            f": twin {r['twin_us']} us a call, kernel {r.get('kernel_us')} "
            f"for a floor of {r['floor_us']}")
        errs = "".join(f" {k}={r[k]:.3g}" for k in ("err_y", "err_s") if k in r)
        say(f"phase=H selective_step alone, {r['rows']} rows{errs}{times}")
        if max(r.get("err_y", 0.0), r.get("err_s", 0.0)) > 1e-4:
            problems.append("selective_step differs from its twin")
    out = {"cases": sum(r["kind"] == "case" for r in records)}
    out.update(_say_programs("H", records, args, problems))
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def _say_cases(phase: str, records: list, args) -> list:
    """A hybrid child's kernel cases, one line each; the problems found."""
    summary = next(r for r in records if r["kind"] == "summary")
    problems = []
    for c in (r for r in records if r["kind"] == "case"):
        say(f"phase={phase} kernel={c['kernel']} {c['case']}: "
            + (f"max_err={c['max_err']:.3g} of tol {c['tol']:.3g} "
               f"first_call_s={c['first_call_s']}" if "max_err" in c
               else f"FAILED {c.get('error')}"))
        if not c["ok"]:
            problems.append(f"{c['kernel']} {c['case']}")
    if summary["interpret"] != [args.rehearse_cpu]:
        problems.append(
            f"pallas_call was traced with interpret={summary['interpret']}")
    return problems


def _say_programs(phase: str, records: list, args, problems: list,
                  counts=lambda op: True) -> dict:
    """A hybrid child's compiled programs (``pool_audit.audit_hybrid_
    programs``): what moves the pool or the state is a problem; a state copy
    that ``counts`` does not take is said beside it (``window_copies``)."""
    out = {}
    for r in (r for r in records if r["kind"] == "program"):
        copies = [op for op in r["state_copies"] if counts(op)]
        # What the CPU's compiler copies says nothing of the chip's layouts:
        # the rehearsal holds the programs to their jaxprs alone.
        compiled = [] if args.rehearse_cpu else r["pool_ops"] + copies
        moved = r["scans"] + r["state_scans"] + compiled
        say(f"phase={phase} program={r['program']} temp_bytes={r['temp_bytes']} "
            f"pool_bytes={r['pool_bytes']} state_bytes={r['state_bytes']} "
            f"kernels={r['kernels']} moving_ops={len(moved)} "
            f"window_copies={len(r['state_copies']) - len(copies)} "
            f"compile_s={r['seconds']}")
        for m in moved:
            say(f"phase={phase}   {r['program']} moves the pool or the state: {m}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move the "
                            "pool or the state")
        if (not args.rehearse_cpu and r["temp_bytes"] is not None
                and r["temp_bytes"] >= r["state_bytes"]):
            problems.append(f"{r['program']}: {r['temp_bytes']} B of "
                            f"temporaries, the state is {r['state_bytes']} B")
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
    return out


def phase_olmo(args, preset) -> dict:
    """Phase O: a model whose state layers run the gated delta rule at the
    benchmark cell's geometry (olmo-hybrid-7b-chat-closed): kernel cases, the
    rule's two forms alone on the clock, then the compiled programs."""
    records = run_child("olmo", args, timeout=1800)
    problems = _say_cases("O", records, args)
    _say_timed_decode("O", records, args)
    for r in next(r for r in records if r["kind"] == "delta")["rows"]:
        forms = [k[:-3] for k in r if k.endswith("_us")]
        # a time is a device's: the rehearsal prints the errors alone
        times = "" if args.rehearse_cpu else ": " + ", ".join(
            f"{f} {r[f + '_us']} us a call" for f in forms)
        kinds = ("err_o", "err_s", "kernel_err_o", "kernel_err_s")
        errs = " ".join(f"{k}={r[k]:.3g}" for k in kinds if k in r)
        live = f" ({r['live']} live)" if "live" in r else ""
        say(f"phase=O {r['op']} alone, {r['rows']} x {r['length']}{live} "
            f"{errs}{times}")
        # float32 both sides; the chunkwise form's algebra sums in another
        # order, the kernel's products are three bfloat16 passes
        if max(r.get(k, 0.0) for k in kinds) > 1e-4:
            problems.append(
                f"{r['op']} {r['rows']} x {r['length']} differs from the "
                "stepwise rule")
        # the step's kernel passes over a dead row: state as it was, o zero
        if r.get("dead_rows_moved"):
            problems.append(
                f"{r['op']} {r['rows']} rows, {r['live']} live: "
                f"{r['dead_rows_moved']} dead rows did not keep their state")
    out = {"cases": sum(r["kind"] == "case" for r in records)}
    # The convolution's window (bf16, 26 MB) changes layout at a decode
    # program's two ends; the float32 state must not be copied at all.
    out.update(_say_programs("O", records, args, problems,
                             counts=lambda op: "f32[" in op))
    # a join and one group of an epoch's prefill, every window's rule as the
    # XLA twin and as the kernel (the same where the widths do not tile)
    for r in next(r for r in records if r["kind"] == "sizes")["rows"]:
        say(f"phase=O program={r['program']} rule={r['form']} "
            f"code_bytes={r['code_bytes']} temp_bytes={r['temp_bytes']} "
            f"compile_s={r['compile_s']}")
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def say_experts(phase: str, records: list, where: str) -> list[str]:
    """A child's ``timed_expert_layer`` rows, one a line with the path the
    rule takes at that shape; the sizes at which the two paths' results
    differ, as problems."""
    problems = []
    for row in next(r for r in records if r["kind"] == "experts")["rows"]:
        say(f"phase={phase} routed experts{where}, tokens={row['tokens']} "
            f"live={row['live']}: rule={row['rule']} "
            f"dense_ms={row.get('dense_ms')} grouped_ms={row.get('grouped_ms')} "
            f"max_diff_in_stds={row.get('max_diff_in_stds')}")
        if row.get("max_diff_in_stds", 0.0) > 0.1:
            problems.append(f"dense and grouped experts differ at {row['tokens']} tokens")
    return problems


def say_weight_ops(phase: str, r: dict, args) -> list[str]:
    """A compiled program's operations that write a watched weight out again
    (``pool_audit.weight_ops_in_hlo``: the queries' up-projection, the
    index's), with the offending operation's own text. A problem in the
    DECODE chunk, where a layer's weights are the step (PR 56: 75 MB laid
    out again a layer-step); a window's program runs a layer once, is bound
    by its products, and is only said. The CPU's compiler lays weights out
    otherwise: the rehearsal says nothing."""
    if args.rehearse_cpu:
        return []
    for f in r["weight_ops"]:
        say(f"phase={phase}   {r['program']} writes a weight out again: {f['op']}")
        if r["program"] == "decode":
            say(f["text"])
    if r["program"] == "decode" and r["weight_ops"]:
        return [f"decode: {len(r['weight_ops'])} op(s) write a layer's query projection out again"]
    return []


def phase_latent(args, preset) -> dict:
    """Phase L: a model with latent attention and a share of its experts at
    the benchmark cell's geometry (pangu-ultra-ep16-chat-closed): the
    absorbed decode kernel's cases and its time alone, one sparse layer by
    the dense combine and by the grouped path, then the compiled programs."""
    records = run_child("latent", args, timeout=1800)
    summary = next(r for r in records if r["kind"] == "summary")
    problems = []
    for c in (r for r in records if r["kind"] == "case"):
        say(f"phase=L kernel={c['kernel']} {c['case']}: "
            + (f"max_err={c['max_err']:.3g} of tol {c['tol']:.3g} "
               f"first_call_s={c['first_call_s']}" if "max_err" in c
               else f"FAILED {c.get('error')}"))
        if not c["ok"]:
            problems.append(f"{c['kernel']} {c['case']}")
    if summary["interpret"] != [args.rehearse_cpu]:
        problems.append(
            f"pallas_call was traced with interpret={summary['interpret']}")
    where = "" if not args.rehearse_cpu else " (cpu rehearsal: no device time)"
    for row in next(r for r in records if r["kind"] == "timed")["rows"]:
        say(f"phase=L latent_decode_attention alone{where}, "
            f"table_pages={row['table_pages']}: full_us={row['full_us']} "
            f"live_us={row['live_us']} (live_tokens={row['live_tokens']})")
    problems += say_experts("L", records, where)
    out = {"cases": sum(r["kind"] == "case" for r in records)}
    for r in (r for r in records if r["kind"] == "program"):
        moved = r["scans"] + ([] if args.rehearse_cpu else r["pool_ops"])
        say(f"phase=L program={r['program']} temp_bytes={r['temp_bytes']} "
            f"argument_bytes={r['argument_bytes']} pool_bytes={r['pool_bytes']} "
            f"kernels={r['kernels']} moving_ops={len(moved)} "
            f"weight_ops={'unread' if args.rehearse_cpu else len(r['weight_ops'])} compile_s={r['seconds']}")
        for m in moved:
            say(f"phase=L   {r['program']} moves the pool: {m}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move the pool")
        problems += say_weight_ops("L", r, args)
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_sparse(args, preset) -> dict:
    """Phase S: a learned index over the cached tokens and latent attention
    over the tokens it chooses, at the benchmark cell's geometry
    (deepseek-v32-ep16-longdoc-closed): the case against float32, the three
    pieces' times alone beside the work's floor, then the compiled programs.
    ``sparse_us`` must not follow the cached length: its bytes are the chosen
    tokens'."""
    from bench.manifest import architecture

    records = run_child("sparse", args, timeout=1800)
    g = preset["sparse"]
    arch = architecture(REPO, g["model"])
    problems = []
    for c in (r for r in records if r["kind"] == "case"):
        say(f"phase=S kernel={c['kernel']} {c['case']} scores={c.get('form')}: "
            + (f"max_err={c['max_err']:.3g} of tol {c['tol']:.3g} "
               f"score_err_in_spreads={c['score_err_in_spreads']:.3g} "
               f"chosen_overlap min={c['chosen_overlap_min']:.4f} "
               f"mean={c['chosen_overlap_mean']:.4f} first_call_s={c['first_call_s']}"
               if "max_err" in c else f"FAILED {c.get('error')}"))
        if not c["ok"]:
            problems.append(f"{c['kernel']} {c['case']}")
    where = "" if not args.rehearse_cpu else " (cpu rehearsal: no device time)"
    rows = next(r for r in records if r["kind"] == "timed")["rows"]
    topk = g["model"]["index_topk"]
    for row in rows:
        ops, moved = arch.index_scores_cost(
            g["model"], row["live_rows"], row["scanned_tokens"], preset["dtype"])
        scores_floor = max(ops / 197e12, moved / 819e9) * 1e6
        ops, moved = arch.sparse_attention_cost(
            g["model"], row["live_rows"], row["chosen_tokens"], preset["dtype"])
        sparse_floor = max(ops / 197e12, moved / 819e9) * 1e6
        say(f"phase=S alone{where}, cached_tokens={row['cached_tokens']} a row "
            f"(live_rows={row['live_rows']} scanned={row['scanned_tokens']}): "
            f"scores_us={row['scores_us']} ({row['scores_form']}, floor {scores_floor:.1f}) "
            f"select_us={row['select_us']} ({row['select_form']}) select_set_err={row['select_set_err']} "
            f"sparse_us={row['sparse_us']} (floor {sparse_floor:.1f})")
    wrong = {row["cached_tokens"]: row["select_set_err"] for row in rows if row["select_set_err"]}
    if wrong:
        problems.append(f"the choice is not a stable argsort's set: rows wrong by cached_tokens {wrong}")
    uniform = [row for row in rows if row["cached_tokens"] != "mixed"]
    sparse = [row["sparse_us"] for row in uniform if row["cached_tokens"] >= topk]
    if not args.rehearse_cpu and sparse and max(sparse) > 1.3 * min(sparse):
        problems.append(f"sparse attention follows the cached length: {sparse} us")
    # The scores' kernel walks a row's live pages: its time follows what the
    # rows hold (the XLA form's was flat: it gathered the whole table).
    shortest, longest = uniform[0], uniform[-1]
    if not args.rehearse_cpu and (
        longest["cached_tokens"] >= 4 * shortest["cached_tokens"]
        and shortest["scores_us"] > 0.5 * longest["scores_us"]
    ):
        problems.append(
            f"the index scores do not follow the cached length: {shortest['scores_us']} us at "
            f"{shortest['cached_tokens']} tokens a row, {longest['scores_us']} at "
            f"{longest['cached_tokens']}")
    out = {"cases": sum(r["kind"] == "case" for r in records)}
    for r in (r for r in records if r["kind"] == "program"):
        moved = r["scans"] + ([] if args.rehearse_cpu else r["pool_ops"])
        say(f"phase=S program={r['program']} temp_bytes={r['temp_bytes']} "
            f"argument_bytes={r['argument_bytes']} pool_bytes={r['pool_bytes']} "
            f"code_bytes={r['code_bytes']} kernels={r['kernels']} "
            f"moving_ops={len(moved)} weight_ops={'unread' if args.rehearse_cpu else len(r['weight_ops'])} compile_s={r['seconds']}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move a pool")
        problems += say_weight_ops("S", r, args)
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_lfm2(args, preset) -> dict:
    """Phase F: gated short convolutions beside routed experts, all held, at
    the benchmark cell's geometry (lfm2-8b-a1b-chat-closed): one period of
    the stack served for real by the kernels' programs and by their twins',
    then the compiled programs."""
    return _phase_sparse_hybrid("F", "lfm2", args, preset)


def phase_qwen3next(args, preset) -> dict:
    """Phase Q: grouped delta-rule heads and gated attention on heads of 256
    beside 128 held of 512 softmax-routed experts and a gated shared one, at
    the benchmark cell's geometry (qwen3-next-ep4-chat-closed): one period of
    the stack served for real by the kernels' programs (both delta kernels,
    the three paged kernels at a group of 8 query heads) and by their twins',
    then the compiled programs, the group of three joining rows among them
    (the float32 state placed a row at a time, never copied whole)."""
    return _phase_sparse_hybrid("Q", "qwen3next", args, preset)


def _phase_sparse_hybrid(phase: str, key: str, args, preset) -> dict:
    records = run_child(key, args, timeout=2400)
    problems = []
    b = next(r for r in records if r["kind"] == "block")
    c = b["counts"]
    say(f"phase={phase} one period at the cell's widths, {b['rows']} rows on {b['lanes']} lanes: "
        f"kernels against twins logit_err_in_spreads={b['logit_err_in_spreads']:.3g} "
        f"tokens_agree={b['tokens_agree']:.3f}; against the plain float32 reference over "
        f"{b['reference_positions']} served positions deficit worst="
        f"{b['reference_deficit_worst']:.3g} mean={b['reference_deficit_mean']:.3g} "
        f"prefill_logit_err_in_spreads={b['reference_prefill_err_in_spreads']:.3g}; "
        f"dispatches={c['dispatches']} "
        f"held={c['held']} touched={c['touched']} "
        f"first_pass_s={b['kernel_s']} twins_s={b['twin_s']}")
    # The served type on both sides: the kernels' online softmax and the
    # grouped products sum in another order than their twins. Jamba's and
    # Pangu's sound reads against float32 are 0.05 to 0.23 of a spread; a
    # fault in the packed pool's rows (two KV heads of 64 side by side) or in
    # a window's taps moves a logit by whole spreads.
    if not b["finite"] or b["logit_err_in_spreads"] > 0.25:
        problems.append(
            f"the kernels' programs differ from their twins' by "
            f"{b['logit_err_in_spreads']:.3g} of a logit spread")
    # Against the plain float32 reference the period read 0.074 at its worst
    # of 18 served positions (0.004 on average; PR 48's call 3): three sparse
    # layers turn few of the router's near-ties. The whole cut's 14 turn so
    # many that its judge tells nothing (``judge.why`` in the configuration);
    # a fault in the path moves a position here by whole spreads.
    if not b["reference_deficit_worst"] <= 0.5:
        problems.append(
            f"one period differs from the plain reference by "
            f"{b['reference_deficit_worst']:.3g} of a logit spread at its worst position")
    steps = preset[key]["steps"]
    want = steps * b["sparse_layers"] * b["rows"] * b["top_k"]
    # a share holds its part of what the router deals: all of it where every
    # expert is held, about ``held_share`` of it otherwise (within a half)
    share = b.get("held_share", 1.0)
    held_ok = c["held"] == want if share == 1.0 else 0.5 * share * want <= c["held"] <= 1.5 * share * want
    if not held_ok or c["routed"] != want:
        problems.append(
            f"the decode chunk counted {c['held']} held assignments of "
            f"{c['routed']} routed; {want} live ones were made (dead lanes take none), "
            f"a share of {share:g} held")
    if not args.rehearse_cpu and key == "qwen3next" and b["forms"] != ["pallas", "pallas"]:
        problems.append(f"the delta rule's window and step run as {b['forms']}, not the kernels")
    out = {"block_logit_err_in_spreads": b["logit_err_in_spreads"]}
    where = "" if not args.rehearse_cpu else " (cpu rehearsal: no device time)"
    problems += say_experts(phase, records, where)
    for r in (r for r in records if r["kind"] == "program"):
        # (what the CPU's compiler copies says nothing of the chip's layouts)
        compiled = [] if args.rehearse_cpu else r["pool_ops"] + [
            # the bf16 window changes layout at a program's two ends (Olmo-Hybrid's
            # ``window_copies``, 28 MB here); a copy of the float32 state is a fault
            m for m in r["state_copies"] if " f32[" in m or key == "lfm2"]
        moved = r["scans"] + r["state_scans"] + compiled
        say(f"phase={phase} program={r['program']} temp_bytes={r['temp_bytes']} "
            f"argument_bytes={r['argument_bytes']} pool_bytes={r['pool_bytes']} "
            f"state_bytes={r['state_bytes']} code_bytes={r['code_bytes']} "
            f"kernels={r['kernels']} moving_ops={len(moved)} compile_s={r['seconds']}")
        for m in moved:
            say(f"phase={phase}   {r['program']} moves the pool or a window: {m}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move the pool or a window")
        # the windows are 6 MB: the temporaries are held to the pool (2.1 GB)
        if (not args.rehearse_cpu and r["temp_bytes"] is not None
                and r["temp_bytes"] >= r["pool_bytes"]):
            problems.append(f"{r['program']}: {r['temp_bytes']} B of temporaries, "
                            f"the pool is {r['pool_bytes']} B")
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_sdar(args, preset) -> dict:
    """Phase M: generation by diffusion over blocks at the benchmark cell's
    geometry (sdar-30b-a3b-chat-closed): ``child_sdar``'s four readings."""
    records = run_child("sdar", args, timeout=2400)
    problems = []
    a = next(r for r in records if r["kind"] == "attention")
    say(f"phase=M the paged chunk kernel at {a['queries']} queries a lane, {a['lanes']} lanes x "
        f"{a['cached']} cached tokens, block-causal: against its twin "
        f"err_in_spreads={a['err_in_spreads']:.3g}, the chunk kernel's "
        f"{a['chunk_err_in_spreads']:.3g} (the causal mask differs by "
        f"{a['causal_differs_by']:.3g}); us_a_call={a['us_a_call']} kv_bytes={a['kv_bytes']}")
    if not max(a["err_in_spreads"], a["chunk_err_in_spreads"]) <= 0.08 or not (
            a["causal_differs_by"] > 0.2):
        problems.append(
            f"the chunk kernel under the block-causal mask differs from its twin by "
            f"{a['err_in_spreads']:.3g} of a spread (from the causal mask by "
            f"{a['causal_differs_by']:.3g})")
    where = "" if not args.rehearse_cpu else " (cpu rehearsal: no device time)"
    problems += say_experts("M", records, where)
    b = next(r for r in records if r["kind"] == "block")
    c, d = b["counts"], b["diffusion"]
    say(f"phase=M {b['layers']} layers at the cell's widths, {b['rows']} rows on {b['lanes']} "
        f"lanes, a dispatch of two blocks: kernels against twins tokens_agree="
        f"{b['tokens_agree']:.3f} known_kept={b['known_kept']} unmasked={b['unmasked']}; "
        f"against the plain float32 reference's states (worst, mean deficit) kernels "
        f"{b['kernel_deficit']} twins {b['twin_deficit']}; "
        f"passes={d['passes']} commit_passes={d['commit_passes']} blocks={d['blocks']} "
        f"lane_passes={d['lane_passes']} revealed={d['revealed']} held={c['held']} "
        f"touched={c['touched']} first_pass_s={b['first_pass_s']} "
        f"dead_dispatch_ms={b['dead_dispatch_ms']}")
    passes = 2 * (preset["sdar"]["model"].get("denoising_steps", 4) + 1)
    want = passes * b["layers"] * b["rows"] * 4 * b["top_k"]
    if c["held"] != want or c["routed"] != want:
        problems.append(f"the dispatch counted {c['held']} held assignments of {c['routed']} "
                        f"routed; {want} live ones were made (dead lanes take none)")
    if (d["passes"], d["commit_passes"], d["blocks"], d["lane_passes"]) != (
            passes, 2, 2 * b["rows"], passes * b["rows"]):
        problems.append(f"the dispatch's account is {d}")
    # Two programs in the served type need not choose the same token where two
    # logits lie within their rounding; each must lie near the float32
    # reference's best (a fault in the mask, the fold of a block's queries into
    # heads or the commit moves a position by whole spreads).
    if not (b["known_kept"] and b["unmasked"]):
        problems.append(f"known_kept={b['known_kept']} unmasked={b['unmasked']}")
    for who in ("kernel_deficit", "twin_deficit"):
        if b[who][0] is not None and not b[who][0] <= 0.5:
            problems.append(f"{who}: {b[who][0]:.3g} of a logit spread from the plain "
                            "reference at its worst position")
    out = {"tokens_agree": b["tokens_agree"], "chunk_kernel_us": a["us_a_call"]}
    for r in (r for r in records if r["kind"] == "program"):
        moved = r["scans"] + ([] if args.rehearse_cpu else r["pool_ops"])
        say(f"phase=M program={r['program']} temp_bytes={r['temp_bytes']} "
            f"argument_bytes={r['argument_bytes']} pool_bytes={r['pool_bytes']} "
            f"code_bytes={r['code_bytes']} kernels={r['kernels']} "
            f"grouped_products={r['grouped_products']} pool_writes={r['pool_writes']} "
            f"moving_ops={len(moved)} weight_ops={len(r['weight_ops'])} compile_s={r['seconds']}")
        for m in moved:
            say(f"phase=M   {r['program']} moves the pool: {m}")
        if moved:
            problems.append(f"{r['program']}: {len(moved)} op(s) move the pool")
        if not args.rehearse_cpu:
            for f in r["weight_ops"]:
                say(f"phase=M   {r['program']} writes a weight out again: {f['op']}")
            if r["program"] == "decode" and r["weight_ops"]:
                say(r["weight_ops"][0]["text"])
                problems.append(f"decode: {len(r['weight_ops'])} op(s) lay a weight out again a pass")
        out[f"{r['program']}_temp_bytes"] = r["temp_bytes"]
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_joins(args, preset) -> dict:
    """Phase J: LFM2's join programs alone on the clock (``child_joins``): a
    line a case, the group beside its live rows one a program (each at its
    OWN width's one-row program, which is what the engine would dispatch),
    and what ``shapes.join_groups`` makes of those rows. Fails where the
    rule takes a group at the cell's own row count that the device runs more
    than a tenth slower than its rows one by one."""
    records = [r for r in run_child("joins", args, timeout=2400) if r["kind"] == "join"]
    ms = "wall_ms" if args.rehearse_cpu else "dev_ms"
    alone = {}  # tokens -> a one-row join of them, at the narrowest width that holds them
    for r in sorted((r for r in records if r["rows"] == 1), key=lambda r: -r["slots"]):
        alone[r["tokens"]] = r
    problems, out = [], {}
    for r in records:
        own = alone[r["tokens"]]
        singly = r["live"] * own[ms]
        grouped = r["rule"] == "group"
        say(f"phase=J join rows={r['rows']} slots={r['slots']} live={r['live']} "
            f"tokens={r['tokens']} {ms}={r[ms]} one_a_program_{ms}={singly:.3f} "
            f"rule={r['rule']} first_s={r['first_s']} "
            f"wall_ms={r['wall_ms']} parts_ms={r['parts_ms']}")
        if grouped and not args.rehearse_cpu and r[ms] > 1.1 * singly:
            problems.append(
                f"{r['rows']} rows x {r['slots']} slots with {r['live']} live rows of "
                f"{r['tokens']} tokens ran {r[ms]} ms, its rows one a program {singly:.3f}")
        if grouped:
            out[f"join_{r['rows']}x{r['slots']}_live{r['live']}_{r['tokens']}_{ms}"] = r[ms]
    if problems:
        raise PhaseFailed("; ".join(problems))
    return out


def phase_four_chips(args, preset) -> dict:
    cpu = args.rehearse_cpu
    out = {}
    out["tp4"] = serve_phase(
        "D-tp4", args, preset, ["--tp", "4"],
        expect_impl="xla" if cpu else "pallas",
        memory_check=_spread_check(0.10),
    )
    n_layers = preset["model"]["num_hidden_layers"]
    per = n_layers // 4
    topology = os.path.join(WORK, "topology-4.yml")
    with open(topology, "w") as f:
        for i in range(4):
            lo, hi = i * per, (i + 1) * per - 1
            f.write(f'stage{i}:\n  host: "127.0.0.1:{20000 + i}"\n'
                    f'  layers:\n    - "model.layers.{lo}-{hi}"\n')
    out["mesh4"] = serve_phase(
        "D-mesh4", args, preset,
        ["--backend", "mesh", "--topology", topology],
        expect_impl="xla" if cpu else "pallas",
        memory_check=_stage_check(preset, 4),
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same phases at a tiny size on the CPU (a rehearsal "
        "of this script, never a result: no JSON line is a device's)",
    )
    ap.add_argument(
        "--phases", default=",".join(PHASES),
        help=f"comma list from {','.join(PHASES)} (default: all; probe "
        "always runs)",
    )
    ap.add_argument(
        "--seed-failure", default=None, metavar="PHASE",
        help="make PHASE fail on purpose (the rehearsal's proof that a "
        "failing phase turns the exit code non-zero)",
    )
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    preset = PRESETS["tiny" if args.rehearse_cpu else "full"]

    if not os.path.isdir(os.path.join(REPO, "cake_tpu")):
        print("chip_smoke.py: no cake_tpu package beside this script; it "
              "checks that repository and is nothing without it",
              file=sys.stderr)
        return 2
    if args.child:
        CHILDREN[args.child](preset)
        return 0

    wanted = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")
    signal.signal(signal.SIGTERM, _kill_all)
    signal.signal(signal.SIGINT, _kill_all)
    os.makedirs(WORK, exist_ok=True)
    last_path = os.path.join(WORK, "last_run.json")
    try:
        with open(last_path) as f:
            records = json.load(f)
    except (OSError, ValueError):
        records = {}

    cpu = args.rehearse_cpu
    paged = ["--kv-mode", "paged", "--page-size", str(preset["page_size"]),
             "--scheduler", "continuous", "--prefix-cache", "on",
             "--attention-impl", "pallas"]
    plan = {
        "native": lambda: phase_native(args, preset),
        "setup": lambda: run_child("setup", args, timeout=900)[0],
        "A": lambda: serve_phase(
            "A", args, preset, [], expect_impl="xla" if cpu else "pallas"),
        "B": lambda: serve_phase(
            "B", args, preset, paged, expect_impl="pallas",
            shared_prefix=True),
        "Bf": lambda: serve_phase(
            "Bf", args, preset, [*paged, "--fusion", "all@pallas"],
            expect_impl="pallas", shared_prefix=True),
        "C": lambda: phase_kernels(args, preset),
        "P": lambda: phase_pool(args, preset),
        "H": lambda: phase_hybrid(args, preset),
        "O": lambda: phase_olmo(args, preset),
        "L": lambda: phase_latent(args, preset),
        "S": lambda: phase_sparse(args, preset),
        "F": lambda: phase_lfm2(args, preset),
        "Q": lambda: phase_qwen3next(args, preset),
        "M": lambda: phase_sdar(args, preset),
        "J": lambda: phase_joins(args, preset),
        "D": lambda: phase_four_chips(args, preset),
    }

    failed = []
    report = {}
    device = None
    t_start = time.perf_counter()
    try:
        # ---- probe: always, first. No accelerator, no run.
        try:
            device = run_child("probe", args, timeout=300)[0]
        except PhaseFailed as e:
            say(f"phase=probe FAILED: {e}")
            return 1
        _state["platform"] = device["platform"]
        # A record is comparable with a run on the same kind of device only.
        run_key = f"{device['device_kind']} x{device['device_count']}"
        previous = records.get(run_key, {})
        cache_dir = device["cache_dir"]  # None: no cache on the cpu
        n_cached = (
            len(os.listdir(cache_dir))
            if cache_dir and os.path.isdir(cache_dir) else 0
        )
        say(f"phase=probe ok device_kind={device['device_kind']!r} "
            f"devices={device['device_count']} jax={device['jax_version']} "
            f"compile_cache={cache_dir} entries={n_cached} "
            f"({'populated' if n_cached else 'empty'})")
        want = "cpu" if cpu else "tpu"
        if device["platform"] != want:
            say(f"phase=probe FAILED: JAX sees platform="
                f"{device['platform']!r}, this run needs {want!r}"
                + ("" if cpu else " (no accelerator: nothing to smoke; "
                   "--rehearse-cpu rehearses the script, on purpose)"))
            return 1

        for name in PHASES[1:]:
            if name not in wanted:
                continue
            if name == "D" and device["device_count"] < 4:
                say(f"phase=D not run: {device['device_count']} device(s), "
                    "it needs four")
                continue
            t0 = time.perf_counter()
            try:
                if args.seed_failure == name:
                    raise PhaseFailed("seeded failure (--seed-failure)")
                result = plan[name]()
            except PhaseFailed as e:
                failed.append(name)
                say(f"phase={name} FAILED after "
                    f"{time.perf_counter() - t0:.1f} s: {e}")
                continue
            wall = round(time.perf_counter() - t0, 1)
            report[name] = {"wall_s": wall, **result}
            parts = (
                {f"D-{k}": v for k, v in result.items()} if name == "D"
                else {name: result}
            )
            for part, figures in parts.items():
                fields = " ".join(f"{k}={v}" for k, v in figures.items())
                say(f"phase={part} ok wall_s={wall} {fields}")
            before = previous.get(name, {})
            if "compile_s" in result and "compile_s" in before:
                say(f"phase={name} compile_s={result['compile_s']} now, "
                    f"{before['compile_s']} on the previous run in this "
                    f"checkout (cache entries at start: {n_cached})")
    finally:
        _kill_all()
        # The weights are bulk, the cache and the record are what a second
        # run in this checkout reads.
        for name in os.listdir(WORK):
            if name.startswith("model-"):
                shutil.rmtree(os.path.join(WORK, name), ignore_errors=True)
        if device is not None:
            records[run_key] = {**previous, **report}
            with open(last_path, "w") as f:
                json.dump(records, f, indent=1)

    say(f"total wall_s={time.perf_counter() - t_start:.1f}")
    if "jax" in sys.modules:
        say("FAILED: the parent imported jax")
        return 1
    if failed:
        say(f"FAILED phases: {','.join(failed)}")
        return 1
    if cpu:
        say("rehearsal passed (a CPU rehearsal of the script, not a result)")
        return 0
    if set(PHASES) - set(wanted) - {"probe"}:
        say(f"partial run passed (phases={','.join(wanted)}): no result line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
