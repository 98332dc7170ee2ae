"""1F1B interleaved pipelined decode (runtime/batch_backend.py).

Contract under test: with the batch split into S microbatch groups in
staggered flight, token streams are IDENTICAL to the serialized stage walk
(same per-row PRNG splits, penalty rings, slots), while the per-device
critical path per emitted token drops ~S-fold (each wall-step runs a
1/S-width group per stage instead of the whole batch on one stage).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.batch import layout_prompts, seed_rings, first_sample
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.runtime.batch_backend import PipelineBatchBackend

S = 4  # stages
B = 8  # rows (2 per group)
MAX_SEQ = 64


@pytest.fixture(scope="module")
def setup():
    if jax.device_count() < S:
        pytest.skip(f"needs {S} devices")
    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(21), jnp.float32)
    boundaries = [(i, i + 1) for i in range(4)]
    return cfg, params, boundaries


def _backend(setup, interleave):
    cfg, params, boundaries = setup
    return PipelineBatchBackend(
        cfg, params, boundaries, max_seq_len=MAX_SEQ,
        cache_dtype=jnp.float32, interleave=interleave,
    )


def _decode_both(setup, s: SamplingConfig, n: int = 5):
    """Prefill identically on both walks, decode n tokens, return streams."""
    cfg, params, boundaries = setup
    # Unequal prompt lengths exercise the per-row pads inside the groups.
    ids_list = [[7 + r, 3, 11 + r][: 2 + (r % 2)] for r in range(B)]
    tokens, pads, bucket = layout_prompts(ids_list, MAX_SEQ)
    window = s.repeat_last_n
    keys0 = jax.random.split(jax.random.PRNGKey(5), B)

    outs = []
    for interleave in (False, True):
        be = _backend(setup, interleave)
        kv = be.init_kv(B)
        logits, kv = be.prefill(jnp.asarray(tokens), kv, jnp.asarray(pads))
        ring, ring_idx = seed_rings(ids_list, window)
        first, keys, ring, ring_idx = first_sample(
            logits, s, ring, ring_idx, keys0
        )
        toks, kv, keys, ring_j, ridx_j = be.decode(
            kv, jnp.asarray(first), bucket, jnp.asarray(pads), keys,
            jnp.asarray(ring), jnp.asarray(ring_idx), n, s,
        )
        outs.append(
            (
                np.asarray(toks),
                np.asarray(ring_j),
                np.asarray(ridx_j),
                np.asarray(keys),
            )
        )
    return outs


def test_greedy_streams_identical(setup):
    (a, ra, ia, ka), (b, rb, ib, kb) = _decode_both(
        setup, SamplingConfig(temperature=0.0, repeat_penalty=1.0, repeat_last_n=0)
    )
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ka, kb)  # PRNG carries advance identically


def test_sampled_streams_identical(setup):
    """temperature > 0 + repeat penalty + rings: the full sampling arithmetic
    must walk the same per-row streams on both schedules."""
    (a, ra, ia, ka), (b, rb, ib, kb) = _decode_both(
        setup,
        SamplingConfig(
            temperature=0.8, top_k=20, top_p=0.9,
            repeat_penalty=1.15, repeat_last_n=16,
        ),
    )
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(ra, rb)
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_array_equal(ka, kb)


def test_interleaved_routing_and_fallback(setup):
    """B % S != 0 or single keys must fall back to the serialized walk."""
    be = _backend(setup, True)
    assert be.interleave
    # 6 rows over 4 stages: fallback (no crash, serialized path).
    cfg, params, boundaries = setup
    ids_list = [[5, 3]] * 6
    tokens, pads, bucket = layout_prompts(ids_list, MAX_SEQ)
    kv = be.init_kv(6)
    logits, kv = be.prefill(jnp.asarray(tokens), kv, jnp.asarray(pads))
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.0, repeat_last_n=0)
    ring, ring_idx = seed_rings(ids_list, 0)
    keys0 = jax.random.split(jax.random.PRNGKey(1), 6)
    first, keys, ring, ring_idx = first_sample(logits, s, ring, ring_idx, keys0)
    toks, *_ = be.decode(
        kv, jnp.asarray(first), bucket, jnp.asarray(pads), keys,
        jnp.asarray(ring), jnp.asarray(ring_idx), 3, s,
    )
    assert np.asarray(toks).shape == (6, 3)


def test_scalar_ring_idx_accepted(setup):
    """Equal-length prompts may pass a SCALAR ring_idx (valid on the
    serialized walk, fused.py sample_step); the interleaved dispatch must
    broadcast it, not crash on the group row slice."""
    be = _backend(setup, True)
    ids_list = [[5, 3]] * B
    tokens, pads, bucket = layout_prompts(ids_list, MAX_SEQ)
    kv = be.init_kv(B)
    logits, kv = be.prefill(jnp.asarray(tokens), kv, jnp.asarray(pads))
    s = SamplingConfig(temperature=0.7, repeat_penalty=1.1, repeat_last_n=8)
    ring, _ = seed_rings(ids_list, 8)
    keys0 = jax.random.split(jax.random.PRNGKey(2), B)
    first, keys, ring, _ = first_sample(logits, s, ring, np.zeros(B, np.int32), keys0)
    toks, kv, *_ = be.decode(
        kv, jnp.asarray(first), bucket, jnp.asarray(pads), keys,
        jnp.asarray(ring), jnp.int32(1), 3, s,  # scalar ring_idx
    )
    assert np.asarray(toks).shape == (B, 3)
    assert "1f1b" in str(next(iter(be._decode_cache)))


def test_per_device_critical_path_drops(setup):
    """The measured step-count win: per-DEVICE compiled FLOPs for n decoded
    tokens. Serialized: every device's program walks n*S full-batch stage
    steps (S-1 idle per wall-step but the critical path pays the full-batch
    stage each step). 1F1B: (n*S + S - 1) wall-steps of 1/S-width group work.
    The per-device program cost must drop by ~S/(1 + 1/n) — here ~3x of the
    ideal 4."""
    cfg, params, boundaries = setup
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.0, repeat_last_n=0)
    n = 8
    costs = {}
    for interleave in (False, True):
        be = _backend(setup, interleave)
        kv = be.init_kv(B)
        pads = jnp.zeros((B,), jnp.int32)
        tok = jnp.zeros((B,), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(0), B)
        ring = jnp.full((B, 0), -1, jnp.int32)
        ridx = jnp.zeros((B,), jnp.int32)
        if interleave:
            window = 0
            mapped = be._interleaved_body(n, window, s)

            def run(kv, tok, slot, pads, keys, ring, ridx, mapped=mapped, be=be):
                out, kv, kf, rf, xf = mapped(
                    be.stage_params, be.valid, be.head_params, tok, kv,
                    slot, pads, keys, ring, ridx,
                )
                return out[be.n_stages - 1], kv
        else:
            from cake_tpu.models.llama.fused import sampled_decode_scan

            def run(kv, tok, slot, pads, keys, ring, ridx, be=be):
                return sampled_decode_scan(
                    be._forward_one(be._weights, pads), kv, tok, slot, keys,
                    ring, ridx,
                    n_steps=n, temperature=0.0, top_k=None, top_p=None,
                    repeat_penalty=1.0,
                )[:2]

        # One fresh jit per interleave variant IS the experiment (comparing
        # compiled FLOPs across configs).
        lowered = jax.jit(run).lower(  # cake-lint: disable=jit-in-hot-loop
            kv, tok, jnp.int32(8), pads, keys, ring, ridx
        )
        analysis = lowered.compile().cost_analysis()
        costs[interleave] = float(analysis["flops"])
    # Ideal ratio S / (1 + (S-1)/(n*S)) ~ 3.7 at S=4, n=8; require a solid
    # margin over half the ideal so compiler noise cannot flake the test.
    assert costs[True] < costs[False] / 2.0, costs


def test_engine_over_interleaved_matches_local(setup):
    """End-to-end: the continuous-batching engine over the 1F1B pipeline
    backend emits the same per-request streams as over the local backend."""
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.runtime.batch_backend import LocalBatchBackend
    from cake_tpu.runtime.serving import BatchEngine

    cfg, params, boundaries = setup
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.0)

    def run_engine(backend):
        eng = BatchEngine(
            cfg, None, ByteTokenizer(), max_seq_len=MAX_SEQ,
            cache_dtype=jnp.float32, decode_chunk_size=3, max_batch=S,
            admission_window=0.05, backend=backend,
        )
        eng.start()
        try:
            handles = [
                eng.submit([Message.user(f"req {i} body")], 6, s)
                for i in range(S)
            ]
            return [[t.id for t in h.tokens()] for h in handles]
        finally:
            eng.stop()

    local = run_engine(
        LocalBatchBackend(
            cfg, params, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
        )
    )
    pipe = run_engine(_backend(setup, True))
    assert pipe == local
