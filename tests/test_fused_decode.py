"""Fused multi-token decode (models/llama/fused.py): parity with per-step
path — and the decode hot-path OP fusions (ISSUE 13): fused_norm_matmul /
fused_sample_tail streams identical to unfused on the CPU interpreter, with
kernel-vs-XLA-twin oracles."""

import dataclasses

import numpy as np
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.generator import (
    LlamaGenerator,
    LocalForwardStep,
    SamplingConfig,
)
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.utils import metrics

import jax
import jax.numpy as jnp


def make_gen(sampling: SamplingConfig, chunk: int) -> LlamaGenerator:
    cfg = LlamaConfig.tiny()
    params = M.init_params(cfg, jax.random.PRNGKey(7), np.float32)
    step = LocalForwardStep(cfg, params, max_seq_len=128, cache_dtype=np.float32)
    return LlamaGenerator(
        cfg, step, ByteTokenizer(), sampling, decode_chunk_size=chunk
    )


@pytest.mark.parametrize(
    "sampling",
    [
        SamplingConfig(temperature=0.0, repeat_penalty=1.1, repeat_last_n=8),
        SamplingConfig(temperature=0.0, repeat_penalty=1.0, repeat_last_n=0),
        SamplingConfig(temperature=0.9, top_k=20, repeat_penalty=1.1, seed=123),
    ],
    ids=["greedy+penalty", "greedy-no-penalty", "sampled"],
)
def test_fused_matches_per_step(sampling):
    """Same params + seed: chunked decode must emit the identical token stream.

    Covers the penalty-ring reseeding, PRNG split ordering, and position
    bookkeeping all at once; 11 tokens with chunk 4 exercises first-token
    per-step entry, two full fused chunks, and a per-step tail.
    """
    outs = []
    for chunk in (1, 4):
        gen = make_gen(sampling, chunk)
        gen.add_message(Message.user("tell me a story"))
        text = gen.generate(11)
        outs.append((text, list(gen.generated_token_ids)))
    (t1, ids1), (t4, ids4) = outs
    assert ids1 == ids4
    assert t1 == t4
    assert len(ids1) == 11 or 259 in ids1 or 260 in ids1


def test_fused_chunk_composes_with_continued_decode():
    """State after a fused chunk must let per-step decode continue seamlessly."""
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.1, repeat_last_n=6)
    ref = make_gen(s, 1)
    ref.add_message(Message.user("abc"))
    want = ref.generate(9)

    gen = make_gen(s, 4)
    gen.add_message(Message.user("abc"))
    first = gen.generate(5)  # 1 per-step + 1 fused chunk of 4
    rest = gen.generate(4)  # continues the same sequence per-step/fused
    assert (first + rest) == want


class ScriptedFusedStep:
    """Fake step with decode_chunk: scripted ids, records call granularity."""

    max_seq_len = 64

    def __init__(self, script, vocab=512):
        self.script = list(script)
        self.vocab = vocab
        self.i = 0
        self.chunk_calls = []
        self.step_calls = 0

    def reset(self):
        self.i = 0

    def __call__(self, tokens, pos, seq_len):
        self.step_calls += 1
        logits = np.full((1, self.vocab), -100.0, np.float32)
        logits[0, self.script[self.i]] = 100.0
        self.i += 1
        return logits

    def decode_chunk(self, last_token, pos, n_steps, sampling, key, ring, ring_idx):
        self.chunk_calls.append(n_steps)
        ids = self.script[self.i : self.i + n_steps]
        self.i += n_steps
        return np.asarray([ids], np.int32), key


def make_scripted(script, chunk):
    cfg = LlamaConfig.tiny()
    step = ScriptedFusedStep(script)
    gen = LlamaGenerator(
        cfg,
        step,
        ByteTokenizer(),
        SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        decode_chunk_size=chunk,
    )
    return gen, step


def test_fused_eos_mid_chunk_truncates():
    eos = 259
    script = [ord("A"), ord("B"), eos, ord("X"), ord("Y"), ord("Z"), ord("W")]
    gen, step = make_scripted(script, 4)
    gen.add_message(Message.user("x"))
    text = gen.generate(10)
    assert text == "AB"
    assert gen.last_finish_reason == "stop"
    # Token history ends AT the EOS — the chunk tail was discarded.
    assert gen.generated_token_ids[-1] == eos
    assert len(gen.generated_token_ids) == 3
    assert step.chunk_calls == [4]
    assert step.step_calls == 1  # prefill only


def test_fused_tail_falls_back_to_per_step():
    script = [ord(c) for c in "ABCDEFGHIJ"]
    gen, step = make_scripted(script, 4)
    gen.add_message(Message.user("x"))
    text = gen.generate(10)
    assert text == "ABCDEFGHIJ"
    assert gen.last_finish_reason == "length"
    # 1 prefill step + 2 full chunks (4+4) + 1 leftover... budget math:
    # after first token, 9 remain -> chunks [4, 4], then 1 per-step tail.
    assert step.chunk_calls == [4, 4]
    assert step.step_calls == 2  # prefill + 1 tail token


def _gen_with_step(step, cfg, sampling, chunk):
    return LlamaGenerator(cfg, step, ByteTokenizer(), sampling, decode_chunk_size=chunk)


def test_fused_pipeline_matches_per_step():
    """Mesh backend: fused scan over the shard_mapped pipeline == per-step."""
    from cake_tpu.parallel.pipeline import PipelineRunner

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(3), np.float32)
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.1, repeat_last_n=8)
    outs = []
    for chunk in (1, 4):
        step = PipelineRunner(
            cfg, params, [(0, 2), (2, 4)], max_seq_len=64, cache_dtype=np.float32
        )
        gen = _gen_with_step(step, cfg, s, chunk)
        gen.add_message(Message.user("pipeline story"))
        outs.append((gen.generate(9), list(gen.generated_token_ids)))
    assert outs[0] == outs[1]


def test_fused_tensor_parallel_matches_per_step():
    """tp backend: fused scan with in-scan psums == per-step decode."""
    from cake_tpu.parallel.tensor import TensorParallelRunner

    cfg = LlamaConfig.tiny()
    params = M.init_params(cfg, jax.random.PRNGKey(5), np.float32)
    s = SamplingConfig(temperature=0.0, repeat_penalty=1.0, repeat_last_n=0)
    outs = []
    for chunk in (1, 4):
        step = TensorParallelRunner(
            cfg, params, tp=2, max_seq_len=64, cache_dtype=np.float32
        )
        gen = _gen_with_step(step, cfg, s, chunk)
        gen.add_message(Message.user("tp story"))
        outs.append((gen.generate(9), list(gen.generated_token_ids)))
    assert outs[0] == outs[1]


# ===================================================================== op
# fusion (ISSUE 13): the decode hot-path kernels and their dispatch. Every
# fusion is BIT-IDENTICAL to the unfused arithmetic on fp32 CPU — the
# engine-level tests pin whole streams, the kernel-level tests pin each
# kernel (interpret mode) against its XLA twin, which IS the unfused path.

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
SAMPLED = SamplingConfig(
    temperature=0.9, top_k=20, repeat_penalty=1.1, repeat_last_n=8, seed=11
)


@pytest.fixture(scope="module")
def fmodel():
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(7), np.float32)
    return cfg, params


def _engine_streams(
    cfg, params, fusion, *, kv_mode="paged", prefix=False, spec_k=0,
    sampling=GREEDY, rounds=1,
):
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    eng = BatchEngine(
        dataclasses.replace(cfg, fusion_impl=fusion), params, ByteTokenizer(),
        max_seq_len=256, cache_dtype=np.float32, speculative_k=spec_k,
        serve=ServeConfig(
            max_batch=4, decode_chunk_size=4, kv_mode=kv_mode, page_size=16,
            prefix_cache=prefix,
        ),
    )
    eng.start()
    outs = []
    try:
        for _ in range(rounds):
            hs = [
                eng.submit([Message.user(p)], 10, sampling)
                for p in ("shared system prompt: a", "shared system prompt: bb")
            ]
            outs.append([[t.id for t in h.tokens()] for h in hs])
            assert eng.quiesce(30.0)
    finally:
        eng.stop()
    return outs


@pytest.mark.parametrize("kv_mode", ["dense", "paged"])
@pytest.mark.parametrize(
    "sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"]
)
def test_fused_streams_bit_identical(fmodel, kv_mode, sampling):
    """fusion_impl=all (twin AND pallas kernels) == unfused, dense + paged,
    greedy + sampled: whole engine streams, token for token."""
    cfg, params = fmodel
    base = _engine_streams(cfg, params, "none", kv_mode=kv_mode, sampling=sampling)
    for spec in ("all", "all@pallas"):
        got = _engine_streams(
            cfg, params, spec, kv_mode=kv_mode, sampling=sampling
        )
        assert got == base, f"{spec} diverged under {kv_mode}"


def test_fused_per_fusion_opt_in_bit_identical(fmodel):
    """Each fusion opts in independently and alone preserves the stream."""
    cfg, params = fmodel
    base = _engine_streams(cfg, params, "none", sampling=SAMPLED)
    for spec in ("norm", "tail", "norm,tail"):
        assert _engine_streams(cfg, params, spec, sampling=SAMPLED) == base


def test_fused_warm_prefix_cache_identical_to_cold(fmodel):
    """Warm (prefix-cache fork) rounds under fusion == cold rounds == the
    unfused engine's rounds — the fusions compose with the PR 8 suffix
    arithmetic without perturbing a byte."""
    cfg, params = fmodel
    base = _engine_streams(cfg, params, "none", prefix=True, rounds=2)
    assert base[0] == base[1]  # warm == cold, the PR 8 contract
    for spec in ("all", "all@pallas"):
        got = _engine_streams(cfg, params, spec, prefix=True, rounds=2)
        assert got == base


def test_fused_spec_verify_round_unaffected(fmodel):
    """Speculative rounds (paged verify) under fusion_impl=all emit the
    same accepted stream: the verify chunk keeps the unfused cached-chunk
    path (multi-token), and the fusions around it are exact."""
    cfg, params = fmodel
    base = _engine_streams(cfg, params, "none", spec_k=3)
    for spec in ("all", "all@pallas"):
        assert _engine_streams(cfg, params, spec, spec_k=3) == base


# ----------------------------------------------------- kernel-vs-twin oracles


def test_norm_matmul_kernel_matches_unfused():
    """fused_norm_matmul (interpret) == rms_norm + qmat across out-tile
    counts and the Gemma (1 + w) offset, to f32 rounding: the kernel's
    reciprocal-sqrt and the 96-term dot are the same arithmetic in another
    order, so the two differ in the last ulp (2**-23 relative) of a few
    outputs. 1e-6 of the largest value is eight ulps."""
    from cake_tpu.ops.norm import rms_norm
    from cake_tpu.ops.pallas.fused_norm_matmul import fused_norm_matmul
    from cake_tpu.ops.quant import qmat

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (3, 1, 96), jnp.float32) * 3.0
    nw = jax.random.normal(jax.random.PRNGKey(1), (96,), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (96, 384), jnp.float32)
    for offset in (False, True):
        for block_n in (128, 384):
            got = fused_norm_matmul(
                x, nw, w, eps=1e-5, offset=offset, impl="pallas",
                block_n=block_n, interpret=True,
            )
            want = qmat(rms_norm(x, nw, 1e-5, offset), w)
            assert got.dtype == want.dtype
            err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
            assert err <= 1e-6, (offset, block_n, err)


def test_norm_matmul_untiled_out_dim_takes_twin():
    """An output dim that does not tile into 128 lanes silently (and
    bit-identically) runs the twin — never a wrong kernel launch."""
    from cake_tpu.ops.norm import rms_norm
    from cake_tpu.ops.pallas.fused_norm_matmul import (
        fused_norm_matmul,
        norm_matmul_supported,
    )
    from cake_tpu.ops.quant import qmat

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 64), jnp.float32)
    nw = jnp.ones((64,), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 96), jnp.float32)
    assert not norm_matmul_supported(w)
    got = fused_norm_matmul(x, nw, w, eps=1e-5, impl="pallas")
    assert jnp.array_equal(got, qmat(rms_norm(x, nw, 1e-5, False), w))


def _tail_ref(logits, ring, key, s):
    """The UNFUSED sampling tail — fused.sample_step with tail_impl=None."""
    from cake_tpu.models.llama.fused import sample_step

    nxt, _, _, _ = sample_step(
        logits, key, ring, jnp.zeros((logits.shape[0],), jnp.int32),
        temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
        repeat_penalty=s.repeat_penalty,
    )
    return nxt


def _tail_fused(logits, ring, key, s, impl):
    from cake_tpu.models.llama.fused import sample_step

    nxt, _, _, _ = sample_step(
        logits, key, ring, jnp.zeros((logits.shape[0],), jnp.int32),
        temperature=s.temperature, top_k=s.top_k, top_p=s.top_p,
        repeat_penalty=s.repeat_penalty, tail_impl=impl,
    )
    return nxt


@pytest.mark.parametrize(
    "s",
    [
        SamplingConfig(temperature=0.0, repeat_penalty=1.2, repeat_last_n=4),
        SamplingConfig(temperature=0.7, top_k=5, repeat_penalty=1.1),
        SamplingConfig(temperature=0.7, top_k=None, repeat_penalty=1.0),
    ],
    ids=["greedy+penalty", "topk+penalty", "plain"],
)
@pytest.mark.parametrize("per_row", [True, False], ids=["row-keys", "shared"])
def test_sample_tail_kernel_matches_unfused_bits(s, per_row):
    """fused_sample_tail (interpret AND twin) == the unfused sample_step
    chain, per-row and shared-stream keys, duplicate-heavy logits included
    (the top-k descent must count duplicates exactly like lax.top_k)."""
    b, vocab = 4, 256
    logits = jax.random.normal(jax.random.PRNGKey(9), (b, vocab), jnp.float32)
    # Quantize to force duplicate logit values — the top-k tie shape.
    logits = jnp.round(logits * 4) / 4
    ring = jnp.asarray(
        [[1, 2, -1, -1], [7, 7, 3, -1], [-1] * 4, [250, 0, 1, 2]], jnp.int32
    )[:, : max(1, s.repeat_last_n or 4)]
    key = jax.random.PRNGKey(42)
    if per_row:
        key = jax.random.split(key, b)
    want = _tail_ref(logits, ring, key, s)
    for impl in ("xla", "pallas"):
        got = _tail_fused(logits, ring, key, s, impl)
        assert jnp.array_equal(got, want), impl


def test_sample_tail_top_p_falls_back_bit_identically():
    """top_p set: the kernel path is refused in favor of the XLA sort twin
    — and the stream still byte-matches the unfused path."""
    s = SamplingConfig(temperature=0.8, top_p=0.9, repeat_penalty=1.1)
    b, vocab = 3, 256
    logits = jax.random.normal(jax.random.PRNGKey(10), (b, vocab), jnp.float32)
    ring = jnp.full((b, 4), -1, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), b)
    want = _tail_ref(logits, ring, keys, s)
    for impl in ("xla", "pallas"):
        assert jnp.array_equal(_tail_fused(logits, ring, keys, s, impl), want)


def test_sample_tail_all_masked_and_nan_guards():
    """All -inf rows and NaN-carrying rows produce exactly what the unfused
    path produces (index 0 for a fully dead row) — no crash, no divergence."""
    vocab = 256
    dead = jnp.full((2, vocab), -jnp.inf, jnp.float32)
    ring = jnp.full((2, 4), -1, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    for s in (
        SamplingConfig(temperature=0.0, repeat_penalty=1.0),
        SamplingConfig(temperature=0.9, top_k=4, repeat_penalty=1.0),
    ):
        want = _tail_ref(dead, ring, keys, s)
        for impl in ("xla", "pallas"):
            got = _tail_fused(dead, ring, keys, s, impl)
            assert jnp.array_equal(got, want)
            assert jnp.array_equal(got, jnp.zeros((2,), jnp.int32))
    nan_row = dead.at[:, 7].set(jnp.nan)
    sg = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    want = _tail_ref(nan_row, ring, keys, sg)
    for impl in ("xla", "pallas"):
        assert jnp.array_equal(_tail_fused(nan_row, ring, keys, sg, impl), want)


def test_sample_tail_untiled_vocab_refuses():
    """A vocab that does not tile into 128 lanes is a LOUD ValueError on
    the kernel path — never a silently wrong launch."""
    from cake_tpu.ops.pallas.fused_sample_tail import fused_sample_tail

    logits = jnp.zeros((2, 250), jnp.float32)
    ring = jnp.full((2, 2), -1, jnp.int32)
    with pytest.raises(ValueError, match="128-lane"):
        fused_sample_tail(
            logits, ring, None, temperature=0.0, top_k=None, top_p=None,
            repeat_penalty=1.0, impl="pallas",
        )


def test_fused_fallback_event_fires_exactly_once(fmodel):
    """fusion all@pallas + top_p: the tail runs the documented XLA sort
    fallback and surfaces ONE kernel-fallback flight event across many
    decode dispatches; an xla-by-choice fusion run emits none."""
    cfg, params = fmodel
    metrics.flight.clear()
    s = SamplingConfig(temperature=0.8, top_p=0.9, repeat_penalty=1.0, seed=2)
    _engine_streams(cfg, params, "all@pallas", sampling=s, rounds=2)
    events = [
        e for e in metrics.flight.snapshot()
        if e["event"] == "kernel-fallback"
    ]
    assert len(events) == 1
    assert events[0]["op"] == "fused_sample_tail"
    metrics.flight.clear()
    _engine_streams(cfg, params, "all@xla", sampling=s)
    assert not [
        e for e in metrics.flight.snapshot()
        if e["event"] == "kernel-fallback"
    ]


def test_sample_tail_untiled_vocab_downgrades_in_sample_step():
    """The SERVING dispatch (sample_step) downgrades an untileable vocab to
    the twin instead of raising — the same sample_tail_supported rule the
    backends' kernel-fallback note reads, so note and dispatch agree; only
    direct kernel calls refuse loudly (the test above)."""
    from cake_tpu.models.llama.fused import sample_step

    b, vocab = 2, 250  # not a 128-lane multiple
    logits = jax.random.normal(jax.random.PRNGKey(4), (b, vocab), jnp.float32)
    ring = jnp.full((b, 4), -1, jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), b)
    ridx = jnp.zeros((b,), jnp.int32)
    kw = dict(temperature=0.7, top_k=5, top_p=None, repeat_penalty=1.1)
    want, *_ = sample_step(logits, keys, ring, ridx, **kw)
    got, *_ = sample_step(logits, keys, ring, ridx, tail_impl="pallas", **kw)
    assert jnp.array_equal(got, want)
