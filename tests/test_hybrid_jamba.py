"""``model_type: jamba`` on the served path, at a tiny size on the CPU.

A tiny Jamba (hidden 64, 8 layers with attention at ``i % 4 == 2``, d_state
4, dt_rank 4, 4 query heads on 1 KV head, tied head, seeded float32
weights) against the plain reference of ``bench/architectures/jamba.py``
(a ``lax.scan`` over the whole sequence, no cache), and against itself:
what the recurrence must not see (pads, dead tails, dead lanes), what a lane
must not inherit (its last tenant's state), and what is refused outright.
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.checkpoint import Reader
from bench.manifest import architecture
from cake_tpu.io.safetensors_io import load_params, save_tiny_checkpoint
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import programs
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import SUPPORTED_MODEL_TYPES, LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.ops import ssm as S
from cake_tpu.runtime.batch_backend import paged_backend
from cake_tpu.runtime.serving import BatchEngine, ServeConfig

REPO = Path(__file__).resolve().parents[1]
HF = dict(
    model_type="jamba", hidden_size=64, intermediate_size=128, vocab_size=512,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=1,
    rms_norm_eps=1e-6, attn_layer_period=4, attn_layer_offset=2,
    mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
    mamba_conv_bias=True, mamba_proj_bias=False, num_experts=1,
    num_experts_per_tok=1, tie_word_embeddings=True, bos_token_id=1,
    eos_token_id=2, pad_token_id=0, max_position_embeddings=256,
    sliding_window=None,
)
GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
PAGE = 16


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(config, params as loaded from an HF-named checkpoint, the
    benchmark's reader over the same files, the reference module)."""
    config = LlamaConfig.from_hf_dict(HF)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    path = tmp_path_factory.mktemp("tiny_jamba")
    save_tiny_checkpoint(path, params, config)
    loaded = load_params(path, LlamaConfig.from_model_dir(path), jnp.float32)
    return config, params, loaded, Reader(path), architecture(REPO, HF), path


def backend(config, params, **kw):
    be = paged_backend(
        config, params, max_seq_len=128, cache_dtype=jnp.float32,
        page_size=PAGE, max_pages=48, allow_pallas=False, **kw,
    )
    # picked from the config alone; what only plain K and V can do is absent
    assert be.cache_kind == config.cache_kind
    assert not hasattr(be, "suffix_prefill") and not hasattr(be, "verify_greedy")
    return be


# Widths at which the state layers' kernels engage: d_inner 128 is one lane
# tile, d_state 8 one sublane tile (the tiny model's 4 is none: the twins').
TILING = {**HF, "mamba_d_state": 8}
STEP_FORMS = ("xla", "pallas")


@pytest.fixture(scope="module")
def tiling_model():
    config = LlamaConfig.from_hf_dict(TILING)
    return config, H.init_params(config, jax.random.PRNGKey(0), jnp.float32)


def backend_of_form(config, params, step_form):
    """A backend whose decode steps take ``step_form``: every kernel on
    (interpreted here; pages of 128 slots, the kernels' page) or every twin."""
    if step_form == "xla":
        be = backend(config, params)
    else:
        be = paged_backend(
            dataclasses.replace(config, attention_impl="pallas"), params,
            max_seq_len=256, cache_dtype=jnp.float32, page_size=128,
            max_pages=16, allow_pallas=True,
        )
    assert be.state_facts()["step_form"] == step_form
    return be


def window_gathers(traced, config, lanes) -> list:
    """The ``gather`` equations of a traced program, nested jaxprs included,
    whose operand is a state layer's convolution input with its window in
    front ([lanes, K-1 + 1, channels]: ``ops/ssm.with_window`` at one
    position)."""
    kept, channels = config.conv_window
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "gather"
                    and tuple(eqn.invars[0].aval.shape) == (lanes, kept + 1, channels)):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(traced.jaxpr.jaxpr)
    return found


def decode_program(config, lanes=3, allow_pallas=False):
    """A decode chunk of 4 steps over ``lanes`` rows, traced from shapes."""
    return programs.served_programs(
        config, n_pages=8, page_size=PAGE, lanes=lanes, table_pages=8, n_steps=4,
        width=64, dtype=jnp.float32, allow_pallas=allow_pallas,
    ).programs["decode"]()


def with_shapes(be, **fields):
    """The backend's own instance with some of its tables replaced."""
    be.shapes = dataclasses.replace(be.shapes, **fields)
    return be


def lay_out(be, prompts, lanes, bucket):
    """The engine's epoch layout: left-padded rows, real lanes mapped."""
    cache = be.init_kv(lanes)
    tokens = np.zeros((lanes, bucket), np.int32)
    pads = np.full((lanes,), bucket - 1, np.int32)
    tokens[:, -1] = 1  # spare lanes: the engine's one-token dummy prompt
    for r, ids in enumerate(prompts):
        pads[r] = bucket - len(ids)
        tokens[r, pads[r]:] = ids
        be.allocator.map_range(r, int(pads[r]), bucket)
    return cache, tokens, pads


def decode(be, cache, tok, slot, pads, n, live):
    lanes = len(pads)
    for r in live:
        be.allocator.map_range(r, slot, slot + n)
    keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
    toks, cache, *_ = be.decode(
        cache, jnp.asarray(tok), slot, jnp.asarray(pads), keys,
        jnp.zeros((lanes, 0), jnp.int32), jnp.zeros((lanes,), jnp.int32), n,
        GREEDY,
    )
    return np.asarray(toks), cache


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 512, n).tolist() for n in lengths]


# ------------------------------------------ (1) against the plain reference


def test_prefill_then_decode_matches_the_reference_scan(model):
    """Paged prefill, then 24 decode steps through the cache, against the
    reference's logits on the FULL sequence. Float32 on both sides, so only
    the order of sums differs: 2e-5 of a logit spread of about 0.3, and the
    served tokens are the reference's argmax at every position."""
    config, _, loaded, reader, arch, _ = model
    be = backend(config, loaded)
    rows = prompts(0, 21, 37)
    cache, tokens, pads = lay_out(be, rows, 4, 48)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    want = arch.forward_logits(reader, HF, rows)
    for r in range(2):
        np.testing.assert_allclose(logits[r], want[r][-1], atol=2e-5)
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    served = [[int(tok[r])] for r in range(2)]
    slot = 48
    for _ in range(3):
        toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1))
        for r in range(2):
            served[r] += toks[r].tolist()
        tok, slot = toks[:, -1], slot + 8
    full = arch.forward_logits(
        reader, HF, [p + s[:-1] for p, s in zip(rows, served)]
    )
    for r, p in enumerate(rows):
        lg = full[r][len(p) - 1:]
        assert lg.shape[0] == 25 and (lg.argmax(-1) == served[r]).all()


# ------------------------------------------- (2) pads, (3) joins and re-use


def test_a_left_padded_row_equals_the_row_unpadded(model):
    config, _, loaded, *_ = model
    (ids,) = prompts(1, 29)
    out = {}
    for bucket in (32, 64):  # 3 pads, 35 pads
        be = backend(config, loaded)
        cache, tokens, pads = lay_out(be, [ids], 2, bucket)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        out[bucket] = (np.asarray(logits[0]), np.asarray(cache.ssm[:, 0]),
                       np.asarray(cache.conv[:, :, 0]))
    for a, b in zip(out[32], out[64]):
        np.testing.assert_array_equal(a, b)
    assert np.abs(out[32][1]).max() > 0  # a state was there to compare


def test_a_joined_row_and_a_reused_lane_equal_the_row_alone(model):
    """Lane 1 first serves another request (its state is left behind), then
    a joiner takes it while lane 0 runs on: the joiner's logits and state
    are those of the same row prefilled alone in a fresh cache."""
    config, _, loaded, *_ = model
    first, other, joiner = prompts(2, 20, 33, 26)
    be = backend(config, loaded)
    cache, tokens, pads = lay_out(be, [first, other], 2, 48)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    toks, cache = decode(be, cache, tok, 48, pads, 8, live=(0, 1))
    stale = np.asarray(cache.ssm[:, 1])
    be.allocator.release(1)  # the other request ends; its state stays
    slot = 56
    row = np.zeros((1, 64), np.int32)
    row[0, slot - len(joiner):slot] = joiner
    be.allocator.map_range(1, slot - len(joiner), slot)
    lane0 = np.asarray(cache.ssm[:, 0])
    j_logits, cache = be.join(
        cache, row, jnp.asarray([slot - len(joiner)], jnp.int32),
        jnp.asarray([slot], jnp.int32), 1,
    )
    np.testing.assert_array_equal(np.asarray(cache.ssm[:, 0]), lane0)
    assert not np.array_equal(np.asarray(cache.ssm[:, 1]), stale)

    alone = backend(config, loaded)
    a_cache, a_tokens, a_pads = lay_out(alone, [joiner], 2, 32)
    a_logits, a_cache = alone.prefill(a_tokens, a_cache, jnp.asarray(a_pads))
    # A join is a program of one row, the prefill alone one of two: the
    # CPU's matmuls sum in another order (2e-7 seen). The stale state it
    # must not have continued is 0.1 and more away.
    near = dict(rtol=0, atol=2e-6)
    np.testing.assert_allclose(j_logits[0], a_logits[0], **near)
    np.testing.assert_allclose(cache.ssm[:, 1], a_cache.ssm[:, 0], **near)
    np.testing.assert_allclose(cache.conv[:, :, 1], a_cache.conv[:, :, 0], **near)
    assert np.abs(stale - np.asarray(a_cache.ssm[:, 0])).max() > 0.01
    assert be.state_facts()["lane_writes"] == 3  # two at the prefill, one join


def test_an_epoch_prefill_in_groups_equals_one_program(model):
    config, _, loaded, *_ = model
    rows = prompts(3, 9, 30, 17, 25)
    out = []
    for budget in (1 << 20, 256):  # one program of 4 rows; four of 1 row
        be = with_shapes(backend(config, loaded), prefill_tokens=budget)
        cache, tokens, pads = lay_out(be, rows, 4, 32)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        out.append(jax.tree.map(np.asarray, (logits, cache)))
    # programs of 4 rows and of 1: the CPU's matmuls sum in another order
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=0, atol=2e-6), *out
    )
    assert np.abs(out[0][1].ssm).max() > 0.01


def test_a_join_costs_its_prompt_and_programs_come_in_few_shapes(model, monkeypatch):
    """The joiner's window is cut once, by the backend's shapes: as wide as
    the PROMPT's bucket, ending at the slot, not a row from slot 0. Widths
    and decode capacities are a fixed few."""
    config, _, loaded, *_ = model
    be = backend(config, loaded)  # max_seq_len 128: 8 pages of 16
    assert (be.shapes.widths, be.shapes.capacities) == ((64, 128), (32, 64, 128))
    with_shapes(be, widths=(16, 32, 64, 128), capacity_multiple=8)
    assert [be.shapes.program_width(n) for n in (1, 16, 17, 100, 500)] == [16, 16, 32, 128, 500]
    be.set_epoch_capacity(be.shapes.capacity(40, 128))  # 3 pages of 16 -> 4
    assert be.capacity_slots() == 64
    be.set_epoch_capacity(None)
    first, joiner = prompts(5, 60, 11)
    cache, tokens, pads = lay_out(be, [first], 2, 64)
    _, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    slot = 100
    start, width = be.shapes.window(slot - 11, slot, 128)
    assert (start, width) == (84, 16)
    row = np.zeros((1, width), np.int32)
    row[0, slot - 11 - start:slot - start] = joiner
    be.allocator.map_range(1, slot - 11, slot)
    seen = []
    real = be._join_program

    def recording(cfg, width, allow_pallas=True):
        seen.append(width)
        return real(cfg, width, allow_pallas)

    monkeypatch.setattr(be, "_join_program", recording)
    j_logits, cache = be.join(
        cache, row, jnp.asarray([slot - 11], jnp.int32),
        jnp.asarray([slot], jnp.int32), 1, start,
    )
    assert seen == [16]  # not the 128 slots a row from slot 0 would span
    alone = backend(config, loaded)
    a_cache, a_tokens, a_pads = lay_out(alone, [joiner], 2, 16)
    a_logits, a_cache = alone.prefill(a_tokens, a_cache, jnp.asarray(a_pads))
    near = dict(rtol=0, atol=2e-6)
    np.testing.assert_allclose(j_logits[0], a_logits[0], **near)
    np.testing.assert_allclose(cache.ssm[:, 1], a_cache.ssm[:, 0], **near)


def test_warm_programs_runs_the_closed_set_and_leaves_nothing(model, monkeypatch):
    config, _, loaded, *_ = model
    be = backend(config, loaded)  # max_seq_len 128: 8 pages of 16
    with_shapes(be, widths=(16, 32), capacities=(32, 128))
    assert len(be.shapes.programs(2)) == 2 * 2 + 2
    seen = []
    real = be._join_program
    monkeypatch.setattr(
        be, "_join_program",
        lambda cfg, w, ok=True: seen.append(w) or real(cfg, w, ok),
    )
    got = be.warm_programs(2, GREEDY, 4)
    assert got["programs"] == 2 * 2 + 2 and seen == [16, 32]
    assert be.capacity_slots() == 128 and be.state_lane_writes == 0
    assert be.allocator.pages_free == be.allocator.pages_total
    # and the engine serves after it as before it
    rows = prompts(6, 12)
    cache, tokens, pads = lay_out(be, rows, 2, 16)
    logits, _ = be.prefill(tokens, cache, jnp.asarray(pads))
    fresh = with_shapes(backend(config, loaded), widths=be.shapes.widths)
    cache, tokens, pads = lay_out(fresh, rows, 2, 16)
    want, _ = fresh.prefill(tokens, cache, jnp.asarray(pads))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))


# --------------------------------------------------------- (5) dead lanes


@pytest.mark.parametrize("step_form", STEP_FORMS)
def test_a_lane_that_is_not_live_keeps_its_state(tiling_model, step_form):
    config, params = tiling_model
    be = backend_of_form(config, params, step_form)
    cache, tokens, pads = lay_out(be, prompts(4, 18, 27), 4, 32)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    be.allocator.release(1)  # lane 1's request ended; lanes 2, 3 never lived
    before = jax.tree.map(np.asarray, (cache.ssm, cache.conv))
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    _, cache = decode(be, cache, tok, 32, pads, 8, live=(0,))
    after = jax.tree.map(np.asarray, (cache.ssm, cache.conv))
    np.testing.assert_array_equal(after[0][:, 1:], before[0][:, 1:])
    np.testing.assert_array_equal(after[1][:, :, 1:], before[1][:, :, 1:])
    assert not np.array_equal(after[0][:, 0], before[0][:, 0])


def test_the_in_place_path_equals_the_twins_path(tiling_model):
    """A prefill and 16 decode steps with the state stepped in place by the
    kernel (``ops/pallas/selective_step.py``, the stack whole) and with the
    twin over a layer's slice: float32 both, the sums over ``d_state`` and a
    window's scan in another order. Logits of spread 0.3 agree to 2e-5, the
    served tokens are the same, and so is what the lanes hold after."""
    config, params = tiling_model
    rows = prompts(7, 23, 31)
    out = {}
    for form in STEP_FORMS:
        be = backend_of_form(config, params, form)
        cache, tokens, pads = lay_out(be, rows, 3, 32)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
        served, slot = [], 32
        for _ in range(2):
            toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1))
            served.append(toks[:2])
            tok, slot = toks[:, -1], slot + 8
        out[form] = (np.asarray(logits[:2]), np.concatenate(served, 1),
                     np.asarray(cache.ssm[:, :2]), np.asarray(cache.conv[:, :, :2]))
    near = dict(rtol=0, atol=2e-5)
    np.testing.assert_allclose(out["pallas"][0], out["xla"][0], **near)
    np.testing.assert_array_equal(out["pallas"][1], out["xla"][1])
    np.testing.assert_allclose(out["pallas"][2], out["xla"][2], **near)
    np.testing.assert_allclose(out["pallas"][3], out["xla"][3], **near)
    assert np.abs(out["xla"][2]).max() > 0.01 and out["xla"][1].shape == (2, 16)


# ------------------------------------------- (5b) the window's shift, decode


def gathered_window(padded, k1):
    """``ops/ssm.window_at`` as it was for a decode step before PR 42: a
    gather at every row's ``ends`` = the chunk's length."""
    ends = jnp.full((padded.shape[0],), padded.shape[1] - k1, jnp.int32)
    return S.window_at(padded, ends, k1)


def test_a_decode_step_shifts_the_window_by_a_slice(tiling_model):
    """With ``ends`` None the window after a step is ``padded``'s tail, bit
    for bit what the gather at constant indices gave; a decode program holds
    no gather over a window (a join, ``ends`` given, keeps it)."""
    rng = np.random.default_rng(0)
    padded = jnp.asarray(rng.normal(size=(5, 4, 128)), jnp.float32)
    np.testing.assert_array_equal(
        S.window_at(padded, None, 3), gathered_window(padded, 3))
    long = jnp.asarray(rng.normal(size=(2, 3 + 9, 128)), jnp.float32)
    np.testing.assert_array_equal(
        S.window_at(long, None, 3), gathered_window(long, 3))
    for config in (LlamaConfig.from_hf_dict(HF), tiling_model[0]):
        assert window_gathers(decode_program(config), config, 3) == []
    # the reader finds the gathered form where it is
    kept, channels = tiling_model[0].conv_window
    old = jax.jit(lambda p: gathered_window(p, kept)).trace(
        jax.ShapeDtypeStruct((3, kept + 1, channels), jnp.float32))
    assert len(window_gathers(old, tiling_model[0], 3)) == 1


def state_after_a_dispatch(make_backend, monkeypatch, gathered):
    """(conv, ssm) after a prefill and one decode dispatch of 8 steps over
    four lanes, two of them live; with ``gathered`` the decode steps' window
    is taken by the gather that ``window_at`` was before PR 42."""
    with monkeypatch.context() as mp:
        if gathered:
            real = S.window_at
            mp.setattr(
                S, "window_at",
                lambda padded, ends, k1: real(padded, ends, k1) if ends is not None
                else gathered_window(padded, k1))
        jax.clear_caches()  # the decode program is traced anew under the patch
        programs.decode_program.cache_clear()
        be = make_backend()
        cache, tokens, pads = lay_out(be, prompts(4, 18, 27), 4, 32)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        tok = np.asarray(logits).argmax(-1).astype(np.int32)
        _, cache = decode(be, cache, tok, 32, pads, 8, live=(0, 1))
        out = jax.tree.map(np.asarray, (cache.conv, cache.ssm))
    jax.clear_caches()
    programs.decode_program.cache_clear()
    return out


def test_conv_after_a_step_equals_the_gathered_forms(model, monkeypatch):
    """One decode dispatch of the tiny model with ``window_at`` as it is and
    with the gather in its place: ``cache.conv`` (and ``cache.ssm``) equal
    bit for bit, dead lanes included."""
    config, _, loaded, *_ = model
    make = lambda: backend(config, loaded)
    sliced = state_after_a_dispatch(make, monkeypatch, gathered=False)
    jax.tree.map(
        np.testing.assert_array_equal, sliced,
        state_after_a_dispatch(make, monkeypatch, gathered=True))
    assert np.abs(sliced[0]).max() > 0


# ----------------------------------------------------- (6) the chunked scan


@pytest.mark.parametrize("length,chunk", [(37, 16), (16, 16), (5, 8)])
def test_chunked_scan_equals_the_stepwise_scan(length, chunk):
    rng = np.random.default_rng(length)
    b, d, n = 2, 8, 4
    u = jnp.asarray(rng.normal(size=(b, length, d)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, length, d)), jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.normal(size=(n, d)), jnp.float32))
    b_in = jnp.asarray(rng.normal(size=(b, length, n)), jnp.float32)
    c_out = jnp.asarray(rng.normal(size=(b, length, n)), jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, n, d)), jnp.float32)
    y, s = S.selective_scan(u, dt, a, b_in, c_out, s0, chunk)
    y1, s1 = S.selective_scan(u, dt, a, b_in, c_out, s0, 1)  # a step a chunk
    np.testing.assert_allclose(y, y1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s, s1, rtol=1e-6, atol=1e-6)
    # only the chunks that touch the live span are walked: the same answer
    # where nothing is live outside it (dt zero there, as at a join's pads)
    lo, hi = length // 3, length - 2
    grid = jnp.arange(length)[None, :, None]
    dt_span = jnp.where((grid >= lo) & (grid < hi), dt, 0.0)
    y2, s2 = S.selective_scan(u, dt_span, a, b_in, c_out, jnp.zeros_like(s0), chunk,
                              (jnp.int32(lo), jnp.int32(hi)))
    y3, s3 = S.selective_scan(u, dt_span, a, b_in, c_out, jnp.zeros_like(s0), chunk)
    first = (lo // chunk) * chunk  # before the first walked chunk y stays zero
    np.testing.assert_array_equal(y2[:, first:], y3[:, first:])
    np.testing.assert_array_equal(s2, s3)
    assert not np.asarray(y2[:, :first]).any() and not np.asarray(y3[:, :lo]).any()
    want, ys = np.asarray(s0), []
    for t in range(length):  # the recurrence as written, in numpy
        want = np.exp(np.asarray(dt[:, t, None, :]) * np.asarray(a)[None]) * want + (
            np.asarray(dt[:, t] * u[:, t])[:, None, :] * np.asarray(b_in[:, t])[:, :, None])
        ys.append(np.einsum("bnd,bn->bd", want, np.asarray(c_out[:, t])))
    np.testing.assert_allclose(s, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y, np.stack(ys, 1), rtol=2e-5, atol=2e-5)


# ------------------------------------- (3), (4) through the engine's loop


def engine(config, params, **serve_kw):
    serve_kw = {
        "max_batch": 4, "decode_chunk_size": 4, "admission_window": 0.05,
        "scheduler": "continuous", "kv_mode": "paged", "page_size": PAGE,
        **serve_kw,
    }
    config = dataclasses.replace(  # ByteTokenizer's special ids
        config, bos_token_id=256, eos_token_ids=(259, 260)
    )
    eng = BatchEngine(
        config, params, ByteTokenizer(), max_seq_len=256,
        cache_dtype=jnp.float32, serve=ServeConfig(**serve_kw),
    )
    eng.start()
    return eng


def collect(handle):
    return [tok.id for tok in handle.tokens()]


def test_engine_join_and_lane_reuse_equal_the_request_alone(model):
    """Through serving.py: a late request joins a running segment (left-
    padded to the shared slot), and a third takes a lane another request
    left: each stream equals the same request served alone."""
    config, _, loaded, *_ = model
    texts = ["the first, long-running stream of this test", "late joiner",
             "a third request that takes over a lane somebody left"]
    alone = []
    for text in texts:
        eng = engine(config, loaded)
        alone.append(collect(eng.submit([Message.user(text)], 12, GREEDY)))
        eng.stop()
    eng = engine(config, loaded, max_batch=2)
    h0 = eng.submit([Message.user(texts[0])], 40, GREEDY)
    deadline = time.time() + 60
    while h0.completion_tokens < 2 and time.time() < deadline:
        time.sleep(0.005)
    h1 = eng.submit([Message.user(texts[1])], 12, GREEDY)
    got1 = collect(h1)
    h2 = eng.submit([Message.user(texts[2])], 12, GREEDY)  # h1's lane, re-used
    got2 = collect(h2)
    got0 = collect(h0)
    assert eng.stats["joins"] >= 2
    state = eng.backend.state_facts()
    assert state["layers"] == 6 and state["lane_writes"] >= 4
    assert state["bytes_per_lane"] == config.state_bytes_per_lane == 6 * 128 * (16 + 6)
    eng.stop()
    assert got0[:12] == alone[0] and got1 == alone[1] and got2 == alone[2]


PRESSURE_PAGES = 20  # both rows' prompts fit (8 pages each), their answers do not


def test_spill_and_restore_is_bit_identical_to_no_spill(model):
    """Page pressure spills a lane; the restore re-prefills its history
    through the join path, which overwrites the lane's state: the stream is
    the unpressured run's."""
    config, _, loaded, *_ = model
    texts = ["alpha prompt padded out to be long " * 2,
             "row two also made quite long here " * 2]

    def run(max_pages):
        eng = engine(config, loaded, max_pages=max_pages)
        handles = [eng.submit([Message.user(t)], 48, GREEDY) for t in texts]
        out = [collect(h) for h in handles]
        stats = dict(eng.stats)
        assert eng.quiesce()
        eng.stop()
        return out, stats

    want, big = run(64)
    got, small = run(PRESSURE_PAGES)
    assert big["preemptions"] == 0
    assert small["preemptions"] >= 1 and small["restores"] >= 1
    assert got == want


# ------------------------------------------------------- (7) the refusals

SERVE = ["--api", "127.0.0.1:1", "--api-batch", "4", "--kv-mode", "paged",
         "--prefix-cache", "off", "--cpu"]
REFUSED = {
    "--prefix-cache on": SERVE[:7] + ["on", "--cpu"],
    "--speculative-k": SERVE + ["--speculative-k", "4"],
    "--draft-model": SERVE + ["--speculative-k", "4", "--draft-model", "/nowhere"],
    "--tp": SERVE + ["--tp", "2"],
    "--sp": SERVE + ["--sp", "2"],
    "--topology": SERVE + ["--backend", "mesh"],
    "--kv-mode dense": SERVE[:4] + ["--kv-mode", "dense", "--prefix-cache", "off", "--cpu"],
    "the single-stream generator": ["--prompt", "hi", "--cpu"],
    "the single-stream generator (--api-batch 1)": SERVE[:2] + ["--cpu"],
    "--quantize": SERVE + ["--quantize", "int8"],
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_each_refused_feature_exits_with_the_one_message(model, feature, capsys):
    from cake_tpu.cli import main

    path = model[-1]
    assert main(["--model", str(path), *REFUSED[feature]]) == 2
    err = capsys.readouterr().err
    assert feature.split(" (")[0] in err
    assert "is not supported for model_type 'jamba'" in err
    assert "6 of its 8 layers keep a recurrent state per lane" in err
    assert "--kv-mode paged --prefix-cache off" in err


def test_refusals_outside_the_cli(model, tmp_path):
    config, _, loaded, _, _, path = model
    from cake_tpu.io.splitter import split_model
    from cake_tpu.models.llama.generator import LocalForwardStep

    (tmp_path / "topology.yml").write_text(
        "w0:\n  host: 127.0.0.1:1\n  layers:\n    - model.layers.0-3\n")
    with pytest.raises(UnsupportedForCacheKind, match="cake-split-model"):
        split_model(path, tmp_path / "topology.yml", tmp_path / "out")
    with pytest.raises(UnsupportedForCacheKind, match="layer range"):
        load_params(path, config, jnp.float32, layer_range=(0, 4))
    step = LocalForwardStep(config, loaded, max_seq_len=64, cache_dtype=jnp.float32)
    with pytest.raises(UnsupportedForCacheKind, match="single-stream"):
        step(np.zeros((1, 4), np.int32), 0, 4)
    for kw, name in ((dict(kv_mode="dense"), "--kv-mode dense"),
                     (dict(kv_mode="paged", prefix_cache=True), "--prefix-cache on")):
        with pytest.raises(UnsupportedForCacheKind, match=name):
            BatchEngine(config, loaded, ByteTokenizer(), max_seq_len=64,
                        cache_dtype=jnp.float32, serve=ServeConfig(max_batch=2, **kw))
    with pytest.raises(UnsupportedForCacheKind, match="--speculative-k"):
        BatchEngine(config, loaded, ByteTokenizer(), max_seq_len=64,
                    cache_dtype=jnp.float32, speculative_k=4,
                    serve=ServeConfig(max_batch=2, kv_mode="paged"))
    refuse_unsupported(LlamaConfig.tiny(), tp=True)  # no state layers: nothing


@pytest.mark.parametrize("change,message", [
    ({"num_experts": 16}, "num_experts=16"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
])
def test_what_of_jamba_is_not_brought_is_an_explicit_error(change, message):
    with pytest.raises(ValueError, match=message):
        LlamaConfig.from_hf_dict({**HF, **change})


# ---------------------------------------------- (8) loader, (9) the others


def test_loader_round_trips_the_hf_names(model):
    config, params, loaded, reader, _, path = model
    jax.tree.map(np.testing.assert_array_equal, params, loaded)
    assert [
        {name: w.shape[1:] for name, w in run.items()} for run in loaded["layers"]
    ] == [H.run_shapes(config, kind) for kind, _, _ in config.layer_runs]
    names = set(json.loads(
        (path / "model.safetensors.index.json").read_text())["weight_map"])
    per_state = {
        "mamba.in_proj.weight", "mamba.x_proj.weight", "mamba.dt_proj.weight",
        "mamba.dt_proj.bias", "mamba.out_proj.weight", "mamba.conv1d.weight",
        "mamba.conv1d.bias", "mamba.A_log", "mamba.D", "mamba.dt_layernorm.weight",
        "mamba.b_layernorm.weight", "mamba.c_layernorm.weight",
    }
    shared = {"feed_forward.gate_proj.weight", "feed_forward.up_proj.weight",
              "feed_forward.down_proj.weight", "input_layernorm.weight",
              "pre_ff_layernorm.weight"}
    attn = {f"self_attn.{p}_proj.weight" for p in "qkvo"}
    want = {"model.embed_tokens.weight", "model.final_layernorm.weight"}
    for i in range(8):
        want |= {f"model.layers.{i}.{n}" for n in shared | (attn if i % 4 == 2 else per_state)}
    assert names == want  # tied head: no lm_head.weight
    assert reader("model.layers.0.mamba.conv1d.weight").shape == (128, 1, 4)
    assert reader("model.layers.0.mamba.A_log").shape == (128, 4)
    assert loaded["layers"][0]["A_log"].shape == (2, 4, 128)
    assert loaded["layers"][0]["conv_w"].shape == (2, 4, 128)
    assert config.layer_runs == (("state", 0, 2), ("attention", 0, 1), ("state", 2, 5),
                                 ("attention", 1, 2), ("state", 5, 6))
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config


GOLDEN = json.loads((REPO / "tests/data/from_hf_dict_pr27.json").read_text())


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_the_other_families_parse_as_they_did(family):
    """``from_hf_dict`` of every family PR 27 had, field for field as PR 27's
    code gave it (tests/data/from_hf_dict_pr27.json was written by it); the
    fields PR 28 added say "every layer is attention, with RoPE", those of
    PR 32 "K and V a KV head, the whole model's experts, softmax routing"."""
    config = LlamaConfig.from_hf_dict(GOLDEN[family]["hf"])
    got = json.loads(json.dumps(dataclasses.asdict(config)))
    old = GOLDEN[family]["parsed"]
    assert {k: got[k] for k in old} == old
    assert {k: got[k] for k in set(got) - set(old)} == {
        "attn_layer_period": 0, "attn_layer_offset": 0, "mamba_d_state": 16,
        "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 0, "use_rope": True,
        "q_lora_rank": 0, "kv_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0,
        "v_head_dim": 0, "first_k_dense_replace": 0, "router_experts": 0, "expert_offset": 0,
        "moe_scoring": "softmax", "routed_scaling_factor": 1.0,
        # PR 34: no list of layer kinds, Mamba's mixer where a layer keeps a
        # state (none does), norms on a branch's input, q/k norms a head
        "layer_types": None, "state_mixer": "mamba", "linear_num_key_heads": 0,
        "linear_num_value_heads": 0, "linear_key_head_dim": 0, "linear_value_head_dim": 0,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": False,
        "pre_block_norms": True, "qk_norm_whole": False,
        # PR 41: attention layers of one kind at one head count under the
        # family's one rope, no gate a head, the feed-forward by
        # ``first_k_dense_replace``
        "attention_types": None, "heads_per_layer": None, "kind_ropes": None,
        "attn_gate": None, "attn_gate_act": "sigmoid", "ff_types": None,
        # PR 43: no group limit and no correction bias on the router, no
        # learned index over the cache, the family's one rope on a latent
        "n_group": 1, "topk_group": 1, "router_bias": False, "index_n_heads": 0,
        "index_head_dim": 0, "index_topk": 0, "latent_rope": None, "latent_mscale": 1.0,
        # PR 48: a short convolution's taps, read where it is the mixer
        "short_conv_taps": 3,
        # PR 53: the rotary term turns the whole head
        "partial_rotary_factor": 1.0,
        # PR 57: one token a step (the block-diffusion fields at their rest)
        "generation": "autoregressive", "block_length": 0, "mask_token_id": -1,
        "denoising_steps": 0, "remask": "low_confidence_dynamic",
        "confidence_threshold": 0.9}
    assert config.attention_kinds == ("full",)
    assert set(config.layer_kinds) == {"attention"} and not config.has_state_layers
    assert config.cache_kind == "kv" and len(set(config.ff_kinds)) == 1
    assert config.n_router_experts == config.num_local_experts
    assert config.layer_runs == (("attention", 0, config.num_hidden_layers),)


def test_unsupported_message_is_built_from_the_tuple():
    assert sorted([*GOLDEN, "jamba", "pangu_ultra_moe", "olmo_hybrid", "laguna",
                   "deepseek_v32", "lfm2_moe", "qwen3_next", "sdar_moe"]) == sorted(SUPPORTED_MODEL_TYPES)
    with pytest.raises(ValueError) as e:
        LlamaConfig.from_hf_dict({"model_type": "mamba2"})
    assert f"(supported: {', '.join(SUPPORTED_MODEL_TYPES)})" in str(e.value)
