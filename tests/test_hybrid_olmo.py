"""``model_type: olmo_hybrid`` on the served path, at a tiny size on the CPU.

A tiny Olmo-Hybrid (hidden 64, 6 layers, ``layer_types`` two linear then one
full, twice; 3 delta-rule heads of 8 keys and 24 values: ``dk != dv`` and
``H`` no power of two; 4 attention heads on 4 KV heads; untied head; seeded
float32 weights) against the plain reference of
``bench/architectures/olmo_hybrid.py`` (the delta rule token by token, no
cache), and against itself: what the recurrence must not see (pads, dead
tails, dead lanes), what a lane must not inherit (its last tenant's state),
and what is refused outright.

Tolerances. Program and reference are both float32 here and differ in the
order of sums, and in the chunkwise form's algebra (a triangular solve and
products in place of 64 dependent updates): logits of spread about 0.3 agree
to 3e-5 (1.1e-5 seen). A state held in bfloat16 between programs is off by
2^-9 of a state of size 0.1 to 1 (2e-4 and more: the comparisons of states
here see it), ``beta = sigmoid(b)`` by the size of the state itself (every
comparison here sees it).
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
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.chat import Message, encode_dialog
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.ops import delta_rule as D
from cake_tpu.ops.pallas import delta_step
from cake_tpu.runtime.batch_backend import paged_backend
from cake_tpu.runtime.serving import BatchEngine, ServeConfig

# The engine's epoch layout, a decode dispatch, the engine fixture and the
# table of refused command lines are Jamba's tests' (one hybrid stack, two
# mixers): what is refused for one is refused for the other.
from test_hybrid_jamba import (GREEDY, PAGE, REFUSED, collect, decode, decode_program,
                               engine, lay_out, prompts, state_after_a_dispatch,
                               window_gathers)

REPO = Path(__file__).resolve().parents[1]
HF = dict(
    model_type="olmo_hybrid", hidden_size=64, intermediate_size=128, vocab_size=512,
    num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=4,
    rms_norm_eps=1e-6, attention_bias=False, tie_word_embeddings=False,
    layer_types=["linear_attention", "linear_attention", "full_attention"] * 2,
    linear_num_key_heads=3, linear_num_value_heads=3, linear_key_head_dim=8,
    linear_value_head_dim=24, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None}, bos_token_id=2, eos_token_id=2,
    pad_token_id=0, max_position_embeddings=256,
)
NEAR = dict(rtol=0, atol=3e-5)  # float32 both sides: the module docstring


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(config, params as drawn, params as loaded from an HF-named
    checkpoint, the benchmark's reader over the same files, the reference
    module, the checkpoint's path)."""
    config = LlamaConfig.from_hf_dict(HF)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    path = tmp_path_factory.mktemp("tiny_olmo_hybrid")
    save_tiny_checkpoint(path, params, config)
    loaded = load_params(path, LlamaConfig.from_model_dir(path), jnp.float32)
    return config, params, loaded, Reader(path), architecture(REPO, HF), path


def backend(config, params, **kw):
    be = paged_backend(
        config, params, max_seq_len=256, cache_dtype=jnp.float32,
        page_size=PAGE, max_pages=64, allow_pallas=False, **kw,
    )
    # picked from the config alone; what only plain K and V can do is absent
    assert be.cache_kind == config.cache_kind
    assert not hasattr(be, "suffix_prefill") and not hasattr(be, "verify_greedy")
    return be


# -------------------------------- (1) the chunkwise form against the steps


def delta_inputs(seed, b, length, heads=3, dk=8, dv=24, pad=0, tail=0):
    """Normalised q and k (keys correlated, so the triangle is not near the
    identity), gates over their whole range (beta up to 2), a left pad and a
    dead tail on row 0 and 1."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = D._unit(draw(b, length, heads, dk)) * dk ** -0.5
    k = D._unit(draw(b, length, heads, dk) + 1.5 * draw(b, 1, heads, dk))
    v = draw(b, length, heads, dv)
    log_alpha = -jax.nn.softplus(draw(b, length, heads))
    beta = 2.0 * jax.nn.sigmoid(2.0 * draw(b, length, heads))
    live = np.ones((b, length), bool)
    live[0, :pad] = False
    live[-1, length - tail:] = False
    live = jnp.asarray(live)[..., None]
    log_alpha, beta = jnp.where(live, log_alpha, 0.0), jnp.where(live, beta, 0.0)
    return q, k, v, log_alpha, beta, draw(b, heads, dk, dv)


def stepwise(q, k, v, log_alpha, beta, s):
    """The recurrence as written, one position at a time, in numpy float64."""
    q, k, v, log_alpha, beta, s = (np.asarray(x, np.float64) for x in (q, k, v, log_alpha, beta, s))
    out = []
    for t in range(q.shape[1]):
        s = np.exp(log_alpha[:, t])[..., None, None] * s  # s is S^T: [b, H, dk, dv]
        err = v[:, t] - np.einsum("bhkv,bhk->bhv", s, k[:, t])
        s = s + np.einsum("bhk,bhv->bhkv", k[:, t], beta[:, t][..., None] * err)
        out.append(np.einsum("bhkv,bhk->bhv", s, q[:, t]))
    return np.stack(out, 1), s


@pytest.mark.parametrize("length,chunk,pad,tail", [
    (150, 64, 37, 9),  # two chunks and a part, pads that end inside a chunk
    (64, 64, 0, 0), (65, 64, 1, 0), (37, 16, 5, 3), (5, 8, 2, 1), (200, 64, 130, 0),
])
def test_chunkwise_form_equals_the_stepwise_recurrence(length, chunk, pad, tail):
    """Float32 against float64 steps: 2e-5 of outputs and states of size 1 to
    3 (3e-6 seen); the chunk's algebra is exact, so only rounding differs."""
    args = delta_inputs(length, 2, length, pad=pad, tail=tail)
    assert float(args[4].max()) > 1.5  # beta above 1 is exercised
    o, s = jax.jit(D.gated_delta_rule, static_argnames="chunk")(*args, chunk=chunk)
    want_o, want_s = stepwise(*args)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-5)
    np.testing.assert_allclose(s, want_s, rtol=0, atol=2e-5)
    # and the one-token form is the same recurrence
    s1, outs = args[5], []
    for t in range(min(length, 20)):
        o1, s1 = D.gated_delta_step(*(x[:, t] for x in args[:5]), s1)
        outs.append(o1)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o[:, :len(outs)], rtol=0, atol=2e-5)


def test_a_row_with_no_live_position_returns_its_state_bit_for_bit():
    q, k, v, log_alpha, beta, s0 = delta_inputs(3, 2, 70)
    dead = jnp.asarray([True, False])[:, None, None]
    log_alpha, beta = jnp.where(dead, 0.0, log_alpha), jnp.where(dead, 0.0, beta)
    _, s = D.gated_delta_rule(q, k, v, log_alpha, beta, s0)
    np.testing.assert_array_equal(s[0], s0[0])
    assert np.abs(np.asarray(s[1] - s0[1])).max() > 0.1
    _, s = D.gated_delta_step(q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], s0)
    np.testing.assert_array_equal(s[0], s0[0])


@pytest.mark.parametrize("heads,dk,dv", [(3, 8, 128), (6, 16, 64)])
def test_the_step_kernel_equals_its_twin_in_place(heads, dk, dv):
    """``ops/pallas/delta_step.py`` (interpreted here) on layer 1 of a stack
    of 3, at heads that are whole lane tiles (one a group) and at heads of
    64 values (two a group, told apart by a select on the lane's index): the
    twin's output and state to float32 rounding (the sums over ``dk`` are
    taken in another order), the other layers and a dead row bit for bit."""
    q, k, v, log_alpha, beta, _ = delta_inputs(7, 4, 1, heads, dk, dv)
    q, k, v, log_alpha, beta = (x[:, 0] for x in (q, k, v, log_alpha, beta))
    log_alpha, beta = log_alpha.at[2].set(0.0), beta.at[2].set(0.0)  # row 2 is not live
    stack = jnp.asarray(np.random.default_rng(0).normal(size=(3, 4, dk, heads * dv)), jnp.float32)
    assert delta_step.tiles(dk, heads * dv, dv) and not delta_step.tiles(8, 72, 24)
    o, out = delta_step.gated_delta_step(stack, jnp.int32(1), q, k, v, log_alpha, beta)
    want_o, want_s = D.gated_delta_step(q, k, v, log_alpha, beta, D.to_heads(stack[1], heads))
    np.testing.assert_allclose(o, want_o, rtol=0, atol=2e-6)
    np.testing.assert_allclose(out[1], D.from_heads(want_s), rtol=0, atol=2e-6)
    np.testing.assert_array_equal(out[0], stack[0])
    np.testing.assert_array_equal(out[2], stack[2])
    np.testing.assert_array_equal(out[1, 2], stack[1, 2])
    with pytest.raises(ValueError, match="do not tile"):
        delta_step.gated_delta_step(
            jnp.zeros((1, 1, 8, 72)), 0, *(x[:1, :, :8] for x in (q, k)), v[:1, :, :24],
            log_alpha[:1], beta[:1])


def test_the_mixer_takes_the_kernel_where_the_widths_tile():
    """One decode step of a stack whose state tiles (3 heads of 128 values):
    through ``mixer_step_stacked`` and through the XLA form, the same
    ``gated``, state and window; a layer whose widths do not tile (the tiny
    model's) has no kernel call in its program."""
    hf = {**HF, "linear_value_head_dim": 128}
    config = LlamaConfig.from_hf_dict(hf)
    params = H.init_params(config, jax.random.PRNGKey(1), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"][0])
    rng = np.random.default_rng(5)
    stack = jnp.asarray(rng.normal(size=(2, 4, *config.state_shape)), jnp.float32)
    window = jnp.asarray(rng.normal(size=(3, 4, config.conv_window[1])), jnp.float32)
    h = jnp.asarray(rng.normal(size=(4, 1, 64)), jnp.float32)
    live = jnp.asarray([[True], [True], [False], [True]])
    assert D.steps_in_place(stack, 3)
    gated, out, conv = D.mixer_step_stacked(lp, h, stack, jnp.int32(1), window, live, 1e-6)
    want, s, want_conv = D.mixer_forward(lp, h, stack[1], window, live, None, 1e-6)
    at = np.asarray(live[:, 0])
    np.testing.assert_allclose(np.asarray(gated)[at], np.asarray(want)[at], rtol=0, atol=2e-6)
    np.testing.assert_allclose(out[1], s, rtol=0, atol=2e-6)
    np.testing.assert_array_equal(conv, want_conv)
    np.testing.assert_array_equal(out[1, 2], stack[1, 2])  # the dead lane
    # its ``o`` is zero where the twin's is S q of a state it read: nobody's either way
    assert not np.asarray(gated)[2].any() and np.asarray(want)[2].any()
    np.testing.assert_array_equal(conv[:, 2], window[:, 2])
    tiny = H.init_hybrid_cache(LlamaConfig.from_hf_dict(HF), 2, 4, PAGE, jnp.float32)
    assert not D.steps_in_place(tiny.ssm, 3)


# The two cells' head shapes cut small: Olmo-Hybrid's dk 96 / dv 192 (a
# group is two heads: 384 lanes), and Qwen3-Next's dk 128 / dv 128 with 16
# key heads under 32 value heads. Two blocks of columns a row in both.
STEP_SHAPES = {
    "olmo": dict(heads=4, key_heads=4, dk=96, dv=192, w_block=384),
    "qwen3next": dict(heads=32, key_heads=16, dk=128, dv=128, w_block=2048),
}
LIVE_MASKS = {
    "all_live": (1, 1, 1, 1, 1, 1), "alternating": (1, 0, 1, 0, 1, 0),
    "first_and_last_dead": (0, 1, 1, 1, 1, 0), "one_live": (0, 0, 0, 1, 0, 0),
    "none_live": (0, 0, 0, 0, 0, 0),
}


def step_inputs(seed, b, heads, key_heads, dk, dv):
    """One position's q, k (a key head read by its group of value heads, as
    ``D._to_value_heads`` hands them to the kernels), v and gates."""
    q, k, _, _, _, _ = delta_inputs(seed, b, 1, key_heads, dk, dv)
    _, _, v, log_alpha, beta, _ = delta_inputs(seed + 1, b, 1, heads, dk, dv)
    q, k = D._to_value_heads(q, heads), D._to_value_heads(k, heads)
    return tuple(x[:, 0] for x in (q, k, v, log_alpha, beta))


@pytest.mark.parametrize("mask", LIVE_MASKS)
@pytest.mark.parametrize("shape", STEP_SHAPES)
def test_the_step_kernel_walks_the_live_rows_alone(shape, mask):
    """Layer 1 of a stack of 3, six rows under a mask: a live row's ``o``
    and state are the twin's to float32 rounding; a dead row's state is the
    input's BIT FOR BIT (it is not read: gates that are NOT the identity ride
    in for it here, and the twin is given the identity), its ``o`` exactly
    zero; the other layers untouched. With no row live the one row the grid
    still walks goes back as it came."""
    widths = dict(STEP_SHAPES[shape])
    w_block, heads = widths.pop("w_block"), widths["heads"]
    live = jnp.asarray(LIVE_MASKS[mask], bool)
    q, k, v, log_alpha, beta = step_inputs(11, 6, **widths)
    stack = jnp.asarray(
        np.random.default_rng(3).normal(size=(3, 6, widths["dk"], heads * widths["dv"])),
        jnp.float32)
    stack = stack.at[1, 5].multiply(-0.0)  # zeros of both signs in a row: a copy keeps them
    o, out = delta_step.gated_delta_step(
        stack, jnp.int32(1), q, k, v, log_alpha, beta, live, w_block=w_block)
    want_o, want_s = D.gated_delta_step(
        q, k, v, jnp.where(live[:, None], log_alpha, 0.0), jnp.where(live[:, None], beta, 0.0),
        D.to_heads(stack[1], heads))
    at, dead = np.asarray(live), ~np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[at], np.asarray(want_o)[at], rtol=0, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(out[1])[at], np.asarray(D.from_heads(want_s))[at], rtol=0, atol=2e-6)
    assert np.asarray(out[1])[dead].tobytes() == np.asarray(stack[1])[dead].tobytes()
    if mask != "none_live":  # (the twin's -0.0 + 0.0 is +0.0: the kernel's copy is stricter)
        np.testing.assert_array_equal(np.asarray(D.from_heads(want_s))[dead][:-1], np.asarray(stack[1])[dead][:-1])
    assert not np.asarray(o)[dead].any()
    np.testing.assert_array_equal(out[0], stack[0])
    np.testing.assert_array_equal(out[2], stack[2])
    if at.any():
        assert np.abs(np.asarray(out[1] - stack[1])[at]).max() > 0.1


def test_live_rows_lists_the_live_rows_first_and_counts_them():
    for mask in LIVE_MASKS.values():
        rows = np.asarray(delta_step.live_rows(jnp.asarray(mask, bool)))
        n = sum(mask)
        assert rows[-1] == n and sorted(rows[:-1]) == list(range(6))
        assert list(rows[:n]) == [r for r in range(6) if mask[r]]
        assert list(rows[n:-1]) == [r for r in range(6) if not mask[r]]


def test_eight_steps_in_a_scan_under_one_mask_equal_the_twins():
    """The served shape: a decode chunk's eight steps of a state layer
    through ``mixer_step_stacked`` inside a ``lax.scan``, the live rows made
    ONCE outside it, against the XLA form stepped the same way. Dead rows
    (the first and the last among them) hold their state and window bit for
    bit after all eight, and their ``gated`` is finite (a norm of zeros)."""
    hf = {**HF, "linear_value_head_dim": 128}
    config = LlamaConfig.from_hf_dict(hf)
    params = H.init_params(config, jax.random.PRNGKey(1), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"][0])
    rng = np.random.default_rng(9)
    stack = jnp.asarray(rng.normal(size=(2, 5, *config.state_shape)), jnp.float32)
    window = jnp.asarray(rng.normal(size=(3, 5, config.conv_window[1])), jnp.float32)
    hs = jnp.asarray(rng.normal(size=(8, 5, 1, 64)), jnp.float32)
    live = jnp.asarray([[False], [True], [False], [True], [False]])

    @jax.jit
    def kernel(stack, window):
        rows = D.live_rows(live[:, 0])

        def step(carry, h):
            gated, stack, window = D.mixer_step_stacked(
                lp, h, carry[0], jnp.int32(1), carry[1], live, 1e-6, rows=rows)
            return (stack, window), gated

        return jax.lax.scan(step, (stack, window), hs)

    @jax.jit
    def twin(s, window):
        def step(carry, h):
            gated, s, window = D.mixer_forward(lp, h, *carry, live, None, 1e-6)
            return (s, window), gated

        return jax.lax.scan(step, (s, window), hs)

    (out, conv), gated = kernel(stack, window)
    (want_s, want_conv), want = twin(stack[1], window)
    at = np.asarray(live[:, 0])
    np.testing.assert_allclose(np.asarray(gated)[:, at], np.asarray(want)[:, at], rtol=0, atol=5e-6)
    np.testing.assert_allclose(out[1], want_s, rtol=0, atol=5e-6)
    np.testing.assert_array_equal(conv, want_conv)
    np.testing.assert_array_equal(np.asarray(out[1])[~at], np.asarray(stack[1])[~at])
    np.testing.assert_array_equal(out[0], stack[0])
    assert np.isfinite(np.asarray(gated)).all() and not np.asarray(gated)[:, ~at].any()
    assert np.abs(np.asarray(out[1] - stack[1])[at]).max() > 0.1


def test_the_live_rows_are_listed_once_a_dispatch():
    """The decode chunk of a model whose step is the kernel sorts its lanes'
    mask ONCE, at the program's top, outside the step scan and the runs'
    layer scans; every ``gated_delta_step`` call inside them takes that list
    (two scalar-prefetch operands). A model whose step is the twin sorts
    nothing."""
    def sorts(jaxpr, depth=0):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                found.append(depth)
            if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "gated_delta_step":
                assert eqn.params["grid_mapping"].num_index_operands == 2 and depth >= 2
                found.append("kernel")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if eqn.primitive.name != "pallas_call":
                    found += sorts(sub, depth + (eqn.primitive.name in ("scan", "while")))
        return found

    tiling = dataclasses.replace(
        LlamaConfig.from_hf_dict({**HF, "linear_value_head_dim": 128}), attention_impl="pallas")
    found = sorts(decode_program(tiling, lanes=4, allow_pallas=True).jaxpr.jaxpr)
    assert found.count(0) == 1 and found.count("kernel") >= 1
    assert set(found) == {0, "kernel"}, found
    assert sorts(decode_program(LlamaConfig.from_hf_dict(HF), lanes=4).jaxpr.jaxpr) == []


@pytest.mark.parametrize("form", ["pallas", "xla"])
def test_engine_state_counts_the_rows_stepped_and_the_lanes(form):
    """``/stats engine.state`` after two decode dispatches of four lanes with
    two and then one live: ``decode_lanes`` adds the rows of each, and
    ``decode_rows`` the rows the one-token update read and wrote: the live
    ones where the step is the kernel (interpreted here), every row where it
    is the twin. Either way the dead lanes' state comes back bit for bit."""
    config = LlamaConfig.from_hf_dict({**HF, "linear_value_head_dim": 128})
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    if form == "xla":
        be = backend(config, params)
    else:
        be = paged_backend(
            dataclasses.replace(config, attention_impl="pallas"), params, max_seq_len=256,
            cache_dtype=jnp.float32, page_size=128, max_pages=16, allow_pallas=True)
    assert be.state_facts()["step_form"] == form
    assert H.steps_live_rows(be.config, be.allow_pallas) == (form == "pallas")
    cache, tokens, pads = lay_out(be, prompts(4, 18, 27), 4, 32)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    before = np.asarray(cache.ssm)
    toks, cache = decode(be, cache, tok, 32, pads, 4, live=(0, 1))
    state = be.state_facts()
    assert (state["decode_dispatches"], state["decode_lanes"]) == (1, 4)
    assert state["decode_rows"] == (2 if form == "pallas" else 4)
    be.allocator.release(1)
    after = np.asarray(cache.ssm)
    _, cache = decode(be, cache, toks[:, -1], 36, pads, 4, live=(0,))
    state = be.state_facts()
    assert (state["decode_dispatches"], state["decode_lanes"]) == (2, 8)
    assert state["decode_rows"] == (3 if form == "pallas" else 8)
    np.testing.assert_array_equal(after[:, 2:], before[:, 2:])
    np.testing.assert_array_equal(np.asarray(cache.ssm)[:, 1:], after[:, 1:])
    assert not np.array_equal(after[:, :2], before[:, :2])


# ------------------------------------------ (2) against the plain reference


def test_prefill_then_decode_matches_the_reference(model):
    """Paged prefill (70 and 101 tokens: the chunkwise form over two chunks,
    left pads that end inside a chunk), then 24 decode steps through the
    cache, against the reference's LOGITS on the full sequence, and its
    argmax at every served position."""
    config, _, loaded, reader, arch, _ = model
    be = backend(config, loaded)
    rows = prompts(0, 70, 101)
    cache, tokens, pads = lay_out(be, rows, 4, 112)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    want = arch.forward_logits(reader, HF, rows)
    for r in range(2):
        np.testing.assert_allclose(logits[r], want[r][-1], **NEAR)
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    served = [[int(tok[r])] for r in range(2)]
    slot = 112
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
    # the state after 24 steps through the cache is the reference's: one more
    # prefill of the whole sequence lands on the same state
    again = backend(config, loaded)
    whole = [p + s[:-1] for p, s in zip(rows, served)]
    a_cache, a_tokens, a_pads = lay_out(again, whole, 4, 128)
    _, a_cache = again.prefill(a_tokens, a_cache, jnp.asarray(a_pads))
    np.testing.assert_allclose(cache.ssm[:, :2], a_cache.ssm[:, :2], **NEAR)
    assert np.abs(np.asarray(a_cache.ssm[:, :2])).max() > 0.05


def test_beta_above_one_occurs_and_the_reference_without_it_differs(model):
    """``linear_allow_neg_eigval``: beta = 2 sigmoid(b) passes 1 in the drawn
    weights at the model's own activations, and a reference that takes
    sigmoid(b), or leaves the decay out, is 100 tolerances away."""
    config, params, loaded, reader, arch, _ = model
    (row,) = prompts(9, 40)
    lp = jax.tree.map(lambda a: a[1], params["layers"][0])  # layer 1: unit-size input
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 40, 64)), jnp.float32)
    beta = D._inputs(lp, x, jnp.zeros((3, 1, config.conv_window[1])),
                     jnp.ones((1, 40), bool), None, True)[4]
    assert float(beta.max()) > 1.2 and float(beta.min()) < 0.8
    be = backend(config, loaded)
    cache, tokens, pads = lay_out(be, [row], 2, 48)
    logits, _ = be.prefill(tokens, cache, jnp.asarray(pads))
    sound = arch.forward_logits(reader, HF, [row])[0][-1]
    np.testing.assert_allclose(logits[0], sound, **NEAR)
    for fault in arch.FAULTS:
        arch.FAULT = fault
        try:
            wrong = arch.forward_logits(reader, HF, [row])[0][-1]
        finally:
            arch.FAULT = None
        assert np.abs(wrong - np.asarray(logits[0])).max() > 100 * NEAR["atol"], fault


# ------------------------------------------- (3) pads, joins and re-use


def test_a_left_padded_row_equals_the_row_unpadded(model):
    config, _, loaded, *_ = model
    (ids,) = prompts(1, 75)
    out = {}
    for bucket in (80, 144):  # 5 pads; 69 pads: a chunk of nothing but pads
        be = backend(config, loaded)
        cache, tokens, pads = lay_out(be, [ids], 2, bucket)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        out[bucket] = (np.asarray(logits[0]), np.asarray(cache.ssm[:, 0]),
                       np.asarray(cache.conv[:, :, 0]))
    # the pads move the row's tokens to other places in their chunks: the
    # same sums in another order
    for a, b in zip(out[80], out[144]):
        np.testing.assert_allclose(a, b, **NEAR)
    assert np.abs(out[80][1]).max() > 0.05  # a state was there to compare


def test_a_joined_row_and_a_reused_lane_equal_the_row_alone(model):
    """Lane 1 first serves another request (its state is left behind), then
    a joiner takes it while lane 0 runs on: the joiner's logits and state
    are those of the same row prefilled alone in a fresh cache: the old
    tenant's state is OVERWRITTEN, not continued."""
    config, _, loaded, *_ = model
    first, other, joiner = prompts(2, 20, 33, 70)
    be = backend(config, loaded)
    cache, tokens, pads = lay_out(be, [first, other], 2, 48)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    toks, cache = decode(be, cache, tok, 48, pads, 8, live=(0, 1))
    stale = np.asarray(cache.ssm[:, 1])
    be.allocator.release(1)  # the other request ends; its state stays
    slot = 96
    toks, cache = decode(be, cache, toks[:, -1], 56, pads, 8, live=(0,))
    start, width = be.shapes.window(slot - len(joiner), slot, 256)
    row = np.zeros((1, width), np.int32)
    row[0, slot - len(joiner) - start:slot - start] = joiner
    be.allocator.map_range(1, slot - len(joiner), slot)
    lane0 = np.asarray(cache.ssm[:, 0])
    j_logits, cache = be.join(
        cache, row, jnp.asarray([slot - len(joiner)], jnp.int32),
        jnp.asarray([slot], jnp.int32), 1, start,
    )
    np.testing.assert_array_equal(np.asarray(cache.ssm[:, 0]), lane0)
    alone = backend(config, loaded)
    a_cache, a_tokens, a_pads = lay_out(alone, [joiner], 2, 80)
    a_logits, a_cache = alone.prefill(a_tokens, a_cache, jnp.asarray(a_pads))
    np.testing.assert_allclose(j_logits[0], a_logits[0], **NEAR)
    np.testing.assert_allclose(cache.ssm[:, 1], a_cache.ssm[:, 0], **NEAR)
    np.testing.assert_allclose(cache.conv[:, :, 1], a_cache.conv[:, :, 0], **NEAR)
    assert np.abs(stale - np.asarray(a_cache.ssm[:, 0])).max() > 0.01
    assert be.state_facts()["lane_writes"] == 3  # two at the prefill, one join


def test_a_lane_that_is_not_live_keeps_its_state(model):
    config, _, loaded, *_ = model
    be = backend(config, loaded)
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


def test_a_decode_step_shifts_the_window_by_a_slice(model, monkeypatch):
    """The delta rule's window (q | k | v, ``D._inputs``) goes through the
    same helper as Jamba's: no gather over it in a decode program, and
    ``cache.conv`` after a dispatch is the gathered form's bit for bit."""
    config, _, loaded, *_ = model
    assert window_gathers(decode_program(config), config, 3) == []
    make = lambda: backend(config, loaded)
    sliced = state_after_a_dispatch(make, monkeypatch, gathered=False)
    jax.tree.map(
        np.testing.assert_array_equal, sliced,
        state_after_a_dispatch(make, monkeypatch, gathered=True))
    assert np.abs(sliced[0]).max() > 0


def test_an_epoch_prefill_in_groups_equals_one_program(model):
    config, _, loaded, *_ = model
    rows = prompts(3, 9, 70, 17, 25)
    out = []
    for budget in (1 << 20, 256):  # one program of 4 rows; four of 1 row
        be = backend(config, loaded)
        be.shapes = dataclasses.replace(be.shapes, prefill_tokens=budget)
        cache, tokens, pads = lay_out(be, rows, 4, 80)
        logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
        out.append(jax.tree.map(np.asarray, (logits, cache)))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, **NEAR), *out)
    assert np.abs(out[0][1].ssm).max() > 0.01


# ------------------------------------------ (4) through the engine's loop


def test_engine_join_and_lane_reuse_equal_the_request_alone(model):
    """Through serving.py: a late request joins a running segment, and a
    third takes a lane another request left: each stream equals the same
    request served alone; ``engine.state`` names the mixer and its bytes."""
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
    assert state["layers"] == 4 and state["mixer"] == "gated_delta"
    assert state["lane_writes"] >= 4 and state["decode_rows"] >= state["decode_dispatches"] > 0
    # a layer: float32 [dk 8, H dv 72] and a bf16-counted window [3, 2*24 + 72]
    assert state["bytes_per_lane"] == config.state_bytes_per_lane == 4 * (4 * 8 * 72 + 2 * 3 * 120)
    eng.stop()
    assert got0[:12] == alone[0] and got1 == alone[1] and got2 == alone[2]


# ------------------------------------------------------- (5) the refusals

@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_each_refused_feature_exits_with_the_one_message(model, feature, capsys):
    """Everything ``capability.REFUSED`` refuses for Jamba is refused here,
    with the same message."""
    from cake_tpu.cli import main

    path = model[-1]
    assert main(["--model", str(path), *REFUSED[feature]]) == 2
    err = capsys.readouterr().err
    assert feature.split(" (")[0] in err
    assert "is not supported for model_type 'olmo_hybrid'" in err
    assert "4 of its 6 layers keep a recurrent state per lane" in err
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
    with pytest.raises(UnsupportedForCacheKind, match="--prefix-cache on"):
        BatchEngine(config, loaded, ByteTokenizer(), max_seq_len=64,
                    cache_dtype=jnp.float32,
                    serve=ServeConfig(max_batch=2, kv_mode="paged", prefix_cache=True))
    for fact in ("tp", "sp", "quantize", "draft_model", "distributed"):
        with pytest.raises(UnsupportedForCacheKind, match="olmo_hybrid"):
            refuse_unsupported(config, **{fact: True})


@pytest.mark.parametrize("change,message", [
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    ({"layer_types": ["linear_attention"] * 5}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 6}, "layer_types"),
    ({"linear_num_key_heads": 1}, "linear_num_key_heads"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_what_of_olmo_hybrid_is_not_brought_is_an_explicit_error(change, message):
    with pytest.raises(ValueError, match=message):
        LlamaConfig.from_hf_dict({**HF, **change})


# ------------------------------------------- (6) loader, config, template


def test_loader_round_trips_the_hf_names(model):
    config, params, loaded, reader, arch, path = model
    jax.tree.map(np.testing.assert_array_equal, params, loaded)
    assert [
        {name: w.shape[1:] for name, w in run.items()} for run in loaded["layers"]
    ] == [H.run_shapes(config, kind) for kind, _, _ in config.layer_runs]
    names = set(json.loads(
        (path / "model.safetensors.index.json").read_text())["weight_map"])
    # the checkpoint holds exactly the architecture file's table
    want = set(arch.top_tensors(HF))
    for i in range(6):
        want |= set(arch.layer_tensors(HF, i))
    assert names == want
    for name, (shape, _) in {**arch.layer_tensors(HF, 0), **arch.layer_tensors(HF, 2),
                             **arch.top_tensors(HF)}.items():
        assert reader(name).shape == shape, name
    assert loaded["layers"][0]["in_proj"].shape == (2, 64, 24 + 24 + 72 + 72)
    assert loaded["layers"][0]["conv_w"].shape == (2, 4, 120)
    assert "ln_attn" not in loaded["layers"][0] and "ln_mlp" not in loaded["layers"][1]
    assert loaded["layers"][1]["q_norm"].shape == (1, 64)  # the whole projection
    assert config.layer_runs == (("state", 0, 2), ("attention", 0, 1),
                                 ("state", 2, 4), ("attention", 1, 2))
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config


def test_the_config_says_the_mixer_and_the_state_by_it(model):
    config = model[0]
    arch = model[4]
    assert (config.state_mixer, config.cache_kind) == ("gated_delta", "kv+state")
    assert (config.state_shape, config.conv_window) == ((8, 72), (3, 120))
    assert config.state_bytes_per_lane == arch.state_bytes_per_lane(HF)
    assert not config.use_rope and not config.pre_block_norms and config.post_block_norms
    jamba = LlamaConfig.from_hf_dict(dict(
        model_type="jamba", hidden_size=64, num_hidden_layers=8, attn_layer_period=4,
        attn_layer_offset=2, mamba_d_state=4, mamba_dt_rank=4, num_experts=1,
        num_attention_heads=4, num_key_value_heads=1))
    # one reader for both sources of the layer kinds, one formula for both mixers
    assert jamba.layer_kinds == ("state", "state", "attention", "state") * 2
    assert (jamba.state_mixer, jamba.state_shape, jamba.conv_window) == ("mamba", (4, 128), (3, 128))
    assert jamba.state_bytes_per_lane == 6 * 128 * (16 + 6)


def test_the_template_is_the_architecture_files():
    arch = architecture(REPO, HF)
    assert encode_dialog([Message.user("w5 w9")], "olmo_hybrid") == arch.chat_text("w5 w9")
    text = encode_dialog([Message.system("s"), Message.user("u"), Message.assistant("a"),
                          Message.user("v")], "olmo_hybrid")
    assert text == ("<|endoftext|><|system|>\ns\n<|user|>\nu\n<|assistant|>\na<|endoftext|>\n"
                    "<|user|>\nv\n<|assistant|>\n")
