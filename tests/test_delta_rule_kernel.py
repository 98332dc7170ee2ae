"""A window's gated delta rule as a Pallas kernel
(ops/pallas/delta_rule.py) under the interpreter, held against its XLA twin
(ops/delta_rule.gated_delta_rule) and against the rule one position at a
time in float64 (tests/test_hybrid_olmo.stepwise).

  * the same o and the same last state over one row and three, from zero and
    from a state, lengths that are no multiple of a chunk, a left pad and a
    dead tail, beta up to 2 over near-parallel keys, heads two a group of
    lane tiles and one a group;
  * chunks outside a row's (lo, hi) are not walked: q, k and v are NaN there,
    the result is unchanged and o is zero there;
  * a row with no live position returns its state BIT FOR BIT, an empty span
    walks nothing: the engine's lanes rely on it;
  * ``mixer_forward`` gives the same answer with and without the kernel, and
    which form a call takes is read off its shapes and the switch;
  * ``GET /stats`` engine.state.window_form says which, decided once.

Tolerances. The kernel's products are three bfloat16 passes (the twin's
``Precision.HIGH`` on the chip; on the CPU the twin's are float32): 4e-5 of
the largest value against the float64 steps (2.2e-5 seen).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops import delta_rule as D
from cake_tpu.ops.pallas import delta_rule as K
from cake_tpu.runtime.batch_backend import paged_backend

from test_hybrid_jamba import HF as JAMBA_HF
from test_hybrid_olmo import HF as OLMO_HF
from test_hybrid_olmo import delta_inputs, stepwise

PAIRS = dict(heads=4, dk=16, dv=64)  # two heads a group of 128 lanes
WHOLE = dict(heads=3, dk=8, dv=128)  # a head is a lane tile
NEAR = 4e-5  # of the largest value: the module docstring


def close(got, want, scale=None):
    want = np.asarray(want, np.float64)
    scale = float(np.abs(want).max()) if scale is None else scale
    np.testing.assert_allclose(got, want, rtol=0, atol=NEAR * scale)


def spans_of(log_alpha, beta):
    """(lo, hi) a row from the gates: a position is live where either moves."""
    live = np.asarray((log_alpha != 0) | (beta != 0)).any(-1)
    length = live.shape[1]
    lo = live.argmax(1)
    hi = np.where(live.any(1), length - live[:, ::-1].argmax(1), lo)
    return jnp.asarray(np.stack([lo, hi], 1), jnp.int32)


# ------------------------- (1) the kernel against the twin and the steps


@pytest.mark.parametrize("from_state", [False, True], ids=["from_zero", "from_state"])
@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize(
    "length,pad,tail", [(64, 0, 0), (150, 37, 9), (200, 130, 0), (37, 5, 3)],
    ids=["64", "150", "200-pad-130", "37"],
)
def test_kernel_equals_the_twin_and_the_steps(length, pad, tail, rows, from_state):
    q, k, v, log_alpha, beta, s0 = delta_inputs(
        length, rows, length, **PAIRS, pad=pad, tail=tail)
    assert float(beta.max()) > 1.5  # beta above 1 is exercised
    if not from_state:
        s0 = jnp.zeros_like(s0)
    spans = spans_of(log_alpha, beta)
    o, s = K.gated_delta_rule(q, k, v, log_alpha, beta, D.from_heads(s0), spans)
    assert o.shape == v.shape and o.dtype == s.dtype == jnp.float32
    want_o, want_s = stepwise(q, k, v, log_alpha, beta, s0)
    twin_o, twin_s = D.gated_delta_rule(q, k, v, log_alpha, beta, s0)
    scale = float(np.abs(want_o).max())
    for r, (lo, hi) in enumerate(np.asarray(spans)):
        # from the first live position's chunk to the last one's: a dead
        # position there is computed like the twin's (beta 0, alpha 1)
        first, last = lo // K.CHUNK * K.CHUNK, min(-(-hi // K.CHUNK) * K.CHUNK, length)
        close(o[r, first:last], want_o[r, first:last], scale)
        close(o[r, first:last], twin_o[r, first:last], scale)
        assert not np.asarray(o[r, :first]).any()
    close(D.to_heads(s, PAIRS["heads"]), want_s)
    close(D.to_heads(s, PAIRS["heads"]), twin_s)


@pytest.mark.parametrize("length,pad,tail", [(130, 70, 3), (64, 0, 0)], ids=["130", "64"])
@pytest.mark.parametrize("widths", [PAIRS, WHOLE], ids=["pairs", "whole"])
def test_heads_two_a_group_and_one_a_group(widths, length, pad, tail):
    """Heads of 64 values come two a group of 128 lanes (a group's triangles
    side by side, its products block-diagonal), heads of 128 one: the same
    window either way."""
    q, k, v, log_alpha, beta, s0 = delta_inputs(
        3, 2, length, **widths, pad=pad, tail=tail)
    o, s = K.gated_delta_rule(q, k, v, log_alpha, beta, D.from_heads(s0))
    want_o, want_s = stepwise(q, k, v, log_alpha, beta, s0)
    close(o, want_o)
    close(D.to_heads(s, widths["heads"]), want_s)


# ------------------------------------------------ (2) only the live chunks


@pytest.mark.parametrize("widths", [PAIRS, WHOLE], ids=["pairs", "whole"])
@pytest.mark.parametrize("live", [(130, 300), (257, 263), (448, 512)])
def test_chunks_outside_the_span_are_not_walked(live, widths):
    """512 positions, eight chunks: the span leaves chunks out before and
    after. q, k and v there are NaN, so a chunk that was walked shows."""
    lo, hi = live
    q, k, v, log_alpha, beta, s0 = delta_inputs(
        lo, 2, 512, **widths, pad=lo, tail=512 - hi)
    # every row the same span (``delta_inputs`` pads row 0, cuts the last)
    at = jnp.arange(512)[None, :, None]
    inside = (at >= lo) & (at < hi)
    log_alpha, beta = jnp.where(inside, log_alpha, 0.0), jnp.where(inside, beta, 0.0)
    first, last = lo // K.CHUNK * K.CHUNK, -(-hi // K.CHUNK) * K.CHUNK
    walked = ((at >= first) & (at < last))[..., None]
    poison = lambda x: jnp.where(walked, x, jnp.nan)
    spans = jnp.tile(jnp.asarray([[lo, hi]], jnp.int32), (2, 1))
    o, s = K.gated_delta_rule(
        poison(q), poison(k), poison(v), log_alpha, beta, D.from_heads(s0), spans,
    )
    want_o, want_s = stepwise(q, k, v, log_alpha, beta, s0)
    close(D.to_heads(s, widths["heads"]), want_s)
    close(o[:, first:last], want_o[:, first:last])
    assert not np.asarray(o[:, :first]).any() and not np.asarray(o[:, last:]).any()


# ------------------------------ (3) a dead row keeps its state bit for bit


@pytest.mark.parametrize("dead", [0, 1, 2])
def test_a_row_with_no_live_position_returns_its_state_bit_for_bit(dead):
    q, k, v, log_alpha, beta, s0 = delta_inputs(dead, 3, 150, **PAIRS)
    log_alpha, beta = log_alpha.at[dead].set(0.0), beta.at[dead].set(0.0)
    s0 = D.from_heads(s0)
    for spans in (None, spans_of(log_alpha, beta)):  # walked, and not walked
        _, s = K.gated_delta_rule(q, k, v, log_alpha, beta, s0, spans)
        np.testing.assert_array_equal(s[dead], s0[dead])
        assert np.abs(np.asarray(s - s0)).max() > 0.1  # the others moved


@pytest.mark.parametrize("at", [0, 96, 256])
def test_an_empty_span_walks_nothing(at):
    q, k, v, log_alpha, beta, s0 = delta_inputs(at, 2, 256, **PAIRS)
    nan = lambda x: jnp.full_like(x, jnp.nan)
    spans = jnp.full((2, 2), at, jnp.int32)
    o, s = K.gated_delta_rule(
        nan(q), nan(k), nan(v), log_alpha, beta, D.from_heads(s0), spans)
    np.testing.assert_array_equal(s, D.from_heads(s0))
    assert not np.asarray(o).any()


# ------------------------------------------------ (4) what chooses the form


def tiny(**changes) -> LlamaConfig:
    return LlamaConfig.from_hf_dict({**OLMO_HF, **changes})


TILING = dict(linear_value_head_dim=128)  # 3 heads of 128 values: lane tiles


def mixer_args(config, rows, length, pads):
    params = H.init_params(config, jax.random.PRNGKey(1), jnp.float32)
    lp = jax.tree.map(lambda a: a[1], params["layers"][0])
    rng = np.random.default_rng(length)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    h = f32(rows, length, config.hidden_size)
    live = jnp.arange(length)[None, :] >= jnp.asarray(pads)[:, None]
    state = f32(rows, *config.state_shape)
    window = f32(config.conv_window[0], rows, config.conv_window[1])
    ends = None if length == 1 else jnp.full((rows,), length, jnp.int32)
    return lp, h, state, window, live, ends, 1e-6


def kernel_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("rows,pads", [(1, (40,)), (3, (0, 77, 128))])
def test_mixer_with_and_without_the_kernel_agree(rows, pads):
    args = mixer_args(tiny(**TILING), rows, 130, pads)
    with_kernel = D.mixer_forward(*args, allow_pallas=True)
    without = D.mixer_forward(*args, allow_pallas=False)
    live = np.asarray(args[4])  # a pad's output is nobody's
    close(with_kernel[0][live], without[0][live])
    close(with_kernel[1], without[1])
    np.testing.assert_array_equal(with_kernel[2], without[2])
    # a row whose window is all pad keeps its state
    dead = (jnp.zeros_like(args[4]),)
    _, s, _ = D.mixer_forward(*args[:4], *dead, *args[5:], allow_pallas=True)
    np.testing.assert_array_equal(s, args[2])


@pytest.mark.parametrize(
    "widths,length,allow,calls",
    [
        (TILING, 128, True, 1),  # a window, widths that tile: the kernel
        (TILING, 128, False, 0),  # the switch is off: the twin
        ({}, 128, True, 0),  # 3 heads of 24 values, no lane tiles: the twin
        (TILING, 1, True, 0),  # one position: the one-token update in line
        (TILING, 1, False, 0),
    ],
)
def test_the_shapes_choose_the_form(widths, length, allow, calls):
    lp, h, state, window, live, ends, eps = mixer_args(
        tiny(**widths), 2, length, (0, 0))
    fn = lambda lp, h, state, window: D.mixer_forward(
        lp, h, state, window, live, ends, eps, allow_pallas=allow
    )
    assert kernel_calls(fn, lp, h, state, window) == calls


@pytest.mark.parametrize(
    "dk,heads,dv", [(96, 30, 24), (12, 4, 64), (16, 3, 64)],
    ids=["dv-24", "dk-12", "odd-heads-of-64"],
)
def test_widths_that_do_not_tile_are_refused_by_the_kernel(dk, heads, dv):
    assert K.tiles(96, 30 * 192, 192) and not K.tiles(dk, heads * dv, dv)
    q, k, v, log_alpha, beta, s0 = delta_inputs(0, 1, 16, heads, dk, dv)
    with pytest.raises(ValueError, match="do not tile"):
        K.gated_delta_rule(q, k, v, log_alpha, beta, D.from_heads(s0))


# ------------------------------------- (5) /stats engine.state.window_form


def state_facts(config, allow_pallas=True):
    params = (H if config.layers_of("state") else M).init_params(
        config, jax.random.PRNGKey(0), jnp.float32)
    return paged_backend(
        config, params, max_seq_len=128, cache_dtype=jnp.float32,
        page_size=16, max_pages=16, allow_pallas=allow_pallas,
    ).state_facts()


@pytest.mark.parametrize(
    "widths,impl,allow,form",
    [
        (TILING, "pallas", True, "pallas"),
        (TILING, "pallas", False, "xla"),  # the backend's switch is off
        (TILING, "xla", True, "xla"),  # the attention kernels' switch is off
        ({}, "pallas", True, "xla"),  # widths that do not tile
    ],
)
def test_window_form_is_a_fact_of_the_widths_and_the_switch(widths, impl, allow, form):
    config = dataclasses.replace(tiny(**widths), attention_impl=impl)
    facts = state_facts(config, allow)
    assert facts["mixer"] == "gated_delta" and facts["window_form"] == form
    assert H.window_form(config, allow) == form
    # the one-token update's kernel follows the same switch and tiles here too
    assert facts["step_form"] == H.step_form(config, allow) == form


@pytest.mark.parametrize("d_state,form", [(4, "xla"), (8, "pallas")])
def test_a_jamba_shaped_state_is_unchanged_but_for_the_key(d_state, form):
    """A vector state's ``engine.state`` as PR 34 left it, and the new keys
    beside it: its scan kernel and (PR 42) its step kernel take ``d_inner``
    128 at whole sublane tiles of ``d_state``."""
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict({**JAMBA_HF, "mamba_d_state": d_state}),
        attention_impl="pallas",
    )
    facts = state_facts(config)
    assert facts.pop("window_form") == form
    assert facts.pop("step_form") == form
    assert facts == {
        "layers": 6, "mixer": config.state_mixer,
        "bytes_per_lane": config.state_bytes_per_lane, "bytes": 0,
        "lane_writes": 0, "decode_dispatches": 0, "decode_rows": 0, "decode_lanes": 0,
    }


def test_a_model_without_state_layers_has_no_window_form():
    facts = state_facts(LlamaConfig.tiny(num_hidden_layers=2))
    assert facts["layers"] == 0 and facts["mixer"] is None
    assert facts["window_form"] is None and facts["step_form"] is None
