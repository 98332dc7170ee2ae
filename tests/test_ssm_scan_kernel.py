"""The prefill scan's Pallas kernel (ops/pallas/selective_scan.py) under the
interpreter, held against its XLA twin (ops/ssm.selective_scan) and against
the recurrence a step at a time (the twin at ``chunk=1``).

  * the same y and the same last state for a short window, a join's window
    (300 live positions right-aligned in 512) and a long one, one row and
    three, from a zero state and from one that is there;
  * a span that leaves whole tiles of time out on both sides: they are not
    walked, y is zero there;
  * where dt is zero the state passes through BIT FOR BIT (a left pad, a
    dead tail, a row with no live position, an empty span): the engine's
    lanes rely on it;
  * ``mixer_forward`` gives the same answer with and without the kernel, and
    which form a call takes is read off its shapes: one position (decode)
    never holds a kernel call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.hybrid import run_shapes
from cake_tpu.ops import ssm as S
from cake_tpu.ops.pallas import selective_scan as K

D, N = 256, 16  # two 128-lane columns, two sublane tiles of state


def inputs(rows, length, live=None, seed=0, s0_scale=1.0, d=D, n=N):
    """(u, dt, a, b_in, c_out, s0), dt zero outside ``live`` = (lo, hi)."""
    rng = np.random.default_rng(seed + 7 * rows + length)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt = jax.nn.softplus(f32(rows, length, d) - 2.0)
    if live is not None:
        grid = jnp.arange(length)[None, :, None]
        dt = jnp.where((grid >= live[0]) & (grid < live[1]), dt, 0.0)
    return (
        f32(rows, length, d), dt, -jnp.exp(0.5 * f32(n, d)),
        f32(rows, length, n), f32(rows, length, n),
        s0_scale * jnp.abs(f32(rows, n, d)),
    )


def span_of(live):
    return jnp.int32(live[0]), jnp.int32(live[1])


# ------------------------------------------ (1) the kernel against the twin


@pytest.mark.parametrize("chunk", [S.SCAN_CHUNK, 1], ids=["chunked", "stepwise"])
@pytest.mark.parametrize("s0_scale", [0.0, 1.0], ids=["from_zero", "from_state"])
@pytest.mark.parametrize("rows", [1, 3])
@pytest.mark.parametrize(
    "length,live", [(16, (0, 16)), (512, (212, 512)), (2048, (0, 2048))],
    ids=["16", "300-in-512", "2048"],
)
def test_kernel_equals_the_xla_scan(length, live, rows, s0_scale, chunk):
    args = inputs(rows, length, live, s0_scale=s0_scale)
    y, s = K.selective_scan(*args, span_of(live))
    want_y, want_s = S.selective_scan(*args, chunk, span_of(live))
    assert y.shape == want_y.shape and y.dtype == s.dtype == jnp.float32
    lo, hi = live
    scale = float(np.abs(want_y).max())
    np.testing.assert_allclose(
        y[:, lo:hi], want_y[:, lo:hi], rtol=1e-5, atol=1e-6 * scale
    )
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-6)
    # outside the groups of eight that touch the span nothing was walked
    assert not np.asarray(y[:, : lo // 8 * 8]).any()


def test_no_span_is_the_whole_window():
    args = inputs(2, 136)  # not a multiple of a tile of time: one is picked
    y, s = K.selective_scan(*args)
    want_y, want_s = S.selective_scan(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-6)


def test_a_window_that_is_no_multiple_of_eight():
    args = inputs(1, 37)
    y, s = K.selective_scan(*args)
    want_y, want_s = S.selective_scan(*args, 1)
    assert y.shape == (1, 37, D)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------- (2) only the span


@pytest.mark.parametrize(
    "t_tile,d_block,columns", [(64, 1024, 2), (128, 128, 2), (128, 256, 1)]
)
@pytest.mark.parametrize("live", [(130, 300), (257, 263), (448, 512)])
def test_tiles_outside_the_span_are_not_walked(live, t_tile, d_block, columns):
    """512 positions in tiles of ``t_tile``, ``d_inner`` in one block or in
    two, a block's columns together or one after the other: the span leaves
    tiles out before and after. u outside the span is NaN, so a tile that
    was walked shows."""
    u, dt, a, b_in, c_out, s0 = inputs(2, 512, live)
    lo, hi = live
    first, last = lo // 8 * 8, -(-hi // 8) * 8  # the groups that touch it
    grid = jnp.arange(512)[None, :, None]
    u_nan = jnp.where((grid >= first) & (grid < last), u, jnp.nan)
    y, s = K.selective_scan(
        u_nan, dt, a, b_in, c_out, s0, span_of(live), t_tile=t_tile,
        d_block=d_block, columns=columns,
    )
    want_y, want_s = S.selective_scan(u, dt, a, b_in, c_out, s0, 1)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, first:last], want_y[:, first:last], rtol=1e-5, atol=1e-5
    )
    assert not np.asarray(y[:, :first]).any() and not np.asarray(y[:, last:]).any()


# ------------------------------------- (3) dt = 0 leaves s bit for bit


def test_dt_zero_leaves_the_state_bit_for_bit():
    """A dead tail: the state after 512 positions of which the last 212 read
    dt = 0 is, bit for bit, the state after the first 300 alone."""
    u, dt, a, b_in, c_out, s0 = inputs(2, 512, (0, 300))
    _, s = K.selective_scan(u, dt, a, b_in, c_out, s0)  # every tile walked
    cut = lambda x: x[:, :304]  # whole groups of eight
    _, want = K.selective_scan(cut(u), cut(dt), a, cut(b_in), cut(c_out), s0)
    np.testing.assert_array_equal(s, want)
    # and a window whose dt is zero throughout returns what it was given
    _, same = K.selective_scan(u, jnp.zeros_like(dt), a, b_in, c_out, s0)
    np.testing.assert_array_equal(same, s0)


@pytest.mark.parametrize("dead", [0, 1, 2])
def test_a_row_with_no_live_position_returns_its_state(dead):
    u, dt, a, b_in, c_out, s0 = inputs(3, 512, (212, 512))
    dt = dt.at[dead].set(0.0)
    y, s = K.selective_scan(u, dt, a, b_in, c_out, s0, span_of((212, 512)))
    np.testing.assert_array_equal(s[dead], s0[dead])
    _, want = S.selective_scan(u, dt, a, b_in, c_out, s0, span=span_of((212, 512)))
    np.testing.assert_allclose(s, want, rtol=1e-5, atol=1e-6)


def test_an_empty_span_walks_nothing():
    u, dt, a, b_in, c_out, s0 = inputs(2, 256)
    nan = jnp.full_like(u, jnp.nan)
    y, s = K.selective_scan(nan, dt, a, b_in, c_out, s0, span_of((96, 96)))
    np.testing.assert_array_equal(s, s0)
    assert not np.asarray(y).any()


# ------------------------------------------------ (4) what chooses the form


def mixer_layer(d_inner, n=N, hidden=64):
    config = LlamaConfig(
        model_type="jamba", hidden_size=hidden, mamba_d_state=n,
        mamba_d_conv=4, mamba_expand=d_inner // hidden, mamba_dt_rank=8,
        attn_layer_period=2, attn_layer_offset=1, num_attention_heads=4,
        num_key_value_heads=1, head_dim_override=16,
    )
    rng = np.random.default_rng(d_inner)
    ones = ("D", "dt_ln", "b_ln", "c_ln")
    return {
        name: jnp.ones(shape, jnp.float32) if name in ones
        else jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
        for name, shape in run_shapes(config, "state").items()
        if name not in ("wo", "w_gate", "w_up", "w_down", "ln_attn", "ln_mlp")
    }, hidden


def mixer_args(d_inner, rows, length, pads):
    lp, hidden = mixer_layer(d_inner)
    rng = np.random.default_rng(length)
    h = jnp.asarray(rng.normal(size=(rows, length, hidden)), jnp.float32)
    live = jnp.arange(length)[None, :] >= jnp.asarray(pads)[:, None]
    state = jnp.asarray(rng.normal(size=(rows, N, d_inner)), jnp.float32)
    window = jnp.asarray(rng.normal(size=(3, rows, d_inner)), jnp.float32)
    ends = jnp.full((rows,), length, jnp.int32)
    return lp, h, state, window, live, ends, 1e-6


def kernel_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("rows,pads", [(1, (40,)), (3, (0, 77, 128))])
def test_mixer_with_and_without_the_kernel_agree(rows, pads):
    args = mixer_args(D, rows, 128, pads)
    with_kernel = S.mixer_forward(*args, allow_pallas=True)
    without = S.mixer_forward(*args, allow_pallas=False)
    # a pad's output is nobody's: the twin's first chunk and the kernel's
    # first group start at different pads (state . C there, or zero)
    live = np.asarray(args[4])
    np.testing.assert_allclose(
        with_kernel[0][live], without[0][live], rtol=2e-5, atol=2e-5
    )
    for got, want in zip(with_kernel[1:], without[1:], strict=True):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # a row whose window is all pad keeps its state
    dead = (jnp.zeros_like(args[4]),)
    _, s, _ = S.mixer_forward(*args[:4], *dead, *args[5:], allow_pallas=True)
    np.testing.assert_array_equal(s, args[2])


@pytest.mark.parametrize(
    "d_inner,length,allow,calls",
    [
        (D, 128, True, 1),  # a window, widths that tile: the kernel
        (D, 128, False, 0),  # the switch is off: the twin
        (192, 128, True, 0),  # d_inner in no whole lane tiles: the twin
        (D, 1, True, 0),  # decode: one position, the update in line
        (D, 1, False, 0),
    ],
)
def test_the_shapes_choose_the_form(d_inner, length, allow, calls):
    lp, h, state, window, live, ends, eps = mixer_args(d_inner, 2, length, (0, 0))
    if length == 1:
        ends = None
    fn = lambda lp, h, state, window: S.mixer_forward(
        lp, h, state, window, live, ends, eps, allow_pallas=allow
    )
    assert kernel_calls(fn, lp, h, state, window) == calls


def test_widths_that_do_not_tile_are_refused_by_the_kernel():
    assert K.tiles(5120, 16) and not K.tiles(192, 16) and not K.tiles(256, 4)
    with pytest.raises(ValueError, match="do not tile"):
        K.selective_scan(*inputs(1, 16, d=192))
