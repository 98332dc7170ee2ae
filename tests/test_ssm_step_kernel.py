"""The one-token update's Pallas kernel (ops/pallas/selective_step.py) under
the interpreter, held against its XLA twin, ``ops/ssm.mixer_forward``'s
decode branch.

  * the same ``y`` and the same new state at 1, 3 and 8 rows and two widths,
    whatever block of rows a grid step takes;
  * the first, a middle and the last layer of a stack: every OTHER layer's
    state comes back bit for bit (the stack is the operand, aliased);
  * a row with ``dt = 0`` and ``u = 0`` (a lane that is not live) gets its
    state back BIT FOR BIT: the engine's lanes rely on it;
  * widths that do not tile are refused by the kernel and served by the
    twin: ``mixer_step_stacked`` where ``steps_in_place`` holds,
    ``mixer_forward`` everywhere else, the same ``gated``, state and window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.ops import ssm as S
from cake_tpu.ops.pallas import selective_step as K

from test_ssm_scan_kernel import mixer_layer

WIDTHS = [(256, 16), (384, 8)]  # (d_inner, d_state): whole tiles both
NEAR = dict(rtol=1e-5, atol=1e-6)  # float32 both sides; sums in another order


def inputs(layers, rows, d, n, seed=0, dead=()):
    """(stack, u, dt, a, b_in, c_out); ``dead`` rows read dt = 0 and u = 0."""
    rng = np.random.default_rng(seed + 7 * rows + d)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    alive = jnp.asarray([r not in dead for r in range(rows)])[:, None]
    return (
        f32(layers, rows, n, d), jnp.where(alive, f32(rows, d), 0.0),
        jnp.where(alive, jax.nn.softplus(f32(rows, d) - 2.0), 0.0),
        -jnp.exp(0.5 * f32(n, d)), f32(rows, n), f32(rows, n),
    )


def twin(stack, layer, u, dt, a, b_in, c_out):
    """``ops/ssm.mixer_forward``'s decode branch over one layer of a stack."""
    s = jnp.exp(dt[:, None, :] * a[None]) * stack[layer] + (
        (dt * u)[:, None, :] * b_in[:, :, None]
    )
    return jnp.einsum("bnd,bn->bd", s, c_out), s


# ------------------------------------------ (1) the kernel against the twin


@pytest.mark.parametrize("block", [None, 1, 2], ids=["own_block", "1_row", "2_rows"])
@pytest.mark.parametrize("d,n", WIDTHS)
@pytest.mark.parametrize("rows", [1, 3, 8])
def test_kernel_equals_the_decode_branch(rows, d, n, block):
    stack, *args = inputs(2, rows, d, n)
    kw = {} if block is None else {"rows": block, "w_block": 128}
    y, out = K.selective_step(stack, jnp.int32(1), *args, **kw)
    want_y, want_s = twin(stack, 1, *args)
    assert y.shape == (rows, d) and y.dtype == out.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, **NEAR)
    np.testing.assert_allclose(out[1], want_s, **NEAR)


# -------------------------------------- (2) one layer of the stack, in place


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
def test_every_other_layer_comes_back_bit_for_bit(layer):
    stack, *args = inputs(5, 3, *WIDTHS[0], seed=layer)
    y, out = K.selective_step(stack, jnp.int32(layer), *args)
    want_y, want_s = twin(stack, layer, *args)
    np.testing.assert_allclose(y, want_y, **NEAR)
    np.testing.assert_allclose(out[layer], want_s, **NEAR)
    others = [i for i in range(5) if i != layer]
    np.testing.assert_array_equal(out[jnp.asarray(others)], stack[jnp.asarray(others)])
    assert not np.array_equal(out[layer], stack[layer])


# ----------------------------------------------------------- (3) dead rows


@pytest.mark.parametrize("dead", [(0,), (1, 2), (0, 1, 2, 3)], ids=str)
def test_a_dead_row_gets_its_state_back_bit_for_bit(dead):
    stack, *args = inputs(3, 4, *WIDTHS[0], dead=dead)
    _, out = K.selective_step(stack, jnp.int32(1), *args)
    rows = jnp.asarray(dead)
    np.testing.assert_array_equal(out[1, rows], stack[1, rows])
    live = [r for r in range(4) if r not in dead]
    for r in live:
        assert not np.array_equal(out[1, r], stack[1, r])


# ------------------------------------------------ (4) what chooses the form


@pytest.mark.parametrize(
    "d,n", [(192, 16), (256, 4), (100, 8)], ids=["d_192", "n_4", "d_100"]
)
def test_widths_that_do_not_tile_are_refused_and_served_by_the_twin(d, n):
    assert K.tiles(5120, 16) and not K.tiles(d, n)
    stack, *args = inputs(2, 2, d, n)
    assert not S.steps_in_place(stack)
    with pytest.raises(ValueError, match="do not tile"):
        K.selective_step(stack, jnp.int32(0), *args)
    y, s = twin(stack, 0, *args)
    assert y.shape == (2, d) and s.shape == stack[0].shape


@pytest.mark.parametrize("rows,live", [(1, (True,)), (4, (True, True, False, True))])
def test_the_mixer_in_place_equals_the_mixer_over_a_slice(rows, live):
    """One decode step of a layer through ``mixer_step_stacked`` (the stack
    whole, the kernel) and through ``mixer_forward`` over the layer's slice:
    the same ``gated`` and state to rounding, the same window bit for bit;
    a dead row keeps state and window."""
    d, n = WIDTHS[0]
    lp, hidden = mixer_layer(d, n)
    rng = np.random.default_rng(rows)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    stack, window, h = f32(3, rows, n, d), f32(3, rows, d), f32(rows, 1, hidden)
    alive = jnp.asarray(live)[:, None]
    assert S.steps_in_place(stack)
    gated, out, conv = S.mixer_step_stacked(lp, h, stack, jnp.int32(1), window, alive, 1e-6)
    want, s, want_conv = S.mixer_forward(lp, h, stack[1], window, alive, None, 1e-6)
    np.testing.assert_allclose(gated, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out[1], s, **NEAR)
    np.testing.assert_array_equal(conv, want_conv)
    np.testing.assert_array_equal(out[jnp.asarray([0, 2])], stack[jnp.asarray([0, 2])])
    for r in (r for r in range(rows) if not live[r]):
        np.testing.assert_array_equal(out[1, r], stack[1, r])
        np.testing.assert_array_equal(conv[:, r], window[:, r])
