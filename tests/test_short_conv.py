"""``ops/short_conv.py``: the gated short convolution's window form, its
one-token step, and its window in the lane cache.

The reference here is the convolution written out over the whole row
(``bench/architectures/lfm2_moe._conv_mixer``'s arithmetic without the norms
and the out-projection). Float32 on both sides and the same three
multiply-adds a channel in the same order: the tolerance is a few float32
roundings of the three taps' terms (values of order 1 here), the projections' sums in
another order and nothing else; a fault (a tap dropped, a gate left out)
moves a value by its own size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops import short_conv as C
from cake_tpu.runtime.batch_backend import paged_backend

from test_hybrid_jamba import decode, lay_out, prompts
from test_lfm2_moe import HF, PAGE

D, TAPS = 32, 3
TOL = dict(rtol=1e-5, atol=1e-6)


def layer(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"in_proj": jax.random.normal(k[0], (24, 3 * D)) * 0.1,
            "conv_w": jax.random.normal(k[1], (TAPS, D))}


def whole_row(lp, h):
    """The reference: one row [L, hidden], zeros before its first token."""
    bcu = h @ lp["in_proj"]
    b, c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    v = jnp.concatenate([jnp.zeros((TAPS - 1, D)), b * u], 0)
    conv = sum(lp["conv_w"][j] * v[j:j + h.shape[0]] for j in range(TAPS))
    return c * conv, v[-(TAPS - 1):]


def inputs(seed, b, length):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, length, 24))


ZERO = lambda b: jnp.zeros((TAPS - 1, b, D))


@pytest.mark.parametrize("length", [1, 2, 7, 33])
def test_a_window_equals_the_whole_row_and_steps_one_at_a_time(length):
    lp, h = layer(), inputs(1, 2, length)
    live = jnp.ones((2, length), bool)
    y, state, window = C.mixer_forward(lp, h, None, ZERO(2), live, jnp.full((2,), length))
    assert state is None and window.shape == (TAPS - 1, 2, D)
    conv = ZERO(2)
    for t in range(length):
        y_t, _, conv = C.mixer_forward(lp, h[:, t:t + 1], None, conv, live[:, :1], None)
        np.testing.assert_allclose(y_t[:, 0], y[:, t], **TOL)
    np.testing.assert_allclose(conv, window, **TOL)
    for r in range(2):
        want, tail = whole_row(lp, h[r])
        np.testing.assert_allclose(y[r], want, **TOL)
        # a row shorter than the window keeps zeros in front of it
        np.testing.assert_allclose(window[:, r], tail, **TOL)


def test_a_window_then_steps_continue_it():
    lp, h = layer(), inputs(2, 3, 12)
    live = jnp.ones((3, 12), bool)
    y_all, _, _ = C.mixer_forward(lp, h, None, ZERO(3), live, jnp.full((3,), 12))
    _, _, conv = C.mixer_forward(lp, h[:, :9], None, ZERO(3), live[:, :9], jnp.full((3,), 9))
    for t in range(9, 12):
        y_t, _, conv = C.mixer_forward(lp, h[:, t:t + 1], None, conv, live[:, :1], None)
        np.testing.assert_allclose(y_t[:, 0], y_all[:, t], **TOL)


def test_left_pads_and_a_dead_tail_pass_the_window_through():
    """Row 0: 4 left pads and 3 dead slots behind its 9 tokens; row 1 live
    throughout. A pad's garbage input reaches nothing, and the window after
    the chunk is the one after the row's LAST LIVE token."""
    lp, h = layer(), inputs(3, 2, 16)
    at = jnp.arange(16)[None, :]
    pads, ends = jnp.asarray([4, 0]), jnp.asarray([13, 16])
    live = (at >= pads[:, None]) & (at < ends[:, None])
    y, _, window = C.mixer_forward(lp, h, None, ZERO(2), live, ends)
    want, tail = whole_row(lp, h[0, 4:13])
    np.testing.assert_allclose(y[0, 4:13], want, **TOL)
    np.testing.assert_allclose(window[:, 0], tail, **TOL)
    noisy = h.at[0, :4].set(1e3).at[0, 13:].set(-1e3)
    y2, _, window2 = C.mixer_forward(lp, noisy, None, ZERO(2), live, ends)
    np.testing.assert_array_equal(y2[0, 4:13], y[0, 4:13])
    np.testing.assert_array_equal(window2, window)


def test_a_dead_lane_keeps_its_window_bit_for_bit():
    lp, h = layer(), inputs(4, 3, 1)
    old = jax.random.normal(jax.random.PRNGKey(5), (TAPS - 1, 3, D))
    live = jnp.asarray([[True], [False], [True]])
    _, _, new = C.mixer_forward(lp, h, None, old, live, None)
    np.testing.assert_array_equal(new[:, 1], old[:, 1])
    # a live lane's window shifts by one: the old window's last tap in front
    np.testing.assert_array_equal(new[0, 0], old[1, 0])
    assert np.abs(np.asarray(new[1, 0] - old[1, 0])).max() > 0


def test_the_window_is_the_caches_type():
    lp, h = layer(), inputs(6, 2, 5)
    _, _, window = C.mixer_forward(
        lp, h, None, ZERO(2).astype(jnp.bfloat16), jnp.ones((2, 5), bool), jnp.full((2,), 5))
    assert window.dtype == jnp.bfloat16


# ------------------------------------------------- through the lane cache


@pytest.fixture(scope="module")
def served():
    config = LlamaConfig.from_hf_dict(HF)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    return config, params


def fresh(config, params):
    return paged_backend(config, params, max_seq_len=128, cache_dtype=jnp.float32,
                         page_size=PAGE, max_pages=48, allow_pallas=False)


def test_a_new_tenant_overwrites_the_lanes_window(served):
    """Lane 1 served a row and stepped; a joiner into it starts from zeros
    and its window OVERWRITES the lane's: the lane then equals the same row
    joined into a lane never used, and the neighbours' windows are as they
    were."""
    config, params = served
    be = fresh(config, params)
    rows = prompts(0, 21, 30)
    cache, tokens, pads = lay_out(be, rows, 4, 32)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    _, cache = decode(be, cache, tok, 32, pads, 8, live=(0, 1))
    assert cache.ssm is None and np.abs(np.asarray(cache.conv[:, :, 1])).max() > 0
    before = np.asarray(cache.conv)
    (joiner,) = prompts(5, 26)
    row = np.zeros((1, 64), np.int32)
    row[0, 64 - len(joiner):] = joiner

    def join(be, cache, lane):
        if lane == 1:  # the row it served has ended: its pages go back
            be.allocator.release(lane)
        be.allocator.map_range(lane, 40 - len(joiner), 40)
        return be.join(cache, row, jnp.asarray([40 - len(joiner)], jnp.int32),
                       jnp.asarray([40], jnp.int32), lane, start=40 - 64)

    j1, cache = join(be, cache, 1)
    j2, cache = join(be, cache, 2)
    np.testing.assert_array_equal(j1, j2)
    after = np.asarray(cache.conv)
    np.testing.assert_array_equal(after[:, :, 1], after[:, :, 2])
    np.testing.assert_array_equal(after[:, :, 0], before[:, :, 0])
    np.testing.assert_array_equal(after[:, :, 3], before[:, :, 3])
    assert np.abs(after[:, :, 1] - before[:, :, 1]).max() > 0
