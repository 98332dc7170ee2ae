"""The gated short convolution of LFM2 (``model_type: lfm2_moe``), in plain
XLA: a token mixer that is two projections around three multiply-adds a
channel. For one layer with input ``h`` [b, L, hidden] (already input-normed):

    [B | C | u] = split(h @ in_proj)                  each [b, L, hidden]
    v   = B * u
    c_t = sum_j w[j] * v_{t-(K-1)+j}                  depthwise, causal, zeros
                                                      before the sequence; no
                                                      bias, no activation
    y   = C * c
    out = y @ out_proj            (the caller's: model.block_finish, as ``wo``)

State a lane carries between dispatches: the convolution's window ALONE,

    conv  [K-1, b, hidden]   the served type: the last K-1 values of ``v``

in the layout ``ops/ssm.py``'s window has (whose ``with_window`` /
``causal_conv`` / ``window_at`` this module uses: one convolution, three
mixers). There is no float32 state: ``config.state_shape`` is None, the
surface ``hybrid._state_ops`` takes passes None through where the other
mixers pass ``s``, and ``steps_in_place`` is never asked.

``live`` [b, L] marks the positions that are tokens of the row. At every other
position (a left pad, the dead tail of a join window, a dead lane of a decode
dispatch) ``v`` is zero, so the window holds zeros there, and a row without
a live position keeps its window bit for bit. The window after the chunk is
``v`` at each row's last K-1 live positions (``ends``), reaching into the old
window for a row shorter than that; for a decode step (``ends`` None) it is a
static slice of the old window and the new value, not a gather.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.obs.taxonomy import CACHE_WRITE, MIXER, MIXER_IN
from cake_tpu.ops.quant import qmat
from cake_tpu.ops.ssm import causal_conv, window_at, with_window

# The convolution's own scope inside ``mixer`` (a device trace's name for it:
# bench/layer_metrics/short_conv_decode_dev_ms.py).
SCOPE = "short_conv"


def mixer_forward(
    lp: dict,
    h: jnp.ndarray,  # [b, L, hidden] input-normed
    state: None,  # the mixer keeps no float32 state
    conv: jnp.ndarray,  # [K-1, b, hidden]
    live: jnp.ndarray,  # [b, L] bool
    ends: jnp.ndarray | None,  # [b] one past the last live position; None =
    # every row's last position is L - 1 (decode, L == 1)
    eps: float = 0.0,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, None, jnp.ndarray]:
    """One gated short convolution over a chunk of tokens continuing from
    ``conv``: (C * conv(B * u) [b, L, hidden] — the caller applies the
    out-projection with the block's tail —, None, conv'). Any ``L``: a window
    of a prefill or a join, or the one token of a decode step. ``eps`` and
    ``allow_pallas`` are the surface's; nothing here reads them."""
    with jax.named_scope(MIXER_IN):
        bcu = qmat(h, lp["in_proj"])
        d = bcu.shape[-1] // 3
        gate_b, gate_c, u = bcu[..., :d], bcu[..., d : 2 * d], bcu[..., 2 * d :]
        v = jnp.where(live[:, :, None], gate_b * u, 0).astype(h.dtype)
        padded = with_window(v, conv)
    with jax.named_scope(MIXER), jax.named_scope(SCOPE):
        c = causal_conv(padded, lp["conv_w"], None)
        y = (gate_c.astype(jnp.float32) * c).astype(h.dtype)
    with jax.named_scope(CACHE_WRITE):
        new_conv = window_at(padded, ends, conv.shape[0]).astype(conv.dtype)
        touched = jnp.any(live, axis=1)
        new_conv = jnp.where(touched[None, :, None], new_conv, conv)
    return y, None, new_conv
