"""The Mamba-1 selective state-space mixer as Jamba uses it (dt, B and C
RMS-normed), in plain XLA: a causal depthwise convolution, a selective scan
for a whole chunk of tokens, and the one-token update of both for decode.

For one layer with input ``h`` [b, L, hidden] (already input-normed):

    [u, z] = split(h @ in_proj)                       each [b, L, d_inner]
    u  = silu(conv(u))        u'_t = bias + sum_k w[k] * u_{t-(K-1)+k}, zeros
                              before the sequence
    [dt_r, B, C] = split(u @ x_proj)                  (dt_rank, d_state, d_state)
    dt = softplus(rms(dt_r) @ dt_proj + dt_bias)      [b, L, d_inner]
    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) * B_t,   A = -exp(A_log)
    y_t = s_t . C_t + D * u_t
    out = (y * silu(z)) @ out_proj        (the caller's: model.block_finish)

State a lane carries between dispatches, both in TPU-friendly layouts (the
minor axis is ``d_inner``, a multiple of 128, so no tile is padded):

    ssm   [b, d_state, d_inner]   float32 — ``s`` is an accumulator
    conv  [K-1, b, d_inner]       the served type — the last K-1 inputs of
                                  the convolution, BEFORE the activation

``live`` [b, L] marks the positions that are tokens of the row. At every
other position (a left pad, the dead tail of a join window) the
convolution's input is zero and ``dt`` is zero, so ``s`` passes through
unchanged and the window holds zeros: a recurrence never sees a pad.

Which scan a window takes is read off what ``mixer_forward`` can see, and
nothing else (no flag, field or environment variable chooses):

  * ``L == 1`` (every decode step): the one-token update. Where the model
    hands the layer stack's state whole (``mixer_step_stacked``: the switch,
    and widths that tile, ``steps_in_place``) it is the Pallas kernel
    ``ops/pallas/selective_step.py``: one read and one write of ``s``, ``y``
    taken on the way, the operation ``selective_step`` of a device trace.
    Else the XLA form in ``mixer_forward``, its twin and oracle.
  * ``L > 1`` with ``allow_pallas`` (the switch every Pallas kernel follows,
    handed down from ``hybrid_blocks_forward``) and widths that tile
    (``d_inner`` in 128-lane tiles, ``d_state`` in sublane tiles):
    ``ops/pallas/selective_scan.py``, which keeps ``s`` in VMEM over the
    whole window and writes no per-step state to HBM.
  * anything else (the tests' tiny models, the CPU): ``selective_scan``
    below, the plain XLA form. It is the kernel's twin: the same
    mathematics, the form the kernel is held against (``ops/pallas/check.py``,
    ``tests/test_ssm_scan_kernel.py``). Its memory is bounded in L: time is
    walked in chunks of ``SCAN_CHUNK`` steps and only one chunk's
    [b, T, d_state, d_inner] decay, input terms and states exist at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.obs.taxonomy import CACHE_WRITE, MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas import selective_scan as pallas_scan
from cake_tpu.ops.pallas import selective_step as pallas_step
from cake_tpu.ops.quant import qmat

# Steps of the prefill scan whose decay/input terms are built at once:
# [b, T, d_state, d_inner] float32, 5.2 MB a row at Jamba2-3B's sizes.
SCAN_CHUNK = 16


def with_window(u: jnp.ndarray, window: jnp.ndarray) -> jnp.ndarray:
    """[b, K-1 + L, d]: the K-1 inputs before position 0 ([K-1, b, d]), then
    the chunk's own ([b, L, d], zero where not live)."""
    return jnp.concatenate(
        [jnp.moveaxis(window, 0, 1).astype(u.dtype), u], axis=1
    )


def causal_conv(
    padded: jnp.ndarray,  # [b, K-1 + L, d] from ``with_window``
    w: jnp.ndarray,  # [K, d] taps, w[K-1] multiplies the current input
    bias: jnp.ndarray | None,  # [d]; None = no bias
) -> jnp.ndarray:
    """Depthwise causal convolution over time, [b, L, d] float32."""
    k = w.shape[0]
    length = padded.shape[1] - (k - 1)
    padded = padded.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    out = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(k):
        out = out + wf[j] * jax.lax.dynamic_slice_in_dim(padded, j, length, 1)
    return out


def window_at(
    padded: jnp.ndarray, ends: jnp.ndarray | None, k1: int
) -> jnp.ndarray:
    """The convolution's window after each row's last live token: inputs at
    positions ends-(K-1) .. ends-1 of the chunk (``ends`` [b], one past the
    last live position), reaching into the old window for a row shorter
    than K-1. [K-1, b, d]. ``ends`` None = every row's last position is the
    chunk's last (a decode step): the window is ``padded``'s tail, a static
    slice where the general form gathers at indices that are constants."""
    if ends is None:
        picked = padded[:, padded.shape[1] - k1:]
    else:
        idx = ends[:, None] + jnp.arange(k1, dtype=jnp.int32)[None, :]  # [b, K-1]
        picked = jnp.take_along_axis(padded, idx[:, :, None], axis=1)
    return jnp.moveaxis(picked, 1, 0)


def selective_scan(
    u: jnp.ndarray,  # [b, L, d] float32, after conv and silu
    dt: jnp.ndarray,  # [b, L, d] float32, zero where not live
    a: jnp.ndarray,  # [n, d] float32, -exp(A_log) transposed
    b_in: jnp.ndarray,  # [b, L, n] float32
    c_out: jnp.ndarray,  # [b, L, n] float32
    s0: jnp.ndarray,  # [b, n, d] float32
    chunk: int = SCAN_CHUNK,
    span: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(y [b, L, d] float32 without the D term, s after the last position).

    Time is cut into chunks of ``chunk`` steps (the tail padded with dt = 0,
    which leaves ``s`` alone). Per chunk the decay exp(dt * A) and the input
    dt * u * B are built for all its steps at once, the recurrence itself is
    ``chunk`` fused multiply-adds in sequence, and y is one contraction of
    the chunk's states with C.

    ``span`` = (lo, hi), traced positions: no row is live before ``lo`` or
    from ``hi`` on, so only the chunks that touch [lo, hi) are walked (y is
    zero elsewhere, as it is wherever dt is zero and ``s0`` is zero). A join
    is left-padded to the batch's shared slot: its window may be ten times
    its prompt, and the scan is the one part of a layer that is sequential
    in the window's length."""
    b, length, d = u.shape
    n_chunks = -(-length // chunk)
    pad = n_chunks * chunk - length

    def padded(x):  # [b, L, f] -> [b, n_chunks * chunk, f]
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0)))

    u, dt, b_in, c_out = padded(u), padded(dt), padded(b_in), padded(c_out)
    if span is None:
        first, last = 0, n_chunks
    else:
        first = jnp.clip(span[0] // chunk, 0, n_chunks)
        last = jnp.clip(-(-span[1] // chunk), first, n_chunks)

    def one_chunk(i, carry):
        s, y = carry
        at = i * chunk
        cut = lambda x: jax.lax.dynamic_slice_in_dim(x, at, chunk, axis=1)
        u_c, dt_c, b_c, c_c = cut(u), cut(dt), cut(b_in), cut(c_out)
        decay = jnp.exp(dt_c[:, :, None, :] * a[None, None])  # [b, T, n, d]
        drive = (dt_c * u_c)[:, :, None, :] * b_c[:, :, :, None]
        states = []
        for t in range(chunk):
            s = decay[:, t] * s + drive[:, t]
            states.append(s)
        y_c = jnp.einsum("btnd,btn->btd", jnp.stack(states, axis=1), c_c)
        return s, jax.lax.dynamic_update_slice_in_dim(y, y_c, at, axis=1)

    s, y = jax.lax.fori_loop(
        first, last, one_chunk, (s0, jnp.zeros((b, n_chunks * chunk, d), jnp.float32))
    )
    return y[:, :length], s


def _inputs(lp, h, conv, live, ends, eps):
    """Everything of the mixer before the recurrence: (u, dt [b, L, d], a
    [n, d], B, C [b, L, n], all float32; z [b, L, d]; the convolution's new
    window)."""
    n, d = lp["A_log"].shape  # stored [n, d]
    with jax.named_scope(MIXER_IN):
        uz = qmat(h, lp["in_proj"])
        u_in = jnp.where(live[:, :, None], uz[..., :d], 0).astype(h.dtype)
        z = uz[..., d:]
        padded = with_window(u_in, conv)
        u = jax.nn.silu(causal_conv(padded, lp["conv_w"], lp["conv_b"]))
        dbc = qmat(u.astype(h.dtype), lp["x_proj"])
        r = dbc.shape[-1] - 2 * n
        dt_r = rms_norm(dbc[..., :r], lp["dt_ln"], eps)
        b_in = rms_norm(dbc[..., r : r + n], lp["b_ln"], eps).astype(jnp.float32)
        c_out = rms_norm(dbc[..., r + n :], lp["c_ln"], eps).astype(jnp.float32)
        dt = jax.nn.softplus(
            qmat(dt_r, lp["dt_proj"]).astype(jnp.float32)
            + lp["dt_bias"].astype(jnp.float32)
        )
        dt = jnp.where(live[:, :, None], dt, 0.0)
        a = -jnp.exp(lp["A_log"].astype(jnp.float32))
    with jax.named_scope(CACHE_WRITE):
        new_conv = window_at(padded, ends, conv.shape[0]).astype(conv.dtype)
        # A row without a live position (a dead lane of a decode dispatch) read
        # dt = 0 and u_in = 0 above, so ``s`` is its old state already; its
        # window would shift in a zero, so it is kept explicitly.
        touched = jnp.any(live, axis=1)
        new_conv = jnp.where(touched[None, :, None], new_conv, conv)
    return u, dt, a, b_in, c_out, z, new_conv


def _gated(lp, y, u, z, dtype):
    """(y + D u) * silu(z) [b, L, d]."""
    with jax.named_scope(MIXER_OUT):
        y = y + lp["D"].astype(jnp.float32) * u
        return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)


def mixer_forward(
    lp: dict,
    h: jnp.ndarray,  # [b, L, hidden] input-normed
    ssm: jnp.ndarray,  # [b, n, d] float32
    conv: jnp.ndarray,  # [K-1, b, d]
    live: jnp.ndarray,  # [b, L] bool
    ends: jnp.ndarray | None,  # [b] one past the last live position; None =
    # every row's last position is L - 1 (decode, L == 1)
    eps: float,
    chunk: int = SCAN_CHUNK,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One state-space mixer over a chunk of tokens continuing from
    (``ssm``, ``conv``): (y * silu(z) [b, L, d_inner] — the caller applies
    the out-projection with the block's tail —, ssm', conv'). A row with no
    live position keeps its state bit for bit."""
    d = ssm.shape[-1]
    n = ssm.shape[-2]
    u, dt, a, b_in, c_out, z, new_conv = _inputs(lp, h, conv, live, ends, eps)
    with jax.named_scope(MIXER):
        if h.shape[1] == 1:
            # Decode: the same equations for one t, no chunking.
            s = jnp.exp(dt[:, 0, None, :] * a[None]) * ssm + (
                (dt[:, 0] * u[:, 0])[:, None, :] * b_in[:, 0, :, None]
            )
            y = jnp.einsum("bnd,bn->bd", s, c_out[:, 0])[:, None, :]
        else:
            # The chunks no row is live in are not walked (a join's left pads).
            some = jnp.any(live, axis=0)
            lo = jnp.argmax(some).astype(jnp.int32)
            hi = (some.shape[0] - jnp.argmax(some[::-1])).astype(jnp.int32)
            hi = jnp.where(jnp.any(some), hi, lo)
            if allow_pallas and pallas_scan.tiles(d, n):
                y, s = pallas_scan.selective_scan(
                    u, dt, a, b_in, c_out, ssm, (lo, hi)
                )
            else:
                with jax.named_scope("selective_scan_xla"):
                    y, s = selective_scan(
                        u, dt, a, b_in, c_out, ssm, chunk, (lo, hi)
                    )
    return _gated(lp, y, u, z, h.dtype), s, new_conv


def steps_in_place(ssm: jnp.ndarray) -> bool:
    """Whether the one-token update of a layer stack's state [n_state, b,
    d_state, d_inner] is the Pallas kernel's (widths that tile), given the
    switch."""
    return pallas_step.tiles(ssm.shape[-1], ssm.shape[-2])


def mixer_step_stacked(
    lp: dict,
    h: jnp.ndarray,  # [b, 1, hidden] input-normed
    ssm: jnp.ndarray,  # [n_state, b, n, d] float32: the whole stack
    layer: jnp.ndarray,  # which of the stack's layers this is (traced)
    conv: jnp.ndarray,  # [K-1, b, d] this layer's window
    live: jnp.ndarray,  # [b, 1] bool
    eps: float,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``mixer_forward`` for ``L == 1`` over the STACK's state in place
    (``ops/delta_rule.mixer_step_stacked``'s contract): the kernel is handed
    the whole array and the layer's index, reads and writes that layer's
    rows once, and leaves the others where they are. (gated, the stack,
    conv')."""
    u, dt, a, b_in, c_out, z, new_conv = _inputs(lp, h, conv, live, None, eps)
    with jax.named_scope(MIXER):
        y, ssm = pallas_step.selective_step(
            ssm, layer, u[:, 0], dt[:, 0], a, b_in[:, 0], c_out[:, 0]
        )
    return _gated(lp, y[:, None], u, z, h.dtype), ssm, new_conv
