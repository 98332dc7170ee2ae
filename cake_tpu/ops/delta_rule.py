"""The gated delta rule (Gated DeltaNet) mixer as Olmo-Hybrid uses it: a
causal depthwise convolution over q, k and v, the chunkwise recurrence for a
window of tokens, and the one-token update of both for decode.

For one layer with input ``h`` [b, L, hidden] (NOT normed: the block norms a
branch's output), ``H`` heads of ``dk`` keys and ``dv`` values:

    [q, k, v, z] = split(h @ in_proj)       (H dk, H dk, H dv, H dv)
    [a, b] = split(h @ ab_proj)             (H, H)
    (q, k, v) <- silu(conv(q | k | v))      4 taps a channel, no bias, zeros
                                            before the sequence
    per head:  q <- q / sqrt(|q|^2 + 1e-6) * dk ** -0.5
               k <- k / sqrt(|k|^2 + 1e-6)
    beta  = 2 sigmoid(b)  (``neg_eigval``; else sigmoid(b))
    alpha = exp(-exp(A_log) * softplus(a + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
    o_t = S_t q_t                           S in R^{dv x dk} a head, float32
    y = rms(o; o_norm [dv]) * silu(z)       a head
    out = concat(y) @ wo                    (the caller's: model.block_finish)

State a lane carries between dispatches (``config.state_shape`` /
``conv_window``), the minor axis whole 128-lane tiles at published widths
(neither dk = 96 nor dv = 192 is one):

    ssm   [b, dk, H * dv]   float32: head h's S^T in columns h dv..(h+1) dv
    conv  [K-1, b, H (2 dk + dv)]  the served type: the last K-1 inputs of
                                   the convolution, BEFORE the activation

``live`` [b, L] marks the positions that are tokens of the row. At every
other position (a left pad, the dead tail of a join window) the
convolution's input is zero, beta is 0 and alpha is 1: the state passes
through unchanged and the window holds zeros.

Which form a window takes is read off what the mixer can see, and nothing
else (no flag, field or environment variable chooses), as ``ops/ssm.py``:

  * ``L == 1`` (every decode step): the one-token update,
    ``gated_delta_step``. With ``allow_pallas`` and widths that tile, on the
    layer stack's own state in place (``mixer_step_stacked``: the Pallas
    kernel ``ops/pallas/delta_step.py``, one read and one write of a LIVE
    row's ``S``, the operation ``gated_delta_step`` of a device trace);
    else the XLA form below.
  * ``L > 1``: the chunkwise (WY / UT transform) form under the scope
    ``gated_delta_rule``: within a chunk of ``CHUNK`` = 64 positions every
    product is a matmul and the dependence inside the chunk is one
    unit-triangular solve; across chunks ``S`` is carried with the chunk's
    cumulative decay. No position is walked alone over a state in HBM.
    With ``allow_pallas`` and widths that tile it is the Pallas kernel
    ``ops/pallas/delta_rule.py`` (the operation ``gated_delta_rule`` of a
    device trace; the scope also holds the XLA that lays q, k and the gates
    out for it): ``S`` in VMEM over the whole window in the cache's own
    layout, a chunk's triangles made where they are used, only the chunks
    between a row's first and last live position walked. Else its XLA twin
    and oracle below, ``gated_delta_rule``, which builds every chunk's
    terms at once in HBM (0.29 MB a token) and then walks the chunks.

Float32 throughout the recurrence, products at ``Precision.HIGH`` (on the
TPU a float32 product at the default precision is one bfloat16 pass, which
rounds ``S`` itself: the precision BELOW the one the state is kept in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cake_tpu.obs.taxonomy import CACHE_WRITE, MIXER, MIXER_IN, MIXER_OUT
from cake_tpu.ops import ssm as S
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.pallas import delta_rule as pallas_rule
from cake_tpu.ops.pallas import delta_step as pallas_step
from cake_tpu.ops.quant import qmat

# Positions a chunk of the chunkwise form: [64, 64] triangles a head a chunk.
CHUNK = 64
L2_EPS = 1e-6
# Three bfloat16 passes a float32 product (2e-5 of the largest value against
# the rule one position at a time, chip call 3, where six passes read 1e-6
# and one pass, which rounds S itself to bfloat16, 5e-3). Six would also make
# every prefill program a fifth larger, and the 25 programs a server warms
# must fit the compile cache together (PERF.md section 6, PR 34).
_PRECISION = jax.lax.Precision.HIGH


def to_heads(s: jnp.ndarray, heads: int) -> jnp.ndarray:
    """Cache layout [b, dk, H * dv] -> [b, H, dk, dv]."""
    b, dk, n = s.shape
    return s.reshape(b, dk, heads, n // heads).transpose(0, 2, 1, 3)


def from_heads(s: jnp.ndarray) -> jnp.ndarray:
    """[b, H, dk, dv] -> cache layout [b, dk, H * dv]."""
    b, heads, dk, dv = s.shape
    return s.transpose(0, 2, 1, 3).reshape(b, dk, heads * dv)


def gated_delta_step(q, k, v, log_alpha, beta, s):
    """One position: q, k [b, H, dk], v [b, H, dv], log_alpha, beta [b, H],
    s [b, H, dk, dv] (S^T a head), all float32 -> (o [b, H, dv], s')."""
    s = jnp.exp(log_alpha)[..., None, None] * s
    err = v - jnp.einsum("bhkv,bhk->bhv", s, k, precision=_PRECISION)
    s = s + k[..., :, None] * (beta[..., None] * err)[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_PRECISION), s


def gated_delta_rule(q, k, v, log_alpha, beta, s0, chunk: int = CHUNK):
    """A window: q, k [b, L, H, dk], v [b, L, H, dv], log_alpha, beta
    [b, L, H] (0 and 0 where not live), s0 [b, H, dk, dv], all float32 ->
    (o [b, L, H, dv], s after the last position).

    With g_t the chunk's cumulative log decay and Gamma[t, i] = exp(g_t -
    g_i), the pseudo-values U of a chunk solve

        (I + tril(diag(beta) (Gamma * K K^T), -1)) U
            = diag(beta) V - diag(beta exp(g)) K S0^T

    so with T the inverse of the left side (one unit-triangular solve a
    chunk: forward substitution is the stable way; a Neumann series in powers
    of the triangle cancels catastrophically once beta k_i . k_j nears 2),
    W_v = T diag(beta) V and W_k = T diag(beta exp(g)) K are every chunk's own
    (computed for all chunks at once) and the walk over chunks is four
    products with the carried state:

        U = W_v - W_k S0^T
        O = diag(exp(g)) Q S0^T + tril(Gamma * Q K^T) U
        S^T <- exp(g_C) S0^T + (diag(exp(g_C - g)) K)^T U
    """
    b, length, heads, _ = q.shape
    n = -(-length // chunk)
    pad = n * chunk - length

    def chunks(x):  # [b, L, H, ...] -> [n, b, H, C, ...]; the tail is dead
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    product = functools.partial(jnp.einsum, precision=_PRECISION)
    q, k, v, beta = chunks(q), chunks(k), chunks(v), chunks(beta)
    g = jnp.cumsum(chunks(log_alpha), axis=-1)  # [n, b, H, C]
    at = jnp.arange(chunk)
    below = at[:, None] > at[None, :]
    upto = at[:, None] >= at[None, :]
    # exp of a masked difference: above the diagonal g_t - g_i is positive
    gamma = jnp.exp(jnp.where(upto, g[..., :, None] - g[..., None, :], -jnp.inf))
    a = jnp.where(below, beta[..., None] * gamma * product("...ck,...dk->...cd", k, k), 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    t = jax.scipy.linalg.solve_triangular(
        eye + a, jnp.broadcast_to(eye, a.shape), lower=True, unit_diagonal=True
    )
    decay = jnp.exp(g)
    w_v = product("...cd,...dv->...cv", t, beta[..., None] * v)
    w_k = product("...cd,...dk->...ck", t, (beta * decay)[..., None] * k)
    qk = gamma * product("...ck,...dk->...cd", q, k)
    q_in = decay[..., None] * q
    g_end = g[..., -1]  # [n, b, H]
    k_out = jnp.exp(g_end[..., None] - g)[..., None] * k

    def one_chunk(s, xs):
        w_v, w_k, qk, q_in, k_out, g_end = xs
        u = w_v - product("bhck,bhkv->bhcv", w_k, s)
        o = product("bhck,bhkv->bhcv", q_in, s) + product("bhcd,bhdv->bhcv", qk, u)
        s = jnp.exp(g_end)[..., None, None] * s
        return s + product("bhck,bhcv->bhkv", k_out, u), o

    s, o = jax.lax.scan(one_chunk, s0, (w_v, w_k, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)  # [b, n, C, H, dv]
    return o.reshape(b, n * chunk, heads, -1)[:, :length], s


def _unit(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _inputs(lp, h, conv, live, ends, neg_eigval, dk=None):
    """Everything of the mixer before the recurrence: (q, k [b, L, H, dk], v
    [b, L, H, dv], log_alpha, beta [b, L, H], all float32; z [b, L, H dv];
    the convolution's new window). ``dk``: the state's (``_to_value_heads``)."""
    with jax.named_scope(MIXER_IN):
        heads = lp["A_log"].shape[-1]
        qkvz = qmat(h, lp["in_proj"])
        width = conv.shape[-1]  # the convolution's channels: q | k | v
        n_v = qkvz.shape[-1] - width  # z is as wide as v
        n_k = (width - n_v) // 2
        dk, dv = dk or n_k // heads, n_v // heads
        u_in = jnp.where(live[:, :, None], qkvz[..., :width], 0).astype(h.dtype)
        z = qkvz[..., width:]
        padded = S.with_window(u_in, conv)
        u = jax.nn.silu(S.causal_conv(padded, lp["conv_w"], None))
        b, length = h.shape[:2]
        q = _to_value_heads(_unit(u[..., :n_k].reshape(b, length, -1, dk)) * dk ** -0.5, heads)
        k = _to_value_heads(_unit(u[..., n_k : 2 * n_k].reshape(b, length, -1, dk)), heads)
        v = u[..., 2 * n_k :].reshape(b, length, heads, dv)
        # The gates' projection keeps its float32 sums: alpha is an exponential
        # of a, and a rounded to bfloat16 (2^-8 of values up to 10) moves every
        # step's decay by a percent, which the recurrence compounds.
        ab = jnp.dot(h, lp["ab_proj"], preferred_element_type=jnp.float32)
        beta = jax.nn.sigmoid(ab[..., heads:]) * (2.0 if neg_eigval else 1.0)
        log_alpha = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ab[..., :heads] + lp["dt_bias"].astype(jnp.float32)
        )
        beta = jnp.where(live[:, :, None], beta, 0.0)
        log_alpha = jnp.where(live[:, :, None], log_alpha, 0.0)
    with jax.named_scope(CACHE_WRITE):
        new_conv = S.window_at(padded, ends, conv.shape[0]).astype(conv.dtype)
        # A row without a live position keeps its window (``ops/ssm.py``): its
        # state read beta = 0 and alpha = 1 above and is its old state already.
        touched = jnp.any(live, axis=1)
        new_conv = jnp.where(touched[None, :, None], new_conv, conv)
    return q, k, v, log_alpha, beta, z, new_conv


def _gated(lp, o, z, eps, dtype):
    """rms(o; o_norm) * silu(z) a head, heads side by side: [b, L, H dv]."""
    with jax.named_scope(MIXER_OUT):
        b, length, heads, dv = o.shape
        y = rms_norm(o, lp["o_norm"].astype(jnp.float32), eps)
        y = y * jax.nn.silu(z.astype(jnp.float32)).reshape(b, length, heads, dv)
        return y.reshape(b, length, heads * dv).astype(dtype)


def mixer_forward(
    lp: dict,
    h: jnp.ndarray,  # [b, L, hidden]
    ssm: jnp.ndarray,  # [b, dk, H * dv] float32
    conv: jnp.ndarray,  # [K-1, b, H (2 dk + dv)]
    live: jnp.ndarray,  # [b, L] bool
    ends: jnp.ndarray | None,  # [b] one past the last live position; None =
    # every row's last position is L - 1 (decode, L == 1)
    eps: float,
    neg_eigval: bool = True,
    chunk: int = CHUNK,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One gated-delta-rule mixer over a window continuing from (``ssm``,
    ``conv``): (rms(o) * silu(z) [b, L, H dv] (the caller applies the
    out-projection with the block's tail), ssm', conv'). A row with no live
    position keeps its state bit for bit."""
    q, k, v, log_alpha, beta, z, new_conv = _inputs(
        lp, h, conv, live, ends, neg_eigval, ssm.shape[-2]
    )
    heads = q.shape[2]
    with jax.named_scope(MIXER):
        if h.shape[1] == 1:
            with jax.named_scope("gated_delta_step"):
                o, s = gated_delta_step(
                    q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0],
                    to_heads(ssm, heads),
                )
            o, s = o[:, None], from_heads(s)
        elif window_in_kernel(ssm.shape[-2:], heads, allow_pallas):
            with jax.named_scope("gated_delta_rule"):
                # The chunks before a row's first live position and after its
                # last are not walked (a join's left pads, an epoch's dead tail).
                length = live.shape[1]
                lo = jnp.argmax(live, axis=1)
                hi = length - jnp.argmax(live[:, ::-1], axis=1)
                hi = jnp.where(jnp.any(live, axis=1), hi, lo)
                o, s = pallas_rule.gated_delta_rule(
                    q, k, v, log_alpha, beta, ssm,
                    jnp.stack([lo, hi], axis=1).astype(jnp.int32),
                )
        else:
            with jax.named_scope("gated_delta_rule"):
                o, s = gated_delta_rule(
                    q, k, v, log_alpha, beta, to_heads(ssm, heads), chunk
                )
                s = from_heads(s)
    return _gated(lp, o, z, eps, h.dtype), s, new_conv


def window_in_kernel(
    state_shape: tuple[int, int], heads: int, allow_pallas: bool
) -> bool:
    """Whether a window (``L > 1``) over a state of [dk, H * dv] a row
    (``config.state_shape``) is the Pallas kernel's (the switch, and widths
    that tile) or its XLA twin's."""
    dk, n = state_shape
    return allow_pallas and pallas_rule.tiles(dk, n, n // heads)


def steps_in_place(ssm: jnp.ndarray, heads: int) -> bool:
    """Whether the one-token update of a layer stack's state [n, b, dk,
    H * dv] is the Pallas kernel's (widths that tile), given the switch."""
    return pallas_step.tiles(ssm.shape[-2], ssm.shape[-1], ssm.shape[-1] // heads)


def mixer_step_stacked(
    lp: dict,
    h: jnp.ndarray,  # [b, 1, hidden]
    ssm: jnp.ndarray,  # [n_state, b, dk, H * dv] float32: the whole stack
    layer: jnp.ndarray,  # which of the stack's layers this is (traced)
    conv: jnp.ndarray,  # [K-1, b, H (2 dk + dv)] this layer's window
    live: jnp.ndarray,  # [b, 1] bool
    eps: float,
    neg_eigval: bool = True,
    rows: jnp.ndarray | None = None,  # ``live_rows(live[:, 0])``, made once a dispatch
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``mixer_forward`` for ``L == 1`` over the STACK's state in place: the
    kernel is handed the whole array, the layer's index and the live rows
    (a kernel's operand is a whole array; a slice of the scan's carry would
    be copied out and back), reads and writes that layer's LIVE rows once,
    and leaves every other row where it is. (gated, the stack, conv')."""
    q, k, v, log_alpha, beta, z, new_conv = _inputs(
        lp, h, conv, live, None, neg_eigval, ssm.shape[-2]
    )
    with jax.named_scope(MIXER):
        o, ssm = pallas_step.gated_delta_step(
            ssm, layer, q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0],
            live[:, 0], rows=rows,
        )
    return _gated(lp, o[:, None], z, eps, h.dtype), ssm, new_conv


live_rows = pallas_step.live_rows


def _to_value_heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """q or k [b, L, key heads, dk] as the ``heads`` VALUE heads read it:
    where a model has fewer key heads than value heads (Qwen3-Next: 16 under
    32) value head ``j`` reads key head ``j // group`` (HF's
    ``repeat_interleave``), so the recurrence and both kernels see one q and
    one k a value head, as they do where the counts are equal (Olmo-Hybrid:
    nothing is repeated and nothing is traced here). The key heads are read
    off the convolution's width and the state's ``dk``."""
    group = heads // x.shape[2]
    return x if group == 1 else jnp.repeat(x, group, axis=2)
