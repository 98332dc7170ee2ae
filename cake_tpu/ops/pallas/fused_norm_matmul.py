"""RMSNorm folded into the projection it feeds, as a Pallas TPU kernel.

Batch decode is HBM-bound and every XLA op boundary costs a round trip: the
unfused step materializes the normalized activation (``ops/norm.rms_norm``)
in HBM just so the next matmul can read it back. This kernel computes the
norm on the activation rows ALREADY resident in VMEM and feeds the product
straight into the MXU dot, one output tile per grid step — the normalized
activation never exists in HBM. Applied at the three decode sites that pair
a norm with a projection (models/llama/model.py): the attn input norm ->
``wqkv``, the post-attn norm -> ``w_gu``, and the final norm -> ``lm_head``
(the operation-fusion shape in PAPERS.md, arxiv 2502.17728).

Numerics contract (the tests' bit-identity oracle): the kernel runs exactly
the f32-upcast arithmetic of ``ops/norm.rms_norm`` — upcast, mean of
squares over the hidden dim, ``reciprocal(sqrt(var + eps))``, weight (with
the Gemma (1 + w) offset) — casts back to the activation dtype, and then
dots against the weight tile with f32 accumulation. Tiling the OUTPUT dim
cannot change any column's accumulation order (each output column is an
independent dot over the hidden dim — the ops/fuse.py argument), and the
per-tile recompute of the norm is redundant work, not divergent work: every
tile normalizes the same rows to the same bits. ``fused_norm_matmul`` with
``impl="xla"`` is the twin — it literally calls ``rms_norm`` + ``qmat``, so
the unfused path IS the oracle.

Eligibility: the output dim must tile into 128-lane blocks
(``norm_matmul_supported``); quantized weights keep the unfused path (the
dequant epilogue belongs to ops/quant.qmat). Callers fall back to the twin
— bit-identically — when a site is ineligible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.quant import qmat

_LANES = 128
# The kernel holds the whole activation row-block in VMEM (the point of the
# fusion): decode rows are tiny (the batch), but the SAME block_qkv sites
# serve prefill chunks — a [b * chunk, hidden] block would blow VMEM there.
# Row counts past this bound take the twin, bit-identically.
_MAX_ROWS = 256


def norm_matmul_supported(w) -> bool:
    """Kernel eligibility: a PLAIN weight whose output dim is whole 128-lane
    tiles. One rule for every site; ineligible sites run the twin (callers
    surface the one-time ``kernel-fallback`` flight event host-side, the
    PR 9 convention)."""
    return isinstance(w, jnp.ndarray) and w.ndim == 2 and (
        w.shape[-1] % _LANES == 0
    )


def _norm_matmul_kernel(x_ref, nw_ref, w_ref, o_ref, *, eps, offset):
    # The exact ops/norm.rms_norm arithmetic, on rows resident in VMEM.
    xf = x_ref[...].astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jnp.reciprocal(jnp.sqrt(var + eps))
    w = nw_ref[...].astype(jnp.float32)
    if offset:
        w = 1.0 + w
    h = (y * w).astype(x_ref.dtype)
    o_ref[...] = jax.lax.dot_general(
        h, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("eps", "offset", "block_n", "interpret"),
)
def _norm_matmul_pallas(
    x2: jnp.ndarray,  # [rows, hidden]
    norm_w: jnp.ndarray,  # [1, hidden]
    w: jnp.ndarray,  # [hidden, out]
    *,
    eps: float,
    offset: bool,
    block_n: int,
    interpret: bool,
) -> jnp.ndarray:
    rows, hidden = x2.shape
    out = w.shape[-1]
    grid = (out // block_n,)
    return pl.pallas_call(
        functools.partial(_norm_matmul_kernel, eps=eps, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, hidden), lambda i: (0, 0)),
            pl.BlockSpec((1, hidden), lambda i: (0, 0)),
            pl.BlockSpec((hidden, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((rows, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rows, out), x2.dtype),
        interpret=interpret,
    )(x2, norm_w, w)


def fused_norm_matmul(
    x: jnp.ndarray,  # [b, t, hidden]
    norm_w: jnp.ndarray,  # [hidden]
    w,  # [hidden, out] plain array (kernel) or any qmat weight (twin)
    *,
    eps: float,
    offset: bool = False,
    impl: str = "xla",
    block_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``qmat(rms_norm(x, norm_w, eps, offset), w)`` in one kernel.

    ``impl="xla"`` is the twin: the literal unfused composition, which is
    what makes fused and unfused streams bit-identical by construction on
    the twin path and gives the kernel its oracle. Returns [b, t, out] in
    the matmul's natural dtype (callers cast exactly where the unfused
    path did).
    """
    b, t, hidden = x.shape
    if impl != "pallas" or not norm_matmul_supported(w) or b * t > _MAX_ROWS:
        return qmat(rms_norm(x, norm_w, eps, offset), w)
    out = w.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # The weight is never copied/padded, so blocks must tile it exactly, and
    # Mosaic takes only whole 128-lane blocks: the largest lane multiple not
    # above the requested tile that divides the output dim (256 for a
    # 32000-wide head, where the plain largest divisor, 500, does not lower).
    block_n = max(_LANES, min(block_n, out) // _LANES * _LANES)
    while out % block_n:
        block_n -= _LANES
    y = _norm_matmul_pallas(
        x.reshape(b * t, hidden), norm_w.reshape(1, hidden), w,
        eps=eps, offset=offset, block_n=block_n, interpret=interpret,
    )
    return y.reshape(b, t, out)
