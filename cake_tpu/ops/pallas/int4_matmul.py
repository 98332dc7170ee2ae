"""Packed-int4 matmul (weight-only, group scales) as a Pallas TPU kernel.

Batch-1 decode is weight-bandwidth bound, and the whole point of int4 storage
(ops/quant.py Quant4Weight) is to stream 0.5 byte/weight from HBM. The XLA
formulation of the grouped matmul (G batched K=gs/2 dots) measured 0.10 of
the int4 stream bound on a real v5e — the unpack/interleave does not fuse
into the dot, and the tiny-K batched matmuls strand the MXU. This kernel owns
the whole pipeline instead:

  * HBM -> VMEM moves ONLY the packed bytes (plus the f32 group scales,
    ~3% of the stream) — the unpack happens on VREGs.
  * Both nibble planes of a block are unpacked, scaled by their group's
    per-output-channel scale, and dotted against the even/odd-strided
    activation halves in two MXU calls per block — K = block_p (hundreds),
    not gs/2.
  * The weight never exists interleaved: logical row 2i is the low nibble
    of packed row i (quantize4_weight's adjacent pairing), so the even/odd
    split lands on the (tiny) activation, exactly like the XLA path.

The grid carries a ROW dimension, so the same kernel serves 1-row decode,
verify chunks, and full prefill widths: on TPU every int4 matmul for a given
weight takes the SAME code path regardless of batch/chunk shape, which is
what keeps the pinned byte-parity invariants (engine row == serialized run,
fused == stepwise, chunked == dense prefill) intact — each logical row's
accumulation order depends only on the k-grid, never on which other rows
share the batch.

Scaled weights are cast to the activation dtype before the dot (bf16 on the
real path) with f32 accumulation — the same rounding the int8 path's
convert-into-dot pays, pinned against the dequantize oracle in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES = 8
_ROW_BLOCK = 256  # prefill widths stream in row tiles; decode fits one


def _int4_kernel(x2_ref, w_ref, s_ref, o_ref, acc_ref, *, gs_packed, kb):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w32 = w_ref[...].astype(jnp.int32)  # [block_p, block_n], sign-extended
    lo = jnp.right_shift(jnp.left_shift(w32, 28), 28)  # low nibble, signed
    hi = jnp.right_shift(w32, 4)  # high nibble (arithmetic shift)
    block_p, block_n = w32.shape
    gpb = block_p // gs_packed
    # Group scales repeat over their gs_packed rows; both nibble planes of a
    # packed row belong to the same logical group, so one replication serves
    # both dots. The scale operand arrives sublane-padded to >= 8 rows per
    # k-block (Mosaic's min tile); only the first gpb rows are live.
    sc = s_ref[:gpb, :]  # [gpb, block_n] f32
    sc_rep = jnp.broadcast_to(
        sc[:, None, :], (gpb, gs_packed, block_n)
    ).reshape(block_p, block_n)
    x_dtype = x2_ref.dtype
    lo_s = (lo.astype(jnp.float32) * sc_rep).astype(x_dtype)
    hi_s = (hi.astype(jnp.float32) * sc_rep).astype(x_dtype)
    xe = x2_ref[0]  # [row_block, block_p] — even logical in-rows
    xo = x2_ref[1]  # odd logical in-rows
    acc_ref[...] += jax.lax.dot(
        xe, lo_s, preferred_element_type=jnp.float32
    ) + jax.lax.dot(xo, hi_s, preferred_element_type=jnp.float32)

    @pl.when(ki == kb - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_block(dim: int, candidates: tuple[int, ...]) -> int:
    for c in candidates:
        if dim % c == 0:
            return c
    return dim


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_n", "interpret")
)
def int4_matmul(
    x: jnp.ndarray,
    packed: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    block_p: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``x @ dequant(packed, scale)`` streaming only the packed bytes.

    Args:
      x: [batch, in] activations (bf16/f32) — any row count (1-row decode
        through full prefill widths; rows tile over the grid).
      packed: [in//2, out] int8, quantize4_weight's adjacent nibble pairing.
      scale: [G, out] f32 per-(in-group, out-channel) scales; in//G must be
        even and divide the k-block.

    Returns [batch, out] in x's dtype.
    """
    b, in_dim = x.shape
    p, out = packed.shape
    groups = scale.shape[0]
    gs_packed = p // groups
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if block_p is None:
        # A k-block must hold WHOLE groups (the scale BlockSpec indexes by
        # groups-per-block): largest preferred size that divides p and is a
        # multiple of the group; one full group otherwise (tiny models).
        block_p = next(
            (
                c
                for c in (256, 128, 64)
                if p % c == 0 and c % gs_packed == 0
            ),
            gs_packed,
        )
    if p % block_p or block_p % gs_packed:
        raise ValueError(
            f"k-block {2 * block_p} must tile in={2 * p} in whole "
            f"group-{2 * gs_packed} multiples"
        )
    if block_n is None:
        block_n = _pick_block(out, (512, 256, 128))
    gpb = block_p // gs_packed

    # Rows round up to a sublane tile and tile over the grid in _ROW_BLOCK
    # strips. Even/odd activation halves live on a leading plane axis so a
    # row strip slices BOTH halves coherently.
    row_block = min(_ROW_BLOCK, max(_SUBLANES, -(-b // _SUBLANES) * _SUBLANES))
    bp = -(-b // row_block) * row_block
    xp = jnp.pad(x, ((0, bp - b), (0, 0))) if bp != b else x
    x2 = jnp.stack([xp[:, 0::2], xp[:, 1::2]], axis=0)  # [2, bp, p]

    kb = p // block_p
    # Sublane-pad the scales to >= 8 rows per k-block (Mosaic min tile):
    # [kb, spb, out] flattened; row k*spb+j = scale group k*gpb+j, j < gpb.
    spb = max(_SUBLANES, gpb)
    if spb != gpb:
        sc_pad = jnp.zeros((kb, spb, out), scale.dtype)
        sc_pad = sc_pad.at[:, :gpb, :].set(scale.reshape(kb, gpb, out))
        scale = sc_pad.reshape(kb * spb, out)

    grid = (bp // row_block, out // block_n, kb)
    out_arr = pl.pallas_call(
        functools.partial(_int4_kernel, gs_packed=gs_packed, kb=kb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((2, row_block, block_p), lambda ri, ni, ki: (0, ri, ki)),
            pl.BlockSpec((block_p, block_n), lambda ri, ni, ki: (ki, ni)),
            pl.BlockSpec((spb, block_n), lambda ri, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec(
            (row_block, block_n), lambda ri, ni, ki: (ri, ni)
        ),
        scratch_shapes=[pltpu.VMEM((row_block, block_n), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bp, out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x2, packed, scale)
    return out_arr[:b]
