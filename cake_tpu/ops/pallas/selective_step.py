"""The selective state-space mixer's one-token update with one read and one
write of ``s``.

The Pallas sibling of ``ops/ssm.mixer_forward``'s decode branch (its XLA twin
and oracle), for every decode step of a model whose state layers are Mamba-1
mixers:

    s <- exp(dt * A) * s + (dt * u) B^T ;   y = s . C        a row

over the state as the lane cache lays it out: ``[d_state, d_inner]`` float32
a row and layer, ``d_state`` on sublanes and ``d_inner`` on lanes, whole
tiles both (16 and 5120 at Jamba2-3B's widths). The twin reads ``s``, writes
the new ``s`` into the layer scan's carry and reads it again for ``y``; here
``y`` is taken while the new ``s`` is in registers, so ``s`` moves once each
way, which is the operation's floor (21 MB a layer at 32 rows).

**The operand is the layer stack's whole state** ``[n_state, b, d_state,
d_inner]`` with the layer's index as a scalar-prefetch operand, aliased to
the output, as ``delta_step.py``'s: a kernel's operand is a whole array, and
a slice of the layer scan's carry would be copied out and back. The grid is
(block of rows, block of ``d_inner``); a step reads ``rows`` rows' ``[n, W]``,
updates them, takes their part of ``y`` and writes them back. A step's block
is megabytes (``_ROWS`` rows of the whole width), so that the grid's steps do
not show beside the bytes they move: 38.4 us a call at 32 rows x [16, 5120]
on a v5e, which is what a kernel that only copies the blocks takes (38.3;
8 rows a step; 4 and 2 rows 40.3 and 42.4; the twin 55.0: PERF.md, PR 42).

``dt``, ``dt * u`` and ``y`` are ``[b, d_inner]``, cut ``[b / rows, rows,
d_inner]`` so that a step's rows are one block's sublanes; B and C arrive
with ``B[n]`` on every lane (``[b, n, 128]``, one XLA broadcast, as
``selective_scan.py``'s): a column of the state's sublanes is then a plain
load. All tiny, made by XLA.

Inside a step the block is walked a 128-lane column at a time, every row of
the block for that column (``A``'s column is loaded once for them): all on
the vector unit, float32, the twin's products and ``exp``; the sum over
``d_state`` is taken in another order and a compiler may fuse a product into
a sum, so both agree with the twin to rounding. A row with ``dt = 0`` and
``u = 0`` (a lane that is not live) gets its state back bit for bit:
``exp(0) * s + 0 * B``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.pallas.selective_scan import _largest_divisor, tiles

_LANES = 128
_SUBLANES = 8
# Rows a grid step takes, of the whole width: [8, 16, 5120] float32 is 2.6 MB,
# in and out double-buffered 10.5 MB of the 16 a v5e core gives a kernel.
_ROWS = 8
_BLOCK_BYTES = 2_752_512  # the largest block of state a step takes


def _kernel(layer_ref, s_ref, dt_ref, du_ref, a_ref, b_ref, c_ref,
            y_ref, s_out_ref):
    del layer_ref  # the index maps' own
    rows, n, w = s_ref.shape

    def column(ci, _):
        col = pl.ds(pl.multiple_of(ci * _LANES, _LANES), _LANES)
        a = a_ref[:, col]  # [n, 128]
        for r in range(rows):
            dt = dt_ref[r:r + 1, col]  # [1, 128], over the sublanes below
            s = jnp.exp(dt * a) * s_ref[r, :, col] + du_ref[r:r + 1, col] * b_ref[r]
            s_out_ref[r, :, col] = s
            p = s * c_ref[r]
            p = sum(p[i:i + _SUBLANES] for i in range(0, n, _SUBLANES))
            y_ref[r:r + 1, col] = jnp.sum(p, axis=0, keepdims=True)

    jax.lax.fori_loop(0, w // _LANES, column, None)


@functools.partial(jax.jit, static_argnames=("interpret", "rows", "w_block"))
def selective_step(
    ssm: jnp.ndarray,  # [n_state, b, n, d] float32: a layer stack's state
    layer: jnp.ndarray,  # which layer's rows to step
    u: jnp.ndarray,  # [b, d] float32, after conv and silu
    dt: jnp.ndarray,  # [b, d] float32, zero where not live
    a: jnp.ndarray,  # [n, d] float32, -exp(A_log) transposed
    b_in: jnp.ndarray,  # [b, n] float32
    c_out: jnp.ndarray,  # [b, n] float32
    *,
    interpret: bool | None = None,
    rows: int = _ROWS,
    w_block: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(y [b, d] float32 without the D term, the stack with layer ``layer``
    stepped): ``ops/ssm.mixer_forward``'s decode branch, ``tiles(d, n)``
    required."""
    _, b, n, d = ssm.shape
    if not tiles(d, n):
        raise ValueError(
            f"d_inner {d} / d_state {n} do not tile (use ops/ssm.mixer_forward)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows = _largest_divisor(b, 1, rows)
    if w_block is None:
        w_block = max(_BLOCK_BYTES // (rows * n * 4), _LANES)
    w = _largest_divisor(d, _LANES, w_block)
    state = pl.BlockSpec(
        (None, rows, n, w), lambda bi, wi, layer: (layer[0], bi, 0, wi)
    )
    row = pl.BlockSpec((None, rows, w), lambda bi, wi, layer: (bi, 0, wi))
    on_lanes = pl.BlockSpec((rows, n, _LANES), lambda bi, wi, layer: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // rows, d // w),
        in_specs=[
            state, row, row,
            pl.BlockSpec((n, w), lambda bi, wi, layer: (0, wi)),
            on_lanes, on_lanes,
        ],
        out_specs=[row, state],
    )
    by_block = lambda x: x.reshape(b // rows, rows, d)
    on_every_lane = lambda x: jnp.broadcast_to(x[..., None], (b, n, _LANES))
    y, ssm = pl.pallas_call(  # cake-lint: disable=prefetch-ref-unused (``state``'s index map reads it)
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b // rows, rows, d), jnp.float32),
            jax.ShapeDtypeStruct(ssm.shape, jnp.float32),
        ],
        # operand 1 (after the scalar) is the stack; it is output 1
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="selective_step",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), ssm,
        by_block(dt), by_block(dt * u), a,
        on_every_lane(b_in), on_every_lane(c_out),
    )
    return y.reshape(b, d), ssm
