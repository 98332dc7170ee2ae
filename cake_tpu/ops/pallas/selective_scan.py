"""The selective scan of a prefill window with the running state in VMEM.

The Pallas sibling of ``ops/ssm.selective_scan`` (its XLA twin and oracle):

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * u_t) * B_t
    y_t = s_t . C_t

for one window of ``L`` positions a row. The twin builds a chunk's decay and
input terms and STACKS the chunk's states ([b, T, n, d] float32) to contract
them with C: every step's state goes to HBM and comes back. Here ``s``
([n, d_inner] float32 a row, ``n`` on sublanes, ``d`` on lanes) stays in VMEM
for the whole window and ``y_t`` is taken while ``s_t`` is still in
registers, so what moves is u, dt and y (12 bytes an element of
``[L, d_inner]``, not 8 * n) and B and C.

**The grid** is (row, tile of time, block of ``d_inner``), sequential in
time. The row's state is the kernel's second OUTPUT block, [n, d_inner],
whose index changes only with the row: it is filled from ``s0`` at the row's
first tile, each step updates its own block of ``d`` in it, and it is
written to HBM once, after the row's last step. Pallas pipelines the
``[T, d_block]`` tiles of u, dt and y. B and C arrive with ``B_t[n]`` on
every lane (``[T, n, 128]``, made by one XLA broadcast, 16 KB a position for
the two: a column of the state's sublanes is then a plain load, where taking
it out of ``[T, n]`` is a lane reduction a step); their block's index holds
over the blocks of ``d``, so a tile of them is fetched once.

Inside a step the block's 128-lane columns are taken ``_COLUMNS`` at a time,
and for them time is walked in groups of ``_GROUP`` = 8 positions with the
columns' ``[n, 128]`` states in registers: eight dependent updates a column,
whose products with C are summed over ``n`` for all eight positions at once
(``_sum_sublanes``) and stored as one ``[8, 128]`` tile of y.

**Only the span is walked.** ``span`` = (lo, hi), as the twin's: no row is
live before ``lo`` or from ``hi`` on. It rides in as a scalar-prefetch
operand: the index maps hold u's, dt's, B's and C's tile inside the span (a
tile outside it is not fetched again), and inside a tile the recurrence runs
over the groups that touch [lo, hi) only. ``y`` is zero everywhere else.
Where ``dt`` is zero the decay is exp(0) = 1 and the input is 0: ``s``
passes through bit for bit, and a row with no live position returns ``s0``.

Same float32 state, products and ``exp`` as the twin; the sum over ``n`` is
taken in another order, so the two agree to rounding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# Steps done between two loads of u and dt and two stores of y: one sublane
# tile of time, so both are whole [8, d_block] tiles.
_GROUP = _SUBLANES
# The widest block of d_inner a grid step takes, and the longest tile of
# time: u, dt and y tiles of [128, 1024] float32 are 512 KB each, B's and
# C's [128, n, 128] 1 MB each at n = 16; double-buffered, 8 MB of the 16 a
# v5e core gives a kernel.
_D_BLOCK = 1024
_T_TILE = 128
# 128-lane columns of a block whose steps are unrolled together; the block's
# other columns are a loop around them. The unrolled body is what a program
# pays to trace and compile, three kernels a join program: at 8 columns a
# call took 128 us for 133 at 2 (1 row x 512, d_inner 5120) and the server's
# 25 start-up programs 117 s for 66 (PERF.md, PR 33). One column at a time
# leaves the eight dependent updates nothing to hide behind: 168 us.
_COLUMNS = 2


def tiles(d: int, n: int) -> bool:
    """Whether the kernel takes these widths: ``d_inner`` in whole 128-lane
    tiles and ``d_state`` in whole sublane tiles. Anything else (the tests'
    tiny models) is the twin's."""
    return d % _LANES == 0 and n % _SUBLANES == 0


def _largest_divisor(total: int, unit: int, limit: int) -> int:
    """The largest multiple of ``unit`` that divides ``total`` and is at most
    ``limit`` (``total`` is a multiple of ``unit``)."""
    best = unit
    for k in range(unit, min(total, limit) + 1, unit):
        if total % k == 0:
            best = k
    return best


def _sum_sublanes(h: list, row: jnp.ndarray) -> jnp.ndarray:
    """[8, 128] whose sublane j is the sum over the sublanes of ``h[j]`` (eight
    [8, 128] arrays): a transposing reduction in three halvings, 10 sublane
    rotations for the 24 that eight separate reductions take."""

    def halve(x, y, k):
        # sublane r with bit k clear: x[r] + x[r + k]; set: y[r] + y[r - k]
        return jnp.where(
            (row & k) == 0,
            x + pltpu.roll(x, _SUBLANES - k, 0),
            y + pltpu.roll(y, k, 0),
        )

    z = [halve(h[i], h[i + 4], 4) for i in range(4)]
    w = [halve(z[i], z[i + 2], 2) for i in range(2)]
    return halve(w[0], w[1], 1)


def _scan_kernel(
    span_ref,  # [2] int32: lo, hi
    u_ref,  # [T, D]
    dt_ref,  # [T, D]
    a_ref,  # [n, D]
    b_ref,  # [T, n, 128]: B_t[n] on every lane
    c_ref,  # [T, n, 128]
    s0_ref,  # [n, d]
    y_ref,  # [T, D]
    s_ref,  # [n, d]: the running state, this row's, all blocks of d
    *,
    columns,  # 128-lane columns of the block whose steps are interleaved
):
    ti, di = pl.program_id(1), pl.program_id(2)
    t_tile, d_block = u_ref.shape
    n = a_ref.shape[0]
    lo, hi = span_ref[0], span_ref[1]
    block = pl.ds(pl.multiple_of(di * d_block, _LANES), d_block)

    @pl.when(ti == 0)
    def _():
        s_ref[:, block] = s0_ref[:, block]

    at = ti * t_tile  # the tile's first position
    first = jnp.maximum(lo - at, 0) // _GROUP
    last = jnp.clip(-(-(hi - at) // _GROUP), first, t_tile // _GROUP)

    @pl.when((first > 0) | (last < t_tile // _GROUP))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)

    def some_columns(ci, _):
        """The tile's groups for ``columns`` 128-lane columns of the block,
        their state in registers from group to group."""
        at_lane = [
            pl.multiple_of((ci * columns + k) * _LANES, _LANES)
            for k in range(columns)
        ]
        cols = [pl.ds(x, _LANES) for x in at_lane]
        mine = [pl.ds(di * d_block + x, _LANES) for x in at_lane]

        def group(g, s):
            t0 = pl.multiple_of(g * _GROUP, _GROUP)
            s, prods = list(s), [[] for _ in cols]
            dt_g = [dt_ref[pl.ds(t0, _GROUP), col] for col in cols]  # [8, 128]
            du_g = [
                dt_k * u_ref[pl.ds(t0, _GROUP), col]
                for dt_k, col in zip(dt_g, cols, strict=True)
            ]
            for j in range(_GROUP):
                b_t, c_t = b_ref[t0 + j], c_ref[t0 + j]  # [n, 128]
                for k, col in enumerate(cols):
                    dt_t = dt_g[k][j : j + 1]  # [1, 128]
                    du_t = du_g[k][j : j + 1]
                    s[k] = jnp.exp(dt_t * a_ref[:, col]) * s[k] + du_t * b_t
                    p = s[k] * c_t
                    prods[k].append(
                        sum(p[i : i + _SUBLANES] for i in range(0, n, _SUBLANES))
                    )
            for k, col in enumerate(cols):
                y_ref[pl.ds(t0, _GROUP), col] = _sum_sublanes(prods[k], row)
            return tuple(s)

        s = jax.lax.fori_loop(
            first, last, group, tuple(s_ref[:, m] for m in mine)
        )
        for m, s_k in zip(mine, s, strict=True):
            s_ref[:, m] = s_k

    jax.lax.fori_loop(0, d_block // _LANES // columns, some_columns, None)


@functools.partial(jax.jit, static_argnames=("interpret", "d_block", "t_tile", "columns"))
def selective_scan(
    u: jnp.ndarray,  # [b, L, d] float32, after conv and silu
    dt: jnp.ndarray,  # [b, L, d] float32, zero where not live
    a: jnp.ndarray,  # [n, d] float32, -exp(A_log) transposed
    b_in: jnp.ndarray,  # [b, L, n] float32
    c_out: jnp.ndarray,  # [b, L, n] float32
    s0: jnp.ndarray,  # [b, n, d] float32
    span: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    *,
    interpret: bool | None = None,
    d_block: int = _D_BLOCK,
    t_tile: int = _T_TILE,
    columns: int = _COLUMNS,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(y [b, L, d] float32 without the D term, s after the last position):
    ``ops/ssm.selective_scan``'s contract, ``tiles(d, n)`` required. ``y`` is
    zero outside the groups of ``_GROUP`` positions that touch the span."""
    b, length, d = u.shape
    n = a.shape[0]
    if not tiles(d, n):
        raise ValueError(
            f"d_inner {d} / d_state {n} do not tile (use ops/ssm.selective_scan)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # Time in whole groups; the tail reads dt = 0, which leaves ``s`` alone,
    # and its y is cut off.
    padded = -(-length // _GROUP) * _GROUP
    if padded != length:
        grow = lambda x: jnp.pad(x, ((0, 0), (0, padded - length), (0, 0)))
        u, dt, b_in, c_out = grow(u), grow(dt), grow(b_in), grow(c_out)
    t_tile = _largest_divisor(padded, _GROUP, t_tile)
    d_block = _largest_divisor(d, _LANES, d_block)
    columns = _largest_divisor(d_block // _LANES, 1, columns)
    n_t = padded // t_tile
    if span is None:
        lo, hi = jnp.int32(0), jnp.int32(length)
    else:
        lo = jnp.clip(span[0], 0, length).astype(jnp.int32)
        hi = jnp.clip(span[1], lo, length).astype(jnp.int32)

    def held(ti, span_ref):
        # A tile outside the span is not walked: its block index stays on the
        # nearest tile inside, and nothing is fetched for it.
        first = span_ref[0] // t_tile
        last = jnp.maximum((span_ref[1] - 1) // t_tile, first)
        return jnp.clip(ti, first, jnp.minimum(last, n_t - 1))

    tile = pl.BlockSpec(
        (None, t_tile, d_block), lambda bi, ti, di, sp: (bi, held(ti, sp), di)
    )
    on_lanes = pl.BlockSpec(
        (None, t_tile, n, _LANES), lambda bi, ti, di, sp: (bi, held(ti, sp), 0, 0)
    )
    row_state = pl.BlockSpec((None, n, d), lambda bi, ti, di, sp: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_t, d // d_block),
        in_specs=[
            tile,
            tile,
            pl.BlockSpec((n, d_block), lambda bi, ti, di, sp: (0, di)),
            on_lanes,
            on_lanes,
            row_state,
        ],
        out_specs=[
            pl.BlockSpec(
                (None, t_tile, d_block), lambda bi, ti, di, sp: (bi, ti, di)
            ),
            row_state,
        ],
    )
    on_every_lane = lambda x: jnp.broadcast_to(
        x[..., None], (b, padded, n, _LANES)
    )
    y, s = pl.pallas_call(
        functools.partial(_scan_kernel, columns=columns),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, padded, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="selective_scan",
    )(
        jnp.stack([lo, hi]), u, dt, a,
        on_every_lane(b_in), on_every_lane(c_out), s0,
    )
    return y[:, :length], s
