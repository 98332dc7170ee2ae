"""The gated delta rule's one-token update with one read and one write of S.

The Pallas sibling of ``ops/delta_rule.gated_delta_step`` (its XLA twin and
oracle), for every decode step of a model whose state layers run the gated
delta rule:

    S <- alpha S;   S <- S + beta (v - S k) k^T;   o = S q      a head

over the state as the lane cache lays it out: ``[dk, H * dv]`` float32 a row
and layer, head h's S^T in columns h dv .. (h + 1) dv, so that ``dk`` is on
sublanes and the minor axis is whole 128-lane tiles (neither dk = 96 nor
dv = 192 is one). In that layout the update is elementwise over ``[dk, W]``
blocks with two sums over sublanes (S k and S q), IF k and q are spread out
the same way: ``kx[j, h dv + c] = k[h, j]``. A head's 192 columns are not a
whole number of lane tiles, so inside a block the columns are taken a GROUP
of heads at a time, the fewest whose columns are whole tiles (2 heads of 192
= 384 lanes): each head's k is one column of ``k^T`` broadcast over the
lanes, and a select on the lane's index puts each head's where its columns
are. All on the vector unit; no product, no rounding.

**The operand is the layer stack's whole state** ``[n, b, dk, H dv]`` with
the layer's index as a scalar-prefetch operand, aliased to the output: a
kernel's operand is a whole array, and a slice of the layer scan's carry
would be copied out and back (70 MB a layer at 32 lanes). The grid is (row,
block of columns); a step reads one ``[dk, W]`` block, updates it, takes its
part of o, and writes it back: S moves once each way, which is the
operation's floor. k^T and q^T ride in cut to the blocks' heads (``[b,
blocks, dk, 128]``, a block's heads on the first lanes), the gates and v
spread over the columns (``[b, 1, H dv]``); all tiny, made by XLA.

**Only the dispatch's LIVE rows are walked** (``live`` [b]; PR 54). Their
indices, first, and their count ride in as a second scalar-prefetch operand
(``live_rows``: made once a dispatch by whoever holds the mask, outside the
step and layer scans), the grid's row bound is the count (a traced bound, as
``megablox.gmm``'s tiles) and the index maps read the row from the list. A
row that is not live is neither fetched nor written: its state stays where
it is, bit for bit, because the stack is aliased, and its ``o`` is zero (a
select after the call: what the kernel never wrote is nobody's). With no
row live the grid still has one row, the first, and its blocks are copied
through: a grid of none would leave the pipeline's one block unwritten.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
# The widest block of columns a grid step takes: [96, 1920] float32 is 737
# KB, in and out double-buffered.
_W_BLOCK = 2048


def _group(dv: int) -> int:
    """The fewest heads whose columns are whole lane tiles."""
    return math.lcm(dv, _LANES) // dv


def tiles(dk: int, n: int, dv: int) -> bool:
    """Whether the kernel takes these widths: ``H * dv`` in whole groups of
    heads (so in whole 128-lane tiles) and ``dk`` in whole sublane tiles.
    Anything else (the tests' tiny models) is the twin's."""
    return n % (_group(dv) * dv) == 0 and dk % _SUBLANES == 0


def _largest_divisor(total: int, unit: int, limit: int) -> int:
    best = unit
    for k in range(unit, min(total, limit) + 1, unit):
        if total % k == 0:
            best = k
    return best


def live_rows(live: jnp.ndarray) -> jnp.ndarray:
    """[b] bool -> [b + 1] int32: the rows' indices with the live ones first
    (each kind in its own order), and how many are live last. Constant over
    a dispatch's steps and layers: make it once."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return jnp.concatenate([order, jnp.sum(live, dtype=jnp.int32)[None]])


# The chip's clock (v5e, my chip call 4, PR 54: ``check.timed_delta_step``, a
# decode chunk's 8 steps of a stack's layers a program, host clock, us a CALL
# with the XLA that lays k, q and the gates out: 56-65 us of it, the kernel
# at one live row):
#
#   rows  live | this kernel | the parent's (every row stepped)
#   Qwen3-Next's widths (32 value heads, dk 128, dv 128; 9 layers):
#    64    64  |    476.2    |   471.8
#    64    45  |    352.3    |   468.8
#    64    16  |    162.1    |   467.4
#    64     1  |     65.1    |   471.5
#   Olmo-Hybrid's widths (30 heads, dk 96, dv 192; 12 layers):
#    32    32  |    251.8    |   269.5
#    32    20  |    190.3    |   266.7
#    32    11  |    127.3    |   268.2
#    32     1  |     56.1    |   268.5
#
# 6.5 us a live row at Qwen3-Next's widths and 6.3-7.0 at Olmo-Hybrid's,
# nothing a dead one. The other way to pass over them was tried and not kept
# (my chip call 1): the parent's grid of every row, the steps past the live
# count held on the block the last live step left in place with their body
# under ``pl.when``. No fetch and no write either, but a grid step of its own
# each, about 0.2 us: 8.3 and 11.9 us a call more than this form at 45 and 16
# live of 64, 5.6 and 7.4 at 20 and 11 of 32, the same at full lanes.


def _kernel(layer_ref, rows_ref, s_ref, k_ref, q_ref, v_ref, a_ref, b_ref,
            o_ref, s_out_ref, *, dv: int, group: int):
    """One [dk, w] block of one LIVE row (the index maps chose it), or, where
    the dispatch has no live row, of the one row its grid still walks: that
    block goes back as it came and ``o`` is left unwritten."""
    del layer_ref  # the index maps' own
    none_live = rows_ref[rows_ref.shape[0] - 1] == 0

    @pl.when(none_live)
    def _():
        s_out_ref[...] = s_ref[...]

    @pl.when(jnp.logical_not(none_live))
    def _():
        _step(s_ref, k_ref, q_ref, v_ref, a_ref, b_ref, o_ref, s_out_ref,
              dv=dv, group=group)


def _step(s_ref, k_ref, q_ref, v_ref, a_ref, b_ref, o_ref, s_out_ref, *,
          dv: int, group: int):
    """Two walks over the block's sublane tiles a group of heads, everything
    between a load and a store in registers: the first sums S k, S q and k q
    over the OLD state (o = alpha S q + beta (v - alpha S k) (k . q), so no
    second sum over the new state is needed), the second writes alpha S +
    k (beta err)."""
    dk, w = s_ref.shape
    span = group * dv  # lanes a group of heads takes: whole tiles
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, span), 1) // dv

    def spread(ref, first, rows):
        # [8, span]: head ``first + g``'s column over that head's lanes
        out = jnp.broadcast_to(ref[rows, first:first + 1], (_SUBLANES, span))
        for g in range(1, group):
            col = jnp.broadcast_to(
                ref[rows, first + g:first + g + 1], (_SUBLANES, span))
            out = jnp.where(head_of_lane == g, col, out)
        return out

    tiles_of_rows = [pl.ds(r * _SUBLANES, _SUBLANES) for r in range(dk // _SUBLANES)]
    for i in range(w // span):
        at = pl.ds(i * span, span)
        zero = jnp.zeros((_SUBLANES, span), jnp.float32)
        sk, sq, kq = zero, zero, zero
        for rows in tiles_of_rows:
            s = s_ref[rows, at]
            kx, qx = spread(k_ref, i * group, rows), spread(q_ref, i * group, rows)
            sk, sq, kq = sk + s * kx, sq + s * qx, kq + kx * qx
        total = lambda x: jnp.sum(x, axis=0, keepdims=True)  # [1, span]
        a = a_ref[:, at]
        step = b_ref[:, at] * (v_ref[:, at] - a * total(sk))  # beta err
        o_ref[:, at] = a * total(sq) + step * total(kq)
        for rows in tiles_of_rows:
            s_out_ref[rows, at] = a * s_ref[rows, at] + spread(k_ref, i * group, rows) * step


def _by_block(x: jnp.ndarray, heads_a_block: int) -> jnp.ndarray:
    """[b, H, dk] -> [b, blocks, dk, 128]: x^T cut to each block's heads, on
    the first lanes."""
    b, heads, dk = x.shape
    x = x.reshape(b, heads // heads_a_block, heads_a_block, dk)
    x = jnp.swapaxes(x, 2, 3)
    return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, _LANES - heads_a_block)))


@functools.partial(jax.jit, static_argnames=("interpret", "w_block"))
def gated_delta_step(
    ssm: jnp.ndarray,  # [n, b, dk, H * dv] float32: a layer stack's state
    layer: jnp.ndarray,  # which layer's rows to step
    q: jnp.ndarray,  # [b, H, dk] float32
    k: jnp.ndarray,  # [b, H, dk] float32
    v: jnp.ndarray,  # [b, H, dv] float32
    log_alpha: jnp.ndarray,  # [b, H] float32
    beta: jnp.ndarray,  # [b, H] float32
    live: jnp.ndarray | None = None,  # [b] bool: the rows to step; None = all
    *,
    rows: jnp.ndarray | None = None,  # ``live_rows(live)`` where it is made already
    interpret: bool | None = None,
    w_block: int = _W_BLOCK,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o [b, H, dv] float32, the stack with layer ``layer``'s live rows
    stepped): ``ops/delta_rule.gated_delta_step``'s mathematics on the live
    rows, ``tiles`` required. A row that is not live keeps its state, not
    read and not written, and its ``o`` is zero."""
    _, b, dk, n = ssm.shape
    heads, dv = v.shape[1], v.shape[2]
    if not tiles(dk, n, dv):
        raise ValueError(
            f"dk {dk} / H dv {n} do not tile (use ops/delta_rule.gated_delta_step)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if live is None:
        live = jnp.ones((b,), bool)
    if rows is None:
        rows = live_rows(live)
    group = _group(dv)
    w = _largest_divisor(n, group * dv, max(w_block, group * dv))
    over_columns = lambda x: jnp.repeat(x, dv, axis=-1)[:, None, :]  # [b, 1, H dv]
    # grid step (i, wi) is block wi of the i-th LIVE row
    state = pl.BlockSpec(
        (None, None, dk, w), lambda i, wi, layer, rows: (layer[0], rows[i], 0, wi)
    )
    columns = pl.BlockSpec(
        (None, None, dk, _LANES), lambda i, wi, layer, rows: (rows[i], wi, 0, 0)
    )
    row = pl.BlockSpec((None, 1, w), lambda i, wi, layer, rows: (rows[i], 0, wi))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # As many rows as are live, and one where none is (``_kernel``): a
        # TRACED bound, which the TPU's pipeline takes (``megablox.gmm``'s
        # tiles are one); every block's shape stays static.
        grid=(jnp.maximum(rows[b], 1), n // w),  # cake-lint: disable=traced-block-dim
        in_specs=[state, columns, columns, row, row, row],
        out_specs=[row, state],
    )
    o, ssm = pl.pallas_call(  # cake-lint: disable=prefetch-ref-unused (``state``'s index map reads it)
        functools.partial(_kernel, dv=dv, group=group),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
            jax.ShapeDtypeStruct(ssm.shape, jnp.float32),
        ],
        # operand 2 (after the scalars) is the stack; it is output 1
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="gated_delta_step",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), rows, ssm,
        _by_block(k, w // dv), _by_block(q, w // dv),
        v.reshape(b, 1, n), over_columns(jnp.exp(log_alpha)), over_columns(beta),
    )
    return jnp.where(live[:, None, None], o.reshape(b, heads, dv), 0.0), ssm
