"""A window of the gated delta rule with S in VMEM across its chunks.

The Pallas sibling of ``ops/delta_rule.gated_delta_rule`` (its XLA twin and
oracle): the chunkwise (WY / UT transform) form of

    S <- alpha_t S;   S <- S + beta_t (v_t - S k_t) k_t^T;   o_t = S q_t

for one window of ``L > 1`` positions a row. The twin builds every chunk's
decay triangle, its unit-triangular inverse and five more [64, 64]- or
[64, d]-sized terms for ALL chunks at once in HBM (0.29 MB a token) and then
walks the chunks reading them back. Here a chunk's triangles are made where
they are used: what moves through HBM is q, k, v and the gates in and ``o``
out, and ``S`` once each way.

**The grid** is (row, group of heads, chunk), sequential in chunks. A group
is the fewest heads whose columns are whole lane tiles (2 heads of 192 =
384 lanes; ``delta_step.py``'s group). The group's state is the kernel's
second OUTPUT block, ``[dk, g dv]`` ([96, 384] float32, 147 KB) of the lane
cache's own layout ``[b, dk, H dv]`` (head h's S^T in columns h dv .. (h +
1) dv), whose index changes only with the row and the group: filled from
``s0`` at the row's first chunk, updated by every live chunk, written to HBM
once after the last. No ``to_heads`` / ``from_heads``.

**A group's terms** lie side by side on the lanes: its [64, 64] triangles
as [64, g 64], its [64, dv] terms in the state's own columns. A product a
head is then ONE product a group whose right side holds each head's rows
masked to that head's columns (block-diagonal: the contraction runs over
both heads' positions and the other head's terms are zeros), so nothing is
ever cut at a lane that is not a tile's edge. q and k arrive a head at a
time ([b, H, L, dk], made by XLA: no group of heads that is whole tiles of
dk = 96 divides 30), the gates a group with the positions on lanes
([b, groups, chunks, 2, g 64]); the kernel forms the cumulative decay and
the gates' columns from them by masked sums.

**A chunk**, with g the cumulative log decay, Gamma[t, i] = exp(g_t - g_i):

    A   = tril(diag(beta) (Gamma * K K^T), -1)
    T   = (I + A)^-1
    U   = T diag(beta) (V - diag(exp g) K S)
    O   = diag(exp g) Q S + tril(Gamma * Q K^T) U
    S  <- exp(g_C) S + (diag(exp(g_C - g)) K)^T U

``T`` is by forward substitution, never a Neumann series in powers of ``A``
(beta k_i . k_j reaches 2 and the powers cancel catastrophically), blocked:
the 16-row diagonal blocks row by row on the vector unit in float32 (a
group's eight blocks side by side, fifteen rank-one eliminations), then the
blocks below the diagonal by products, doubling the solved block twice
(``_unit_lower_inverse``). It is most of a chunk's time, 1.7 of 2.7 us a
group: what bounds the row-by-row part is the cross-lane unit (a column of
``A`` has to go out over lanes), and sixty-three eliminations over the
whole [64, 64] with no product at all read the same 2.7 us and made every
program that holds the kernel slower to trace and lower (chip calls 3 to
6, PR 35).

**Only live chunks are walked.** Each row's (lo, hi) rides in as a
scalar-prefetch operand: a chunk with no position in [lo, hi) is neither
fetched (the index maps hold the nearest live chunk) nor computed: ``S``
passes through bit for bit and the kernel writes zeros to ``o``. A row
with no live position returns ``s0``.

**The same arithmetic** as the twin: float32 ``S`` and sums, every product
three bfloat16 passes (``Precision.HIGH``'s: Mosaic takes DEFAULT and
HIGHEST only, so the operands are split into bfloat16 halves by hand; one
pass rounds ``S`` itself, 5e-3 in PR 34).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.pallas import delta_step

# Positions a chunk (``ops/delta_rule.CHUNK``).
CHUNK = 64
_BLOCK = 16  # rows a diagonal block of a chunk's triangle that is solved row by row
_LANES = 128
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def tiles(dk: int, n: int, dv: int) -> bool:
    """Whether the kernel takes these widths (``delta_step.tiles``: ``H dv``
    in whole groups of heads, ``dk`` in whole sublane tiles). Anything else
    (the tests' tiny models) is the twin's."""
    return delta_step.tiles(dk, n, dv)


def _split(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """float32 -> its two bfloat16 halves (x = hi + lo to 2^-17)."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot3(a, b, dims=_NN) -> jnp.ndarray:
    """A float32 product of two split operands in three bfloat16 passes."""
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=dims,
        preferred_element_type=jnp.float32,
    )
    return dot(a[0], b[0]) + (dot(a[0], b[1]) + dot(a[1], b[0]))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


# ``jnp.where``, ``//`` and ``%`` are jitted functions of their own: a nested
# call each in the kernel's jaxpr, 87 of them a kernel, which every program
# that holds the kernel pays to trace and lower at start-up (25 programs a
# server; PERF.md section 6, PR 35). The primitives, then.
def _where(mask, x, y=0.0):
    full = lambda v: jnp.broadcast_to(jnp.asarray(v, jnp.float32), mask.shape)
    return jax.lax.select(mask, full(x), full(y))


def _div(x, by: int):
    return jax.lax.div(x, jnp.int32(by))


def _rem(x, by: int):
    return jax.lax.rem(x, jnp.int32(by))


def _by_head(parts: list, lane: jnp.ndarray, width: int) -> jnp.ndarray:
    """``parts[h]`` on the lanes of head h (``width`` lanes a head)."""
    out = parts[0]
    for h in range(1, len(parts)):
        out = _where(lane >= h * width, parts[h], out)
    return out


def _head_rows(x: jnp.ndarray, lane: jnp.ndarray, width: int, g: int):
    """[g * c, g * width] (head h's product with everything) -> [c, g *
    width]: head h's rows on head h's lanes."""
    c = x.shape[0] // g
    return _by_head([x[h * c:(h + 1) * c] for h in range(g)], lane, width)


def _block_diagonal(x: jnp.ndarray, lane: jnp.ndarray, width: int, g: int):
    """[c, g * width] -> [g * c, g * width]: head h's columns in rows h c ..
    (h + 1) c, zeros elsewhere (the right side of a product a group)."""
    if g == 1:
        return x
    head = lambda h: (lane >= h * width) & (lane < (h + 1) * width)
    return jnp.concatenate(
        [_where(head(h), x) for h in range(g)], axis=0)


def _unit_lower_inverse(a: jnp.ndarray, lane: jnp.ndarray, g: int) -> jnp.ndarray:
    """(I + A)^-1 a head for ``a`` [c, g c]: ``g`` heads' strictly lower
    triangles side by side (sublane t, lane (h, i)): A_h[t, i]), the
    inverses in the same layout, by forward substitution.

    The 16-row diagonal blocks on the vector unit in float32, all of them at
    once ([16, g c]: block b of head h in its own 16 lanes): W starts as I,
    and once row r is final every later row t takes ``W[t] -= A[t, r] W[r]``;
    fifteen steps. A block's column r goes out over its 16 lanes by rolls
    (to the block's first lane, then doubled four times). Then the blocks
    below the diagonal by products, doubling the solved block twice:
    [[L1, 0], [B, L2]]^-1 = [[T1, 0], [-T2 B T1, T2]]."""
    c, m = a.shape
    n_blocks = c // _BLOCK
    wide = -(-m // _LANES) * _LANES  # rolls take whole lane tiles
    at_lane = _iota((_BLOCK, wide), 1)
    block_of_lane = _rem(_div(at_lane, _BLOCK), n_blocks)
    col = _rem(at_lane, _BLOCK)
    row = _iota((_BLOCK, wide), 0)
    grow = lambda x: x if wide == m else jnp.concatenate(
        [x, jnp.zeros((x.shape[0], wide - m), x.dtype)], axis=1)
    d = jnp.zeros((_BLOCK, wide), jnp.float32)  # d[t, (.., 16 b + i)] = A_b[t, i]
    for blk in range(n_blocks):
        d = _where(
            block_of_lane == blk, grow(a[blk * _BLOCK:(blk + 1) * _BLOCK]), d)
    w = _where(row == col, 1.0, 0.0)
    for r in range(_BLOCK - 1):
        coef = pltpu.roll(_where(col == r, d, 0.0), (wide - r) % wide, 1)
        for by in (1, 2, 4, 8):
            coef = coef + pltpu.roll(coef, by, 1)
        w = w - coef * jnp.broadcast_to(w[r:r + 1], w.shape)
    inverse = jnp.concatenate(
        [_where(block_of_lane == blk, w, 0.0)[:, :m] for blk in range(n_blocks)],
        axis=0,
    )
    row_block = _div(_iota((c, m), 0), _BLOCK)
    col_block = _div(_rem(lane, c), _BLOCK)
    size = 1
    while size * _BLOCK < c:
        below = (
            (_div(row_block, 2 * size) == _div(col_block, 2 * size))
            & (_rem(_div(row_block, size), 2) == 1)
            & (_rem(_div(col_block, size), 2) == 0)
        )
        x = _dot3(_split(inverse), _split(_block_diagonal(
            _where(below, a, 0.0), lane, c, g)))
        inverse = inverse - _dot3(
            _split(x), _split(_block_diagonal(inverse, lane, c, g)))
        size *= 2
    return inverse


def _one_group(q, k, v, log_alpha, beta, s, *, g: int, dv: int):
    """A chunk of one group of ``g`` heads: q, k [g c, dk] (the heads one
    under another), v [c, g dv], log_alpha, beta [1, g c] (side by side),
    s [dk, g dv] -> (o [c, g dv], the new s)."""
    c = CHUNK
    m = g * c
    t = _iota((c, m), 0)
    lane = _iota((c, m), 1)
    i = _rem(lane, c)
    lane_v = _iota((c, g * dv), 1)
    incl, eye = i <= t, i == t
    head = lambda h: (lane >= h * c) & (lane < (h + 1) * c)
    over = lambda x, shape: jnp.broadcast_to(x, shape)

    # the gates as columns a head: g_t = sum_{i <= t} log_alpha_i, beta_t
    la_b, beta_b = over(log_alpha, (c, m)), over(beta, (c, m))
    g_cols = [
        jnp.sum(_where(incl & head(h), la_b, 0.0), axis=1, keepdims=True)
        for h in range(g)
    ]
    beta_cols = [
        jnp.sum(_where(eye & head(h), beta_b, 0.0), axis=1, keepdims=True)
        for h in range(g)
    ]
    g_col = _by_head([over(x, (c, m)) for x in g_cols], lane, c)
    g_row = jnp.sum(_where(eye, g_col, 0.0), axis=0, keepdims=True)
    diff = g_col - g_row  # g_t - g_i at (sublane t, lane i)
    gamma = _where(incl, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)

    kq = _split(jnp.concatenate([k, q], axis=0))  # [2 m, dk]
    k_parts = (kq[0][:m], kq[1][:m])
    products = _dot3(kq, k_parts, _NT)  # [2 m, m]: K K^T over Q K^T
    kk = _head_rows(products[:m], lane, c, g)
    qk = gamma * _head_rows(products[m:], lane, c, g)
    beta_col = _by_head([over(x, (c, m)) for x in beta_cols], lane, c)
    inverse = _unit_lower_inverse(  # T = (I + A)^-1
        _where(i < t, beta_col * gamma * kk, 0.0), lane, g)

    decay_v = _by_head(
        [over(jnp.exp(x), (c, g * dv)) for x in g_cols], lane_v, dv)
    beta_v = _by_head([over(x, (c, g * dv)) for x in beta_cols], lane_v, dv)
    with_s = _dot3(kq, _split(s))  # [2 m, g dv]: K S over Q S
    ks = _head_rows(with_s[:m], lane_v, dv, g)
    qs = _head_rows(with_s[m:], lane_v, dv, g)
    u = _dot3(
        _split(inverse),
        _split(_block_diagonal(beta_v * (v - decay_v * ks), lane_v, dv, g)),
    )
    u_rows = _split(_block_diagonal(u, lane_v, dv, g))  # [m, g dv]
    o = decay_v * qs + _dot3(_split(qk), u_rows)
    at = _iota((1, m), 1)
    g_ends = [  # [1, 1] a head: the chunk's whole log decay
        jnp.sum(
            _where((at >= h * c) & (at < (h + 1) * c), log_alpha),
            axis=1, keepdims=True,
        )
        for h in range(g)
    ]
    k_out = k * jnp.concatenate(
        [jnp.exp(end - x) for end, x in zip(g_ends, g_cols, strict=True)],
        axis=0,
    )
    lane_s = _iota(s.shape, 1)
    s = _by_head(
        [over(jnp.exp(x), s.shape) for x in g_ends], lane_s, dv
    ) * s + _dot3(_split(k_out), u_rows, _TN)
    return o, s


def _kernel(
    span_ref,  # [2 b] int32: lo, hi a row
    q_ref,  # [g, c, dk]
    k_ref,  # [g, c, dk]
    v_ref,  # [c, g dv]
    gates_ref,  # [2, g c]: log_alpha, beta
    s0_ref,  # [dk, g dv]
    o_ref,  # [c, g dv]
    s_ref,  # [dk, g dv]: the running state of this row and group
    *,
    dv: int,
):
    bi, ci = pl.program_id(0), pl.program_id(2)
    g, c, dk = q_ref.shape
    lo, hi = span_ref[2 * bi], span_ref[2 * bi + 1]

    @pl.when(ci == 0)
    def _():
        s_ref[...] = s0_ref[...]

    live = jnp.maximum(ci * c, lo) < jnp.minimum((ci + 1) * c, hi)

    @pl.when(live)
    def _():
        o_ref[...], s_ref[...] = _one_group(
            q_ref[...].reshape(g * c, dk), k_ref[...].reshape(g * c, dk),
            v_ref[...], gates_ref[0:1, :], gates_ref[1:2, :], s_ref[...],
            g=g, dv=dv,
        )

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_rule(
    q: jnp.ndarray,  # [b, L, H, dk] float32
    k: jnp.ndarray,  # [b, L, H, dk] float32
    v: jnp.ndarray,  # [b, L, H, dv] float32
    log_alpha: jnp.ndarray,  # [b, L, H] float32, 0 where not live
    beta: jnp.ndarray,  # [b, L, H] float32, 0 where not live
    s0: jnp.ndarray,  # [b, dk, H * dv] float32: the lane cache's layout
    spans: jnp.ndarray | None = None,  # [b, 2] int32: lo, hi a row
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(o [b, L, H, dv] float32, s after the last position in ``s0``'s
    layout): ``ops/delta_rule.gated_delta_rule``'s mathematics, ``tiles``
    required. No position of row r is live before ``spans[r, 0]`` or from
    ``spans[r, 1]`` on (None: the whole window may be); ``o`` is zero in
    the chunks outside the span."""
    b, length, heads, dk = q.shape
    dv = v.shape[-1]
    n = heads * dv
    if not tiles(dk, n, dv) or s0.shape != (b, dk, n):
        raise ValueError(
            f"dk {dk} / H dv {n} do not tile (use ops/delta_rule.gated_delta_rule)"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    c = CHUNK
    g = delta_step._group(dv)
    groups = heads // g
    n_chunks = -(-length // c)
    padded = n_chunks * c
    if spans is None:
        spans = jnp.tile(jnp.array([[0, length]], jnp.int32), (b, 1))
    lo = jnp.clip(spans[:, 0], 0, length).astype(jnp.int32)
    hi = jnp.clip(spans[:, 1], lo, length).astype(jnp.int32)

    grow = lambda x: jnp.pad(
        x, ((0, 0), (0, padded - length)) + ((0, 0),) * (x.ndim - 2))
    by_head = lambda x: jnp.swapaxes(grow(x), 1, 2)  # [b, H, L', dk]

    def by_group(x):  # [b, L, H] -> [b, groups, chunks, 1, g c]
        x = grow(x).reshape(b, n_chunks, c, groups, g)
        return x.transpose(0, 3, 1, 4, 2).reshape(b, groups, n_chunks, 1, g * c)

    gates = jnp.concatenate([by_group(log_alpha), by_group(beta)], axis=3)

    def held(bi, ci, span_ref):
        # A chunk outside the row's span is not walked: its block index
        # stays on the nearest chunk inside, and nothing is fetched for it.
        first = span_ref[2 * bi] // c
        last = jnp.maximum((span_ref[2 * bi + 1] - 1) // c, first)
        return jnp.clip(ci, first, jnp.minimum(last, n_chunks - 1))

    a_head = pl.BlockSpec(
        (None, g, c, dk), lambda bi, gi, ci, sp: (bi, gi, held(bi, ci, sp), 0)
    )
    a_chunk = lambda at: pl.BlockSpec(
        (None, c, g * dv), lambda bi, gi, ci, sp: (bi, at(bi, ci, sp), gi)
    )
    state = pl.BlockSpec((None, dk, g * dv), lambda bi, gi, ci, sp: (bi, 0, gi))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, groups, n_chunks),
        in_specs=[
            a_head,
            a_head,
            a_chunk(held),
            pl.BlockSpec(
                (None, None, None, 2, g * c),
                lambda bi, gi, ci, sp: (bi, gi, held(bi, ci, sp), 0, 0),
            ),
            state,
        ],
        out_specs=[a_chunk(lambda bi, ci, sp: ci), state],
    )
    o, s = pl.pallas_call(
        functools.partial(_kernel, dv=dv),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, padded, n), jnp.float32),
            jax.ShapeDtypeStruct((b, dk, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="gated_delta_rule",
    )(
        jnp.stack([lo, hi], axis=1).reshape(-1),
        by_head(q), by_head(k), grow(v).reshape(b, padded, n), gates, s0,
    )
    return o[:, :length].reshape(b, length, heads, dv), s
