"""The k-th largest score of each row, found bit by bit, as ONE operation.

A decode step of a model with a learned index (``model_type: deepseek_v32``;
ops/sparse_index.py) chooses the ``index_topk`` best of a row's table of
scores. The choice needs no order, only the k-th largest score a row: with
the scores mapped to keys whose integer order is the floats' order, the key
is found from its top bit down, 32 counts of ``key >= candidate`` over the
row (``sparse_index._kth_largest``, whose XLA form is a ``fori_loop`` of three
small fusions a turn). The arithmetic is the same here; what differs is that
a row block's keys stay in VMEM for all 32 counts and the program holds ONE
operation where the loop ran 96: a decode chunk of 8 steps x 5 layers ran
23,000 operations where its parent ran 10,000, and the profiler took longer
to close a traced 25 s of them than the benchmark waits (PERF.md section 6,
PR 45). The time is the loop's (21 us a layer-step at 16 rows x 21,504).

The grid is blocks of eight rows (all of them where the batch is not whole
eights), each with its rows' whole width: 21,504 float32 a row are 0.7 MB a
block. ``sparse_index._kth_largest`` is the twin (the CPU, a window's rows,
widths that are not whole 128s) and the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_SUBLANES = 8
_SIGN = -(2**31)


def kth_largest_supported(slots: int) -> bool:
    """A row of whole 128-lane tiles."""
    return slots % _LANES == 0


def _kth_kernel(scores_ref, kth_ref, room_ref, *, k: int):
    x = scores_ref[...]  # [rows, slots] float32
    # The floats' order as int32's (the two zeros one key), ``_sortable``'s
    # uint32 with its top bit flipped: the search builds the unsigned key
    # and compares its signed image.
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(2**31 - 1), bits)
    rows = x.shape[0]

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=-1, keepdims=True)  # [rows, 1]

    def bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), jnp.int32(31) - i)
        enough = count(key >= (cand ^ jnp.int32(_SIGN))) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows, 1), jnp.int32))
    above = count(key > (kth ^ jnp.int32(_SIGN)))
    kth_ref[...] = jnp.broadcast_to(kth, kth_ref.shape)
    room_ref[...] = jnp.broadcast_to(k - above, room_ref.shape)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def kth_largest_key(
    scores: jnp.ndarray, *, k: int, interpret: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(the k-th largest key a row, ``sparse_index._sortable``'s uint32
    [rows, 1]; ``k`` less the scores above it, int32 [rows, 1]) of float32
    ``scores`` [rows, slots], ``slots`` in whole 128s and more than ``k``."""
    b, slots = scores.shape
    if not kth_largest_supported(slots):
        raise ValueError(f"a row of {slots} slots is not whole {_LANES}s (use the XLA twin)")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    rows = _SUBLANES if b % _SUBLANES == 0 else b
    out = jax.ShapeDtypeStruct((b, _LANES), jnp.int32)
    kth, room = pl.pallas_call(
        functools.partial(_kth_kernel, k=k),
        grid=(b // rows,),
        in_specs=[pl.BlockSpec((rows, slots), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0))] * 2,
        out_shape=[out, out],
        interpret=interpret,
        name="kth_largest_key",
    )(scores.astype(jnp.float32))
    return jax.lax.bitcast_convert_type(kth[:, :1], jnp.uint32), room[:, :1]
