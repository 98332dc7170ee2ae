"""Fused sampling tail: penalty ring + temperature + top-k + draw, one kernel.

The unfused decode tail walks the [b, vocab] logits through four XLA ops —
repeat-penalty scatter/select, temperature scale, top-k threshold mask, and
the categorical draw — each materializing a fresh [b, vocab] array in HBM.
Here the logits stream HBM -> VMEM ONCE over the vocab tile grid: each tile
is penalized and scaled on the VREGs into a VMEM row, and the last tile of
each batch row computes the top-k threshold, applies the mask, and argmaxes
the noisy row down to a single token id — the only HBM writes are ``b``
int32s.

Numerics contract (tests/test_fused_decode.py pins every piece bitwise):

  * Penalty: the exact ops/sampling.apply_repeat_penalty select — penalize
    everywhere, keep where unseen — with the seen mask rebuilt from the
    ring (a scalar-prefetch operand) by comparison instead of scatter.
  * Top-k: the k-th largest value COUNTING DUPLICATES (what
    ``jax.lax.top_k(x, k)[..., -1]`` returns), computed by a distinct-value
    descent of at most k max+count sweeps over the VMEM row.
  * Draw: ``jax.random.categorical(key, logits)`` IS
    ``argmax(logits + gumbel(key))`` (jax's own definition); the caller
    keeps the PRNG split and the gumbel transform in XLA (bit-identity with
    the unfused stream demands jax's threefry, which no kernel should
    reimplement) and passes the per-row noise as an operand — the kernel
    adds, masks, and argmaxes. Greedy (temperature <= 0) takes no noise and
    argmaxes the penalized row, exactly like ops/sampling.sample.

``top_p`` keeps the XLA sort path: nucleus filtering needs a full sort,
which is exactly the op the vocab-tile grid cannot express — the entry
falls back to the twin (callers surface the one-time ``kernel-fallback``
flight event, the PR 9 convention). ``impl="xla"`` is the twin for every
knob set: it literally composes ops/sampling's penalty/filter with the
gumbel-argmax draw, so fused and unfused streams are bit-identical by
construction there and the kernel is pinned against it.

Eligibility: the vocab must tile into 128-lane blocks — an untiled vocab is
a loud ValueError on the kernel path, never a silent wrong answer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cake_tpu.ops.sampling import _filter, apply_repeat_penalty

_LANES = 128
_ROWS = 8  # rows per block: one f32 sublane tile
_NO_INDEX = float(2**24)  # above any vocabulary, exact in f32


def sample_tail_supported(vocab: int, top_p) -> bool:
    """Kernel eligibility: lane-tileable vocab, and no top-p (the sort
    fallback). One rule for every caller, so the host-side fallback note
    (runtime/batch_backend.py) and the dispatch cannot drift."""
    return top_p is None and vocab % _LANES == 0


def gumbel_noise(key: jax.Array, logits: jnp.ndarray) -> jnp.ndarray:
    """The categorical draw's noise, exactly as jax.random.categorical
    makes it: per-row gumbel when ``key`` is [b, 2] (the vmapped
    sample_per_row stream), one [b, vocab] plane when it is a single key
    (the shared-stream ``sample``). Kept OUT of the kernel: bit-identity
    with the unfused stream requires jax's own threefry bits."""
    if key.ndim == 2:
        return jax.vmap(
            lambda k: jax.random.gumbel(k, logits.shape[-1:], logits.dtype)
        )(key)
    return jax.random.gumbel(key, logits.shape, logits.dtype)


def _tail_kernel(
    *refs,
    block_v,
    n_v,
    temperature,
    top_k,
    repeat_penalty,
    window,
):
    """One [8, block_v] tile of eight rows' logits per grid step.

    Everything is shaped for Mosaic: eight rows fill the sublanes, tiles are
    whole 128-lane multiples, the resident row lives in scratch as
    ``[n_v, 8, block_v]`` so a tile is addressed by its (untiled) leading
    index, and every reduction over the vocabulary is an elementwise sweep
    over the tiles followed by one lane reduction. Counts and indices ride
    in f32 (exact below 2**24, far above any vocabulary)."""
    greedy = temperature is None or temperature <= 0.0
    penalize = repeat_penalty != 1.0 and window > 0
    if penalize:
        ring_ref, *refs = refs
    if greedy:
        logits_ref, o_ref, scaled_scr = refs
        noisy_scr = None
    else:
        logits_ref, noise_ref, o_ref, scaled_scr, noisy_scr = refs
    bi = pl.program_id(0)
    vi = pl.program_id(1)
    tile = logits_ref[...]  # [8, block_v] f32

    if penalize:
        vpos = vi * block_v + jax.lax.broadcasted_iota(
            jnp.int32, (_ROWS, block_v), 1
        )
        row_id = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)

        def seen_body(w, acc):
            # The eight rows' ring entries at slot w as an [8, 1] column,
            # assembled from scalar reads (the ring is a prefetch operand).
            tok = jnp.full((_ROWS, 1), -1, jnp.int32)
            for r in range(_ROWS):
                tok = jnp.where(row_id == r, ring_ref[bi * _ROWS + r, w], tok)
            return jnp.where((tok >= 0) & (vpos == tok), 1.0, acc)

        seen = jax.lax.fori_loop(
            0, window, seen_body, jnp.zeros((_ROWS, block_v), jnp.float32)
        )
        # apply_repeat_penalty's exact select: penalize everywhere, keep
        # where unseen.
        pen = jnp.where(
            tile > 0, tile / repeat_penalty, tile * repeat_penalty
        )
        tile = jnp.where(seen > 0, pen, tile)

    if greedy:
        scaled_scr[vi] = tile
    else:
        scaled = tile / temperature
        scaled_scr[vi] = scaled
        noisy_scr[vi] = scaled + noise_ref[...]

    def sweep(fn, init):
        """Lane-reduce the elementwise accumulation of fn(tile t) over t."""
        return jax.lax.fori_loop(
            0, n_v, lambda t, acc: fn(t, acc),
            jnp.full((_ROWS, block_v), init, jnp.float32),
        )

    def row_max(scr, below=None):
        def fn(t, acc):
            x = scr[t]
            if below is not None:
                x = jnp.where(x < below, x, -jnp.inf)
            return jnp.maximum(acc, x)

        return jnp.max(sweep(fn, -jnp.inf), axis=1, keepdims=True)

    def row_count(scr, value):
        return jnp.sum(
            sweep(
                lambda t, acc: acc + jnp.where(scr[t] == value, 1.0, 0.0), 0.0
            ),
            axis=1, keepdims=True,
        )

    def row_argmax(scr, threshold=None):
        """jnp.argmax of each resident row (first index of the maximum; a
        NaN counts as the maximum), with entries whose SCALED value lies
        under ``threshold`` masked to -inf first."""

        def load(t):
            x = scr[t]
            if threshold is not None:
                x = jnp.where(scaled_scr[t] < threshold, -jnp.inf, x)
            return x

        has_nan = jnp.max(  # [8, 1] f32, 1.0 where the row holds a NaN
            sweep(lambda t, acc: jnp.where(jnp.isnan(load(t)), 1.0, acc), 0.0),
            axis=1, keepdims=True,
        )
        top = jnp.max(
            sweep(
                lambda t, acc: jnp.maximum(
                    acc, jnp.where(jnp.isnan(load(t)), -jnp.inf, load(t))
                ),
                -jnp.inf,
            ),
            axis=1, keepdims=True,
        )
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (_ROWS, block_v), 1
        ).astype(jnp.float32)

        def first(t, acc):
            x = load(t)
            # Masks stay f32 so that only full-shape comparisons make bools.
            hit = jnp.where(
                jnp.isnan(x), has_nan, jnp.where(x == top, 1.0 - has_nan, 0.0)
            )
            idx = lane + (t * block_v).astype(jnp.float32)
            return jnp.minimum(acc, jnp.where(hit > 0, idx, _NO_INDEX))

        return jnp.min(sweep(first, _NO_INDEX), axis=1, keepdims=True)

    @pl.when(vi == n_v - 1)
    def _finish():
        if greedy:
            idx = row_argmax(scaled_scr)
        elif top_k is None:
            idx = row_argmax(noisy_scr)
        else:
            # The k-th largest scaled value COUNTING duplicates — what
            # ``jax.lax.top_k(row, k)[..., -1]`` returns — by a descent over
            # distinct values: at most k - 1 max-below + count sweeps.
            t0 = row_max(scaled_scr)
            c0 = row_count(scaled_scr, t0)

            def descend(_, state):
                t, c = state
                nxt = row_max(scaled_scr, below=t)
                take = c < top_k
                return (
                    jnp.where(take, nxt, t),
                    jnp.where(take, c + row_count(scaled_scr, nxt), c),
                )

            t, _ = jax.lax.fori_loop(0, top_k - 1, descend, (t0, c0))
            # ops/sampling._top_k_mask's strict-< threshold; masked entries
            # are -inf both here and unfused (-inf + finite noise is -inf),
            # so the argmax sees identical values.
            idx = row_argmax(noisy_scr, threshold=t)
        o_ref[...] = jnp.broadcast_to(idx.astype(jnp.int32), o_ref.shape)


def _tail_xla(logits, ring, noise, temperature, top_k, top_p, repeat_penalty):
    """The twin: literally ops/sampling's penalty + filter with the
    gumbel-argmax draw — what jax.random.categorical computes, on the same
    bits."""
    pen = apply_repeat_penalty(logits, repeat_penalty, ring)
    if temperature is None or temperature <= 0.0:
        return jnp.argmax(pen, axis=-1).astype(jnp.int32)
    scaled = _filter(pen, temperature, top_k, top_p)
    return jnp.argmax(scaled + noise, axis=-1).astype(jnp.int32)


def fused_sample_tail(
    logits: jnp.ndarray,  # [b, vocab] f32
    ring: jnp.ndarray,  # [b, window] int32, -1 = empty
    noise: jnp.ndarray | None,  # [b, vocab] gumbel rows; None when greedy
    *,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    repeat_penalty: float,
    impl: str = "xla",
    block_v: int = 2048,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One fused decode sampling tail -> next-token ids [b] int32.

    Knobs are STATIC (the ops/sampling contract: they're compiled into the
    sampler); ``ring``/``noise`` and the logits are traced operands. top_p
    set, or a vocab that does not tile into 128-lane blocks under
    ``impl="pallas"``, raises/falls back per ``sample_tail_supported``.
    """
    greedy = temperature is None or temperature <= 0.0
    if impl != "pallas" or top_p is not None:
        return _tail_xla(
            logits, ring, noise, temperature, top_k, top_p, repeat_penalty
        )
    b, vocab = logits.shape
    if vocab % _LANES:
        raise ValueError(
            f"fused_sample_tail needs a 128-lane-tileable vocab, got "
            f"{vocab} — pad the vocab or run impl='xla'"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # Whole 128-lane tiles that divide the vocab exactly (1280 for 32000,
    # 768 for 128256); rows pad up to whole 8-sublane blocks.
    block_v = max(_LANES, min(block_v, vocab) // _LANES * _LANES)
    while vocab % block_v:
        block_v -= _LANES
    n_v = vocab // block_v
    window = int(ring.shape[1])
    penalize = repeat_penalty != 1.0 and window > 0
    pad = (-b) % _ROWS

    def rows(x, fill=0):
        return jnp.pad(x, ((0, pad), (0, 0)), constant_values=fill)

    def _tile(bi, vi, *_):
        return (bi, vi)

    in_specs = [pl.BlockSpec((_ROWS, block_v), _tile)]
    operands = [rows(jnp.asarray(logits, jnp.float32))]
    scratch = [pltpu.VMEM((n_v, _ROWS, block_v), jnp.float32)]
    if not greedy:
        in_specs.append(pl.BlockSpec((_ROWS, block_v), _tile))
        operands.append(rows(jnp.asarray(noise, jnp.float32)))
        scratch.append(pltpu.VMEM((n_v, _ROWS, block_v), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if penalize else 0,
        grid=((b + pad) // _ROWS, n_v),
        in_specs=in_specs,
        # Lane-dense output: every lane of a row holds its token id.
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda bi, vi, *_: (bi, 0)),
        scratch_shapes=scratch,
    )
    prefix = (rows(jnp.asarray(ring, jnp.int32), -1),) if penalize else ()
    out = pl.pallas_call(
        functools.partial(
            _tail_kernel,
            block_v=block_v, n_v=n_v, temperature=temperature,
            top_k=top_k, repeat_penalty=repeat_penalty, window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b + pad, _LANES), jnp.int32),
        interpret=interpret,
    )(*prefix, *operands)
    return out[:b, 0]
