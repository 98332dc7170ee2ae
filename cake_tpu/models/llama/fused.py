"""Fused multi-token decode: N steps in ONE jitted ``lax.scan``.

The reference's decode loop pays a host round trip per token (llama.rs:271-335:
sample on host, re-enter forward). The per-step analogue here
(generator.LlamaGenerator.next_token) pays a device->host sync per token to pull
the sampled id out. This module removes that: the whole chain

    forward -> repeat penalty -> temperature/top-k/top-p sample -> feed token back

runs on-device for ``n_steps`` tokens per dispatch, carrying (token, KV cache,
position, PRNG key, penalty ring) through a ``lax.scan``. Sampling knobs are
static (compiled in), matching ops/sampling.py; the PRNG key is split once per
step exactly like the host loop, so for a given seed the fused and per-step
paths walk the SAME random stream and emit identical tokens.

EOS cannot early-exit a scan without degrading it to a ``while_loop`` (which
serializes compilation benefits and breaks donation); instead the caller decodes
in chunks, scans the returned ids for EOS on host, and discards the tail. Wasted
work is bounded by chunk_size - 1 steps; stale KV writes past EOS sit at
positions beyond the live length and are masked by the position-comparison
causal mask, then overwritten if the sequence continues.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import KVCache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.obs.taxonomy import SAMPLE
from cake_tpu.ops.sampling import apply_repeat_penalty, sample, sample_per_row


def sample_step(
    logits: jnp.ndarray,  # [b, vocab] f32
    key: jax.Array,  # [2] shared stream, or [b, 2] per-row streams
    ring: jnp.ndarray,  # [b, window] int32, -1 = empty
    ring_idx,  # scalar or [b] int32 next circular slot
    *,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    repeat_penalty: float,
    tail_impl: str | None = None,
):
    """ONE decode sampling step: penalty -> key split -> sample -> ring update.

    THE single definition of the arithmetic (the module's bit-exactness
    invariant): the fused scan below, the serving backends' serialized walks,
    and the 1F1B interleaved pipeline walk (runtime/batch_backend.py) all
    sample through here, so their token streams cannot drift.

    ``tail_impl`` (STATIC; None = unfused) routes the penalty/scale/top-k/
    draw chain through the fused sampling tail
    (ops/pallas/fused_sample_tail.py, "pallas" kernel or its "xla" twin).
    The key split happens HERE either way and the draw is the literal
    gumbel-argmax identity of jax.random.categorical, so the fused and
    unfused paths walk the SAME random stream and emit identical tokens
    (pinned in tests/test_fused_decode.py). top_p set falls back to the
    twin (the documented sort fallback).

    Returns (next_token [b] int32, advanced key(s), ring, ring_idx).
    """
    with jax.named_scope(SAMPLE):
        window = ring.shape[1]
        if tail_impl is not None:
            from cake_tpu.ops.pallas.fused_sample_tail import (
                fused_sample_tail,
                gumbel_noise,
                sample_tail_supported,
            )

            if tail_impl == "pallas" and not sample_tail_supported(
                logits.shape[-1], top_p
            ):
                # The serving-path downgrade for what the kernel cannot express
                # (top_p's sort; an untileable vocab) — the SAME rule the
                # backends' kernel-fallback note reads, so the flight event and
                # the dispatch agree. The low-level entry still refuses an
                # untiled vocab loudly for direct callers.
                tail_impl = "xla"
            if key.ndim == 2:
                pair = jax.vmap(jax.random.split)(key)  # [b, 2, 2]
                key, sub = pair[:, 0], pair[:, 1]
            else:
                key, sub = jax.random.split(key)
            noise = None
            if not (temperature is None or temperature <= 0.0):
                noise = gumbel_noise(sub, logits)
            nxt = fused_sample_tail(
                logits, ring, noise,
                temperature=temperature, top_k=top_k, top_p=top_p,
                repeat_penalty=repeat_penalty, impl=tail_impl,
            )
        elif key.ndim == 2:
            logits = apply_repeat_penalty(logits, repeat_penalty, ring)
            pair = jax.vmap(jax.random.split)(key)  # [b, 2, 2]
            key, sub = pair[:, 0], pair[:, 1]
            nxt = sample_per_row(logits, sub, temperature, top_k, top_p)
            nxt = nxt.astype(jnp.int32)
        else:
            logits = apply_repeat_penalty(logits, repeat_penalty, ring)
            key, sub = jax.random.split(key)
            nxt = sample(logits, sub, temperature, top_k, top_p).astype(jnp.int32)
        if window > 0:
            # ring_idx may be a scalar (single sequence) or [b] (per-row prompt
            # lengths — exact penalty windows); its rank is preserved.
            b = nxt.shape[0]
            idx = jnp.broadcast_to(ring_idx, (b,))
            ring = ring.at[jnp.arange(b), idx].set(nxt, mode="drop")
            ring_idx = (ring_idx + 1) % window
        return nxt, key, ring, ring_idx


def sampled_decode_scan(
    forward_one,
    kv,
    last_token: jnp.ndarray,  # [batch] int32 — most recently sampled/known token
    pos: jnp.ndarray,  # scalar int32 — position of last_token in the sequence
    key: jax.Array,
    ring: jnp.ndarray,  # [batch, window] int32 recent tokens, -1 = empty slot
    ring_idx: jnp.ndarray,  # scalar int32 — next circular write slot
    *,
    n_steps: int,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    repeat_penalty: float,
    tail_impl: str | None = None,
):
    """Step-agnostic fused decode: scan sampling around any one-token forward.

    ``forward_one(tok [b, 1], kv, pos) -> (logits [b, vocab] f32, kv)`` may be
    the plain local model, the shard_mapped pipeline step, or a tensor-parallel
    step — whatever closes over the params. Returns (tokens [batch, n_steps],
    kv, key, ring, ring_idx), carries ready for the next chunk (assuming no
    EOS; on EOS the caller re-seeds the ring from host state).

    ``key`` may be one PRNG key ([2], the whole batch shares a stream) or one
    key PER ROW ([batch, 2]): each row then splits/samples from its own stream,
    making row r's tokens bit-identical to a single-sequence run seeded with
    row r's key — the concurrent-serving reproducibility contract
    (runtime/serving.py).
    """
    def body(carry, _):
        tok, kv, pos, key, ring, ring_idx = carry
        # tok sits at sequence position pos; its KV is written there and the
        # logits predict position pos + 1 (generator.next_token's decode branch
        # makes the same call shape: step([last], len(tokens) - 1, 1)).
        logits, kv = forward_one(tok[:, None], kv, pos)
        nxt, key, ring, ring_idx = sample_step(
            logits, key, ring, ring_idx,
            temperature=temperature, top_k=top_k, top_p=top_p,
            repeat_penalty=repeat_penalty, tail_impl=tail_impl,
        )
        return (nxt, kv, pos + 1, key, ring, ring_idx), nxt

    (_, kv, _, key, ring, ring_idx), toks = jax.lax.scan(
        body,
        (last_token, kv, pos, key, ring, ring_idx),
        None,
        length=n_steps,
    )
    return jnp.moveaxis(toks, 0, 1), kv, key, ring, ring_idx


def decode_scan(
    params: M.Params,
    kv: KVCache,
    last_token: jnp.ndarray,
    pos: jnp.ndarray,
    key: jax.Array,
    ring: jnp.ndarray,
    ring_idx: jnp.ndarray,
    config: LlamaConfig,
    *,
    n_steps: int,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    repeat_penalty: float,
) -> tuple[jnp.ndarray, KVCache, jax.Array, jnp.ndarray, jnp.ndarray]:
    """Fused decode over the plain local model (see sampled_decode_scan)."""
    from cake_tpu.ops.fuse import resolve_fusion

    fusions, fimpl = resolve_fusion(config)

    def forward_one(tok, kv, pos):
        return M.forward(params, tok, kv, pos, jnp.int32(1), config)

    return sampled_decode_scan(
        forward_one,
        kv,
        last_token,
        pos,
        key,
        ring,
        ring_idx,
        n_steps=n_steps,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        repeat_penalty=repeat_penalty,
        tail_impl=fimpl if "tail" in fusions else None,
    )


class FusedDecodeCapability:
    """Mixin granting a ForwardStep the ``decode_chunk`` capability.

    The host class supplies ``_fused_forward_one()`` — returning a callable
    ``(tok [b, 1], kv, pos) -> (logits, kv)`` that closes over its params and
    execution machinery (plain model, shard_mapped pipeline, tensor-parallel
    step) — and keeps its KV state in ``self._kv``. The mixin jits one fused
    scan per (n_steps, sampling knobs); the generator only ever requests its
    construction-time knobs and a single chunk size, so the cache stays tiny.
    """

    def decode_chunk(
        self,
        last_token: np.ndarray,
        pos: int,
        n_steps: int,
        sampling,
        key: jax.Array,
        ring: np.ndarray,
        ring_idx: int,
    ) -> tuple[np.ndarray, jax.Array]:
        """Fused on-device decode of ``n_steps`` tokens.

        Returns (token ids [batch, n_steps], advanced PRNG key). The ring is a
        value argument — the caller reseeds it from its token history each
        call, so EOS truncation never leaves stale ring state behind.
        """
        cache = getattr(self, "_fused_decode_cache", None)
        if cache is None:
            cache = self._fused_decode_cache = {}
        knobs = (
            n_steps,
            sampling.temperature,
            sampling.top_k,
            sampling.top_p,
            sampling.repeat_penalty,
        )
        fn = cache.get(knobs)
        if fn is None:
            impl = functools.partial(
                sampled_decode_scan,
                self._fused_forward_one(),
                n_steps=n_steps,
                temperature=sampling.temperature,
                top_k=sampling.top_k,
                top_p=sampling.top_p,
                repeat_penalty=sampling.repeat_penalty,
            )
            fn = cache[knobs] = jax.jit(impl, donate_argnums=(0,))
        toks, self._kv, key, _, _ = fn(
            self._kv,
            jnp.asarray(last_token, jnp.int32),
            jnp.int32(pos),
            key,
            jnp.asarray(ring, jnp.int32),
            jnp.int32(ring_idx),
        )
        return np.asarray(toks), key


@functools.lru_cache(maxsize=32)
def build_decode_fn(
    config: LlamaConfig,
    n_steps: int,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
    repeat_penalty: float,
):
    """One compiled fused-decode entry per (config, n_steps, sampling knobs)."""
    fn = functools.partial(
        decode_scan,
        config=config,
        n_steps=n_steps,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        repeat_penalty=repeat_penalty,
    )
    return jax.jit(fn, donate_argnums=(1,))
