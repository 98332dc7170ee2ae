"""Generation by diffusion over blocks, on the plain K-and-V cache.

A model whose config says ``generation == "block_diffusion"`` (``sdar_moe``)
advances a lane a BLOCK of ``block_length`` slots at a time. The block's slots
start as ``mask_token_id`` (a joiner's first block carries the last ``P mod
B`` tokens of its prompt, unmasked); each DENOISING pass runs the whole model
over the block's ``B`` rows a lane (bidirectional inside the block, causal
over the cache: the block-causal mask, ``ops/attention.block_query_end``),
draws a token for every slot from the logits AT that slot (no shift), and
reveals some of the masked ones; after ``denoising_steps`` passes nothing is
masked, and one more pass over the finished block, the COMMIT, leaves its K
and V in the pool for the blocks after it. A denoising pass writes the
block's K and V to the block's own slots too (the attention kernels read
a block's keys from the pool), which the next pass and the commit overwrite:
the same mathematics as "K and V not kept".

The cache IS plain K and V (``programs.KINDS[CACHE_KV]``'s pool, pages and
block table); what differs is the programs over it, and this module holds
their bodies:

  * ``block_window``: every prefill, an epoch's and a joiner's, of a row's
    first ``P0 = (P // B) * B`` prompt tokens under the block-causal mask;
  * ``block_decode``: a dispatch of ``n // B`` blocks, ``steps + 1`` passes
    each; what ``programs.block_decode_program`` jits.

Both run the layer stack through ``batch.batched_blocks_forward``'s cached
chunk (the arithmetic of ``paged_verify_logits`` at the window's width), with
the routed experts in the layer's ``tail``: the stacks ride OUTSIDE the
scanned tree, whole, with the layer's index (``latent.latent_blocks_forward``
says why), dead rows take no expert's rows, and ``latent.MOE_COUNTS`` ride
back, a PASS and sparse layer a dispatch. Slots are positions plus a lane's
left pad, and every pad, bucket and chunk is whole blocks
(``batch.layout_prompts``), so all lanes' block boundaries fall on the same
slots.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.batch import (
    batched_blocks_forward, decode_positions, paged_seq_len,
)
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.hybrid import _routed_tail
from cake_tpu.models.llama.latent import _EXPERT_STACKS, MOE_COUNTS, _add_counts
from cake_tpu.obs.taxonomy import EMBED, FEED_FORWARD, SAMPLE, UNMASK
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.rope import model_rope_tables
from cake_tpu.ops.sampling import sample_per_row

# What a decode dispatch counts beside its tokens (``DiffusionAccount``):
# lane-blocks committed, passes the program ran (denoising and commit alike),
# those of them that were commits, live lanes x passes, slots revealed.
DIFFUSION_COUNTS = ("blocks", "passes", "commit_passes", "lane_passes", "revealed")


def _stack(params: M.Params):
    """(the scanned layer tree, the routed experts' stacks outside it)."""
    layers = params["layers"]
    experts = {k: layers[k] for k in _EXPERT_STACKS}
    return {k: v for k, v in layers.items() if k not in _EXPERT_STACKS}, experts


def _chunk_forward(
    params, tokens, cache, pads, block_tables, live, config, start, allow_pallas,
):
    """The layer stack over ``tokens`` [b, w] at slots [start, start + w) as
    a cached chunk under the block-causal mask: (x, cache, ``MOE_COUNTS``).
    ``live`` [b, w]: the positions that are a row's tokens (a pad, a dead
    tail and a dead lane take no expert's rows)."""
    w = tokens.shape[1]
    capacity = paged_seq_len(cache, block_tables)
    fusion = resolve_fusion(config, allow_pallas)
    cos, sin = model_rope_tables(config, capacity)
    x = M.embed_tokens(params, tokens, config)
    with jax.named_scope(EMBED):
        grid = start + jnp.arange(w, dtype=jnp.int32)[None, :]
        q_pos = jnp.maximum(grid - pads[:, None], 0)
        _, k_pos, _ = decode_positions(start, pads, capacity)
        lengths = jnp.broadcast_to(start + w, pads.shape).astype(jnp.int32)
    layers, experts = _stack(params)

    def routed(lp, x, attn, k, counts):
        x, c = _routed_tail({**lp, **experts}, x, attn, live, k, config, fusion)
        with jax.named_scope(FEED_FORWARD):  # the account is the experts' own
            one = jnp.ones((1,), jnp.int32)
            return x, _add_counts(counts, jnp.concatenate([one, c]))

    with jax.named_scope(FEED_FORWARD):
        zeros = jnp.zeros((len(MOE_COUNTS),), jnp.int32)
    return batched_blocks_forward(
        layers, x, cache, cos, sin, q_pos, k_pos, config,
        decode=False, cached_chunk=True, pads=pads, lengths=lengths,
        write_pos=start, block_tables=block_tables, allow_pallas=allow_pallas,
        tail=routed, tail_carry=zeros,
    )


def block_window(
    params: M.Params,
    tokens: jnp.ndarray,  # [b, W]: absolute slots [start, start + W)
    cache,
    pads: jnp.ndarray,  # [b] each row's first slot (absolute; whole blocks)
    ends: jnp.ndarray,  # [b] one past each row's last slot (whole blocks)
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    start: jnp.ndarray | int = 0,
    allow_pallas: bool = True,
):
    """Every prefill of a block-diffusion model: each row's first ``P0``
    prompt tokens at slots [pads, ends) of a window that starts at ``start``
    (the closed shapes' layout: an epoch's starts at 0 with a dead tail, a
    joiner's ends at the shared slot), their K and V through the table. The
    logits are the first row's last slot's, which nobody samples from (a
    block's tokens come from the block's own passes); the third value is the
    window's ``MOE_COUNTS``."""
    start = jnp.asarray(start, jnp.int32)
    with jax.named_scope(EMBED):
        grid = start + jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        live = (grid >= pads[:, None]) & (grid < ends[:, None])
    x, cache, counts = _chunk_forward(
        params, tokens, cache, pads, block_tables, live, config, start, allow_pallas,
    )
    last = jnp.maximum(ends[0] - start, 1)
    return M.head_forward(params, x, last, config), cache, counts


def _ranks(masked: jnp.ndarray, conf: jnp.ndarray):
    """(rank by slot, rank by confidence) of every slot among a block's
    MASKED slots, 0 first; an equal confidence goes to the earlier slot."""
    by_slot = jnp.cumsum(masked, axis=-1) - 1
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(conf.shape[-1])[None, :] < jnp.arange(conf.shape[-1])[:, None])[None]
    )
    return by_slot, jnp.sum(ahead & masked[:, None, :], axis=-1)


def reveal(tokens, x0, conf, n_t, config: LlamaConfig):
    """One pass's reveal: ``tokens`` [b, B] with the pass's choice of masked
    slots set to ``x0``, and how many that was a row. ``sequential``: the
    first ``n_t`` masked slots; ``low_confidence_static``: the ``n_t`` masked
    slots of greatest ``conf``; ``low_confidence_dynamic``: every masked slot
    whose ``conf`` passes the threshold where they are at least ``n_t``, else
    as static."""
    masked = tokens == config.mask_token_id
    conf = jnp.where(masked, conf, -jnp.inf)
    by_slot, by_conf = _ranks(masked, conf)
    if config.remask == "sequential":
        chosen = by_slot < n_t
    else:
        chosen = by_conf < n_t
        if config.remask == "low_confidence_dynamic":
            sure = masked & (conf > config.confidence_threshold)
            enough = jnp.sum(sure, axis=-1, keepdims=True) >= n_t
            chosen = jnp.where(enough, sure, chosen)
    chosen &= masked
    return jnp.where(chosen, x0, tokens), jnp.sum(chosen, axis=-1)


def _draw(logits, keys, temperature, top_k, top_p):
    """(x0 [b, B], its softmax probability [b, B], the rows' keys advanced):
    the arg-max, or a draw a slot from the row's own stream."""
    b, width, vocab = logits.shape
    if temperature is None or temperature <= 0.0:
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        pair = jax.vmap(jax.random.split)(keys)  # [b, 2, 2]
        keys, sub = pair[:, 0], pair[:, 1]
        slots = jax.vmap(lambda k: jax.random.split(k, width))(sub)  # [b, B, 2]
        x0 = sample_per_row(
            logits.reshape(b * width, vocab), slots.reshape(b * width, 2),
            temperature, top_k, top_p,
        ).reshape(b, width).astype(jnp.int32)
    top = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - top
    chosen = jnp.take_along_axis(shifted, x0[..., None], axis=-1)[..., 0]
    conf = jnp.exp(chosen) / jnp.sum(jnp.exp(shifted), axis=-1)
    return x0, conf, keys


def block_decode(
    params: M.Params,
    cache,
    known: jnp.ndarray,  # [lanes, B]: the first block's known tokens, mask id elsewhere
    slot: jnp.ndarray,  # the first block's first slot (shared; whole blocks)
    pads: jnp.ndarray,
    block_tables: jnp.ndarray,
    valid: jnp.ndarray,  # [lanes] bool: lanes that hold pages
    keys: jax.Array,  # [lanes, 2]
    config: LlamaConfig,
    *,
    n_steps: int,  # slots the dispatch advances: whole blocks
    temperature: float,
    top_k,
    top_p,
    allow_pallas: bool = True,
    with_logits: bool = False,
):
    """``n_steps // B`` blocks, one after another: ``denoising_steps``
    denoising passes (the head over the block's rows, the draw, the
    confidence, the reveal by ``config.remask``), then the commit pass
    without the head. A first block that holds known tokens runs the same
    passes (a pass with nothing masked reveals nothing): one program a
    capacity, none a remainder. A lane that holds no pages takes no expert's
    rows and writes nothing (its table row is unmapped). Returns (tokens
    [lanes, n_steps], cache, keys, ``MOE_COUNTS`` then ``DIFFUSION_COUNTS``
    as one vector) and, ``with_logits`` (the tests'), every denoising pass's
    logits [blocks, steps, lanes, B, vocab]."""
    width, steps = config.block_length, config.denoising_steps
    assert n_steps % width == 0, (n_steps, width)
    n_blocks = n_steps // width
    live = jnp.broadcast_to(valid[:, None], known.shape)
    base, extra = divmod(width, steps)

    def forward(tokens, cache, at):
        return _chunk_forward(
            params, tokens, cache, pads, block_tables, live, config, at, allow_pallas,
        )

    # ``DIFFUSION_COUNTS`` are counted where the work is done, a pass at a
    # time inside the scans: a pass left out is a pass not counted.
    with jax.named_scope(SAMPLE):
        lanes = jnp.sum(valid.astype(jnp.int32))
        one, none = jnp.int32(1), jnp.int32(0)
        a_commit = jnp.stack([lanes, one, one, lanes, none])

    def denoise(carry, t):
        tokens, cache, keys, at, moe, done = carry
        x, cache, counts = forward(tokens, cache, at)
        logits = M.head_forward_all(params, x, config)
        with jax.named_scope(SAMPLE):
            x0, conf, keys = _draw(logits, keys, temperature, top_k, top_p)
            with jax.named_scope(UNMASK):
                n_t = base + (t < extra).astype(jnp.int32)
                tokens, n = reveal(tokens, x0, conf, n_t, config)
                shown = jnp.sum(jnp.where(valid, n, 0))
                done = done + jnp.stack([none, one, none, lanes, shown])
        carry = (tokens, cache, keys, at, _add_counts(moe, counts), done)
        return carry, (logits if with_logits else None)

    def block(carry, tokens):
        cache, keys, at, moe, done = carry
        (tokens, cache, keys, _, moe, done), logits = jax.lax.scan(
            denoise, (tokens, cache, keys, at, moe, done),
            jnp.arange(steps, dtype=jnp.int32),
        )
        _, cache, counts = forward(tokens, cache, at)  # the commit: no head
        with jax.named_scope(SAMPLE):
            done = done + a_commit
        return (cache, keys, at + width, _add_counts(moe, counts), done), (tokens, logits)

    with jax.named_scope(SAMPLE):
        blank = jnp.full((n_blocks - 1, *known.shape), config.mask_token_id, known.dtype)
        starts = jnp.concatenate([known[None], blank])
        nothing = jnp.zeros((len(DIFFUSION_COUNTS),), jnp.int32)
    with jax.named_scope(FEED_FORWARD):
        zeros = jnp.zeros((len(MOE_COUNTS),), jnp.int32)
    (cache, keys, _, moe, done), (toks, logits) = jax.lax.scan(
        block, (cache, keys, slot, zeros, nothing), starts,
    )
    with jax.named_scope(SAMPLE):
        toks = jnp.moveaxis(toks, 0, 1).reshape(known.shape[0], n_steps)
        counts = jnp.concatenate([moe, done])
    out = (toks, cache, keys, counts)
    return (*out, logits) if with_logits else out


class DiffusionAccount:
    """The cumulative account of a block-diffusion model's decode dispatches,
    beside the expert layer's: ``GET /stats`` engine.diffusion. The facts of
    the configuration, the programs' own counts (``DIFFUSION_COUNTS``, read
    back with each dispatch's tokens) and the engine's two (``note``)."""

    section, names = "diffusion", DIFFUSION_COUNTS
    keeps_traced = False

    @staticmethod
    def add(total, more):
        return total + more

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.counts = dict.fromkeys(("dispatches", *DIFFUSION_COUNTS, "emitted", "known"), 0)

    def facts(self) -> dict:
        """``dispatches`` decode dispatches read; ``blocks`` lane-blocks
        committed; ``passes`` every pass a program ran, denoising and commit
        alike (not lane-passes); ``commit_passes`` those that were commits;
        ``lane_passes`` live lanes x passes; ``revealed`` slots revealed;
        ``emitted`` tokens pushed to streams; ``known`` prompt tokens carried
        into first blocks."""
        c = self.config
        return {
            "block_length": c.block_length, "denoising_steps": c.denoising_steps,
            "remask": c.remask, "mask_token_id": c.mask_token_id,
            "confidence_threshold": c.confidence_threshold, **self.counts,
        }

    def absorb(self, got: dict[str, int], decode: bool, traced: bool, rows: int) -> dict:
        if not decode:  # a join's window denoises nothing
            return {}
        self.counts["dispatches"] += 1
        for key, v in got.items():
            self.counts[key] += v
        return {"blocks": got["blocks"], "passes": got["passes"]}

    def note(self, emitted: int = 0, known: int = 0) -> None:
        """The engine's side: tokens it pushed to streams from a dispatch it
        read, and prompt tokens it carried into a first block."""
        self.counts["emitted"] += emitted
        self.counts["known"] += known
