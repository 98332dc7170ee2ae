"""Static-batch generation: B independent dialogs decoded in lockstep.

The reference serves strictly one request at a time (a global write lock,
api/mod.rs:76; batch dim always 1). The model stack here is batch-native, so
this module adds real throughput serving on top of it:

  * Prompts are **left-padded** to one 16-multiple bucket, so every row's last
    prompt token sits at the same slot and prefill/decode keep SCALAR slot
    offsets (one compiled shape per bucket, `write_layer` untouched).
  * Slot s of row r holds rope position ``s - pad_r``; pad slots rope/mask with
    a sentinel position so no query can ever attend a pad key (ops/attention.py
    masks by position comparison, which this composes with for free). Pad
    QUERY rows clamp to position 0 — they compute garbage nobody reads.
  * Decode runs the whole batch per step inside a fused ``lax.scan``
    (models/llama/fused.py pattern): forward -> per-row repeat penalty ->
    per-row sampling -> feed back, N tokens per dispatch. Rows that hit EOS
    keep computing (lockstep); the host truncates their streams — wasted work
    is bounded by the chunk size, and the batch ends early once every row is
    done.

Decode FLOPs per step grow ~linearly with B while HBM weight traffic stays
constant — on TPU, batched decode is nearly free throughput until the MXU
saturates, which is exactly why this exists beyond reference parity.

Attention dispatches like the single-row path (model.py): the Pallas decode
kernel takes per-row ``starts`` (= the left-pad counts), so each row reads
only its live [pad_r, slot] window — pad slots cost neither compute nor DMA.
Prefill runs the chunk kernel (ops/pallas/chunk_prefill.py) with
``k_starts=pads`` in slot space; the XLA einsum path (position-sentinel
masking) remains the CPU/debug fallback. Both carry the per-family window /
softcap / scale knobs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import KVCache, init_cache, write_layer
from cake_tpu.obs.jitwatch import tracked_jit as _tracked_jit
from cake_tpu.obs.taxonomy import MIXER, MIXER_IN, MIXER_OUT, SAMPLE
from cake_tpu.models.llama.paged_cache import (
    PagedKVCache,
    pack_heads,
    paged_write_pool,
    unpack_heads,
)
from cake_tpu.models.llama.chat import Message, encode_dialog
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.fused import sampled_decode_scan
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import Tokenizer
from cake_tpu.ops.attention import gqa_attention, gqa_attention_hm
from cake_tpu.ops.fuse import resolve_fusion
from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention
from cake_tpu.ops.pallas.decode_attention import decode_attention
from cake_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
)
from cake_tpu.ops.pallas.paged_prefill import (
    paged_chunk_attention,
    paged_chunk_attention_xla,
    paged_kernel_supported,
)
from cake_tpu.ops.pallas.paged_write import compiled_here
from cake_tpu.ops.rope import model_rope_tables
from cake_tpu.ops.sampling import apply_repeat_penalty, sample, sample_per_row

# Far beyond any real position: a pad key's position compares greater than
# every query position, so the causal mask excludes it everywhere.
PAD_SENTINEL = np.int32(2**30)


@dataclasses.dataclass
class BatchResult:
    """One row's outcome."""

    text: str
    token_ids: list[int]
    finish_reason: str  # "stop" | "length"


BUCKET_MULTIPLE = 16


def prompt_bucket(longest: int, max_seq_len: int) -> int:
    """The shared left-pad bucket for a batch whose longest prompt is ``longest``.

    Rounds up to a 16-multiple, not a pow2: a pow2 bucket can burn up to
    longest-1 cache slots, collapsing the decode budget (max_seq_len - bucket)
    for prompts just past a boundary. One compile per distinct 16-multiple is
    acceptable for a batch entry point. Admission checks (serving.submit) must
    call this same helper so rejection agrees with the real layout.
    """
    return min(-(-longest // BUCKET_MULTIPLE) * BUCKET_MULTIPLE, max_seq_len)


def layout_prompts(
    ids_list: list[list[int]], max_seq_len: int, block: int = 0
) -> tuple[np.ndarray, np.ndarray, int]:
    """Left-pad prompts into one shared bucket: (tokens [B, bucket], pads [B], bucket).

    ``block`` (a block-diffusion model's ``block_length``): every row is whole
    blocks and so is the bucket, hence every pad: a lane's block boundaries
    fall on the same SLOTS as every other lane's, which is what lets the
    block-causal mask compare slots (asserted here, where pads are set)."""
    longest = max(len(i) for i in ids_list)
    bucket = prompt_bucket(max(longest, 1) if block else longest, max_seq_len)
    b = len(ids_list)
    tokens = np.zeros((b, bucket), np.int32)
    pads = np.zeros((b,), np.int32)
    for r, ids in enumerate(ids_list):
        pads[r] = bucket - len(ids)
        tokens[r, pads[r] :] = ids
    assert not block or not (pads % block).any(), (pads, block)
    return tokens, pads, bucket


def first_sample(
    logits: jnp.ndarray,
    s,
    ring: np.ndarray,
    ring_idx: np.ndarray,
    row_keys: jax.Array | None,
):
    """Penalize + sample the FIRST post-prefill token and advance the rings.

    THE one definition of the first-token arithmetic (penalty, key split
    order, ring update) shared by lockstep_decode, the serving engine's epoch
    start, and its continuous-batching joins — so the bit-exactness oracle
    cannot drift between them. ``row_keys`` [B, 2] gives each row its own
    stream; None samples the batch from one stream seeded with ``s.seed``.

    Returns (first [B] np.int32, carried key(s), ring, ring_idx).
    """
    per_row = row_keys is not None
    fn = _first_sample_fn(
        s.temperature, s.top_k, s.top_p, s.repeat_penalty, per_row
    )
    first, key = fn(
        logits, jnp.asarray(ring),
        row_keys if per_row else jax.random.PRNGKey(s.seed),
    )
    first = np.asarray(first).astype(np.int32)
    window = ring.shape[1]
    if window > 0:
        b = first.shape[0]
        ring[np.arange(b), ring_idx] = first
        ring_idx = (ring_idx + 1) % window
    return first, key, ring, ring_idx


@functools.lru_cache(maxsize=16)
def _first_sample_fn(temperature, top_k, top_p, repeat_penalty, per_row):
    """Jit the device half of ``first_sample``: one program where the eager
    form dispatched (and, per shape, compiled) a dozen."""

    def run(logits, ring, keys):
        with jax.named_scope(SAMPLE):
            penalized = apply_repeat_penalty(logits, repeat_penalty, ring)
            if per_row:
                pair = jax.vmap(jax.random.split)(keys)
                key, sub = pair[:, 0], pair[:, 1]
                first = sample_per_row(penalized, sub, temperature, top_k, top_p)
            else:
                key, sub = jax.random.split(keys)
                first = sample(penalized, sub, temperature, top_k, top_p)
            return first, key

    return _tracked_jit(
        run,
        name=(
            f"batch.first_sample[t={temperature},k={top_k},p={top_p},"
            f"rp={repeat_penalty},rows={per_row}]"
        ),
    )


def seed_rings(
    ids_list: list[list[int]], window: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-row repeat-penalty rings seeded from each prompt's tail.

    Returns (ring [B, window], ring_idx [B]) — each row's circular window
    behaves exactly like its single-sequence run (generator._penalty_window).
    """
    b = len(ids_list)
    ring = np.full((b, max(window, 0)), -1, np.int32)
    ring_idx = np.zeros((b,), np.int32)
    if window > 0:
        for r, ids in enumerate(ids_list):
            recent = ids[-window:]
            ring[r, : len(recent)] = recent
            ring_idx[r] = min(window, len(ids)) % window
    return ring, ring_idx


def _positions(slot_grid: jnp.ndarray, pads: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(q_positions, k_positions) for slots ``slot_grid`` with left-pads.

    q: pad slots clamp to 0 (finite garbage, unread). k: pad slots get the
    sentinel so they are never attended.
    """
    rel = slot_grid - pads[:, None]
    q_pos = jnp.maximum(rel, 0)
    k_pos = jnp.where(rel < 0, PAD_SENTINEL, rel)
    return q_pos, k_pos


def prefill_positions(
    width: int, pads: jnp.ndarray, ends: jnp.ndarray | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The left-padded PREFILL position grids: (q_pos, k_pos) over [B, width].

    One definition of the slot-grid + pad + dead-tail (``ends`` for join
    windows) arithmetic, shared by the local prefill, the tp/pipeline
    shard_map bodies, and the distributed (TCP) worker ops — so the layout
    cannot drift between execution backends.
    """
    b = pads.shape[0]
    slot_grid = jnp.broadcast_to(
        jnp.arange(width, dtype=jnp.int32)[None, :], (b, width)
    )
    q_pos, k_pos = _positions(slot_grid, pads)
    if ends is not None:
        dead = slot_grid >= ends[:, None]
        k_pos = jnp.where(dead, PAD_SENTINEL, k_pos)
        q_pos = jnp.where(dead, 0, q_pos)
    return q_pos, k_pos


def decode_positions(
    slot: jnp.ndarray, pads: jnp.ndarray, max_seq: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The left-padded DECODE position grids: (q_pos [B,1], k_pos [B,max_seq],
    lengths [B]) for one token at shared ``slot``. One definition shared by
    the local one-token closure, the tp/pipeline bodies, the 1F1B groups,
    and the distributed (TCP) worker op."""
    b = pads.shape[0]
    q_pos = (slot - pads)[:, None]  # slot >= bucket > pads: never a pad
    lengths = jnp.broadcast_to(slot + 1, (b,)).astype(jnp.int32)
    kv_slots = jnp.broadcast_to(
        jnp.arange(max_seq, dtype=jnp.int32)[None, :], (b, max_seq)
    )
    _, k_pos = _positions(kv_slots, pads)
    return q_pos, k_pos, lengths


def make_lockstep_range_ops(config: LlamaConfig, cos: jnp.ndarray, sin: jnp.ndarray):
    """(prefill, decode, join, verify) closures over a BARE stacked-layer range.

    The three lockstep ops a block-range executor needs — shared by the TCP
    worker's jits (runtime/worker.py) and the master's local-range jits
    (runtime/batch_backend.DistributedBatchBackend), so the two sides of the
    wire run literally the same code. Signatures:

      prefill(layers, x, kv, pads, ends)        -> (x, kv)   writes slot 0
      decode(layers, x, kv, pads, slot)         -> (x, kv)   one token at slot
      join(layers, x, kv, pads1, ends1, lane)   -> (x, kv)   single-row
          prefill into a fresh row cache, scattered wholesale into ``lane``
      verify(layers, x, kv, pads, slot)         -> (x, kv)   cached chunk at
          slot (speculative verify; MoE grouped path is exact without tp)
    """

    def bprefill(layers, x, kv, pads, ends):
        q_pos, k_pos = prefill_positions(x.shape[1], pads, ends)
        return batched_blocks_forward(
            layers, x, kv, cos, sin, q_pos, k_pos, config,
            decode=False, pads=pads, lengths=ends, write_pos=jnp.int32(0),
        )

    def bdecode(layers, x, kv, pads, slot):
        q_pos, k_pos, lengths = decode_positions(slot, pads, kv.k.shape[-2])
        return batched_blocks_forward(
            layers, x, kv, cos, sin, q_pos, k_pos, config,
            decode=True, pads=pads, lengths=lengths, write_pos=slot,
        )

    def bverify(layers, x, kv, pads, slot):
        q_pos, k_pos, lengths = verify_positions(
            x.shape[1], pads, slot, kv.k.shape[-2]
        )
        return batched_blocks_forward(
            layers, x, kv, cos, sin, q_pos, k_pos, config,
            decode=False, cached_chunk=True, pads=pads, lengths=lengths,
            write_pos=slot,
        )

    def bjoin(layers, x, kv, pads1, ends1, lane):
        kv_row = KVCache(
            k=jnp.zeros(kv.k.shape[:1] + (1,) + kv.k.shape[2:], kv.k.dtype),
            v=jnp.zeros(kv.v.shape[:1] + (1,) + kv.v.shape[2:], kv.v.dtype),
        )
        x, kv_row = bprefill(layers, x, kv_row, pads1, ends1)
        k = jax.lax.dynamic_update_slice(kv.k, kv_row.k, (0, lane, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(kv.v, kv_row.v, (0, lane, 0, 0, 0))
        return x, KVCache(k=k, v=v)

    return bprefill, bdecode, bjoin, bverify


def verify_positions(
    width: int, pads: jnp.ndarray, slot: jnp.ndarray, max_seq: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cached-chunk (speculative VERIFY) position grids: q_pos [B, width]
    at slots [slot, slot+width), mask-only full-grid k_pos, per-row lengths
    slot + width. One definition shared by batched_verify_logits, the
    pipeline verify walk, and the TCP worker verify op."""
    b = pads.shape[0]
    jgrid = slot + jnp.arange(width, dtype=jnp.int32)
    q_pos = jnp.broadcast_to(jgrid[None, :], (b, width)) - pads[:, None]
    _, k_pos, _ = decode_positions(slot, pads, max_seq)
    lengths = jnp.broadcast_to(slot + width, (b,)).astype(jnp.int32)
    return q_pos, k_pos, lengths


def batched_blocks_forward(
    layers: M.Params,
    x: jnp.ndarray,
    kv: KVCache,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    q_pos: jnp.ndarray,
    k_pos: jnp.ndarray,
    config: LlamaConfig,
    *,
    decode: bool,
    pads: jnp.ndarray,
    lengths: jnp.ndarray,
    write_pos: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    tp_axis: str | None = None,
    allow_pallas: bool = True,
    row_offset: jnp.ndarray | None = None,
    cached_chunk: bool = False,
    moe_dispatch: str = "auto",
    block_tables: jnp.ndarray | None = None,
    write_starts: jnp.ndarray | None = None,
    layer_base: int = 0,
    tail=None,
    tail_carry=None,
) -> tuple[jnp.ndarray, KVCache]:
    """THE pad-aware stacked-layer scan for left-padded batches.

    The batched counterpart of model.blocks_forward, shared by every batch
    execution backend — the local engine, the tensor-parallel shard_map body,
    and the pipelined stage bodies (runtime/batch_backend.py) — so the
    pad/mask/kernel dispatch arithmetic exists exactly once.

    Args:
      q_pos/k_pos: per-row RELATIVE rope/mask positions (_positions); pad
        slots carry the PAD_SENTINEL as keys and clamp to 0 as queries.
      decode: STATIC — one-token step at slot ``write_pos`` (True) vs
        full-width prefill writing at slot 0 (False).
      pads: [B] left-pad counts — the kernels' per-row front bound.
      lengths: [B] live-slot count per row (prefill: width or join ``ends``;
        decode: slot + 1) — the kernels' per-row pruning bound.
      valid: optional [n_layers] gate for ragged pipeline stages (inert
        padded layers), exactly like model.blocks_forward.
      tp_axis: mesh axis for the tensor-parallel partial-sum reductions.
      row_offset: optional TRACED start row — ``x`` then carries a WINDOW of
        ``b`` rows out of a wider cache (kv holds B_total >= b rows): reads
        slice the window per layer (the attention was going to read those
        rows anyway) and the new token's K/V writes land at the offset rows,
        so no block-sized write-back copy exists. This is what lets the 1F1B
        interleaved pipeline walk (runtime/batch_backend.py) run one
        microbatch GROUP per stage against the shared full-batch cache.
        Decode only; pads/q_pos/k_pos/lengths are already the window's rows.
      cached_chunk: STATIC — a multi-token chunk arriving at slot
        ``write_pos`` > 0 that must attend over the LIVE CACHE PREFIX (the
        batched analogue of model.forward's cached_prefill): speculative
        verify feeds [last, draft...] this way. Callers pass k_pos over the
        FULL cache grid and per-row ``lengths`` = write_pos + width.
      block_tables: optional [B, max_pages_per_seq] int32 — PAGED mode: ``kv``
        is then a PagedKVCache (models/llama/paged_cache.py) and every K/V
        write scatters through the table (unmapped entries drop). The pool
        is the layer scan's CARRY: each layer writes its rows in place at
        ``[layer, page, :, offset, :]`` and the kernels read through a layer
        index, so no program slices, stacks or copies the pool (the dense
        cache below still goes in as a scanned input and comes back as a
        stacked output; tests/test_paged_pool_carry.py guards this
        branch). Decode reads
        dispatch to the ragged paged kernel (ops/pallas/paged_attention.py) or
        its gather fallback; fresh prefill attends over the FRESH chunk
        (identical arithmetic to the dense fresh-chunk path — prefill never
        re-reads the cache it just wrote, so no gather is needed); a paged
        CACHED chunk (``cached_chunk=True`` — the prefix-cache suffix prefill,
        runtime/prefix_cache.py) attends over the gathered pool view, the
        multi-query sibling of the paged decode XLA fallback. The
        position/mask grids are the SAME left-padded arithmetic as dense,
        sized to ``max_pages_per_seq * page_size`` slots. Speculative verify
        and the 1F1B row-window mode are dense-only.
      write_starts: optional [B] int32 (PAGED only) — row ``b``'s K/V writes
        at slots below ``write_starts[b]`` DROP even where pages are mapped:
        a suffix prefill's window re-embeds prefix tokens whose KV already
        lives in forked shared pages, and must never scribble them.
      layer_base: STATIC (PAGED only) — ``layers`` is one RUN of a stack
        that other kinds of layer interleave (models/llama/hybrid.py): its
        k-th layer reads and writes pool layer ``layer_base + k``, and the
        pool may hold more layers than the run.
      tail / tail_carry: (PAGED only) the layer's tail in the caller's hands
        in ``block_finish``'s place: ``tail(lp, x, attn, k, carry) -> (x,
        carry)`` with ``k`` the layer's index in the run and ``carry`` riding
        the scan from ``tail_carry`` (a hybrid stack's sparse attention run:
        the routed experts outside the scanned tree and the account of them,
        models/llama/hybrid.py). The carry is then returned third.
    """
    use_pallas = (
        allow_pallas and M.resolve_attention_impl(config.attention_impl) == "pallas"
    )
    # Decode hot-path op fusion (ops/fuse.py resolve_fusion): "norm" rides
    # inside block_qkv/block_finish and gates its Pallas kernel on the same
    # allow_pallas knob as attention.
    fusion = resolve_fusion(config, allow_pallas)
    b = x.shape[0]
    if row_offset is not None:
        assert decode, "row-window execution is a decode-only mode"
    paged = block_tables is not None
    if paged:
        assert row_offset is None, "row-window decode is dense-only (paged)"
    else:
        assert write_starts is None, "write_starts is a paged-only mode"
    # Pad slots (sentinel key positions) must not consume MoE expert
    # capacity (ops/moe.py); decode/cached chunks carry no pads.
    moe_valid = None if (decode or cached_chunk) else (k_pos != PAD_SENTINEL)
    if cached_chunk and paged and write_starts is not None:
        # Suffix-prefill windows (runtime/prefix_cache.py, identified by
        # their write thresholds) CAN contain pad slots, unlike verify
        # windows (those sit past the bucket and keep the dense verify's
        # moe_valid=None so paged greedy speculation stays byte-identical
        # to paged plain decode): pad queries must not consume MoE expert
        # capacity, and their rope positions clamp to finite garbage
        # (outputs discarded, writes dropped by write_starts / unmapped
        # pages).
        moe_valid = q_pos >= 0
        q_pos = jnp.maximum(q_pos, 0)
    if decode and config.use_rope:
        # Decode ropes q and its one new key at the same q_pos (k_pos only
        # feeds the XLA mask): gather the rope rows once per step, not once
        # per layer inside the scan (apply_rope's 3-D form). Prefill keeps
        # the tables — its keys rope at k_pos, distinct from q_pos.
        # Stacked dual-rope tables gather BOTH planes; block_qkv selects.
        if cos.ndim == 3:
            cos, sin = cos[:, q_pos], sin[:, q_pos]
        else:
            cos, sin = cos[q_pos], sin[q_pos]
    attn_kw = dict(
        window=config.sliding_window,
        scale=config.attn_scale,
        softcap=config.attn_logit_softcap,
    )
    if config.block_length:
        # Generation by diffusion over blocks: the block-causal mask. The
        # kernels compare SLOTS, the XLA twins positions (``slot - pad``):
        # the same blocks, because a lane's pad, the bucket and every chunk
        # are whole blocks (``layout_prompts`` asserts the first).
        assert not decode, "a block-diffusion model has no one-token step"
        # (``--kv-mode dense`` is refused for this generation, so the dense
        # chunk kernel has no such mask: ``capability.REFUSED_BY_GENERATION``)
        assert paged or not use_pallas, "block-causal: paged, or the XLA twins"
        attn_kw["block"] = config.block_length
    # Cached chunks start their queries at the write slot (the kernel prunes
    # cache blocks causally from there); fresh prefills start at slot 0.
    q_starts = (
        jnp.broadcast_to(write_pos, (b,)).astype(jnp.int32)
        if cached_chunk
        else jnp.zeros((b,), jnp.int32)
    )

    def qkv(lp, x):
        if decode or cached_chunk:
            # The chunk's keys rope at the chunk's own positions (== q_pos);
            # the full-cache-grid k_pos is mask-only, exactly like decode.
            # Verify chunks never place a pad in [slot, slot+W), but the
            # batched draft ingest (speculative.BatchedDraftModelProposer)
            # DOES feed windows starting before some lanes' left pads:
            # those rows carry NEGATIVE q_pos, every key is masked for
            # them, and the all-masked-row guards in the attention paths
            # (ops/attention.gqa_attention_hm, the Pallas chunk kernel's
            # m_safe) zero the outputs — a LOAD-BEARING contract for that
            # caller; their sub-pad KV writes land at sub-pad slots that
            # stay sentinel-masked forever.
            return M.block_qkv(lp, x, cos, sin, q_pos, config, fusion=fusion)
        return M.block_qkv(
            lp, x, cos, sin, q_pos, config, k_positions=k_pos, fusion=fusion,
        )

    # One pass over ONE block of a block-diffusion model: every query of the
    # block sees the same keys (``block_pass_attention``).
    block_pass = bool(config.block_length) and cached_chunk and (
        x.shape[1] == config.block_length)

    def paged_layer(carry, per_layer):
        # The pool rides in the carry and is only ever written in place and
        # read through ``li``: nothing here may slice a layer out of it.
        x, k_pool, v_pool, *more = carry
        lp, ok, li = per_layer
        q, k, v = qkv(lp, x)
        # A pool whose rows hold several narrow KV heads side by side
        # (``paged_cache.kv_pack``: its builder's choice, read off its shape).
        n_kv, pack = k.shape[2], k_pool.shape[-1] // k.shape[-1]
        kw = attn_kw
        if pack > 1:
            with jax.named_scope(MIXER_IN):
                q, k, v = pack_heads(q, k, v, pack)
            kw = {**kw, "scale": attn_kw["scale"] or config.head_dim ** -0.5}
        # One eligibility rule for every paged kernel (the write, decode AND
        # the chunk family): the page must be a whole number of lane tiles.
        # A backend that wanted pallas but lands here surfaces a one-time
        # `kernel-fallback` flight event host-side
        # (runtime/batch_backend.PagedLocalBackend._kernel_note). The write
        # is the kernel where it is compiled, not interpreted.
        kernel_ok = use_pallas and paged_kernel_supported(kv.page_size)
        # An inert (``valid``-gated) layer still writes its own layer's
        # rows, as the scanned form did; only ``x`` is gated.
        k_pool, v_pool = paged_write_pool(
            k_pool, v_pool, li, k, v, write_pos, block_tables,
            starts=write_starts, kernel=kernel_ok and compiled_here(),
        )
        with jax.named_scope(MIXER):
            if decode:
                if kernel_ok:
                    attn = paged_decode_attention(
                        q, k_pool, v_pool, lengths, block_tables, pads,
                        lp.get("win_flag"), layer=li, **kw,
                    )
                else:
                    attn = paged_decode_attention_xla(
                        q, k_pool, v_pool, q_pos, k_pos, block_tables,
                        window_flag=lp.get("win_flag"), layer=li, **kw,
                    )
            elif kernel_ok and block_pass:
                attn = block_pass_attention(
                    q, k_pool, v_pool, lengths, block_tables, pads, layer=li,
                    **{k: v for k, v in kw.items() if k != "block"},
                )
            elif kernel_ok:
                # Every paged prefill under pallas is one call. A cached chunk
                # at slot ``write_pos`` — the prefix-cache suffix prefill AND
                # the paged speculative verify — attends the LIVE POOL PREFIX
                # (cached/earlier pages plus the chunk's own writes just
                # scattered above); a fresh prefill reads the pool prefix its
                # own writes just produced (q_starts = 0, so causal pruning
                # touches exactly the live pages). The ragged page-resolving
                # chunk kernel (ops/pallas/paged_prefill.py) streams only live
                # pages: no [chunk, chunk] score tensor, O(live) HBM bytes.
                attn = paged_chunk_attention(
                    q, k_pool, v_pool, q_starts, lengths, pads, block_tables,
                    lp.get("win_flag"), layer=li, **kw,
                )
            elif cached_chunk:
                # XLA: the gathered dense view, the multi-query form of the
                # paged decode fallback (bit-identical arithmetic).
                attn = paged_chunk_attention_xla(
                    q, k_pool, v_pool, q_pos, k_pos, block_tables,
                    window_flag=lp.get("win_flag"), layer=li, **kw,
                )
            else:
                # Prefill attends over the chunk it just computed — the
                # dense fresh-chunk arithmetic, no cache read, no gather.
                attn = gqa_attention(
                    q, k, v, q_pos, k_pos,
                    window_flag=lp.get("win_flag"), **kw,
                )
        if pack > 1:
            with jax.named_scope(MIXER_OUT):
                attn = unpack_heads(attn, n_kv, pack)
        if tail is not None:
            x, *more = tail(lp, x, attn, li - layer_base, *more)
            return (x, k_pool, v_pool, *more), None
        x_new = M.block_finish(
            lp, x, attn, config, tp_axis=tp_axis, moe_valid=moe_valid,
            moe_dispatch=moe_dispatch, fusion=fusion,
        )
        x = x_new if valid is None else jnp.where(ok, x_new, x)
        return (x, k_pool, v_pool), None

    def layer(carry, per_layer):
        x = carry
        lp, k_c, v_c, ok = per_layer
        q, k, v = qkv(lp, x)
        k_c, v_c = write_layer(
            k_c, v_c, k, v, write_pos,
            row=0 if row_offset is None else row_offset,
        )
        with jax.named_scope(MIXER):
            if row_offset is not None:
                # Row-window mode: attention reads this group's rows only (the
                # same bytes the kernels were going to stream); writes above
                # already landed at the offset, so the full cache flows through
                # the scan untouched outside the window.
                k_att = jax.lax.dynamic_slice_in_dim(k_c, row_offset, b, axis=0)
                v_att = jax.lax.dynamic_slice_in_dim(v_c, row_offset, b, axis=0)
            else:
                k_att, v_att = k_c, v_c
            if use_pallas:
                # Kernel operands in SLOT space: left-padding shifts a row's
                # queries and keys equally, so causal/window comparisons are
                # pad-invariant; pad key slots are excluded via starts/k_starts
                # (mask + block pruning), dead tails via per-row lengths. Rope
                # still uses the relative positions above.
                if decode:
                    attn = decode_attention(
                        q, k_att, v_att, lengths, pads, lp.get("win_flag"), **attn_kw
                    )
                else:
                    attn = chunk_prefill_attention(
                        q, k_att, v_att, q_starts, lengths, lp.get("win_flag"), pads,
                        **attn_kw,
                    )
            elif decode or cached_chunk:
                # XLA fallback over the cache prefix: decode's one token, or a
                # cached chunk's width-many queries, both masked by the full-grid
                # k_pos the caller supplied.
                attn = gqa_attention_hm(
                    q, k_att, v_att, q_pos, k_pos,
                    window_flag=lp.get("win_flag"), **attn_kw,
                )
            else:
                attn = gqa_attention(
                    q, k, v, q_pos, k_pos,
                    window_flag=lp.get("win_flag"), **attn_kw,
                )
        x_new = M.block_finish(
            lp, x, attn, config, tp_axis=tp_axis, moe_valid=moe_valid,
            moe_dispatch=moe_dispatch, fusion=fusion,
        )
        x = x_new if valid is None else jnp.where(ok, x_new, x)
        return x, (k_c, v_c)

    if paged:
        n_run = jax.tree.leaves(layers)[0].shape[0]
        ok = jnp.ones((n_run,), bool) if valid is None else valid
        li = layer_base + jnp.arange(n_run, dtype=jnp.int32)
        more = () if tail is None else (tail_carry,)
        (x, k_out, v_out, *more), _ = jax.lax.scan(
            paged_layer, (x, kv.k, kv.v, *more), (layers, ok, li)
        )
        return (x, PagedKVCache(k=k_out, v=v_out), *more)
    ok = jnp.ones((kv.k.shape[0],), bool) if valid is None else valid
    x, (k_out, v_out) = jax.lax.scan(layer, x, (layers, kv.k, kv.v, ok))
    return x, KVCache(k=k_out, v=v_out)


def batched_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [B, L] left-padded
    kv: KVCache,
    pads: jnp.ndarray,  # [B] left-pad counts
    config: LlamaConfig,
    ends: jnp.ndarray | None = None,  # [B] absolute end slot per row (< L ok)
    seq_len: jnp.ndarray | None = None,  # logits slot + 1; default L
    tp_axis: str | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """Prefill the padded batch at slots [0, L); logits at slot ``seq_len-1``.

    Row r's prompt occupies slots [pads[r], ends[r]); slots outside get the
    position sentinel so nothing ever attends them (trailing dead slots are
    overwritten by decode, the single-row convention). ``ends``/``seq_len``
    default to the full width L — the plain whole-batch prefill. A
    continuous-batching JOIN (runtime/serving.py) prefills one row whose
    prompt must END at the running batch's shared slot: its window is wider
    than the prompt, so ends < L and seq_len = ends. ``tp_axis`` makes the
    body shard_map-able (runtime/batch_backend.py TPBatchBackend).
    """
    b, l = tokens.shape
    cos, sin = model_rope_tables(config, kv.max_seq_len)
    x = M.embed_tokens(params, tokens, config)
    q_pos, k_pos = prefill_positions(l, pads, ends)
    if seq_len is None:
        seq_len = jnp.int32(l)
    lengths = jnp.broadcast_to(jnp.int32(l), (b,)) if ends is None else ends

    x, kv = batched_blocks_forward(
        params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
        decode=False, pads=pads, lengths=lengths, write_pos=jnp.int32(0),
        tp_axis=tp_axis,
    )
    logits = M.head_forward(params, x, seq_len, config)
    return logits, kv


def batched_forward_one(
    params: M.Params,
    pads: jnp.ndarray,  # [B]
    config: LlamaConfig,
    max_seq: int,
    allow_pallas: bool = True,
    tp_axis: str | None = None,
):
    """Build the one-token batched forward closure for fused.sampled_decode_scan.

    The scan's carried ``pos`` is the SLOT of the fed token (shared across
    rows); per-row rope/mask positions are derived from the left-pads here.
    ``tp_axis`` makes the closure shard_map-able (TPBatchBackend).
    """
    cos, sin = model_rope_tables(config, max_seq)
    fusion = resolve_fusion(config, allow_pallas)

    def forward_one(tok, kv, slot):
        x = M.embed_tokens(params, tok, config)
        q_pos, k_pos, lengths = decode_positions(slot, pads, max_seq)
        x, kv = batched_blocks_forward(
            params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
            decode=True, pads=pads, lengths=lengths, write_pos=slot,
            tp_axis=tp_axis, allow_pallas=allow_pallas,
        )
        logits = M.head_forward(params, x, jnp.int32(1), config, fusion=fusion)
        return logits, kv

    return forward_one


@functools.lru_cache(maxsize=16)
def _decode_fn(
    config: LlamaConfig,
    max_seq: int,
    n_steps: int,
    temperature: float,
    top_k,
    top_p,
    repeat_penalty: float,
    allow_pallas: bool = True,
):
    """Jit one fused batch-decode scan: the SAME step-agnostic harness as
    single-sequence fused decode (models/llama/fused.py) with the batched
    forward closure — sampling/ring/PRNG logic exists once. ``params`` and
    ``pads`` are traced arguments (NOT closure captures), so the compiled
    entry is reused across batches; batch-size changes retrace within it.
    The jit family name carries the fusion spec so tracked_jit attributes
    compile cost per fusion family."""
    fusions, fimpl = resolve_fusion(config, allow_pallas)
    tail_impl = fimpl if "tail" in fusions else None

    def run(params, kv, tok, slot, pads, key, ring, ring_idx):
        # kv.max_seq_len is the cache's PADDED length (SEQ_MULTIPLE rounding) —
        # the mask grid and rope table must size to it, not the user value.
        forward_one = batched_forward_one(
            params, pads, config, kv.max_seq_len, allow_pallas=allow_pallas
        )
        return sampled_decode_scan(
            forward_one,
            kv,
            tok,
            slot,
            key,
            ring,
            ring_idx,
            n_steps=n_steps,
            temperature=temperature,
            top_k=top_k,
            top_p=top_p,
            repeat_penalty=repeat_penalty,
            tail_impl=tail_impl,
        )

    fu = f",fu={config.fusion_impl}" if fusions else ""
    return _tracked_jit(
        run,
        name=(
            f"batch.decode[n={n_steps},t={temperature},k={top_k},"
            f"p={top_p},rp={repeat_penalty}{fu}]"
        ),
        module="decode_chunk_dense",
        donate_argnums=(1,),
    )


# Module names (what a device trace shows, ``jit_<module>``): every prefill
# starts ``prefill_``, every join ``prefill_join_``, every decode chunk
# ``decode_chunk_``. The benchmark's readers select programs by these
# (bench/layer_metrics/*.json); a rename there is a change of yardstick.
_prefill_jit = _tracked_jit(
    batched_prefill,
    name="batch.prefill",
    module="prefill_dense",
    static_argnames=("config",),
    donate_argnames=("kv",),
)


# -------------------------------------------------------------------- paged
#
# The paged lockstep drivers: identical position/mask/sampling arithmetic to
# the dense entry points above (the dense-vs-paged bit-exactness oracle in
# tests/test_paged_serving.py depends on it) with KV routed through the page
# pool. The "sequence length" every grid sizes to is the table capacity
# ``max_pages_per_seq * page_size`` — the paged analogue of the dense cache's
# SEQ_MULTIPLE-padded max_seq.


def paged_seq_len(kv: PagedKVCache, block_tables: jnp.ndarray) -> int:
    """Slot capacity of a lane's block table: the paged ``max_seq``."""
    return int(block_tables.shape[1]) * kv.page_size


def paged_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [B, L] left-padded
    kv: PagedKVCache,
    pads: jnp.ndarray,  # [B] left-pad counts
    block_tables: jnp.ndarray,  # [B, max_pages_per_seq] int32
    config: LlamaConfig,
    ends: jnp.ndarray | None = None,
    seq_len: jnp.ndarray | None = None,
    write_starts: jnp.ndarray | None = None,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """batched_prefill through the page pool: row r's prompt KV lands in the
    pages its block-table row maps; writes outside the mapping drop (left-pad
    garbage costs no storage). ``ends``/``seq_len`` serve the continuous-
    batching join exactly as in the dense path. ``write_starts`` drops a
    row's sub-threshold writes — a prefix-cache warm row riding a cold
    epoch's full prefill recomputes its prefix in-window (same numerics as a
    cold row, so streams stay bit-identical) but must not scribble the
    shared pages already holding that prefix. ``allow_pallas`` (STATIC)
    force-disables the paged chunk kernel — attention_impl is honored
    uniformly with the decode twin paged_forward_one."""
    b, l = tokens.shape
    cos, sin = model_rope_tables(config, paged_seq_len(kv, block_tables))
    x = M.embed_tokens(params, tokens, config)
    q_pos, k_pos = prefill_positions(l, pads, ends)
    if seq_len is None:
        seq_len = jnp.int32(l)
    lengths = jnp.broadcast_to(jnp.int32(l), (b,)) if ends is None else ends

    x, kv = batched_blocks_forward(
        params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
        decode=False, pads=pads, lengths=lengths, write_pos=jnp.int32(0),
        block_tables=block_tables, write_starts=write_starts,
        allow_pallas=allow_pallas,
    )
    logits = M.head_forward(params, x, seq_len, config)
    return logits, kv


def paged_forward_one(
    params: M.Params,
    pads: jnp.ndarray,
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    padded_seq: int,
    allow_pallas: bool = True,
):
    """One-token paged forward closure for fused.sampled_decode_scan — the
    paged twin of batched_forward_one (same carried-slot convention)."""
    cos, sin = model_rope_tables(config, padded_seq)
    fusion = resolve_fusion(config, allow_pallas)

    def forward_one(tok, kv, slot):
        x = M.embed_tokens(params, tok, config)
        q_pos, k_pos, lengths = decode_positions(slot, pads, padded_seq)
        x, kv = batched_blocks_forward(
            params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
            decode=True, pads=pads, lengths=lengths, write_pos=slot,
            allow_pallas=allow_pallas, block_tables=block_tables,
        )
        logits = M.head_forward(params, x, jnp.int32(1), config, fusion=fusion)
        return logits, kv

    return forward_one


_paged_prefill_jit = _tracked_jit(
    paged_prefill,
    name="batch.paged_prefill",
    module="prefill_paged",
    static_argnames=("config", "allow_pallas"),
    donate_argnames=("kv",),
)


def paged_suffix_prefill(
    params: M.Params,
    tokens: jnp.ndarray,  # [B, W] window covering slots [start, start + W)
    kv: PagedKVCache,
    pads: jnp.ndarray,  # [B] TRUE left pads (absolute; may lie outside window)
    write_starts: jnp.ndarray,  # [B] first slot each row may write
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    start: jnp.ndarray,  # window's first absolute slot
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """Warm-path prefill: compute ONLY the window [start, start + W), with
    each row's prefix KV below ``write_starts[b]`` served from forked
    prefix-cache pages instead of recomputed (runtime/prefix_cache.py).

    The cached-chunk analogue of the speculative verify grids: queries carry
    their absolute-slot rope positions, keys are the FULL gathered pool view
    masked positionally, and writes below each row's fresh threshold drop so
    shared pages stay byte-stable. Window rows below a row's own fresh
    region recompute prefix-tail activations whose outputs are discarded
    (their writes drop) — correct by the same induction that makes the pool
    a valid oracle: the gathered prefix IS the values a full prefill would
    have produced. Logits land at the window's last slot (the epoch's shared
    ``bucket - 1``), exactly where the cold path reads them.
    """
    b, w = tokens.shape
    capacity = paged_seq_len(kv, block_tables)
    cos, sin = model_rope_tables(config, capacity)
    x = M.embed_tokens(params, tokens, config)
    q_pos, k_pos, lengths = verify_positions(w, pads, start, capacity)
    x, kv = batched_blocks_forward(
        params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
        decode=False, cached_chunk=True, pads=pads, lengths=lengths,
        write_pos=start, block_tables=block_tables,
        write_starts=write_starts, allow_pallas=allow_pallas,
    )
    logits = M.head_forward(params, x, jnp.int32(w), config)
    return logits, kv


_paged_suffix_jit = _tracked_jit(
    paged_suffix_prefill,
    name="batch.paged_suffix",
    module="prefill_paged_suffix",
    static_argnames=("config", "allow_pallas"),
    donate_argnames=("kv",),
)

# The same arithmetic for one joining (or restored) row: its own jit, so
# that a join is a program of its own name. Its shapes (one row, one lane's
# table) never met the batch prefill's in one cache anyway.
_paged_suffix_join_jit = _tracked_jit(
    paged_suffix_prefill,
    name="batch.paged_suffix_join",
    module="prefill_join_paged_suffix",
    static_argnames=("config", "allow_pallas"),
    donate_argnames=("kv",),
)


def paged_verify_logits(
    params: M.Params,
    tokens: jnp.ndarray,  # [B, W] = [last_r, draft_r..., pad 0s]
    kv: PagedKVCache,
    pads: jnp.ndarray,
    slot: jnp.ndarray,
    block_tables: jnp.ndarray,
    config: LlamaConfig,
    allow_pallas: bool = True,
) -> tuple[jnp.ndarray, PagedKVCache]:
    """batched_verify_logits through the page pool: the SAME cached-chunk
    arithmetic as paged_suffix_prefill (verify grids, keys masked
    positionally over the pool view, writes through the block table at
    slots [slot, slot + W)) scoring every position: [B, W, vocab] f32.

    This is what enables speculative decoding under ``kv_mode="paged"``:
    greedy verify logits are bit-identical to the paged plain-decode path
    on CPU (the dense proof pattern), so accepted tokens byte-match the
    non-speculative stream. The engine must map pages for [slot, slot + W)
    BEFORE the round (runtime/serving.py extends at the chunk boundary) —
    an unmapped slot would silently drop the chunk's KV.
    """
    b, w = tokens.shape
    capacity = paged_seq_len(kv, block_tables)
    cos, sin = model_rope_tables(config, capacity)
    x = M.embed_tokens(params, tokens, config)
    q_pos, k_pos, lengths = verify_positions(w, pads, slot, capacity)
    x, kv = batched_blocks_forward(
        params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
        decode=False, cached_chunk=True, pads=pads, lengths=lengths,
        write_pos=slot, block_tables=block_tables,
        allow_pallas=allow_pallas,
    )
    return M.head_forward_all(params, x, config), kv


@functools.lru_cache(maxsize=8)
def _paged_verify_greedy_fn(config: LlamaConfig, width: int, allow_pallas=True):
    """Jit one greedy PAGED batched verify per (config, width): the dense
    _verify_greedy_fn harness with the block table as a traced operand."""

    def run(params, tokens, kv, pads, slot, block_tables):
        logits, kv = paged_verify_logits(
            params, tokens, kv, pads, slot, block_tables, config,
            allow_pallas=allow_pallas,
        )
        return verify_greedy_ids(logits), kv

    return _tracked_jit(
        run, name=f"batch.paged_verify_greedy[w={width}]", donate_argnums=(2,)
    )


@functools.lru_cache(maxsize=8)
def _paged_verify_sampled_fn(
    config: LlamaConfig,
    width: int,
    temperature: float,
    top_k,
    top_p,
    allow_pallas=True,
):
    """Jit one sampled PAGED batched verify per (config, width, knobs)."""

    def run(params, tokens, kv, pads, slot, block_tables, drafts, n_drafts, keys):
        logits, kv = paged_verify_logits(
            params, tokens, kv, pads, slot, block_tables, config,
            allow_pallas=allow_pallas,
        )
        n_accs, nxts, keys = verify_sampled_accept(
            logits, drafts, n_drafts, keys, temperature, top_k, top_p
        )
        return n_accs, nxts, kv, keys

    return _tracked_jit(
        run,
        name=(
            f"batch.paged_verify_sampled[w={width},t={temperature},"
            f"k={top_k},p={top_p}]"
        ),
        donate_argnums=(2,),
    )


# ---------------------------------------------------------------- speculative
#
# Batched prompt-lookup speculative decoding for the serving engine
# (runtime/serving.py): every row verifies ITS OWN drafted chunk inside ONE
# shared forward over [B, K+1] tokens at the epoch's shared slot, then the
# batch advances by the MINIMUM accepted length across live rows — the
# left-padded lockstep invariants (shared slot, per-row front pads) all hold,
# rows' surplus accepted tokens are simply re-verified next round, and
# rejected-tail KV sits at future-masked slots until overwritten. Greedy rows
# stay byte-identical to plain decode; sampled rows keep the exact
# plain-decode distribution (speculative.sampled_accept per row — emitting a
# PREFIX of an exact process is exact).


def batched_verify_logits(
    params: M.Params,
    tokens: jnp.ndarray,  # [B, W] = [last_r, draft_r..., pad 0s]
    kv: KVCache,
    pads: jnp.ndarray,
    slot: jnp.ndarray,
    config: LlamaConfig,
    tp_axis: str | None = None,
) -> tuple[jnp.ndarray, KVCache]:
    """One cached-chunk forward scoring every position: [B, W, vocab] f32.

    KV for the whole chunk is written at slots [slot, slot + W); callers
    advance the shared slot by the accepted length and let later writes
    overwrite the rejected tail (the single-row convention, speculative.py).
    """
    b, w = tokens.shape
    cos, sin = model_rope_tables(config, kv.max_seq_len)
    x = M.embed_tokens(params, tokens, config)
    q_pos, k_pos, lengths = verify_positions(w, pads, slot, kv.max_seq_len)
    x, kv = batched_blocks_forward(
        params["layers"], x, kv, cos, sin, q_pos, k_pos, config,
        decode=False, cached_chunk=True, pads=pads, lengths=lengths,
        write_pos=slot, tp_axis=tp_axis,
        # Verify chunks must be drop-free: force the dense MoE combine
        # (greedy speculation promises byte-exact streams; ops/moe.py).
        moe_dispatch="dense" if tp_axis is not None else "auto",
    )
    return M.head_forward_all(params, x, config), kv


def verify_greedy_ids(logits: jnp.ndarray) -> jnp.ndarray:
    """Greedy acceptance input: argmax ids [B, W] on device (no logit ship).
    ONE definition shared by the local and tp verify builders."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


def verify_sampled_accept(
    logits: jnp.ndarray,  # [B, W, vocab]
    drafts: jnp.ndarray,  # [B, K]
    n_drafts: jnp.ndarray,  # [B]
    keys: jax.Array,  # [B, 2]
    temperature: float,
    top_k,
    top_p,
):
    """Per-row rejection acceptance on device: vmaps
    speculative.sampled_accept over rows with per-row keys — the single-
    stream acceptance rule, so the per-position marginal stays exactly the
    plain-decode distribution for every row. ONE definition shared by the
    local and tp verify builders. Returns (n_accs [B], nxts [B], keys)."""
    from cake_tpu.models.llama.speculative import sampled_accept

    accept = jax.vmap(
        lambda lg, d, nd, k: sampled_accept(
            lg, d, nd, k, temperature, top_k, top_p
        )
    )
    return accept(logits, drafts, n_drafts, keys)


@functools.lru_cache(maxsize=8)
def _verify_greedy_fn(config: LlamaConfig, width: int):
    """Jit one greedy batched verify per (config, width)."""

    def run(params, tokens, kv, pads, slot):
        logits, kv = batched_verify_logits(
            params, tokens, kv, pads, slot, config
        )
        return verify_greedy_ids(logits), kv

    return _tracked_jit(
        run, name=f"batch.verify_greedy[w={width}]", donate_argnums=(2,)
    )


@functools.lru_cache(maxsize=8)
def _verify_sampled_fn(
    config: LlamaConfig,
    width: int,
    temperature: float,
    top_k,
    top_p,
):
    """Jit one sampled batched verify per (config, width, sampling knobs)."""

    def run(params, tokens, kv, pads, slot, drafts, n_drafts, keys):
        logits, kv = batched_verify_logits(
            params, tokens, kv, pads, slot, config
        )
        n_accs, nxts, keys = verify_sampled_accept(
            logits, drafts, n_drafts, keys, temperature, top_k, top_p
        )
        return n_accs, nxts, kv, keys

    return _tracked_jit(
        run,
        name=(
            f"batch.verify_sampled[w={width},t={temperature},"
            f"k={top_k},p={top_p}]"
        ),
        donate_argnums=(2,),
    )


def lockstep_decode(
    config: LlamaConfig,
    params: M.Params,
    ids_list: list[list[int]],
    s: SamplingConfig,
    *,
    max_seq_len: int,
    cache_dtype,
    decode_chunk_size: int,
    on_tokens,
    row_keys: jax.Array | None = None,
    mesh=None,
) -> None:
    """THE lockstep batch driver: prefill, first sample, chunked fused decode.

    Used by BatchGenerator (one-shot batches); the serving engine
    (runtime/serving.py) owns its own loop for continuous admission but
    shares the parity-critical pieces — layout_prompts, seed_rings,
    first_sample, _prefill_jit, _decode_fn — so the arithmetic exists once. After the first token ([B, 1]) and each
    decode chunk ([B, n]), ``on_tokens(toks)`` receives the raw sampled ids and
    returns True to continue; the driver itself stops only at the cache edge.
    Chunks are always full ``decode_chunk_size`` (host-side truncation handles
    budgets/EOS) — one fused trace, never one per tail length.

    ``row_keys`` = None samples the whole batch from one stream keyed by
    ``s.seed``; a [B, 2] array gives each row its OWN stream (serving's
    reproducibility contract — see ops/sampling.sample_per_row).

    ``mesh`` (a 1-D Mesh over a "dp" axis) shards the BATCH axis across
    devices — data-parallel lockstep decode: rows are independent, so every
    [B, ...] array (tokens, pads, KV cache, rings, keys) carries P("dp") and
    GSPMD partitions the whole prefill + decode with zero collectives.
    Params must already be replicated on the mesh by the caller.
    """
    b = len(ids_list)
    tokens, pads, bucket = layout_prompts(ids_list, max_seq_len)
    kv = init_cache(
        config.num_hidden_layers,
        b,
        max_seq_len,
        config.num_key_value_heads,
        config.head_dim,
        cache_dtype,
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(a, *axes):
            return jax.device_put(a, NamedSharding(mesh, P(*axes)))
    else:
        def place(a, *axes):
            return a

    pads_j = place(jnp.asarray(pads), "dp")
    tokens_j = place(jnp.asarray(tokens), "dp")
    kv = place(kv, None, "dp")
    if row_keys is not None:
        row_keys = place(row_keys, "dp")
    logits, kv = _prefill_jit(params, tokens_j, kv, pads_j, config)

    window = s.repeat_last_n
    ring, ring_idx = seed_rings(ids_list, window)
    first, key, ring, ring_idx = first_sample(logits, s, ring, ring_idx, row_keys)

    cap = max_seq_len - bucket  # cache slots available for generated tokens
    if not on_tokens(first[:, None]) or cap <= 1:
        return

    tok = place(jnp.asarray(first), "dp")
    slot = bucket  # slot of the most recent token
    ring_j = place(jnp.asarray(ring), "dp")
    produced = 1
    while produced < cap:
        n = min(decode_chunk_size, cap - produced)
        fn = _decode_fn(
            config,
            max_seq_len,
            n,
            s.temperature,
            s.top_k,
            s.top_p,
            s.repeat_penalty,
            # GSPMD cannot auto-partition a Mosaic custom call over the dp
            # mesh (only the shard_map backends hand-place kernels); the dp
            # path stays on the XLA decode attention.
            allow_pallas=mesh is None,
        )
        toks, kv, key, ring_j, ring_idx_j = fn(
            params,
            kv,
            tok,
            jnp.int32(slot),
            pads_j,
            key,
            ring_j,
            jnp.asarray(ring_idx),
        )
        ring_idx = np.asarray(ring_idx_j)
        cont = on_tokens(np.asarray(toks))
        tok = toks[:, -1]
        slot += n
        produced += n
        if not cont:
            return


class BatchGenerator:
    """Generate completions for B dialogs at once (single-process).

    One prefill + fused lockstep decode; per-row EOS truncation on host. Unlike
    LlamaGenerator this is stateless per call — each ``generate`` is a fresh
    batch with its own KV cache.
    """

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        tokenizer: Tokenizer,
        sampling: SamplingConfig = SamplingConfig(),
        *,
        max_seq_len: int | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
        decode_chunk_size: int = 8,
        dp: int | None = None,
    ):
        from cake_tpu.ops.fuse import fuse_params

        self.config = config
        self.params = fuse_params(params)  # ops/fuse.py, column-identical
        self.tokenizer = tokenizer
        self.sampling = sampling
        self.max_seq_len = int(max_seq_len or config.max_position_embeddings)
        self.cache_dtype = cache_dtype
        self.decode_chunk_size = max(1, decode_chunk_size)
        # Data parallelism: rows sharded over a 1-D "dp" mesh — independent
        # sequences, so the lockstep decode partitions with zero collectives
        # (params replicated once here; batches must divide by dp).
        self.mesh = None
        if dp is not None and dp > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            devs = jax.devices()
            if len(devs) < dp:
                raise ValueError(f"dp={dp} needs {dp} devices, have {len(devs)}")
            self.mesh = Mesh(np.array(devs[:dp]), ("dp",))
            self.params = jax.device_put(
                self.params, NamedSharding(self.mesh, P())
            )

    def generate(
        self, dialogs: list[list[Message]], max_new_tokens: int
    ) -> list[BatchResult]:
        if not dialogs or max_new_tokens <= 0:
            return [
                BatchResult(text="", token_ids=[], finish_reason="length")
                for _ in dialogs
            ]
        s = self.sampling
        ids_list = [
            self.tokenizer.encode(encode_dialog(d, self.config.dialog_template))
            for d in dialogs
        ]
        longest = max(len(i) for i in ids_list)
        if longest >= self.max_seq_len:
            raise ValueError(
                f"longest prompt ({longest} tokens) exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
        b = len(ids_list)
        if self.mesh is not None and b % self.mesh.shape["dp"]:
            raise ValueError(
                f"batch of {b} rows does not divide over dp="
                f"{self.mesh.shape['dp']} (pad the batch or drop dp)"
            )
        eos = set(self.config.eos_token_ids)
        generated: list[list[int]] = [[] for _ in range(b)]
        done = np.zeros(b, bool)

        def on_tokens(toks: np.ndarray) -> bool:
            for r in range(b):
                if done[r]:
                    continue
                for t in toks[r]:
                    generated[r].append(int(t))
                    if int(t) in eos or len(generated[r]) >= max_new_tokens:
                        done[r] = True
                        break
            return not done.all()

        lockstep_decode(
            self.config,
            self.params,
            ids_list,
            s,
            max_seq_len=self.max_seq_len,
            cache_dtype=self.cache_dtype,
            decode_chunk_size=self.decode_chunk_size,
            on_tokens=on_tokens,
            mesh=self.mesh,
        )

        results = []
        for r in range(b):
            ids = generated[r]
            stopped = bool(ids and ids[-1] in eos)
            text_ids = ids[:-1] if stopped else ids
            results.append(
                BatchResult(
                    text=self.tokenizer.decode(text_ids),
                    token_ids=ids,
                    finish_reason="stop" if stopped else "length",
                )
            )
        return results


def block_pass_attention(
    q: jnp.ndarray,  # [b, B, n_q, d]: ONE block's queries a row
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    lengths: jnp.ndarray,  # [b] one past the block's last slot
    block_tables: jnp.ndarray,
    starts: jnp.ndarray,  # [b] first live slot per row (the left pads)
    **kw,
) -> jnp.ndarray:
    """A denoising or commit pass's attention under the Pallas kernels: the
    queries of ONE block of a block-diffusion model (``config.block_length``
    slots from the shared slot on) all see the same keys, every slot of
    [start, the block's end): no query of the block is masked from another's
    key. So the block's ``B`` queries a head are ``B`` more heads of the same
    KV head, and the call is the paged DECODE kernel's at a group of ``B x
    group`` rows (``ops/pallas/paged_attention.py``: one grid step a lane and
    KV head, the live pages in a loop of its own), where the chunk kernel's
    grid walks lanes x query heads x table pages for 4 rows of a 16-row tile
    (24.7 ms a call at the benchmark cell's shape for the decode kernel's
    0.1: PERF.md, PR 57). ``kw``: the decode kernel's (``layer``, ``scale``,
    ``softcap``, ``window``). Returns [b, B, n_q, d]."""
    b, width, n_q, d = q.shape
    n_kv = k_pool.shape[-3]
    group = n_q // n_kv
    folded = q.reshape(b, width, n_kv, group, d).transpose(0, 2, 1, 3, 4)
    out = paged_decode_attention(
        folded.reshape(b, 1, n_kv * width * group, d), k_pool, v_pool, lengths,
        block_tables, starts, None, **kw,
    )
    out = out.reshape(b, n_kv, width, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, width, n_q, d)
