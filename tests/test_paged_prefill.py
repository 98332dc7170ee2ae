"""Paged chunk-prefill kernel: interpret-mode kernel vs the gather twin vs
the dense chunk kernel, across the three shapes one arithmetic serves —
cold chunked prefill (q_starts = 0), cached-chunk suffix windows
(q_starts = start), and speculative-verify chunks at the shared slot.

Like tests/test_paged_attention.py, the load-bearing property is INDIRECTION
correctness: physical pages are deliberately scattered (LIFO free list hands
out high pages first), so a kernel that ignores its block table and reads
page 0 everywhere fails loudly here (the `prefetch-ref-unused` failure mode).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama.batch import prefill_positions, verify_positions
from cake_tpu.models.llama.paged_cache import PageAllocator
from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention
from cake_tpu.ops.pallas.paged_prefill import (
    paged_chunk_attention,
    paged_chunk_attention_xla,
    paged_kernel_supported,
)

B, N_Q, N_KV, HD = 3, 4, 2, 64
PS = 128  # kernel page size: the 128-lane tile
PER_SEQ = 3  # up to 3 pages per sequence -> 384 slots


def make_pool(alloc, seed=0, n_pages=12):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(n_pages, N_KV, PS, HD)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, N_KV, PS, HD)), jnp.float32)
    return kp, vp, rng


def cold_setup(seed=0, lengths=(160, 257, 40), pads=(3, 0, 10), n_pages=12):
    """A cold prefill shape: queries cover slots [0, W); every row's live
    window [pad, length) is mapped to deliberately out-of-order pages."""
    lengths = np.asarray(lengths, np.int32)
    pads = np.asarray(pads, np.int32)
    alloc = PageAllocator(n_pages, PS, B, PER_SEQ)
    for r in range(B):
        alloc.map_range(r, int(pads[r]), int(lengths[r]))
    kp, vp, rng = make_pool(alloc, seed, n_pages)
    w = int(lengths.max())
    q = jnp.asarray(rng.normal(size=(B, w, N_Q, HD)), jnp.float32)
    bt = jnp.asarray(alloc.block_tables)
    return q, kp, vp, bt, jnp.asarray(lengths), jnp.asarray(pads), w


def assert_live_close(got, want, lengths, pads, atol=2e-5):
    """Compare the VALID query rows only: slots outside [pad, length) are
    garbage nobody reads (the kernel zeroes them, the XLA twin computes
    clamped-position garbage — both contracts are 'finite, unread')."""
    got, want = np.asarray(got), np.asarray(want)
    lengths, pads = np.asarray(lengths), np.asarray(pads)
    for r in range(got.shape[0]):
        lo, hi = int(pads[r]), min(int(lengths[r]), got.shape[1])
        np.testing.assert_allclose(got[r, lo:hi], want[r, lo:hi], atol=atol)


def test_cold_chunk_matches_gather_twin():
    q, kp, vp, bt, lengths, pads, w = cold_setup()
    got = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        interpret=True,
    )
    q_pos, k_pos = prefill_positions(PER_SEQ * PS, pads, ends=lengths)
    want = paged_chunk_attention_xla(
        q, kp, vp, q_pos[:, :w], k_pos, bt
    )
    assert_live_close(got, want, lengths, pads)


def test_cold_chunk_matches_dense_chunk_kernel():
    # Three-way: paged kernel == dense chunk kernel fed the gathered view.
    from cake_tpu.models.llama.paged_cache import gather_pages

    q, kp, vp, bt, lengths, pads, w = cold_setup(seed=1)
    got = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        interpret=True,
    )
    dense_k = gather_pages(kp, bt)
    dense_v = gather_pages(vp, bt)
    want = chunk_prefill_attention(
        q, dense_k, dense_v, jnp.zeros((B,), jnp.int32), lengths,
        None, pads, interpret=True,
    )
    assert_live_close(got, want, lengths, pads)


def test_cached_chunk_matches_gather_twin():
    """Suffix/verify shape: a 16-wide window at absolute slot ``start``
    attends the whole live prefix, queries roped at their own slots."""
    lengths = np.asarray((200, 273, 216), np.int32)
    pads = np.asarray((3, 0, 10), np.int32)
    start = 200 - 16
    alloc = PageAllocator(12, PS, B, PER_SEQ)
    for r in range(B):
        alloc.map_range(r, int(pads[r]), int(lengths[r]))
    kp, vp, rng = make_pool(alloc, seed=2)
    w = 16
    q = jnp.asarray(rng.normal(size=(B, w, N_Q, HD)), jnp.float32)
    bt = jnp.asarray(alloc.block_tables)
    starts = jnp.full((B,), start, jnp.int32)
    lens = jnp.full((B,), start + w, jnp.int32)
    got = paged_chunk_attention(
        q, kp, vp, starts, lens, jnp.asarray(pads), bt, interpret=True
    )
    q_pos, k_pos, _ = verify_positions(
        w, jnp.asarray(pads), jnp.int32(start), PER_SEQ * PS
    )
    want = paged_chunk_attention_xla(q, kp, vp, q_pos, k_pos, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_physical_permutation_invariance():
    """The same logical tokens scattered across DIFFERENT physical pages
    must attend identically — the indirection is real."""
    q, kp, vp, bt, lengths, pads, w = cold_setup(seed=3)
    base = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        interpret=True,
    )
    # Permute physical pages and rewrite the tables to match.
    n_pages = kp.shape[0]
    perm = np.random.default_rng(7).permutation(n_pages)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n_pages)
    kp2 = jnp.asarray(np.asarray(kp)[perm])
    vp2 = jnp.asarray(np.asarray(vp)[perm])
    bt2 = np.asarray(bt).copy()
    bt2[bt2 >= 0] = inv[bt2[bt2 >= 0]]
    moved = paged_chunk_attention(
        q, kp2, vp2, jnp.zeros((B,), jnp.int32), lengths, pads,
        jnp.asarray(bt2), interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(base), np.asarray(moved), atol=1e-6
    )


def test_window_prunes_and_masks_like_the_twin():
    q, kp, vp, bt, lengths, pads, w = cold_setup(seed=4)
    flag = jnp.ones((), bool)
    got = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        window_flag=flag, window=48, interpret=True,
    )
    q_pos, k_pos = prefill_positions(PER_SEQ * PS, pads, ends=lengths)
    want = paged_chunk_attention_xla(
        q, kp, vp, q_pos[:, :w], k_pos, bt, window=48, window_flag=flag
    )
    assert_live_close(got, want, lengths, pads)
    # Flag off = full causal, same knobs.
    off = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        window_flag=jnp.zeros((), bool), window=48, interpret=True,
    )
    full = paged_chunk_attention(
        q, kp, vp, jnp.zeros((B,), jnp.int32), lengths, pads, bt,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(off), np.asarray(full), atol=1e-6)


def test_dead_rows_and_unmapped_tails_are_finite_zero():
    """A row with length 0 (dead join lane) and unmapped tail pages must
    produce exact zeros for its masked queries — never NaN (0 * NaN would
    poison later layers)."""
    lengths = np.asarray((0, 257, 40), np.int32)
    pads = np.asarray((0, 0, 10), np.int32)
    alloc = PageAllocator(12, PS, B, PER_SEQ)
    for r in range(B):
        if lengths[r]:
            alloc.map_range(r, int(pads[r]), int(lengths[r]))
    kp, vp, rng = make_pool(alloc, seed=5)
    w = 64
    q = jnp.asarray(rng.normal(size=(B, w, N_Q, HD)), jnp.float32)
    bt = jnp.asarray(alloc.block_tables)
    out = np.asarray(
        paged_chunk_attention(
            q, kp, vp, jnp.zeros((B,), jnp.int32), jnp.asarray(lengths),
            jnp.asarray(pads), bt, interpret=True,
        )
    )
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0], 0.0)  # dead row: all-masked
    np.testing.assert_array_equal(out[2, :10], 0.0)  # pad queries


def test_untiled_page_size_is_refused_by_kernel():
    assert not paged_kernel_supported(96)
    assert paged_kernel_supported(256)
    kp = jnp.zeros((4, N_KV, 96, HD), jnp.float32)
    q = jnp.zeros((1, 8, N_Q, HD), jnp.float32)
    with pytest.raises(ValueError, match="128-lane"):
        paged_chunk_attention(
            q, kp, kp, jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.zeros((1, 2), jnp.int32),
            interpret=True,
        )


# --------------------------------------------------------------- integration
#
# The kernel family wired through the backend and engine: speculative verify
# under kv_mode="paged" (the capability gate is gone), the bounded epoch
# capacity (and the one-capacity trap it exists to avoid), and the pallas
# dispatch path end to end. Dense-vs-paged bit-identity for cold/warm/join/
# failover streams is pinned by tests/test_paged_serving.py,
# test_prefix_serving.py and test_chaos.py — all of which now run through
# these dispatches.

import time

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.runtime.batch_backend import PagedLocalBackend
from cake_tpu.runtime.serving import BatchEngine, ServeConfig
from cake_tpu.utils import metrics

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
MAX_SEQ = 128
PAGE = 16  # small pages, NOT a lane-tile multiple: the XLA-twin path


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(43), jnp.float32)
    return cfg, params


def _engine(model, speculative_k=0, kv_mode="paged", max_seq=MAX_SEQ, **over):
    cfg, params = model
    kw = dict(
        max_batch=4, decode_chunk_size=4, admission_window=0.05,
        kv_mode=kv_mode,
    )
    if kv_mode == "paged":
        kw["page_size"] = over.pop("page_size", PAGE)
    kw.update(over)
    return BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=max_seq,
        cache_dtype=jnp.float32, speculative_k=speculative_k,
        serve=ServeConfig(**kw),
    )


def _run(eng, prompts, n, s=GREEDY):
    eng.start()
    try:
        handles = [eng.submit([Message.user(p)], n, s) for p in prompts]
        return [[t.id for t in h.tokens()] for h in handles]
    finally:
        eng.stop()


# Repetitive prompts: prompt lookup drafts verify at high rates on these.
SPEC_PROMPTS = ["abc abc abc abc abc abc", "q1 q1 q1 q1 q1 q1 q1"]


def test_paged_spec_greedy_identical_to_dense_spec_and_plain_paged(model):
    """Speculative verify RUNS under kv_mode="paged" (the capability gate
    is gone) and changes nothing: greedy paged-spec streams byte-match both
    the dense-spec streams (gather view ≡ dense arithmetic) and the plain
    paged streams (draft quality affects speed only)."""
    spec_eng = _engine(model, speculative_k=4)
    spec = _run(spec_eng, SPEC_PROMPTS, 16)
    assert spec_eng.stats["spec_rounds"] > 0
    assert spec == _run(_engine(model, speculative_k=4, kv_mode="dense"),
                        SPEC_PROMPTS, 16)
    assert spec == _run(_engine(model, speculative_k=0), SPEC_PROMPTS, 16)


def test_paged_spec_single_row_accepts_drafts(model):
    """One live row, chunk 1 (rounds attempted at every slot): paged verify
    must ACCEPT matching drafts — multi-token advances, not just byte-exact
    corrections."""
    eng = _engine(model, speculative_k=4, decode_chunk_size=1)
    spec = _run(eng, SPEC_PROMPTS[:1], 24)
    assert spec == _run(_engine(model, speculative_k=0), SPEC_PROMPTS[:1], 24)
    assert eng.stats["spec_rounds"] > 0
    assert eng.stats["spec_tokens"] > eng.stats["spec_rounds"]


def test_paged_spec_sampled_identical_to_dense_spec(model):
    """temperature > 0 through the paged verify: the vmapped rejection rule
    over the gather view is the dense arithmetic bit-for-bit, so per-seed
    streams match the dense speculative engine exactly."""
    s = SamplingConfig(temperature=0.9, top_k=12, repeat_penalty=1.0, seed=7)
    paged = _run(_engine(model, speculative_k=4), SPEC_PROMPTS, 12, s)
    dense = _run(_engine(model, speculative_k=4, kv_mode="dense"),
                 SPEC_PROMPTS, 12, s)
    assert paged == dense


def _wait_idle(eng, n_epochs, timeout=30.0):
    from cake_tpu.obs.timeline import timeline

    deadline = time.time() + timeout
    while time.time() < deadline:
        if sum(
            1 for e in timeline.snapshot() if e["name"] == "epoch"
        ) >= n_epochs:
            assert eng.quiesce(max(0.1, deadline - time.time()))
            return
        time.sleep(0.01)
    raise AssertionError("engine did not go idle")


def test_paged_spec_with_prefix_cache_warm_identical(model):
    """Spec + prefix cache + bounded capacity together: the warm round (every
    admission a chain hit, suffix-only prefill) speculates AND stays
    byte-identical to the cold round."""
    eng = _engine(model, speculative_k=4, prefix_cache=True)
    eng.start()
    try:
        rounds = []
        for r in range(2):
            handles = [
                eng.submit([Message.user(p)], 16, GREEDY)
                for p in SPEC_PROMPTS
            ]
            rounds.append([[t.id for t in h.tokens()] for h in handles])
            _wait_idle(eng, r + 1)
        cold, warm = rounds
    finally:
        eng.stop()
    assert warm == cold
    assert eng.stats["prefix_hits"] > 0
    assert eng.stats["spec_rounds"] > 0


def test_bounded_capacity_engages_and_streams_match_dense(model):
    """At max_seq 1024 a short-budget epoch must attend over the bucketed
    live capacity (256 slots), not the padded table width — and produce the
    exact dense streams while doing it."""
    cfg, params = model
    cfg_long = LlamaConfig.tiny(
        num_hidden_layers=2, max_position_embeddings=1024
    )
    eng = _engine((cfg_long, params), max_seq=1024)
    seen = []
    orig = eng.backend.set_epoch_capacity
    eng.backend.set_epoch_capacity = (
        lambda c: (seen.append(c), orig(c))[-1]
    )
    paged = _run(eng, ["a short prompt", "another short one"], 12)
    assert 256 in seen  # bucket + 12-token budget, 256-bucketed
    assert eng.backend._cap_pages is None  # reset at epoch end
    dense = _run(
        _engine((cfg_long, params), kv_mode="dense", max_seq=1024),
        ["a short prompt", "another short one"], 12,
    )
    assert paged == dense


def test_bounded_cap_epoch_refuses_join_it_would_truncate(model):
    """_take_joins prices waiting against what a SOLO epoch would deliver —
    min(max_tokens, max_seq - bucket), sized from the request's OWN budget —
    not this epoch's bounded cap. A high-budget request queued behind a
    short-budget epoch (cap 256 of max_seq 1024) must WAIT for its own
    epoch instead of joining and silently finishing "length" at the cap."""
    from cake_tpu.runtime.serving import StreamHandle, _Request

    cfg, params = model
    cfg_long = LlamaConfig.tiny(
        num_hidden_layers=2, max_position_embeddings=1024
    )
    eng = _engine((cfg_long, params), max_seq=1024)
    big = _Request(list(range(48)), 500, GREEDY, StreamHandle(48), rid="big")
    small = _Request(list(range(48)), 8, GREEDY, StreamHandle(48), rid="small")
    with eng._cv:
        eng._queue.extend([big, small])
    # A bounded short-budget epoch: cap 256, shared slot at 48, a free lane.
    taken = {
        r.rid
        for _, r in eng._take_joins(GREEDY.trace_knobs(), [object(), None],
                                    48, 256)
    }
    # Joining would cap big at ~208 tokens; waiting delivers all 500.
    assert "big" not in taken
    assert "small" in taken  # a small-budget joiner still fits this epoch
    assert [r.rid for r in eng._queue] == ["big"]


def test_one_capacity_mismatch_breaks_oracle(model):
    """THE documented trap: the same suffix window under a capacity that
    still covers the live prefix is bit-identical to the full table, but one
    page short of the live prefix silently TRUNCATES live keys — which is
    why the engine threads ONE capacity through suffix_prefill/suffix_join/
    migrate (a mismatch anywhere breaks the warm/cold identity chain)."""
    from cake_tpu.models.llama.batch import (
        paged_prefill,
        paged_suffix_prefill,
    )
    from cake_tpu.models.llama.paged_cache import init_paged_cache

    cfg, params = model
    alloc = PageAllocator(16, PAGE, batch=1, max_pages_per_seq=16)
    alloc.map_range(0, 0, 192)
    kv = init_paged_cache(
        cfg.num_hidden_layers, 16, cfg.num_key_value_heads, PAGE,
        cfg.head_dim, jnp.float32,
    )
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(1, 500, size=(1, 192)), jnp.int32)
    pads = jnp.zeros((1,), jnp.int32)
    tables = jnp.asarray(alloc.block_tables)
    _, kv = paged_prefill(params, tokens, kv, pads, tables, cfg)

    def suffix(tables_slice):
        # Re-score the last 16 prompt slots; write_starts=192 drops every
        # window write, so `kv` is reusable across calls.
        lg, _ = paged_suffix_prefill(
            params, tokens[:, 176:192], kv, pads,
            jnp.full((1,), 192, jnp.int32), tables_slice, cfg,
            jnp.int32(176),
        )
        return np.asarray(lg)

    full = suffix(tables)            # capacity 256 slots
    cover = suffix(tables[:, :12])   # capacity 192 — still covers the live prefix
    trunc = suffix(tables[:, :8])    # capacity 128 — truncates 64 live keys
    np.testing.assert_array_equal(full, cover)
    assert not np.allclose(full, trunc)


def test_write_past_epoch_capacity_fails_loudly(model):
    """A dispatch writing past the sliced table would DROP KV silently —
    the backend must refuse it instead."""
    cfg, params = model
    be = PagedLocalBackend(
        cfg, params, max_seq_len=256, cache_dtype=jnp.float32,
        page_size=PAGE,
    )
    kv = be.init_kv(2)
    be.set_epoch_capacity(64)
    assert be.capacity_slots() == 64
    with pytest.raises(ValueError, match="one-capacity"):
        be.prefill(np.zeros((2, 128), np.int32), kv, np.zeros((2,), np.int32))
    be.set_epoch_capacity(None)
    assert be.capacity_slots() == be.padded_seq


def test_kernel_fallback_flight_event_fires_once(model):
    """attention_impl=pallas over an untiled page size downgrades to the XLA
    twin — surfaced as ONE `kernel-fallback` flight event, not silence."""
    cfg, params = model
    cfg_p = LlamaConfig.tiny(num_hidden_layers=2, attention_impl="pallas")
    be = PagedLocalBackend(
        cfg_p, params, max_seq_len=128, cache_dtype=jnp.float32,
        page_size=PAGE,  # 16: not a 128-lane tile multiple
    )
    assert be.kernel_impl() == "fallback"
    kv = be.init_kv(1)
    be.allocator.map_range(0, 0, 32)
    tokens = np.zeros((1, 32), np.int32)
    for _ in range(2):
        _, kv = be.prefill(tokens, kv, np.zeros((1,), np.int32))
    events = [
        e for e in metrics.flight.snapshot()
        if e["event"] == "kernel-fallback"
    ]
    assert len(events) == 1
    # the event names the pool's write with the reads, and /stats agrees
    assert (events[0]["pool_write"], events[0]["attention"]) == ("xla", "xla")
    assert be.cache_facts()["pool_write"] == "xla"
    # xla-by-choice is not a fallback: no event.
    metrics.flight.clear()
    be2 = PagedLocalBackend(
        cfg, params, max_seq_len=128, cache_dtype=jnp.float32, page_size=PAGE
    )
    assert be2.kernel_impl() == "xla"
    assert be2.cache_facts()["pool_write"] == "xla"
    kv2 = be2.init_kv(1)
    be2.allocator.map_range(0, 0, 32)
    be2.prefill(tokens, kv2, np.zeros((1,), np.int32))
    assert not [
        e for e in metrics.flight.snapshot()
        if e["event"] == "kernel-fallback"
    ]


def test_pallas_paged_engine_cold_warm_identical(model):
    """The pallas dispatch end to end (interpret mode on CPU): a prefix-
    cache engine over 128-slot pages serves warm streams identical to cold
    ones — cold and warm walk the SAME paged chunk kernel, so the identity
    holds under pallas exactly as under the XLA twin."""
    cfg, params = model
    cfg_p = LlamaConfig.tiny(num_hidden_layers=2, attention_impl="pallas")
    eng = _engine(
        (cfg_p, params), max_seq=256, page_size=128, prefix_cache=True,
        max_batch=2,
    )
    assert eng.backend.kernel_impl() == "pallas"
    # the write is the kernel where Mosaic compiles it; interpreted it is
    # the scatter (ops/pallas/paged_write.compiled_here)
    assert eng.backend.cache_facts()["pool_write"] == "xla"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert eng.backend.cache_facts()["pool_write"] == "pallas"
    eng.start()
    try:
        rounds = []
        for r in range(2):
            h = eng.submit([Message.user("shared system prompt, again")],
                           8, GREEDY)
            rounds.append([t.id for t in h.tokens()])
            _wait_idle(eng, r + 1)
        cold, warm = rounds
    finally:
        eng.stop()
    assert warm == cold
    assert eng.stats["prefix_hits"] > 0


@pytest.mark.parametrize("layer", [1, 2])
def test_stacked_pool_reads_the_indexed_layer(layer):
    """The whole pool [n_layers, n_pages, ...] plus a layer index reads
    exactly what the same kernel reads from that layer sliced out (cold
    chunk, windowed); layer 0 read by mistake shows."""
    q, kp, vp, bt, lengths, pads, w = cold_setup(seed=8)
    rng = np.random.default_rng(80)
    k_pool = jnp.asarray(rng.normal(size=(3,) + kp.shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(3,) + vp.shape), jnp.float32)
    zeros = jnp.zeros((B,), jnp.int32)
    kw = dict(window_flag=jnp.ones((), bool), window=48, interpret=True)
    got = paged_chunk_attention(
        q, k_pool, v_pool, zeros, lengths, pads, bt,
        layer=jnp.int32(layer), **kw,
    )
    want = paged_chunk_attention(
        q, k_pool[layer], v_pool[layer], zeros, lengths, pads, bt, **kw
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    other = paged_chunk_attention(
        q, k_pool[0], v_pool[0], zeros, lengths, pads, bt, **kw
    )
    assert float(jnp.abs(got - other).max()) > 1e-3
    # The gather twin indexes the layer the same way.
    q_pos, k_pos = prefill_positions(PER_SEQ * PS, pads, ends=lengths)
    twin = paged_chunk_attention_xla(
        q, k_pool, v_pool, q_pos[:, :w], k_pos, bt, window=48,
        layer=jnp.int32(layer),
    )
    np.testing.assert_array_equal(
        np.asarray(twin),
        np.asarray(paged_chunk_attention_xla(
            q, k_pool[layer], v_pool[layer], q_pos[:, :w], k_pos, bt,
            window=48,
        )),
    )
