"""``model_type: laguna`` in the program (models/llama/kinds.py): window and
full attention layers of different head counts over a pool a kind, each kind
its own rotary term, the gate a head, a share of the routed experts; against
the plain reference (bench/architectures/laguna.py) on LOGITS, at a tiny
size on the CPU with seeded weights (tests/laguna_tiny.py).

Tolerances. The program in float32 differs from the float32 reference by
the order of sums alone: 2e-6 to 3e-6 on logits of size 2.6 here, held to
``TOL`` = 3e-5 (ten times that, a hundred thousandth of a logit). The same
weights rounded to bfloat16, where float32 is stated, move the logits by
2e-3 and more: ``test_bf16_where_float32_is_stated_fails`` holds that the
tolerance tells the two apart.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import kinds as K
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.config import (
    CACHE_KV_KINDS, FULL, SLIDING, LlamaConfig,
)
from cake_tpu.models.llama.paged_cache import PageAllocator, PagePools
from cake_tpu.ops import moe
from cake_tpu.ops.rope import apply_rope, kind_rope_rows, yarn_frequencies

from laguna_tiny import HF, PAGE, WINDOW, Lanes, checkpoint, reference_module

TOL = 3e-5


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config, params, reader, cfg = checkpoint(tmp_path_factory.mktemp("laguna"))
    ref = reference_module()
    rng = np.random.default_rng(0)
    ids = [int(t) for t in rng.integers(3, HF["vocab_size"], 120)]
    return config, params, reader, cfg, ref, ids


# ------------------------------------------------------------------ parser


def test_the_parser_gives_the_per_layer_facts():
    config = LlamaConfig.from_hf_dict(HF)
    assert config.cache_kind == CACHE_KV_KINDS and config.attention_kinds == (FULL, SLIDING)
    assert config.layer_kinds == ("attention",) * 5 and not config.has_state_layers
    assert config.stack_runs == (
        (FULL, "dense", 0, 1, 0), (SLIDING, "sparse", 1, 3, 0),
        (FULL, "sparse", 3, 4, 1), (SLIDING, "sparse", 4, 5, 2),
    )
    assert config.kind_layers(FULL) == (0, 3) and config.kind_layers(SLIDING) == (1, 2, 4)
    assert (config.kind_window(FULL), config.kind_window(SLIDING)) == (None, WINDOW)
    assert config.heads_per_layer == (12, 18, 18, 12, 18)  # groups of 6 and 9 on 2 KV heads
    ropes = dict(config.kind_ropes)
    assert (ropes[FULL].rotary_dim, ropes[FULL].factor, ropes[FULL].attention_factor) == (8, 8.0, 1.2079)
    assert (ropes[SLIDING].rotary_dim, ropes[SLIDING].factor, ropes[SLIDING].theta) == (16, 1.0, 10000.0)
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (4, 16, 4)
    assert (config.moe_scoring, config.routed_scaling_factor, config.attn_gate) == ("sigmoid", 2.5, "per-head")
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config


@pytest.mark.parametrize("key, value, says", [
    ("layer_types", ["full_attention"] * 4, "layer_types"),
    ("num_attention_heads_per_layer", [12, 18, 18, 12, 17], "multiples of num_key_value_heads"),
    ("num_attention_heads_per_layer", [12, 18, 18, 6, 18], "different head counts"),
    ("gating", "per-layer", "gating"),
    ("moe_router_logit_softcapping", 30.0, "soft-cap"),
    ("first_expert", 14, "must not pass"),
    ("sliding_window", None, "sliding_window"),
])
def test_the_parser_refuses_what_it_cannot_serve(key, value, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_hf_dict({**HF, key: value})


@pytest.mark.parametrize("fact", ["prefix_cache", "tp", "speculative_k", "quantize", "kv_mode_dense"])
def test_what_a_pool_a_kind_cannot_be_served_with_is_refused(fact):
    config = LlamaConfig.from_hf_dict(HF)
    with pytest.raises(UnsupportedForCacheKind, match="freed 16 tokens behind"):
        refuse_unsupported(config, **{fact: True})


# -------------------------------------------------------------------- rope


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rope_frequencies_and_rotation_are_the_references(kind):
    """YaRN with the half rotary, and the plain rope over a whole head:
    the program's table and ``apply_rope`` against the reference's own."""
    ref = reference_module()
    config = LlamaConfig.from_hf_dict(HF)
    rope = dict(config.kind_ropes)[FULL if kind == "full_attention" else SLIDING]
    want = ref.rope_inverse_frequencies(HF["rope_parameters"][kind], 16)
    np.testing.assert_allclose(yarn_frequencies(rope), want, rtol=1e-6)
    if kind == "full_attention":  # the ramp is inside the table: neither end alone
        plain = 1.0 / 500000 ** (np.arange(0, 8, 2) / 8)
        assert not np.allclose(want, plain) and not np.allclose(want, plain / 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 3, 16), jnp.float32)
    cos, sin = kind_rope_rows(rope, jnp.arange(50)[None])
    got = apply_rope(x[None], cos, sin, None)[0]
    np.testing.assert_allclose(got, ref._rope(x, HF["rope_parameters"][kind], 16), atol=2e-6)
    if kind == "full_attention":
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])  # the half that is not rotated


# ------------------------------------------------- served through the pools


def _serve(config, params, ids, n_prompt, slot=64, width=64):
    """A join of ``ids[:n_prompt]`` ending at ``slot``, then a decode step a
    remaining token (a dead lane beside it): logits after every token from
    the prompt's last on, and the pools."""
    lanes = Lanes(config, params, 2, 32, 40, 12)
    out = [lanes.join(0, ids[:n_prompt], slot, width)]
    for t in range(n_prompt, len(ids)):
        out.append(lanes.step([ids[t], 0], slot)[0])
        slot += 1
    return np.stack(out), lanes


def test_prefill_then_decode_through_two_pools_is_the_reference(tiny):
    """A prompt of 37 tokens (more than two windows: the sliding kind stores
    its tail alone), then 83 decode steps that cross the window, ten page
    boundaries of each pool, and free eight pages behind the window."""
    config, params, reader, cfg, ref, ids = tiny
    want = ref.forward_logits(reader, cfg, [ids])[0]
    got, lanes = _serve(config, params, ids, 37)
    assert np.abs(got - want[36:]).max() < TOL < 1e-5 * np.abs(want).max() * 10
    facts = lanes.pools.facts({FULL: 1, SLIDING: 1})
    assert facts[SLIDING]["freed_behind_window"] >= 8 and facts[FULL]["freed_behind_window"] == 0
    assert facts[FULL]["pages_mapped"] == -(-(64 + 83) // PAGE) - (64 - 37) // PAGE
    assert facts[SLIDING]["pages_mapped"] <= WINDOW // PAGE + 2


def test_a_join_that_crosses_the_window_at_a_page_boundary(tiny):
    """The prompt ends where a page does, so the sliding kind's first stored
    page is a whole one behind the boundary, and the first decode step frees
    nothing it still reads."""
    config, params, reader, cfg, ref, ids = tiny
    want = ref.forward_logits(reader, cfg, [ids[:60]])[0]
    got, _ = _serve(config, params, ids[:60], 48, slot=48, width=64)
    assert np.abs(got - want[47:]).max() < TOL


def test_bf16_where_float32_is_stated_fails(tiny):
    config, params, reader, cfg, ref, ids = tiny
    want = ref.forward_logits(reader, cfg, [ids[:50]])[0]
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got, _ = _serve(config, rounded, ids[:50], 37)
    assert np.abs(got - want[36:]).max() > 30 * TOL


@pytest.mark.parametrize("fault", ["no_gate", "no_window", "plain_rope", "full_rotary",
                                   "no_shared_expert", "softmax_scores"])
def test_each_mechanism_against_the_reference_alone(tiny, fault):
    """The gate, the window, YaRN, the half rotary, the shared expert and
    the sigmoid scores each: the program agrees with the sound reference and
    not with the reference that lacks the one thing."""
    config, params, reader, cfg, ref, ids = tiny
    assert fault in ref.FAULTS
    got, _ = _serve(config, params, ids[:48], 40)
    sound = ref.forward_logits(reader, cfg, [ids[:48]])[0][39:]
    ref.FAULT = fault
    try:
        faulty = ref.forward_logits(reader, cfg, [ids[:48]])[0][39:]
    finally:
        ref.FAULT = None
    assert np.abs(got - sound).max() < TOL
    assert np.abs(got - faulty).max() > 100 * TOL


def test_a_norm_on_q_and_k_is_data_in_the_parser_and_the_reference(tmp_path):
    """``assumed``: no norm on q or k. A checkpoint whose config says
    ``use_qk_norm`` carries one a head before the rope; the program applies
    it, and the reference does with ``QK_NORM`` set (a line each)."""
    def weights(params):  # not ones, so that the norm is seen
        for r, run in enumerate(params["layers"]):
            for j, name in enumerate(("q_norm", "k_norm")):
                noise = jax.random.normal(jax.random.PRNGKey(10 * r + j), run[name].shape)
                run[name] = 1.0 + 0.3 * noise
        return params

    config, params, reader, cfg = checkpoint(tmp_path, {**HF, "use_qk_norm": True}, mutate=weights)
    assert config.qk_norm and cfg["use_qk_norm"] is True
    ref = reference_module()
    ids = [int(t) for t in np.random.default_rng(1).integers(3, HF["vocab_size"], 44)]
    got, _ = _serve(config, params, ids, 40)
    without = ref.forward_logits(reader, cfg, [ids])[0][39:]
    ref.QK_NORM = True
    want = ref.forward_logits(reader, cfg, [ids])[0][39:]
    assert np.abs(got - want).max() < TOL and np.abs(got - without).max() > 100 * TOL


def test_a_wide_windows_tail_runs_in_blocks(tiny, monkeypatch):
    """Past ``_TAIL_TOKENS`` the out-projection and the feed-forward take a
    block of tokens at a time: the same logits."""
    config, params, reader, cfg, ref, ids = tiny
    monkeypatch.setattr(K, "_TAIL_TOKENS", 128)
    assert K._tail_block(256, 1) == 128 and K._tail_block(64, 2) == 64 and K._tail_block(384, 1) == 128
    lanes = Lanes(config, params, 1, 40, 40, 12)
    got = lanes.join(0, ids, 256, 256)
    want = ref.forward_logits(reader, cfg, [ids])[0][-1]
    assert np.abs(got - want).max() < TOL


# ---------------------------------------------------------------- the share


def test_the_shares_of_a_sparse_layer_add_up_to_the_uncut_reference():
    """Four ranks of four experts each of sixteen: what each gives of the
    routed part, summed, and the shared expert counted once, is the uncut
    layer as the reference computes it."""
    ref = reference_module()
    keys = jax.random.split(jax.random.PRNGKey(3), 8)
    h, inter, e, k = 64, 32, 16, 6
    x = jax.random.normal(keys[0], (2, 9, h), jnp.float32)
    router = jax.random.normal(keys[1], (h, e), jnp.float32) * 0.5
    gate, up = (jax.random.normal(kk, (e, h, inter), jnp.float32) * 0.1 for kk in keys[2:4])
    down = jax.random.normal(keys[4], (e, inter, h), jnp.float32) * 0.1
    sg, su = (jax.random.normal(kk, (h, inter), jnp.float32) * 0.1 for kk in keys[5:7])
    sd = jax.random.normal(keys[7], (inter, h), jnp.float32) * 0.1
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5}
    flat = x.reshape(-1, h)
    with jax.default_matmul_precision("highest"):
        combine = ref._routing(flat, router.T, cfg=cfg, fault=None)
        want = ref._swiglu(flat, sg.T, su.T, sd.T)
        for j in range(e):
            want = want + combine[:, j:j + 1] * ref._swiglu(flat, gate[j].T, up[j].T, down[j].T)
        parts = [
            moe.moe_swiglu(
                x, router, gate[r:r + 4], up[r:r + 4], down[r:r + 4], k,
                scoring="sigmoid", scale=2.5, expert_offset=r,
            ) for r in range(0, e, 4)
        ]
        from cake_tpu.ops.mlp import swiglu
        got = sum(parts) + swiglu(x, sg, su, sd)
    np.testing.assert_allclose(got.reshape(-1, h), want, atol=2e-5)
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)  # every share gives a part


# ------------------------------------------------------------ the allocator


def _pools(lanes=4, table=64, full=200, sliding=None):
    sliding = lanes * (WINDOW // PAGE + 2) if sliding is None else sliding
    return PagePools({
        FULL: PageAllocator(full, PAGE, lanes, table, reserve_pages=1),
        SLIDING: PageAllocator(sliding, PAGE, lanes, table, reserve_pages=0, window=WINDOW),
    })


def _audit(pools, slot, swept=True):
    """The invariants of a pool a kind: every page free or mapped once, a
    kind at a time; never more of the windowed kind a lane than the window
    and two pages; and once the period whose first query sits at ``slot``
    has swept and extended (``swept``), no page of it wholly behind that
    query's window."""
    for a in pools.kinds.values():
        mapped = a.block_tables[a.block_tables >= 0]
        assert len(set(mapped.tolist())) == len(mapped)
        assert len(mapped) + a.pages_free == a.pages_total
        assert sorted([*mapped.tolist(), *a._free]) == list(range(a.pages_total))
    s = pools.kinds[SLIDING]
    assert ((s.block_tables >= 0).sum(axis=1) <= WINDOW // PAGE + 2).all()
    if swept:
        behind = max(0, slot + 1 - WINDOW) // PAGE
        assert not (s.block_tables[:, :behind] >= 0).any()


@pytest.mark.parametrize("seed", range(8))
def test_allocator_properties_over_a_seeded_walk(seed):
    """Joins of any length, decode chunks, ends: the engine's protocol
    (sweep, extend, join at the shared slot, release) against ``_audit``."""
    rng = np.random.default_rng(seed)
    pools, slot, chunk = _pools(), 40, 4
    live: dict[int, int] = {}
    for lane in range(2):
        n = int(rng.integers(1, slot + 1))
        pools.map_range(lane, slot - n, slot)
        live[lane] = slot - n
    _audit(pools, slot)
    for _ in range(90):
        pools.free_behind(slot)
        assert pools.pages_missing(list(live), slot, slot + chunk) <= pools.pages_free
        for lane in live:
            pools.map_range(lane, slot, slot + chunk)
        _audit(pools, slot)
        assert pools.cached_tokens() == sum(slot + chunk - pad for pad in live.values())
        slot += chunk
        for lane in [ln for ln in live if rng.random() < 0.08]:
            pools.release(lane)
            del live[lane]
            assert not pools.lane_mapped(lane)
            assert not any((a.block_tables[lane] >= 0).any() for a in pools.kinds.values())
        free_lanes = [ln for ln in range(4) if ln not in live]
        if free_lanes and rng.random() < 0.3:
            n = int(rng.integers(1, slot + 1))
            if pools.can_admit(n):
                pools.map_range(free_lanes[0], slot - n, slot)
                live[free_lanes[0]] = slot - n
        _audit(pools, slot, swept=False)  # the next period sweeps for this slot
    assert pools.kinds[SLIDING].freed_behind_window > 0
    for lane in list(live):
        pools.release(lane)
    assert all(a.pages_free == a.pages_total for a in pools.kinds.values())


def test_map_range_maps_every_kind_or_none():
    from cake_tpu.models.llama.paged_cache import PageExhausted

    pools = _pools(lanes=2, full=3, sliding=8)
    with pytest.raises(PageExhausted):
        pools.map_range(0, 0, 40)  # five pages of the first kind, three there
    assert all(a.pages_free == a.pages_total for a in pools.kinds.values())
    assert not pools.can_admit(40) and pools.can_admit(8)
    pools = _pools(lanes=2, full=30, sliding=1)
    assert not pools.can_admit(40)  # the windowed kind prices a window and a page: three
    assert pools.pages_missing([0], 30, 40) == 2 + 1  # and is one short of its own two


def test_one_kind_keeps_the_parents_arithmetic():
    """A ``PageAllocator`` without a window maps and counts what the class
    always did: nothing is clipped, nothing swept."""
    a = PageAllocator(20, PAGE, 2, 16)
    a.map_range(0, 3, 100)
    assert a.lane_pages(0) == 13 and a.pages_missing([0, 1], 0, 128) == 16 + 3
    assert a.window is None and a.freed_behind_window == 0


# ------------------------------------------------------- the served engine


def test_the_engine_serves_it_and_counts_by_kind(tiny):
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.runtime.batch_backend import PagedKindsBackend
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    config = dataclasses.replace(tiny[0], vocab_size=512, bos_token_id=256, eos_token_ids=(259, 260))
    params = K.init_params(config, jax.random.PRNGKey(2), jnp.float32, std=0.1)
    eng = BatchEngine(
        config, params, ByteTokenizer(), max_seq_len=512, cache_dtype=jnp.float32,
        serve=ServeConfig(max_batch=3, decode_chunk_size=4, admission_window=0.0,
                          scheduler="continuous", kv_mode="paged", page_size=PAGE,
                          max_pages=120),
    )
    backend = eng.backend
    assert isinstance(backend, PagedKindsBackend) and eng._free_behind is not None
    assert [a.n_pages for a in backend.allocator.kinds.values()] == [120, 3 * (WINDOW // PAGE + 2)]
    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    prompts = [("abc " * 12, 40), ("defg " * 5, 60), ("h" * 70, 30), ("ab " * 20, 50)]
    handles = [eng.submit([Message.user(p)], n, greedy) for p, n in prompts]
    eng.start()
    try:
        served = [[t.id for t in h.tokens()] for h in handles]
    finally:
        eng.stop()
    assert all(served) and eng.stats["joins"] >= 1
    facts = backend.cache_facts()
    assert facts["kind"] == CACHE_KV_KINDS and set(facts["kinds"]) == {FULL, SLIDING}
    assert facts["kinds"][SLIDING]["freed_behind_window"] > 0
    assert facts["kinds"][FULL]["freed_behind_window"] == 0
    per_layer = 2 * 2 * 16 * PAGE * 4
    assert facts["kinds"][FULL]["bytes_per_page"] == 2 * per_layer
    assert facts["kinds"][SLIDING]["bytes_per_page"] == 3 * per_layer
    assert facts["bytes"] == 120 * 2 * per_layer + 12 * 3 * per_layer
    assert all(k["pages_mapped"] == 0 for k in facts["kinds"].values())  # all released
    moe_facts = backend.moe_facts()
    assert moe_facts["dispatches"] > 0 and 0 < moe_facts["held"] < moe_facts["routed"]
    assert (moe_facts["experts_held"], moe_facts["experts_ranked"], moe_facts["top_k"]) == (4, 16, 6)
