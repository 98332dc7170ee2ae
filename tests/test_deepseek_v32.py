"""``model_type: deepseek_v32`` at a tiny size on the CPU: the program
(models/llama/latent_index.py, ops/sparse_index.py, ops/moe.py's group-limited
choice, ops/rope.py's YaRN over the latent's rotary numbers) against the plain
reference (bench/architectures/deepseek_v32.py) on seeded weights, with
``index_topk`` SMALLER than the prompt so that the choice bites."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import architecture
from cake_tpu.io.safetensors_io import load_params
from cake_tpu.models.llama import latent_index as LI
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.config import KindRope, LlamaConfig
from cake_tpu.ops import moe
from cake_tpu.ops import sparse_index as SI
from cake_tpu.ops.rope import yarn_frequencies

from zbench.conftest import REPO

TINY = {
    "architectures": ["DeepseekV32ForCausalLM"], "model_type": "deepseek_v32",
    "hidden_size": 64, "intermediate_size": 128, "vocab_size": 288, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "n_routed_experts_total": 16,
    "first_routed_expert": 4, "n_shared_experts": 1, "moe_intermediate_size": 32,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "max_position_embeddings": 4096, "bos_token_id": 0, "eos_token_id": 1,
    "tie_word_embeddings": False, "attention_bias": False, "initializer_range": 0.3,
    "num_nextn_predict_layers": 1, "moe_layer_freq": 1, "ep_size": 1, "hidden_act": "silu",
}
PAGE, TABLE = 16, 8


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    arch = architecture(REPO, TINY)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_deepseek_v32")
    write_checkpoint(path, TINY, "f32", 7, arch)
    config = LlamaConfig.from_model_dir(path)
    return arch, Reader(path), config, load_params(path, config, jnp.float32)


def test_the_parser_reads_the_index_the_groups_and_the_rope():
    config = LlamaConfig.from_hf_dict(TINY)
    assert config.cache_kind == "latent+index" and config.model_type == "deepseek_v32"
    assert (config.index_n_heads, config.index_head_dim, config.index_topk) == (4, 16, 8)
    assert (config.n_group, config.topk_group, config.router_bias) == (4, 2, True)
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (4, 16, 4)
    assert config.ff_runs == (("dense", 0, 1), ("sparse", 1, 3)) and not config.post_block_norms
    assert config.latent_rope == KindRope(
        theta=10000.0, rotary_dim=8, factor=40.0, original_max_position_embeddings=64)
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config
    pangu = LlamaConfig.from_hf_dict({"model_type": "pangu_ultra_moe", "num_hidden_layers": 2})
    assert pangu.cache_kind == "latent" and pangu.latent_rope is None and pangu.n_group == 1


@pytest.mark.parametrize("key, value, says", [
    ("scoring_func", "softmax", "scoring_func"), ("topk_method", "greedy", "topk_method"),
    ("n_group", 3, "groups must be equal"), ("topk_group", 5, "topk_group"),
    ("rope_scaling", {"type": "linear", "factor": 2}, "rope_scaling type"),
    ("rope_scaling", {**TINY["rope_scaling"], "mscale_all_dim": 0.5}, "mscale"),
    ("first_routed_expert", 14, "must not pass"), ("index_head_dim", 4, "index_head_dim"),
])
def test_the_parser_refuses_what_it_cannot_serve(key, value, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_hf_dict({**TINY, key: value})


@pytest.mark.parametrize("fact", ["prefix_cache", "tp", "speculative_k", "draft_model",
                                  "quantize", "kv_dtype_narrow", "kv_mode_dense"])
def test_what_this_cache_cannot_be_served_with_is_refused(fact):
    config = LlamaConfig.from_hf_dict(TINY)
    with pytest.raises(UnsupportedForCacheKind, match="an index key of 16"):
        refuse_unsupported(config, **{fact: True})
    refuse_unsupported(config, **{fact: False})
    if fact == "kv_dtype_narrow":  # Pangu's pool may be narrower, as it was
        refuse_unsupported(LlamaConfig.from_hf_dict({"model_type": "pangu_ultra_moe"}), **{fact: True})


def test_yarn_frequencies_and_m_against_numbers_written_here():
    """DeepSeek-V3.2-Exp's rope_scaling: factor 40 over 4096 at theta 1e4,
    beta 32 and 1, over 64 rotary numbers. Dims 0-10 turn more than 32 times
    over the original context and keep theta^(-2i/64); dims 23-31 turn less
    than once and are divided by 40; a linear ramp between."""
    config = LlamaConfig.from_hf_dict({
        **TINY, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "index_head_dim": 128,
        "rope_scaling": {**TINY["rope_scaling"], "original_max_position_embeddings": 4096}})
    freqs = yarn_frequencies(config.latent_rope)
    assert freqs.shape == (32,)
    written = {0: 1.0, 9: 0.0749894231557846, 10: 0.05623413249850273, 15: 0.0083345090970397,
               20: 0.0007905694073997438, 23: 3.333803761051968e-05, 24: 2.499999936844688e-05,
               31: 3.3338035336782923e-06}
    for i, want in written.items():
        assert freqs[i] == pytest.approx(want, rel=1e-6), i
    assert freqs[10] == pytest.approx(10000 ** (-20 / 64), rel=1e-6)
    assert freqs[23] == pytest.approx(10000 ** (-46 / 64) / 40, rel=1e-6)
    assert config.latent_mscale == pytest.approx(0.1 * math.log(40) + 1) == pytest.approx(1.3689, abs=1e-4)
    assert config.mla_scale == pytest.approx(192 ** -0.5 * 1.3688879 ** 2, rel=1e-6)
    arch = architecture(REPO, TINY)  # the reference computes the same, from the dict
    hf = {**TINY, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
          "rope_scaling": {**TINY["rope_scaling"], "original_max_position_embeddings": 4096}}
    np.testing.assert_allclose(arch.yarn_inv_freq(hf), freqs, rtol=1e-6)
    assert arch.score_scale(hf) == pytest.approx(config.mla_scale, rel=1e-9)


def _written_out_choice(p, b, n_group, topk_group, top_k):
    """noaux_tc as a loop: the experts chosen for one token."""
    biased = p + b
    size = len(p) // n_group
    group_scores = [sum(sorted(biased[g * size:(g + 1) * size])[-2:]) for g in range(n_group)]
    stay = sorted(range(n_group), key=lambda g: -group_scores[g])[:topk_group]
    open_ = [e for g in stay for e in range(g * size, (g + 1) * size)]
    return sorted(sorted(open_, key=lambda e: -biased[e])[:top_k])


def test_group_limited_routing_against_a_loop_written_out():
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(50, 32)).astype(np.float32)
    bias = rng.normal(size=(32,)).astype(np.float32) * 0.5
    topv, topi = moe.route_topk_select(
        jnp.asarray(logits), 6, True, "sigmoid", 2.5, bias=jnp.asarray(bias), n_group=8, topk_group=3)
    p = 1 / (1 + np.exp(-logits.astype(np.float64)))
    unlimited = 0
    for t in range(50):
        want = _written_out_choice(p[t], bias, 8, 3, 6)
        assert sorted(np.asarray(topi[t]).tolist()) == want
        weights = p[t][np.asarray(topi[t])]  # the scores WITHOUT the bias
        np.testing.assert_allclose(np.asarray(topv[t]), weights / weights.sum() * 2.5, rtol=1e-5)
        unlimited += want != sorted(np.argsort(-(p[t] + bias))[:6].tolist())
    assert unlimited > 25  # the limit and the bias change most tokens' choice
    plain = moe.route_topk_select(jnp.asarray(logits), 6, True, "sigmoid", 2.5)
    flat = moe.route_topk_select(jnp.asarray(logits), 6, True, "sigmoid", 2.5,
                                 bias=jnp.zeros((32,)), n_group=1)
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(flat[1]))


def test_the_16_shares_parts_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    h, inter, e_total, shares = 32, 16, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(ks[0], (2, 9, h))
    router = jax.random.normal(ks[1], (h, e_total))
    bias = jax.random.normal(ks[2], (e_total,)) * 0.3
    gate, up = (jax.random.normal(k, (e_total, h, inter)) * 0.2 for k in ks[3:5])
    down = jax.random.normal(ks[5], (e_total, inter, h)) * 0.2
    kw = dict(top_k=6, scoring="sigmoid", scale=2.5, router_bias=bias, n_group=8, topk_group=4)
    whole = moe.moe_swiglu(x, router, gate, up, down, **kw)
    held = e_total // shares
    parts = sum(
        moe.moe_swiglu(x, router, gate[lo:lo + held], up[lo:lo + held], down[lo:lo + held],
                       expert_offset=lo, **kw)
        for lo in range(0, e_total, held))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=2e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def test_the_choice_breaks_ties_towards_the_smaller_position():
    scores = jnp.asarray([[0.5, 1.0, 0.5, 0.5, -jnp.inf, 0.5, 2.0, 0.5]])
    assert np.asarray(SI.topk_mask(scores, 4)).tolist() == [[True, True, True, False, False, False, True, False]]
    slots, chosen = SI.select_topk(scores, 4)
    assert sorted(np.asarray(slots[0]).tolist()) == [0, 1, 2, 6] and bool(chosen.all())
    short = jnp.asarray([[0.1, -jnp.inf, -jnp.inf, 0.3, -jnp.inf, -jnp.inf]])
    slots, chosen = SI.select_topk(short, 4)  # fewer tokens than the budget: all of them
    assert sorted(np.asarray(slots[0])[np.asarray(chosen[0])].tolist()) == [0, 3]
    assert np.asarray(SI.topk_mask(short, 4)).tolist() == [[True, False, False, True, False, False]]


def _choice_case(name):
    """(scores [b, slots], k, block table or None, page size or None)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    inf = np.inf

    def scattered(b, groups, g, k, quantum=0.0):
        s = rng.standard_normal((b, groups * g)).astype(np.float32)
        return (np.round(s / quantum) * quantum if quantum else s), k, None, g

    if name == "ties-across-the-threshold-and-a-groups-edge":
        # Page of 8, k 5: one score above, then a run of equal scores from
        # slot 5 to slot 18: the threshold's ties straddle two group edges.
        s = np.full((2, 32), -1.0, np.float32)
        s[:, 5:19] = 0.5
        s[0, 30], s[1, 2] = 2.0, 2.0
        return s, 5, None, 8
    if name == "exactly-k-live":
        s = np.full((2, 64), -inf, np.float32)
        s[0, rng.permutation(64)[:12]] = rng.standard_normal(12)
        s[1, 20:32] = 1.0
        return s, 12, None, 16
    if name == "fewer-than-k-live":
        s = np.full((3, 64), -inf, np.float32)
        s[0, [3, 17, 18, 63]] = [0.1, -2.0, 0.1, 5.0]
        s[1, 40:49] = rng.standard_normal(9)
        s[2, 0] = 0.0
        return s, 12, None, 16
    if name == "a-dead-lane-of-one-slot":
        s = rng.standard_normal((3, 96)).astype(np.float32)
        s[1] = -inf
        s[1, 77] = -3.0  # ``starts = ends - 1``: the lane's one slot
        return s, 10, None, 16
    if name == "both-zeros-are-one-score":
        # Four above the zeros, room for three of them: the first three by
        # POSITION, whatever their sign (the sort held -0.0 == +0.0).
        s = np.where(rng.random((2, 48)) < 0.5, 0.0, -0.0).astype(np.float32)
        s[:, [7, 21, 22, 40]] = 1.0
        s[0, :6], s[1, :6] = -0.0, 0.0
        s[0, 6], s[1, 6] = 0.0, -0.0
        return s, 7, None, 16
    if name == "k-not-a-multiple-of-the-group":
        return scattered(3, 12, 16, 37, quantum=0.25)
    if name in ("42-groups", "84-groups", "168-groups"):
        return scattered(2, int(name.split("-")[0]), 8, 100, quantum=0.125)
    if name == "168-pages-of-128-k-2048":
        s, k, _, g = scattered(2, 168, 128, 2048, quantum=1 / 64)
        s[1, 9000:] = -inf
        return s, k, None, g
    if name == "pool-rows-through-a-shuffled-table-with-unmapped-pages":
        b, pages, page = 3, 20, 16
        s = rng.standard_normal((b, pages * page)).astype(np.float32)
        s = np.round(s * 4) / 4
        tables = rng.permutation(b * pages).reshape(b, pages).astype(np.int32)
        live = [pages * page, 13 * page - 5, 2 * page + 1]
        for r, n in enumerate(live):  # the pages behind a row's tokens are -1
            s[r, n:] = -inf
            tables[r, -(-n // page):] = -1
        return s, 40, tables, page
    if name == "a-page-wider-than-a-group-may-be":
        b, pages, page = 2, 3, 320  # groups of 160 inside a page
        s = np.round(rng.standard_normal((b, pages * page)).astype(np.float32) * 2) / 2
        tables = np.asarray([[4, 0, 2], [5, 1, -1]], np.int32)
        s[1, 500:] = -inf
        return s, 70, tables, page
    raise KeyError(name)


_CHOICE_CASES = [
    "ties-across-the-threshold-and-a-groups-edge", "exactly-k-live", "fewer-than-k-live",
    "a-dead-lane-of-one-slot", "both-zeros-are-one-score", "k-not-a-multiple-of-the-group",
    "42-groups", "84-groups", "168-groups", "168-pages-of-128-k-2048",
    "pool-rows-through-a-shuffled-table-with-unmapped-pages",
    "a-page-wider-than-a-group-may-be",
]


def _select(scores, k, table, page_size):
    run = jax.jit(lambda s, t: SI.select_topk(s, k, t, page_size))
    out = run(jnp.asarray(scores), None if table is None else jnp.asarray(table))
    return tuple(np.asarray(a) for a in out)


@pytest.mark.parametrize("case", _CHOICE_CASES)
def test_the_decode_choice_is_a_stable_argsorts_set(case):
    """``select_topk`` of a table wider than ``k`` (the k-th largest by
    counts, the mask compacted by dense products: no sort) against numpy's
    stable argsort of ``-scores``, AS SETS: what the chosen carry, and how
    many a row."""
    scores, k, tables, page = _choice_case(case)
    assert scores.shape[1] > k
    if tables is None:
        # Without a table the group comes from the width alone; the case's
        # group size is forced through a table of pages in order.
        in_order = np.arange(scores.shape[1] // page, dtype=np.int32)[None].repeat(len(scores), 0)
        forms = [(None, None, None), (in_order, page, None)]
    else:
        forms = [(tables, page, np.asarray(SI.pool_rows(jnp.asarray(tables), page)))]
    for table, page_size, carried in forms:
        picked, chosen = _select(scores, k, table, page_size)
        assert picked.shape == chosen.shape == (len(scores), k)
        for r, row in enumerate(scores):
            order = np.argsort(-row, kind="stable")[:k]
            want = order[row[order] > -np.inf]
            want = want if carried is None else carried[r][want]
            got = picked[r][chosen[r]]
            assert len(got) == len(want), (case, r)
            assert sorted(got.tolist()) == sorted(want.tolist()), (case, r)


def _topk_mask_as_it_was(scores, k):
    """``topk_mask`` as PR 43 wrote it, before the decode step's choice
    shared its search (and before the two zeros shared a key)."""
    if scores.shape[-1] <= k:
        return scores > -jnp.inf
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))[..., None]
    above, ties = key > kth, key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room
    return (above | (ties & first)) & (scores > -jnp.inf)


def _window_scores():
    """``test_the_window_kernel_is_its_twin``'s: two rows of 256 queries
    under the causal mask, the second behind pads with a dead tail."""
    pos = jnp.arange(256)
    starts, lengths = jnp.asarray([0, 37]), jnp.asarray([256, 200])
    live = (pos[None, :] >= starts[:, None]) & (pos[None, :] < lengths[:, None])
    admitted = live[:, None, :] & (pos[None, None, :] <= pos[None, :, None])
    return jnp.where(admitted, jax.random.normal(jax.random.PRNGKey(6), (2, 256, 256)), -jnp.inf)


@pytest.mark.parametrize("case, k", [
    ("ties", 4), ("short", 4), ("a-window", 20), ("a-window", 1), ("a-window", 255),
    ("ties-across-the-threshold-and-a-groups-edge", 5), ("k-not-a-multiple-of-the-group", 37),
    ("168-groups", 100),
])
def test_the_windows_masks_are_what_they_were_before_the_search_was_shared(case, k):
    scores = {
        "ties": lambda: jnp.asarray([[0.5, 1.0, 0.5, 0.5, -jnp.inf, 0.5, 2.0, 0.5]]),
        "short": lambda: jnp.asarray([[0.1, -jnp.inf, -jnp.inf, 0.3, -jnp.inf, -jnp.inf]]),
        "a-window": _window_scores,
    }.get(case, lambda: jnp.asarray(_choice_case(case)[0]))()
    got = jax.jit(lambda s: SI.topk_mask(s, k))(scores)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_topk_mask_as_it_was(scores, k)))
    assert bool(got.any())


def test_the_windows_mask_holds_the_two_zeros_equal_too():
    scores, k, _, _ = _choice_case("both-zeros-are-one-score")
    mask = np.asarray(jax.jit(lambda s: SI.topk_mask(s, k))(jnp.asarray(scores)))
    for r, row in enumerate(scores):
        assert np.flatnonzero(mask[r]).tolist() == sorted(np.argsort(-row, kind="stable")[:k].tolist())


def _recording(monkeypatch, name, keep):
    """Wrap ``sparse_index.<name>`` so that what it returns is also kept."""
    inner = getattr(SI, name)

    def wrapped(*args, **kw):
        out = inner(*args, **kw)
        jax.debug.callback(lambda *a: keep.append([np.asarray(x) for x in a]),
                           *(out if isinstance(out, tuple) else (out,)), ordered=True)
        return out

    monkeypatch.setattr(SI, name, wrapped)


@pytest.mark.parametrize("block", [2048, 16], ids=["one-block", "three-blocks"])
def test_prefill_then_decode_through_the_cache_is_the_reference(tiny, monkeypatch, block):
    """A prompt of 30 tokens in a window of 48 slots (its tokens at the
    window's end, as a join's), then ten teacher-forced decode steps through
    both pools: logits AND the chosen sets equal the reference's, with
    ``index_topk`` 8."""
    arch, reader, config, params = tiny
    monkeypatch.setattr(SI, "WINDOW_BLOCK", block)
    seq = [int(t) for t in np.random.default_rng(0).integers(5, 200, size=40)]
    sets = []
    want = arch.forward_logits(reader, TINY, [seq], chosen_out=sets)[0]
    sets = [layer[0] for layer in sets]  # [layers][L, L]
    assert sets[0].sum(-1).tolist() == [min(8, t + 1) for t in range(40)]

    n_prompt, width = 30, 48
    pad = width - n_prompt
    masks, picks = [], []
    _recording(monkeypatch, "topk_mask", masks)
    _recording(monkeypatch, "select_topk", picks)
    cache = LI.init_cache(config, 16, PAGE, jnp.float32)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, pad:] = seq[:n_prompt]
    tables = jnp.arange(TABLE, dtype=jnp.int32)[None, :]
    one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    logits, cache, counts = LI.latent_index_prefill(
        params, jnp.asarray(tokens), cache, one(pad), one(width), tables, config, allow_pallas=False)
    np.testing.assert_allclose(np.asarray(logits)[0], want[n_prompt - 1], atol=2e-4)
    n_blocks = width // LI.window_block(1, width)
    assert len(masks) == 3 * n_blocks - (3 if block == 16 else 0)  # a block of pads alone is passed by
    live_blocks = len(masks) // 3
    for layer in range(3):
        got = np.concatenate([m[0][0] for m in masks[layer * live_blocks:(layer + 1) * live_blocks]])
        got = got[-n_prompt:, pad:]  # the prompt's queries over the prompt's keys
        np.testing.assert_array_equal(got, sets[layer][:n_prompt, :n_prompt])
    sparse = dict(zip(LI.SPARSE_COUNTS, np.asarray(counts)[-4:].tolist()))
    assert sparse["rows"] == 3 * n_prompt and sparse["scanned"] == 3 * n_prompt * (n_prompt + 1) // 2
    assert sparse["chosen"] == 3 * int(sets[0][:n_prompt].sum())

    for t in range(n_prompt, 40):
        x = M.embed_tokens(params, jnp.asarray([[seq[t]]], jnp.int32), config)
        slot = pad + t
        x, cache, _, s = LI.latent_index_blocks_forward(
            params["layers"], x, cache, jnp.asarray([[t]], jnp.int32), config, decode=True,
            pads=one(pad), ends=one(slot + 1), write_pos=jnp.int32(slot), block_tables=tables,
            live=jnp.ones((1, 1), bool), allow_pallas=False)
        got = M.head_forward(params, x, jnp.int32(1), config)
        np.testing.assert_allclose(np.asarray(got)[0], want[t], atol=2e-4)
        assert np.asarray(s).tolist() == [3, 3, 3 * (t + 1), 3 * 8]
        for layer, (slots, chosen) in enumerate(picks[-3:]):
            assert sorted((slots[0][chosen[0]] - pad).tolist()) == np.flatnonzero(sets[layer][t]).tolist()


def test_the_window_kernel_is_its_twin():
    """ops/pallas/masked_prefill.py (interpreted here) against the XLA twin at
    the published head sizes: two rows, the second with pads in front and a
    dead tail, each query's mask its own 20 keys."""
    b, t, n, nope, rope, vd, rank = 2, 256, 4, 128, 64, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    q_nope, q_rope = jax.random.normal(ks[0], (b, t, n, nope)), jax.random.normal(ks[1], (b, t, n, rope))
    ckv, k_rope = jax.random.normal(ks[2], (b, t, rank)), jax.random.normal(ks[3], (b, t, rope))
    w_uk = jax.random.normal(ks[4], (n, rank, nope)) * 0.1
    w_uv = jax.random.normal(ks[5], (n, rank, vd)) * 0.1
    starts, lengths = jnp.asarray([0, 37], jnp.int32), jnp.asarray([256, 200], jnp.int32)
    pos = jnp.arange(t)
    live = (pos[None, :] >= starts[:, None]) & (pos[None, :] < lengths[:, None])
    admitted = live[:, None, :] & (pos[None, None, :] <= pos[None, :, None])
    scores = jnp.where(admitted, jax.random.normal(ks[6], (b, t, t)), -jnp.inf)
    mask = SI.topk_mask(scores, 20).astype(jnp.int8)
    assert SI.window_kernel_supported(t, nope, rope, vd) and not SI.window_kernel_supported(48, 16, 8, 16)
    out = {kernel: np.asarray(SI.window_attention(
        q_nope, q_rope, ckv, k_rope, w_uk, w_uv, mask, scale=0.07, starts=starts,
        lengths=lengths, kernel=kernel)) for kernel in (False, True)}
    rows = np.asarray(live)
    np.testing.assert_allclose(out[True][rows], out[False][rows], atol=2e-5)
    assert np.abs(out[False][rows]).max() > 1.0


# (starts, lengths) of the row under test, in a 16-page table of 128-token
# pages scored 8 pages a group; a plain full row rides beside it.
_SCORE_ROWS = {
    "pads-in-front": (300, 2048),
    "dead-tail": (0, 777),
    "length-on-a-page-boundary": (0, 1024),
    "a-dead-lanes-one-slot": (1299, 1300),
    "an-unmapped-table-entry": (0, 1100),  # the pages behind the row are -1
    "not-whole-groups": (130, 1300),  # pages 1..10: both groups partly live
    "one-token-on-a-groups-first-slot": (1024, 1025),
}


@pytest.mark.parametrize("case", sorted(_SCORE_ROWS))
def test_the_scores_kernel_is_its_twin(case):
    """ops/pallas/index_scores.py (interpreted here) against the XLA twin at
    the published index sizes (64 heads x 128, pages of 128, bf16): equal to
    float32 rounding where a slot is live, ``-inf`` exactly where the twin's
    is."""
    heads, dim, page, table, layers = 64, 128, 128, 16, 2
    start, length = _SCORE_ROWS[case]
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    n_pages = 2 * table + 1
    pool = jax.random.normal(ks[0], (layers, n_pages, page, dim), jnp.bfloat16)
    q_i = jax.random.normal(ks[1], (2, heads, dim), jnp.bfloat16)
    w = jax.random.normal(ks[2], (2, heads), jnp.float32) * (heads * dim) ** -0.5
    tables = 1 + np.random.default_rng(1).permutation(2 * table).reshape(2, table).astype(np.int32)
    if case == "an-unmapped-table-entry":
        tables[0, -(-length // page):] = -1
    starts, lengths = jnp.asarray([start, 0], jnp.int32), jnp.asarray([length, table * page], jnp.int32)
    got, want = (np.asarray(SI.index_scores(
        q_i, w, pool, jnp.asarray(tables), starts, lengths, layer=jnp.int32(1), kernel=kernel,
    )) for kernel in (True, False))
    live = want > -np.inf
    assert live.sum() == length - start + table * page and np.array_equal(got > -np.inf, live)
    assert np.all(np.isneginf(got[~live]))
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    assert np.abs(want[live]).max() > 0.5
    if case == "not-whole-groups":  # the pages a loop turn takes are the sweep's handle, not the result's
        from cake_tpu.ops.pallas.index_scores import paged_index_scores

        by_two = np.asarray(paged_index_scores(
            q_i, w, pool, jnp.asarray(tables), starts, lengths, layer=jnp.int32(1), group=2))
        assert np.array_equal(by_two > -np.inf, live)
        np.testing.assert_allclose(by_two[live], got[live], atol=2e-5)
        with pytest.raises(ValueError, match="does not divide"):
            paged_index_scores(q_i, w, pool, jnp.asarray(tables), starts, lengths,
                               layer=jnp.int32(1), group=5)


def test_which_form_the_decode_steps_scores_take():
    """The predicate on shapes both ways, and ``scores_form`` as the decode
    program and ``GET /stats`` read it: the kernel switch AND the shapes."""
    from cake_tpu.ops.pallas.index_scores import pages_a_group, paged_index_scores_supported

    assert paged_index_scores_supported(128, 128, 64)
    assert not paged_index_scores_supported(16, 128, 64)  # a page of no whole lane tile
    assert not paged_index_scores_supported(128, 16, 64)  # nor a key
    assert not paged_index_scores_supported(128, 128, 4)  # heads of no whole sublane tile
    assert [pages_a_group(n) for n in (168, 42, 16, 7, 13)] == [8, 7, 8, 7, 1]
    published = LlamaConfig.from_hf_dict({**TINY, "index_n_heads": 64, "index_head_dim": 128})
    pallas = dataclasses.replace(published, attention_impl="pallas")
    assert LI.scores_form(pallas, 128, True) == "pallas"
    assert LI.scores_form(pallas, 128, False) == "xla"  # the kernel switch
    assert LI.scores_form(pallas, 16, True) == "xla"  # the page
    assert LI.scores_form(dataclasses.replace(published, attention_impl="xla"), 128, True) == "xla"
    tiny = dataclasses.replace(LlamaConfig.from_hf_dict(TINY), attention_impl="pallas")
    assert LI.scores_form(tiny, 128, True) == "xla"  # 4 heads x 16
    with pytest.raises(ValueError, match="use the XLA twin"):
        SI.index_scores(jnp.zeros((1, 4, 16)), jnp.zeros((1, 4)), jnp.zeros((1, 2, 16, 16)),
                        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                        jnp.ones((1,), jnp.int32), layer=jnp.int32(0), kernel=True)


_KTH_ROWS = {  # rows x slots, k
    "the-cells-table": (16, 168 * 128, 2048),
    "a-capacity-of-42-pages": (16, 42 * 128, 2048),
    "rows-of-no-whole-eight": (3, 512, 100),
    "k-of-one": (8, 256, 1),
}


@pytest.mark.parametrize("case", sorted(_KTH_ROWS))
def test_the_search_kernel_is_its_twin(case):
    """ops/pallas/kth_largest.py (interpreted here) against the loop of 32
    counts: the k-th largest key and the room among its equals, bit for bit,
    over rows with ties at the threshold, a dead tail, a dead lane's one
    slot, both zeros, fewer than ``k`` live; then the whole choice through
    either is one set."""
    b, slots, k = _KTH_ROWS[case]
    rng = np.random.default_rng(b * slots + k)
    s = (np.round(rng.standard_normal((b, slots)) * 16) / 16).astype(np.float32)
    s[0, slots // 3:] = -np.inf
    s[1, :] = -np.inf
    s[1, slots // 2] = -2.5
    s[2] = np.where(rng.random(slots) < 0.5, 0.0, -0.0)
    s[2, :max(k - 3, 0)] = 1.0
    if b > 3:
        s[3, k // 2:] = -np.inf  # fewer than k live
    scores = jnp.asarray(s)
    got = jax.jit(lambda x: SI._kth_largest(x, k, True))(scores)
    want = jax.jit(lambda x: SI._kth_largest(x, k))(scores)
    assert got[0].dtype == want[0].dtype == jnp.uint32 and got[0].shape == want[0].shape == (b, 1)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    picked, chosen = (np.asarray(a) for a in jax.jit(
        lambda x: SI.select_topk(x, k, kernel=True))(scores))
    for r in range(b):
        order = np.argsort(-s[r], kind="stable")[:k]
        assert sorted(picked[r][chosen[r]].tolist()) == sorted(order[s[r][order] > -np.inf].tolist())


def test_which_form_the_choices_search_takes():
    from cake_tpu.ops.pallas.kth_largest import kth_largest_key, kth_largest_supported

    assert kth_largest_supported(128) and kth_largest_supported(21504)
    assert not kth_largest_supported(16)  # a row of no whole lane tiles
    tiny = LlamaConfig.from_hf_dict(TINY)
    pallas = dataclasses.replace(tiny, attention_impl="pallas")
    assert LI.select_form(pallas, 128, True) == "pallas"  # whatever the index's widths
    assert LI.select_form(pallas, 128, False) == "xla"  # the kernel switch
    assert LI.select_form(pallas, 16, True) == "xla"  # the page
    assert LI.select_form(dataclasses.replace(tiny, attention_impl="xla"), 128, True) == "xla"
    with pytest.raises(ValueError, match="use the XLA twin"):
        kth_largest_key(jnp.zeros((8, 48)), k=4)


def test_pangus_cache_keeps_its_pytree_and_this_one_has_two_leaves(tiny):
    from cake_tpu.models.llama import latent as L

    _, _, config, _ = tiny
    pangu = LlamaConfig.from_hf_dict({"model_type": "pangu_ultra_moe", "num_hidden_layers": 2})
    assert [a.shape for a in jax.tree.leaves(L.init_cache(pangu, 4, 128, jnp.bfloat16))] == [(2, 4, 128, 640)]
    cache = LI.init_cache(config, 4, PAGE, jnp.bfloat16)
    assert cache.latent.shape == (3, 4, PAGE, 128) and cache.index.shape == (3, 4, PAGE, 16)
    per = LI.cache_bytes_per_token(config, jnp.bfloat16)
    assert per == {"latent": 3 * 2 * 128, "latent_needed": 3 * 2 * 40, "index": 3 * 2 * 16}
    full = dataclasses.replace(
        LlamaConfig.from_hf_dict({**TINY, "qk_rope_head_dim": 64, "kv_lora_rank": 512,
                                  "index_head_dim": 128, "num_hidden_layers": 5}))
    per = LI.cache_bytes_per_token(full, jnp.bfloat16)
    assert per["latent"] + per["index"] == 7680  # 1,536 B a token a layer


def test_the_engine_serves_it_and_counts_what_it_scanned_and_chose(tiny):
    """Through ``BatchEngine`` and the paged leaf the config picks: a late
    request joins a running segment, both lanes longer than ``index_topk``;
    the joiner's stream is the request's alone, and ``engine.sparse`` counts
    what the decode steps scanned and chose."""
    import time

    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.runtime.batch_backend import PagedLatentIndexBackend
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    _, _, config, params = tiny
    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    config = dataclasses.replace(config, bos_token_id=256, eos_token_ids=(259, 260))

    def engine():
        eng = BatchEngine(
            config, params, ByteTokenizer(), max_seq_len=256, cache_dtype=jnp.float32,
            serve=ServeConfig(max_batch=2, decode_chunk_size=4, admission_window=0.05,
                              scheduler="continuous", kv_mode="paged", page_size=PAGE))
        eng.start()
        return eng

    collect = lambda handle: [tok.id for tok in handle.tokens()]  # noqa: E731
    eng = engine()
    assert isinstance(eng.backend, PagedLatentIndexBackend)
    alone = collect(eng.submit([Message.user("late joiner")], 12, greedy))
    eng.stop()
    eng = engine()
    first = eng.submit([Message.user("the first, long-running stream")], 40, greedy)
    deadline = time.time() + 60
    while first.completion_tokens < 2 and time.time() < deadline:
        time.sleep(0.005)
    joined = collect(eng.submit([Message.user("late joiner")], 12, greedy))
    collect(first)
    sparse, cache, joins = eng.backend.sparse_facts(), eng.backend.cache_facts(), eng.stats["joins"]
    eng.stop()
    assert joined == alone and joins >= 1
    assert sparse["index_topk"] == 8 and sparse["dispatches"] > 0 and sparse["dispatches"] % 3 == 0
    assert sparse["scores_form"] == "xla"  # the CPU, and 4 index heads x 16
    assert sparse["select_form"] == "xla"  # the CPU, and a page of 16
    assert 0 < sparse["chosen"] < sparse["scanned"]  # both lanes are longer than the budget
    assert sparse["chosen"] <= 8 * sparse["rows"] and sparse["rows"] <= 2 * sparse["dispatches"]
    assert set(sparse["traced"]) == {"index_topk", "dispatches", "rows", "scanned", "chosen"}
    assert sparse["traced"]["dispatches"] == 0  # no profiler was open
    assert sparse["join"]["dispatches"] > 0 and sparse["join"]["chosen"] < sparse["join"]["scanned"]
    assert cache["kind"] == "latent+index"
    assert cache["bytes_per_token_by"] == {"latent": 3 * 4 * 128, "index": 3 * 4 * 16}
    assert cache["bytes_per_token"] == 3 * 4 * (128 + 16)
