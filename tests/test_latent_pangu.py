"""``model_type: pangu_ultra_moe``: latent attention over the latent page
pool, sparse layers that hold a share of their experts, against the plain
float32 reference of ``bench/architectures/pangu_ultra_moe.py``.

All at a small size on the CPU (hidden 128, 8 heads, ranks 48 / 32, 16
experts of which 4 a token, a share of 2). The widths served on the chip
are ``chip_smoke.py``'s and the benchmark's.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.checkpoint import Reader
from bench.manifest import architecture
from cake_tpu.io.safetensors_io import load_params, save_tiny_checkpoint
from cake_tpu.models.llama import latent as L
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import CACHE_LATENT, DENSE, SPARSE, LlamaConfig
from cake_tpu.models.llama.paged_cache import PageAllocator
from cake_tpu.ops import moe

REPO = Path(__file__).resolve().parents[1]

TINY = {
    "model_type": "pangu_ultra_moe", "architectures": ["PanguUltraMoEForCausalLM"],
    "hidden_size": 128, "intermediate_size": 256, "moe_intermediate_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 8, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "rms_norm_eps": 1e-5, "rope_theta": 25600000, "vocab_size": 512,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu", "num_nextn_predict_layers": 1,
    "bos_token_id": 0, "eos_token_id": 1, "initializer_range": 0.1,
}
SHARE = {**TINY, "n_routed_experts": 2, "n_routed_experts_total": 16, "first_routed_expert": 6}
PAGE = 16


def build(tmp_path, hf: dict, seed: int = 0):
    """(config, params as loaded from a checkpoint, reader, arch)."""
    config = LlamaConfig.from_hf_dict(hf)
    params = L.init_params(config, jax.random.PRNGKey(seed), jnp.float32, std=0.1)
    save_tiny_checkpoint(tmp_path, params, config)
    loaded = load_params(tmp_path, config, jnp.float32)
    return config, loaded, Reader(tmp_path), architecture(REPO, hf)


@pytest.fixture(scope="module")
def share(tmp_path_factory):
    return build(tmp_path_factory.mktemp("pangu_share"), SHARE)


def test_config_reads_the_share_and_round_trips():
    config = LlamaConfig.from_hf_dict(SHARE)
    assert config.cache_kind == CACHE_LATENT and config.post_block_norms
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (2, 16, 6)
    assert config.ff_kinds == (DENSE, SPARSE, SPARSE)
    assert config.ff_runs == ((DENSE, 0, 1), (SPARSE, 1, 3))
    assert config.latent_width == 128 and config.head_dim == 24
    assert config.moe_scoring == "sigmoid" and config.routed_scaling_factor == 2.5
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config
    whole = LlamaConfig.from_hf_dict(TINY)
    assert (whole.num_local_experts, whole.n_router_experts, whole.expert_offset) == (16, 16, 0)
    with pytest.raises(ValueError, match="must not pass"):
        LlamaConfig.from_hf_dict({**SHARE, "first_routed_expert": 15})
    with pytest.raises(ValueError, match="group-limited"):
        LlamaConfig.from_hf_dict({**TINY, "n_group": 8})


def test_checkpoint_round_trip_keeps_every_tensor(share):
    config, loaded, reader, arch = share
    table = {**arch.top_tensors(SHARE)}
    for i in range(config.num_hidden_layers):
        table.update(arch.layer_tensors(SHARE, i))
    for name, (shape, _) in table.items():
        assert reader(name).shape == tuple(shape), name
    assert "model.layers.1.mlp.experts.6.up_proj.weight" in table
    assert "model.layers.1.mlp.experts.5.up_proj.weight" not in table
    assert [sorted(run) for run in loaded["layers"]] == [
        sorted(L.run_shapes(config, kind)) for kind, _, _ in config.ff_runs]
    assert loaded["layers"][1]["w_gate"].shape == (2, 2, 128, 64)
    assert loaded["layers"][1]["router"].shape == (2, 128, 16)


def serve(config, params, prompts, n_new, forced):
    """Prefill the rows (left-padded to a shared slot, as the engine lays
    them out), then ``n_new`` paged decode steps, teacher-forced with
    ``forced`` [rows, n_new]. Returns float32 logits [rows, n_new + 1, vocab]:
    the last prompt position's and each decode step's."""
    b = len(prompts)
    slot = max(len(p) for p in prompts)
    pages = -(-(slot + n_new + 1) // PAGE)
    alloc = PageAllocator(b * pages + 2, PAGE, batch=b, max_pages_per_seq=pages)
    pads = np.asarray([slot - len(p) for p in prompts], np.int32)
    tokens = np.zeros((b, slot), np.int32)
    for r, p in enumerate(prompts):
        tokens[r, pads[r]:] = p
        alloc.map_range(r, int(pads[r]), slot + n_new + 1)
    cache = L.init_cache(config, alloc.pages_total, PAGE, jnp.float32)
    tables = jnp.asarray(alloc.block_tables)
    ends = jnp.full((b,), slot, jnp.int32)
    out = []
    for r in range(b):  # logits come for a program's first row: one row each
        lg, cache, _ = L.latent_prefill(
            params, jnp.asarray(tokens[r:r + 1]), cache, jnp.asarray(pads[r:r + 1]),
            ends[:1], tables[r:r + 1], config)
        out.append([np.asarray(lg[0])])
    pads_j = jnp.asarray(pads)
    for j in range(n_new):
        tok = jnp.asarray(forced[:, j], jnp.int32)[:, None]
        s = jnp.int32(slot + j)
        x = M.embed_tokens(params, tok, config)
        x, cache, counts = L.latent_blocks_forward(
            params["layers"], x, cache, (s - pads_j)[:, None], config, decode=True,
            pads=pads_j, ends=jnp.full((b,), slot + j + 1, jnp.int32), write_pos=s,
            block_tables=tables, live=jnp.ones((b, 1), bool))
        lg = M.head_forward(params, x, jnp.int32(1), config)
        for r in range(b):
            out[r].append(np.asarray(lg[r]))
    return np.asarray(out, np.float32), counts


@pytest.mark.parametrize("hf", [SHARE, TINY], ids=["share", "whole"])
def test_prefill_then_paged_decode_gives_the_references_logits(tmp_path, hf):
    config, params, reader, arch = build(tmp_path, hf, seed=3)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(8, 512, n)) for n in (21, 9, 33)]
    n_new = 6
    forced = rng.integers(8, 512, (3, n_new))
    got, counts = serve(config, params, prompts, n_new, forced)
    want = arch.forward_logits(
        reader, hf, [p + list(f) for p, f in zip(prompts, forced)],
        [len(p) - 1 for p in prompts])
    for r in range(3):
        # position len - 1 .. len - 1 + n_new: the prefill's, then every step's
        np.testing.assert_allclose(got[r], want[r], rtol=2e-4, atol=2e-4)
    held = config.num_local_experts
    assert int(counts[0]) == 2 and int(counts[1]) == 3 * 4 * 2  # 2 sparse layers, 3 rows x top-4
    assert 0 <= int(counts[2]) <= int(counts[1]) and int(counts[3]) <= 2 * held
    if held == config.n_router_experts:
        assert int(counts[2]) == int(counts[1])  # the whole model holds every assignment


# ------------------------------------------- absorbed against expanded


def test_absorbed_decode_equals_expanded_attention_over_the_same_latents():
    """One layer's attention for the LAST position of a sequence, computed
    both ways from the same weights: expanded over the window's own K and V
    (the prefill's form) and absorbed over the latents in the pool (the
    decode's form, through the kernel's XLA twin and through the kernel
    itself in interpret mode at tile-sized widths)."""
    from cake_tpu.models.llama.paged_cache import latent_write_pool
    from cake_tpu.ops.attention import mla_prefill_attention
    from cake_tpu.ops.pallas.latent_attention import (
        latent_decode_attention, latent_decode_attention_xla)
    from cake_tpu.ops.rope import rope_table

    config = LlamaConfig.from_hf_dict({
        **TINY, "kv_lora_rank": 128, "qk_rope_head_dim": 64, "qk_nope_head_dim": 32,
        "v_head_dim": 32, "num_hidden_layers": 1, "first_k_dense_replace": 1})
    assert config.latent_width == 256
    params = L.init_params(config, jax.random.PRNGKey(1), jnp.float32, std=0.1)
    lp = jax.tree.map(lambda a: a[0], params["layers"][0])
    b, t, page = 2, 150, 128
    x = jax.random.normal(jax.random.PRNGKey(2), (b, t, config.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    cos, sin = rope_table(config.qk_rope_head_dim, 256, config.rope_theta)
    q_nope, q_rope, latent = L.mla_project(lp, x, cos, sin, pos, config)
    rank, scale = config.kv_lora_rank, (32 + 64) ** -0.5
    k_nope = jnp.einsum("btc,hcd->bthd", latent[..., :rank], lp["w_uk"])
    v = jnp.einsum("btc,hcd->bthd", latent[..., :rank], lp["w_uv"])
    starts, lengths = jnp.asarray([0, 7]), jnp.asarray([t, t])
    live = (pos >= starts[:, None]) & (pos < lengths[:, None])
    expanded = mla_prefill_attention(
        q_nope, q_rope, k_nope, latent[..., rank:rank + 64], v, live, scale=scale,
        starts=starts, lengths=lengths)[:, -1]  # [b, heads, v_dim]
    tables = jnp.asarray([[2, 0], [1, 3]], jnp.int32)
    pool = latent_write_pool(
        jnp.zeros((1, 4, page, 256), jnp.float32), jnp.int32(0), latent, jnp.int32(0), tables)
    q_abs = jnp.einsum("bhd,hcd->bhc", q_nope[:, -1], lp["w_uk"])
    q_full = jnp.concatenate(
        [q_abs, q_rope[:, -1], jnp.zeros((b, 8, 256 - rank - 64))], axis=-1)
    for attend in (latent_decode_attention_xla, latent_decode_attention):
        c = attend(q_full, pool, lengths, tables, starts, layer=jnp.int32(0),
                   rank=rank, scale=scale)
        absorbed = jnp.einsum("bhc,hcd->bhd", c, lp["w_uv"])
        np.testing.assert_allclose(absorbed, expanded, rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ the expert layer


def _moe_layer(seed, n_tokens, e_total=16, top_k=4, h=32, inter=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)
    return (f(1, n_tokens, h), f(h, e_total), f(e_total, h, inter), f(e_total, h, inter),
            f(e_total, inter, h))


def _share(weights, lo, hi):
    return tuple(w[lo:hi] for w in weights)


KW = dict(top_k=4, scoring="sigmoid", scale=2.5, norm_topk=True)


@pytest.mark.parametrize(
    "n_tokens,dispatch", [(5, "grouped"), (64, "grouped"), (5, "dense"), (5, "auto"), (64, "auto")],
    ids=["grouped-5", "grouped-64", "dense-combine", "by-shape-5", "by-shape-64"])
def test_the_shares_add_up_to_the_uncut_layer(n_tokens, dispatch):
    """Eight ranks hold two experts each of sixteen: the parts of a sparse
    layer's result that the shares give, summed, are the uncut layer's, on
    the grouped path, on the dense combine and on whichever of the two the
    dispatch's shape takes."""
    x, router, *experts = _moe_layer(0, n_tokens)
    assert n_tokens >= moe.GROUPED_MIN_TOKENS == 1
    kw = dict(KW, dispatch=dispatch)
    whole = moe.moe_swiglu(x, router, *experts, **kw)
    parts = [
        moe.moe_swiglu(x, router, *_share(experts, lo, lo + 2), expert_offset=lo, **kw)
        for lo in range(0, 16, 2)
    ]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0 and not np.allclose(parts[0], whole, atol=1e-3)


def test_the_layers_shares_with_the_shared_expert_once_equal_the_references(tmp_path):
    """The model-level form of the same: a sparse layer's feed-forward as
    ``block_finish`` computes it for each of eight shares, the routed parts
    summed and the shared expert counted once, against the plain
    reference's uncut layer (16 experts held of 16)."""
    config, params, reader, arch = build(tmp_path, TINY, seed=5)
    lp = jax.tree.map(lambda a: a[0], params["layers"][1])  # layer 1, sparse
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 40, 128), jnp.float32)
    attn = jnp.zeros((1, 40, 8, 16), jnp.float32)
    no_norm = {**lp, "ln_post_mlp": jnp.ones_like(lp["ln_post_mlp"])}
    uncut = M.block_finish(lp, x, attn, config)
    # the routed parts alone: a tree without the shared expert, post-norm off
    routed_only = {k: v for k, v in no_norm.items() if not k.startswith("sh_")}
    del routed_only["ln_post_mlp"]
    parts = []
    for lo in range(0, 16, 2):
        share_cfg = dataclasses.replace(
            config, num_local_experts=2, router_experts=16, expert_offset=lo)
        tree = {**routed_only, **{k: routed_only[k][lo:lo + 2] for k in ("w_gate", "w_up", "w_down")}}
        parts.append(M.block_finish(tree, x, attn, share_cfg) - x)
    shared_tree = {**routed_only, **{k: lp[k] for k in lp if k.startswith("sh_")}}
    with_shared = M.block_finish(
        {**shared_tree, **{k: routed_only[k][:0 + 2] for k in ("w_gate", "w_up", "w_down")}},
        x, attn, dataclasses.replace(config, num_local_experts=2, router_experts=16))
    shared_part = with_shared - x - parts[0]  # what every rank computes alike, once
    from cake_tpu.ops.norm import rms_norm
    total = x + rms_norm(sum(parts) + shared_part, lp["ln_post_mlp"], config.rms_norm_eps)
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)
    # and the uncut layer is the reference's: through the whole model above
    # (test_prefill_then_paged_decode_gives_the_references_logits[whole])


def test_the_grouped_path_drops_nothing_at_a_skewed_routing():
    """Every token routed to the same four experts, three of them held:
    the grouped path's result is the dense combine's (which cannot drop),
    where a capacity bucket of 2 x the mean would have dropped most."""
    x, router, *experts = _moe_layer(1, 96)
    router = router.at[:, :4].add(50.0 * jnp.sign(x[0].mean(0))[:, None])  # skew
    skew = x + 3.0 * jnp.sign(x[0].mean(0))
    held = _share(experts, 1, 9)  # experts 1..8: three of the four favourites
    grouped = moe.moe_swiglu(skew, router, *held, expert_offset=1, **KW)
    dense = moe.moe_swiglu(skew, router, *held, expert_offset=1, dispatch="dense", **KW)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)
    _, counts = moe.moe_swiglu(skew, router, *held, expert_offset=1, with_counts=True, **KW)
    routed, to_held, touched, max_load = (int(c) for c in counts)
    assert routed == 96 * 4 and max_load >= 90  # one held expert takes nearly every token
    assert to_held >= 3 * 90 and touched <= 8


@pytest.mark.parametrize("n_tokens", [1, 2, 5, 8, 33])
def test_the_grouped_path_serves_any_number_of_tokens(n_tokens, monkeypatch):
    """Rows are filled to whole tiles with assignments that belong to no
    expert, so one token or 33 take the same path as 64 and give the dense
    combine's result; with the row budget cut to one tile and overrun, the
    all-rows side of the ``cond`` gives it too."""
    x, router, *experts = _moe_layer(3, n_tokens)
    x = x.reshape(n_tokens, 1, -1)
    held = _share(experts, 2, 6)
    dense = moe.moe_swiglu(x, router, *held, expert_offset=2, dispatch="dense", **KW)
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 0)
    grouped = moe.moe_swiglu(x, router, *held, expert_offset=2, **KW)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(moe, "_TILE", 8)
    monkeypatch.setattr(moe, "_row_budget", lambda nk, e_local, n_ranked: 8)
    tight = moe.moe_swiglu(x, router, *held, expert_offset=2, **KW)
    np.testing.assert_allclose(tight, dense, rtol=1e-5, atol=1e-5)


def test_dead_lanes_take_no_experts_rows_and_are_not_counted():
    x, router, *experts = _moe_layer(2, 64)
    x = x.reshape(64, 1, -1)  # 64 lanes, one token each
    valid = jnp.arange(64) % 2 == 0
    out, counts = moe.moe_swiglu(
        x, router, *_share(experts, 4, 8), expert_offset=4, valid=valid[:, None],
        with_counts=True, **KW)
    alone, counts_alone = moe.moe_swiglu(
        x[::2], router, *_share(experts, 4, 8), expert_offset=4, with_counts=True, **KW)
    np.testing.assert_allclose(out[::2], alone, rtol=1e-6, atol=1e-6)
    assert not np.asarray(out[1::2]).any()
    np.testing.assert_array_equal(counts, counts_alone)
    assert int(counts[0]) == 32 * 4


def test_routing_is_the_published_sigmoid_rule():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0, -2.0, 0.0]])
    v, i = moe.route_topk_select(logits, 2, True, "sigmoid", 2.5)
    s = 1 / (1 + np.exp(-np.asarray([3.0, 2.0])))
    assert i.tolist() == [[3, 0]]
    np.testing.assert_allclose(v[0], s / s.sum() * 2.5, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_topk_select(logits, 2, True, "tanh")


# ------------------------------------------------ through the engine's loop

from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported  # noqa: E402
from cake_tpu.models.llama.chat import Message  # noqa: E402
from cake_tpu.models.llama.generator import SamplingConfig  # noqa: E402
from cake_tpu.models.llama.tokenizer import ByteTokenizer  # noqa: E402
from cake_tpu.runtime.batch_backend import paged_backend  # noqa: E402
from cake_tpu.runtime.serving import BatchEngine, ServeConfig  # noqa: E402
from cake_tpu.runtime.shapes import ProgramShapes  # noqa: E402

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)


def engine(config, params, **serve_kw):
    serve_kw = {
        "max_batch": 4, "decode_chunk_size": 4, "admission_window": 0.05,
        "scheduler": "continuous", "kv_mode": "paged", "page_size": PAGE, **serve_kw,
    }
    config = dataclasses.replace(config, bos_token_id=256, eos_token_ids=(259, 260))
    eng = BatchEngine(config, params, ByteTokenizer(), max_seq_len=256,
                      cache_dtype=jnp.float32, serve=ServeConfig(**serve_kw))
    eng.start()
    return eng


def collect(handle):
    return [tok.id for tok in handle.tokens()]


def test_the_backend_and_the_shapes_are_picked_from_the_config(share):
    config, params, *_ = share
    be = paged_backend(config, params, max_seq_len=128, cache_dtype=jnp.float32,
                       page_size=PAGE, max_pages=48, allow_pallas=False)
    assert be.cache_kind == CACHE_LATENT
    assert not hasattr(be, "suffix_prefill") and not hasattr(be, "verify_greedy")
    assert be.shapes.widths and be.shapes.capacities and be.shapes.prefill_tokens == 4096
    assert be.shapes == ProgramShapes.for_model(config, PAGE, 8)
    facts = be.cache_facts()
    assert facts["kind"] == "latent" and facts["pages"] == 48
    # 3 layers x float32 x (32 + 8) needed, x 128 stored (whole lane tiles)
    assert (facts["bytes_per_token_needed"], facts["bytes_per_token"]) == (480, 1536)
    assert facts["bytes"] == 1536 * PAGE * 48
    assert facts["pool_write"] == "xla"  # latent_write_pool: one row a token
    cache = be.init_kv(2)
    assert cache.latent.shape == (3, 48, PAGE, 128)


def test_engine_join_and_lane_reuse_equal_the_request_alone(share):
    """Through serving.py: a late request joins a running segment and a
    third takes a lane another left: each stream equals the same request
    served alone; the expert account is read with the chunks' tokens."""
    config, params, *_ = share
    texts = ["the first, long-running stream of this test", "late joiner",
             "a third request that takes over a lane somebody left"]
    alone = []
    for text in texts:
        eng = engine(config, params)
        alone.append(collect(eng.submit([Message.user(text)], 12, GREEDY)))
        eng.stop()
    eng = engine(config, params, max_batch=2)
    h0 = eng.submit([Message.user(texts[0])], 40, GREEDY)
    import time
    deadline = time.time() + 60
    while h0.completion_tokens < 2 and time.time() < deadline:
        time.sleep(0.005)
    got1 = collect(eng.submit([Message.user(texts[1])], 12, GREEDY))
    got2 = collect(eng.submit([Message.user(texts[2])], 12, GREEDY))
    got0 = collect(h0)
    assert eng.stats["joins"] >= 2
    m = eng.backend.moe_facts()
    period = eng.periods.snapshot()["period"]
    eng.stop()
    assert got0[:12] == alone[0] and got1 == alone[1] and got2 == alone[2]
    assert (m["experts_held"], m["experts_ranked"], m["first_held"], m["top_k"]) == (2, 16, 6, 4)
    assert m["dispatches"] > 0 and m["dispatches"] % 2 == 0  # steps x 2 sparse layers
    assert m["routed"] >= m["dispatches"] * 4 and 0 <= m["held"] <= m["routed"]
    assert m["touched"] <= m["dispatches"] * 2 and m["max_load"] <= 2
    assert m["join"]["joins"] >= 2 and m["join"]["routed"] > 0
    assert period["cached_tokens"] > period["count"] > 0
    assert period["ahead"] >= 0.5 * period["count"]  # the look-ahead holds


def test_spill_and_restore_is_bit_identical_to_no_spill(share):
    config, params, *_ = share
    texts = ["alpha prompt padded out to be long " * 2,
             "row two also made quite long here " * 2]

    def run(max_pages):
        eng = engine(config, params, max_pages=max_pages)
        handles = [eng.submit([Message.user(t)], 48, GREEDY) for t in texts]
        out = [collect(h) for h in handles]
        stats = dict(eng.stats)
        assert eng.quiesce()
        eng.stop()
        return out, stats

    want, big = run(64)
    got, small = run(20)
    assert big["preemptions"] == 0
    assert small["preemptions"] >= 1 and small["restores"] >= 1
    assert got == want


# ------------------------------------------------------------ the refusals

SERVE = ["--api", "127.0.0.1:1", "--api-batch", "4", "--kv-mode", "paged",
         "--prefix-cache", "off", "--cpu"]
REFUSED = {
    "--prefix-cache on": SERVE[:7] + ["on", "--cpu"],
    "--speculative-k": SERVE + ["--speculative-k", "4"],
    "--draft-model": SERVE + ["--speculative-k", "4", "--draft-model", "/nowhere"],
    "--tp": SERVE + ["--tp", "2"],
    "--sp": SERVE + ["--sp", "2"],
    "--topology": SERVE + ["--backend", "mesh"],
    "--kv-mode dense": SERVE[:4] + ["--kv-mode", "dense", "--prefix-cache", "off", "--cpu"],
    "the single-stream generator": ["--prompt", "hi", "--cpu"],
    "--quantize": SERVE + ["--quantize", "int8"],
}


@pytest.fixture(scope="module")
def share_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("pangu_cli")
    build(path, SHARE)
    return path


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_each_refused_feature_exits_with_the_one_message(share_path, feature, capsys):
    from cake_tpu.cli import main

    assert main(["--model", str(share_path), *REFUSED[feature]]) == 2
    err = capsys.readouterr().err
    assert feature in err
    assert "is not supported for model_type 'pangu_ultra_moe'" in err
    assert "one latent of 32 + 8 numbers a token in a latent page pool" in err
    assert "--kv-mode paged --prefix-cache off" in err


def test_refusals_outside_the_cli(share, share_path, tmp_path):
    config, params, *_ = share
    from cake_tpu.io.splitter import split_model
    from cake_tpu.models.llama.generator import LocalForwardStep

    (tmp_path / "topology.yml").write_text(
        "w0:\n  host: 127.0.0.1:1\n  layers:\n    - model.layers.0-1\n")
    with pytest.raises(UnsupportedForCacheKind, match="cake-split-model"):
        split_model(share_path, tmp_path / "topology.yml", tmp_path / "out")
    with pytest.raises(UnsupportedForCacheKind, match="layer range"):
        load_params(share_path, config, jnp.float32, layer_range=(0, 2))
    step = LocalForwardStep(config, params, max_seq_len=64, cache_dtype=jnp.float32)
    with pytest.raises(UnsupportedForCacheKind, match="single-stream"):
        step(np.zeros((1, 4), np.int32), 0, 4)
    with pytest.raises(UnsupportedForCacheKind, match="--prefix-cache on"):
        BatchEngine(config, params, ByteTokenizer(), max_seq_len=64, cache_dtype=jnp.float32,
                    serve=ServeConfig(max_batch=2, kv_mode="paged", prefix_cache=True))
    refuse_unsupported(LlamaConfig.tiny(), tp=True)  # plain K and V: nothing


def test_the_template_is_the_benchmarks():
    from cake_tpu.models.llama.chat import encode_dialog

    arch = architecture(REPO, SHARE)
    assert encode_dialog([Message.user("w9 w10")], "pangu_ultra_moe") == arch.chat_text("w9 w10")
    assert arch.chat_ids(SHARE, [9, 10]) == [0, 2, 5, 9, 10, 3, 2, 6]
