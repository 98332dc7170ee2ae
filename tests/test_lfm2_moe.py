"""``model_type: lfm2_moe`` on the served path, at a tiny size on the CPU.

The parser on the catalog's row; a tiny LFM2 (hidden 64, the published
period ``conv conv attention conv`` twice, two dense layers then 8 experts of
which 2 a token behind a selection bias, heads of 64 so that two KV heads
share a row of the pool as at the published widths, seeded float32 weights)
through the
served programs of the ``kv+state`` record (an epoch's prefill with dead
lanes, decode chunks through the cache, a join) against the plain reference
of ``bench/architectures/lfm2_moe.py`` (a full forward pass, no cache); the
routing rule; the account; what is refused.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.checkpoint import Reader
from bench.manifest import architecture
from cake_tpu.io.safetensors_io import load_params, save_tiny_checkpoint
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops import moe
from cake_tpu.runtime.batch_backend import paged_backend

from test_hybrid_jamba import GREEDY, collect, decode, engine, lay_out, prompts

REPO = Path(__file__).resolve().parents[1]
PERIOD = ["conv", "conv", "full_attention", "conv"]
HF = dict(
    model_type="lfm2_moe", hidden_size=64, intermediate_size=96, vocab_size=512,
    num_hidden_layers=8, layer_types=PERIOD * 2, conv_L_cache=3, conv_bias=False,
    num_attention_heads=4, num_key_value_heads=2, head_dim=64, norm_eps=1e-5, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, use_expert_bias=True,
    norm_topk_prob=True, routed_scaling_factor=1, rope_theta=1000000,
    max_position_embeddings=256, bos_token_id=1, eos_token_id=7, pad_token_id=0,
)
PAGE = 16
# The catalog row's ``config`` (LFM2-8B-A1B), key for key: the test machine
# may not have the guide.
ROW = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": [*PERIOD * 4, "conv", "conv", "full_attention", "conv", "conv",
                    "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}


# ------------------------------------------------------------- the parser


def test_the_parser_on_the_catalogs_row():
    config = LlamaConfig.from_hf_dict(ROW)
    assert config.layers_of("attention") == (2, 6, 10, 14, 18, 21)
    assert len(config.layers_of("state")) == 18 and config.cache_kind == "kv+state"
    assert config.ff_kinds == ("dense",) * 2 + ("sparse",) * 22
    assert config.state_mixer == "short_conv" and config.state_shape is None
    assert config.conv_window == (2, 2048) and config.head_dim == 64
    assert config.state_bytes_per_lane == 18 * 2 * 2048 * 2
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (32, 32, 0)
    assert (config.moe_scoring, config.router_bias, config.n_group) == ("sigmoid", True, 1)
    assert config.qk_norm and config.use_rope and config.rope_theta == 1e6
    assert config.tie_word_embeddings and config.rms_norm_eps == 1e-5
    assert not config.shared_expert_intermediate_size
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config
    assert config.to_hf_dict()["layer_types"] == ROW["layer_types"]
    assert config.dialog_template == "lfm2_moe"


def test_the_cut_to_sixteen_layers_walks_nine_runs():
    """The cell's cut: the row's first 16 layers (four whole periods)."""
    config = LlamaConfig.from_hf_dict(
        {**ROW, "num_hidden_layers": 16, "layer_types": ROW["layer_types"][:16]})
    assert config.layer_runs == (
        ("state", 0, 2), ("attention", 0, 1), ("state", 2, 5), ("attention", 1, 2),
        ("state", 5, 8), ("attention", 2, 3), ("state", 8, 11), ("attention", 3, 4),
        ("state", 11, 12))
    assert config.run_ff_kinds == ("dense",) + ("sparse",) * 8
    assert config.state_bytes_per_lane == 98_304
    sparse = H.run_shapes(config, "state", "sparse")
    assert sparse["in_proj"] == (2048, 6144) and sparse["conv_w"] == (3, 2048)
    assert sparse["w_gate"] == (32, 2048, 1792) and sparse["router_bias"] == (32,)
    attention = H.run_shapes(config, "attention", "sparse")
    assert attention["q_norm"] == attention["k_norm"] == (64,)
    assert H.run_shapes(config, "state")["w_gate"] == (2048, 7168)
    assert H.window_form(config, True) == H.step_form(config, True) == "xla"
    # the closed set at the cell's geometry: six joins, three decode chunks
    # and their three tails; an epoch's rows go through the join's programs
    # one at a time, and every epoch is as wide as the batch
    from cake_tpu.runtime.shapes import ProgramShapes

    shapes = ProgramShapes.for_model(config, 128, 32)
    assert shapes.widths == (256, 512, 1024, 2048, 3072, 4096)
    assert shapes.capacities == (1024, 2048, 4096) and shapes.one_row_prefill_is_join
    assert shapes.prefill_group(64, 256) == 1
    assert [p for p, _, _ in shapes.programs(64)] == (
        ["join"] * 6 + ["join"] + ["decode"] * 3 + ["decode_tail"] * 3)
    # (the one more: a step's joiners as one program of three rows, PR 52)
    assert shapes.programs(64)[6] == ("join", 3, 512)
    assert shapes.whole_batch and shapes.lanes(1, 64) == shapes.lanes(40, 64) == 64
    assert shapes.decode_steps(8, 1024, 1024 - 8) == 7  # what a tail is warmed at


@pytest.mark.parametrize("change,message", [
    ({"layer_types": PERIOD}, "8 entries"),
    ({"layer_types": ["conv", "mamba"] * 4}, "layer_types"),
    ({"conv_bias": True}, "conv_bias"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
])
def test_what_the_parser_does_not_take_is_an_explicit_error(change, message):
    with pytest.raises(ValueError, match=message):
        LlamaConfig.from_hf_dict({**HF, **change})


@pytest.mark.parametrize("fact,said", [("prefix_cache", "--prefix-cache on"), ("tp", "--tp")])
def test_a_refusal_names_the_feature_and_the_window(fact, said):
    config = LlamaConfig.from_hf_dict(HF)
    with pytest.raises(UnsupportedForCacheKind) as e:
        refuse_unsupported(config, **{fact: True})
    assert said in str(e.value) and "model_type 'lfm2_moe'" in str(e.value)
    assert "6 of its 8 layers keep the window of a short convolution per lane" in str(e.value)
    assert "its last 2 inputs of 64 channels" in str(e.value)


# ------------------------------------------ against the plain reference


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(config, params as loaded from an HF-named checkpoint, the benchmark's
    reader over the same files, the reference module, the written config)."""
    config = LlamaConfig.from_hf_dict(HF)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    path = tmp_path_factory.mktemp("tiny_lfm2")
    save_tiny_checkpoint(path, params, config)
    loaded = load_params(path, LlamaConfig.from_model_dir(path), jnp.float32)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), params, loaded))
    with open(path / "config.json") as f:
        cfg = json.load(f)
    arch = architecture(REPO, HF)
    arch.FAULT = None
    return config, loaded, Reader(path), arch, cfg


def backend(config, params, **kw):
    be = paged_backend(
        config, params, max_seq_len=128, cache_dtype=jnp.float32,
        page_size=PAGE, max_pages=48, allow_pallas=False, **kw,
    )
    assert be.cache_kind == "kv+state" and hasattr(be, "moe_facts")
    return be


def test_the_loader_reads_the_published_names(model):
    config, loaded, reader, *_ = model
    names = set(reader._files)
    assert {"model.embed_tokens.weight", "model.embedding_norm.weight"} <= names
    assert "lm_head.weight" not in names  # tied
    assert reader("model.layers.0.conv.conv.weight").shape == (64, 1, 3)
    assert reader("model.layers.0.conv.in_proj.weight").shape == (192, 64)
    assert reader("model.layers.0.feed_forward.w1.weight").shape == (96, 64)
    assert reader("model.layers.2.self_attn.q_layernorm.weight").shape == (64,)
    assert reader("model.layers.2.self_attn.out_proj.weight").shape == (64, 256)
    assert reader("model.layers.3.feed_forward.experts.7.w2.weight").shape == (64, 32)
    assert reader("model.layers.3.feed_forward.expert_bias").shape == (8,)
    assert {n.split(".", 3)[3] for n in names if n.startswith("model.layers.1.")} == {
        "operator_norm.weight", "ffn_norm.weight", "conv.in_proj.weight", "conv.conv.weight",
        "conv.out_proj.weight", "feed_forward.w1.weight", "feed_forward.w2.weight",
        "feed_forward.w3.weight"}
    assert [r["wo"].shape[0] for r in loaded["layers"]] == [2, 1, 3, 1, 1]
    assert loaded["layers"][2]["w_gate"].shape == (3, 8, 64, 32)
    assert loaded["layers"][2]["conv_w"].shape == (3, 3, 64)


def test_prefill_decode_chunks_and_a_join_match_the_reference(model):
    """An epoch's prefill of two rows on FOUR lanes (two dead: a dummy token
    each, no pages), three decode chunks of 8 through the cache, a joiner
    into a lane never used and a chunk more, against the reference's logits
    on the FULL sequences. Float32 on both sides, so only the order of sums
    differs (the grouped experts against a loop over all of them, the
    convolution's window against the whole row): 2e-6 of a logit spread of
    0.15 seen; the tolerance 2e-5 is Jamba's test's, and the served tokens
    are the reference's argmax at every position. Served with the cache in
    bfloat16, the precision below, the logits miss it by a hundredfold."""
    config, loaded, reader, arch, cfg = model
    be = backend(config, loaded)
    cache = be.init_kv(4)
    assert cache.ssm is None  # the window is all the lane state there is
    # two KV heads of 64 side by side in a pool row of one lane tile
    assert cache.kv.k.shape == (2, 48, 1, PAGE, 128)
    rows = prompts(0, 21, 37)
    cache, tokens, pads = lay_out(be, rows, 4, 48)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    want = arch.forward_logits(reader, cfg, rows)
    for r in range(2):
        np.testing.assert_allclose(logits[r], want[r][-1], atol=2e-5)
    tok = np.asarray(logits).argmax(-1).astype(np.int32)
    served = [[int(tok[r])] for r in range(2)]
    slot = 48
    for _ in range(3):
        toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1))
        said = be.absorb_chunk_counters(be.take_chunk_counters())
        # 8 steps x 6 sparse layers; two live lanes x 2 experts a token, all
        # held: the dead lanes take no expert's rows
        assert said["dispatches"] == 48 and said["routed"] == said["held"] == 48 * 2 * 2
        for r in range(2):
            served[r] += toks[r].tolist()
        tok, slot = toks[:, -1], slot + 8
    full = arch.forward_logits(reader, cfg, [p + s[:-1] for p, s in zip(rows, served)])
    for r, p in enumerate(rows):
        lg = full[r][len(p) - 1:]
        assert lg.shape[0] == 25 and (lg.argmax(-1) == served[r]).all()
    # a joiner takes lane 2 at the shared slot, 26 tokens in a window of 64
    # that ends there: its rotary positions count from ITS pad
    (joiner,) = prompts(5, 26)
    row = np.zeros((1, 64), np.int32)
    row[0, 64 - len(joiner):] = joiner
    be.allocator.map_range(2, slot - len(joiner), slot)
    j_logits, cache = be.join(
        cache, row, jnp.asarray([slot - len(joiner)], jnp.int32),
        jnp.asarray([slot], jnp.int32), 2, start=slot - 64,
    )
    said = be.absorb_chunk_counters(be.take_chunk_counters(), decode=False)
    assert said["routed"] == said["held"] == 6 * 26 * 2
    (j_want,) = arch.forward_logits(reader, cfg, [joiner])
    np.testing.assert_allclose(j_logits[0], j_want[-1], atol=2e-5)
    pads = np.asarray(pads).copy()
    pads[2] = slot - len(joiner)
    tok = np.concatenate([tok[:2], [j_logits[0].argmax()], tok[3:]]).astype(np.int32)
    toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1, 2))
    j_full = arch.forward_logits(reader, cfg, [joiner + [int(tok[2])] + toks[2, :-1].tolist()])
    assert (j_full[0][len(joiner):].argmax(-1) == toks[2]).all()
    facts = be.moe_facts()
    assert facts["experts_held"] == facts["experts_ranked"] == 8 and facts["join"]["joins"] == 1
    state = be.state_facts()
    assert state["mixer"] == "short_conv" and state["layers"] == 6
    assert state["bytes_per_lane"] == config.state_bytes_per_lane == 6 * 2 * 64 * 2
    assert state["window_form"] == state["step_form"] == "xla"


def _two_chunks(model, lanes):
    """Two rows' prefill on ``lanes`` lanes (the rest dead) and two decode
    chunks of 8: (the 16 served tokens a row, ``/stats`` engine.moe)."""
    config, loaded, *_ = model
    be = backend(config, loaded)
    cache, tokens, pads = lay_out(be, prompts(0, 21, 37), lanes, 48)
    logits, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    tok, slot, served = np.asarray(logits).argmax(-1).astype(np.int32), 48, []
    for _ in range(2):
        toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1))
        said = be.absorb_chunk_counters(be.take_chunk_counters())
        assert said["dispatches"] == 48 and said["held"] == 48 * 2 * 2  # dead lanes: no rows
        served.append(toks[:2])
        tok, slot = toks[:, -1], slot + 8
    return np.concatenate(served, axis=1), be.moe_facts()


@pytest.mark.parametrize("lanes,forced,dense", [
    (4, None, False),  # 4 rows leave 0.75 ** 4 = 32% of the 8 experts untouched: grouped
    (20, None, True),  # 20 rows 0.3%: the dense combine, by shape
    (20, 0, False), (4, 10**9, True),  # either path forced for a test
])
def test_a_decode_chunk_serves_the_same_tokens_by_either_path(
        model, monkeypatch, lanes, forced, dense):
    """The decode chunk's sparse layers take the path ``ops/moe.dispatch_path``
    says for the rows of the dispatch (or the one a test forces), the account
    counts the dense ones on the host by the same rule, and the served tokens
    are the same: the paths differ in the order of a sum."""
    from cake_tpu.models.llama import programs

    def fresh():  # a served program is remembered by its shapes, not by the rule's constants
        programs.decode_program.cache_clear()
        programs.join_program.cache_clear()
        jax.clear_caches()

    want, facts = _two_chunks(model, 4)
    assert facts["dispatches"] == 96 and facts["dense_dispatches"] == 0
    fresh()
    traced, combine = [], moe._dense_combine
    try:
        if forced is not None:
            monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", forced)
        monkeypatch.setattr(
            moe, "_dense_combine", lambda x, *a: traced.append(x.shape[:2]) or combine(x, *a))
        got, facts = _two_chunks(model, lanes)
    finally:
        monkeypatch.undo()
        fresh()
    np.testing.assert_array_equal(got, want)
    assert facts["dispatches"] == 96
    assert facts["dense_dispatches"] == (96 if dense else 0)
    assert ((lanes, 1) in traced) == dense  # what the decode program itself took


def test_through_the_engine_a_joiner_equals_the_request_alone(model):
    """Through serving.py's loop, the continuous scheduler and the one paged
    backend: a late request joins a running segment on four lanes (two stay
    dead), and each stream equals the same request served alone; the decode
    chunks' account is read back with their tokens."""
    from cake_tpu.models.llama.chat import Message

    config, loaded, *_ = model
    texts = ["the first, long-running stream of this test", "late joiner"]
    alone = []
    for text in texts:
        eng = engine(config, loaded)
        alone.append(collect(eng.submit([Message.user(text)], 10, GREEDY)))
        eng.stop()
    eng = engine(config, loaded)
    h0 = eng.submit([Message.user(texts[0])], 24, GREEDY)
    first = next(iter(h0.tokens()))
    h1 = eng.submit([Message.user(texts[1])], 10, GREEDY)
    got1 = collect(h1)
    got0 = [first.id, *collect(h0)]
    facts = eng.backend.moe_facts()
    state = eng.backend.state_facts()
    eng.stop()
    assert got1 == alone[1] and got0[:10] == alone[0]
    assert eng.stats["joins"] >= 1 and facts["join"]["joins"] >= 1
    assert facts["dispatches"] > 0 and facts["held"] == facts["routed"] > 0
    assert facts["touched"] <= 8 * facts["dispatches"] and facts["top_k"] == 2
    assert state["mixer"] == "short_conv" and state["lane_writes"] >= 2


def test_a_cache_in_the_precision_below_misses_the_tolerance(model):
    config, loaded, reader, arch, cfg = model
    be = paged_backend(config, loaded, max_seq_len=128, cache_dtype=jnp.bfloat16,
                       page_size=PAGE, max_pages=48, allow_pallas=False)
    rows = prompts(0, 21, 37)
    cache, tokens, pads = lay_out(be, rows, 4, 48)
    logits, _ = be.prefill(tokens, cache, jnp.asarray(pads))
    want = arch.forward_logits(reader, cfg, rows)
    assert max(np.abs(np.asarray(logits[r]) - want[r][-1]).max() for r in range(2)) > 2e-4


def test_a_jamba_backend_has_no_expert_account():
    from test_hybrid_jamba import HF as JAMBA

    config = LlamaConfig.from_hf_dict(JAMBA)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    be = paged_backend(config, params, max_seq_len=128, cache_dtype=jnp.float32,
                       page_size=PAGE, max_pages=48, allow_pallas=False)
    assert be.cache_kind == "kv+state" and not hasattr(be, "moe_facts")
    assert be.state_facts()["mixer"] == "mamba" and be.init_kv(2).ssm is not None


@pytest.mark.parametrize("fault", ["no_gate_c", "taps_dropped", "bias_in_weights", "no_qk_norm",
                                   "renorm_dropped"])
def test_the_reference_with_one_fault_is_another_model(model, fault):
    config, loaded, reader, arch, cfg = model
    assert fault in arch.FAULTS
    (ids,) = prompts(7, 40)
    (sound,) = arch.forward_logits(reader, cfg, [ids])
    arch.FAULT = fault
    try:
        (faulty,) = arch.forward_logits(reader, cfg, [ids])
    finally:
        arch.FAULT = None
    gap = np.abs(faulty - sound).max() / sound.std()
    # at this width and weights of 0.02 a branch adds little to the residual:
    # a gross fault still moves a logit by thousandths of the spread or more,
    # a hundred times the tolerance the served path is held to above
    assert gap > 2e-3, gap


# ------------------------------------------------------------ the routing


def test_with_a_wide_bias_the_chosen_are_the_biased_scores_and_the_weights_the_scores():
    """A bias far wider than the scores' spread: the chosen set is ``s +
    b``'s (here simply the bias's two largest), the weights are ``s``'s
    renormalised; a rule that put ``b`` in the weights gives other numbers."""
    logits = jax.random.normal(jax.random.PRNGKey(1), (5, 8)) * 0.3
    bias = jnp.asarray([3.0, -2.0, 0.5, 2.5, -1.0, 0.0, 1.0, -3.0])
    topv, topi = moe.route_topk_select(logits, 2, True, "sigmoid", 1.0, bias=bias)
    assert (np.sort(np.asarray(topi), -1) == [0, 3]).all()
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(topi), -1)
    np.testing.assert_allclose(topv, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    biased = picked + np.asarray(bias)[np.asarray(topi)]
    assert np.abs(biased / biased.sum(-1, keepdims=True) - np.asarray(topv)).max() > 0.01
    # and the layer's tail routes by that rule: the reference's own routing
    arch = architecture(REPO, HF)
    g = jax.random.normal(jax.random.PRNGKey(2), (5, 64))
    gate = jax.random.normal(jax.random.PRNGKey(3), (8, 64)) * 0.05
    combine = arch._routing(g, gate, bias, top_k=2, norm=True, scale=1.0, fault=None)
    topv, topi = moe.route_topk_select(
        moe.router_logits(g, gate.T), 2, True, "sigmoid", 1.0, bias=bias)
    ours = jnp.sum(jax.nn.one_hot(topi, 8) * topv[..., None], -2)
    np.testing.assert_allclose(ours, combine, atol=1e-6)
    wrong = arch._routing(g, gate, bias, top_k=2, norm=True, scale=1.0, fault="bias_in_weights")
    assert np.abs(np.asarray(wrong) - np.asarray(combine)).max() > 0.01


# ------------------------------------------------------- what the judge reads


def test_the_judge_reads_the_mean_deficit_of_the_calls_served_positions(model):
    """``forward_logits`` with ``first_rows`` hands ``bench/reference.py``'s
    judge rows whose worst position reads the MEAN of the served positions'
    deficits, over all probes of the call; only the served tokens' logits are
    moved, and without ``first_rows`` the logits are the reference's own."""
    from bench import reference

    config, loaded, reader, arch, cfg = model
    rng = np.random.default_rng(5)
    probes = [{"context": p, "served": rng.integers(8, cfg["vocab_size"], n).tolist()}
              for p, n in zip(prompts(12, 30, 20), (6, 9))]
    plain = arch.forward_logits(reader, cfg, [p["context"] + p["served"] for p in probes])
    own = [arch.deficits(lg[len(p["context"]) - 1:], p["served"]) for lg, p in zip(plain, probes)]
    mean = float(np.concatenate(own).mean())
    assert np.ptp(np.concatenate(own)) > 0.5 and mean > 1  # random tokens: far from the best, unevenly
    verdict = reference.judge(arch, reader, cfg, 1e9, probes)
    assert verdict["per_probe"] == pytest.approx([mean, mean], rel=2e-3)
    assert verdict["worst"] == pytest.approx(mean, rel=2e-3) and verdict["positions"] == 15
    assert reference.judge(arch, reader, cfg, 0.99 * mean, probes)["correct"] is False
    assert reference.judge(arch, reader, cfg, 1.01 * mean, probes)["correct"] is True
    rows = [lg[len(p["context"]) - 1:] for lg, p in zip(plain, probes)]
    judged = arch.judged_rows(rows, [p["served"] for p in probes])
    for r, j, p in zip(rows, judged, probes):
        assert ((r != j).sum(-1) == [*[1] * len(p["served"]), 0]).all()
    # a probe that served nothing leaves the rows alone
    assert arch.judged_rows(rows[:1], [[]])[0] is rows[0]


def test_the_reference_in_the_served_type_is_close_and_not_equal(model):
    """``ROUNDING = "bf16"`` (a control: the residual stream and the norms'
    outputs kept as bfloat16 keeps them) moves the logits by thousandths of
    their spread at this size and is off again afterwards."""
    config, loaded, reader, arch, cfg = model
    (ids,) = prompts(11, 40)
    (exact,) = arch.forward_logits(reader, cfg, [ids])
    arch.ROUNDING = "bf16"
    try:
        (rounded,) = arch.forward_logits(reader, cfg, [ids])
    finally:
        arch.ROUNDING = None
    gap = np.abs(rounded - exact).max() / exact.std()
    assert 1e-4 < gap < 0.2, gap
    (again,) = arch.forward_logits(reader, cfg, [ids])
    assert (again == exact).all()
