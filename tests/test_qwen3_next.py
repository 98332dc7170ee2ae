"""``model_type: qwen3_next`` on the served path, at a tiny size on the CPU.

The parser on the catalog's row and on the cell's cut; a tiny Qwen3-Next
(hidden 64, two periods of three gated-delta-rule layers and one gated
attention layer, 4 value heads in groups of 2 on 2 key heads, heads of 128
with a rotary term over their first 32 numbers, 4 experts HELD of the 16 the
router ranks, from expert 4 on, beside a gated shared expert; seeded float32
weights written in HF's names and HF's head-at-a-time layouts) through the
served programs of the ``kv+state`` record (an epoch's prefill with dead
lanes, decode chunks through the cache, a join) against the plain reference
of ``bench/architectures/qwen3_next.py`` (a full forward pass, no cache);
grouped heads in ``ops/delta_rule.py``; the loader's re-ordering; the routing
rule; the share tied to the whole layer; the account; what is refused.
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
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops import delta_rule as D
from cake_tpu.ops import moe
from cake_tpu.runtime.batch_backend import paged_backend

from test_hybrid_jamba import GREEDY, collect, decode, engine, lay_out, prompts

REPO = Path(__file__).resolve().parents[1]
HF = dict(
    model_type="qwen3_next", hidden_size=64, intermediate_size=96, vocab_size=512,
    num_hidden_layers=8, full_attention_interval=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=128, partial_rotary_factor=0.25, rope_theta=10000000,
    rope_scaling=None, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    num_experts=4, num_experts_total=16, first_expert=4, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6, tie_word_embeddings=False,
    max_position_embeddings=256, bos_token_id=0, eos_token_id=1, pad_token_id=0,
)
PAGE = 16
# The catalog row's ``config`` (Qwen3-Next-80B-A3B-Instruct), key for key: the
# test machine may not have the guide.
ROW = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
CUT = {**ROW, "num_hidden_layers": 12, "num_experts": 128, "num_experts_total": 512,
       "first_expert": 0, "vocab_size": 37984}


# ------------------------------------------------------------- the parser


def test_the_parser_on_the_catalogs_row():
    config = LlamaConfig.from_hf_dict(ROW)
    assert config.layers_of("attention") == tuple(range(3, 48, 4))
    assert len(config.layers_of("state")) == 36 and config.cache_kind == "kv+state"
    assert config.ff_kinds == ("sparse",) * 48
    assert config.state_mixer == "gated_delta" and not config.linear_allow_neg_eigval
    assert (config.linear_num_key_heads, config.linear_num_value_heads) == (16, 32)
    assert config.state_shape == (128, 4096) and config.conv_window == (3, 8192)
    assert config.head_dim == 256 and config.rotary_dim == 64 and config.rope_theta == 1e7
    assert config.qk_norm and config.rmsnorm_offset and config.attn_gate == "per-number"
    assert config.pre_block_norms and not config.post_block_norms
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (512, 512, 0)
    assert (config.moe_scoring, config.num_experts_per_tok, config.norm_topk_prob) == (
        "softmax", 10, True)
    assert config.shared_expert_intermediate_size == 512 and not config.tie_word_embeddings
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config
    assert config.dialog_template == "qwen3_next"
    # a ``layer_types`` list, where a file has one, wins over the interval
    listed = LlamaConfig.from_hf_dict(
        {**ROW, "num_hidden_layers": 4, "full_attention_interval": 2,
         "layer_types": ["linear_attention"] * 3 + ["full_attention"]})
    assert listed.layers_of("attention") == (3,)


def test_the_cut_to_a_stages_rank_walks_six_runs():
    """The cell's cut: rank 0 of stage 0, layers 0-11, 128 of the 512 ranked
    experts, a quarter of the vocabulary."""
    config = LlamaConfig.from_hf_dict(CUT)
    assert config.layer_runs == (
        ("state", 0, 3), ("attention", 0, 1), ("state", 3, 6), ("attention", 1, 2),
        ("state", 6, 9), ("attention", 2, 3))
    assert config.run_ff_kinds == ("sparse",) * 6
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (128, 512, 0)
    assert config.state_bytes_per_lane == 9 * (32 * 128 * 128 * 4 + 3 * 8192 * 2) == 19_316_736
    state = H.run_shapes(config, "state", "sparse")
    assert state["in_proj"] == (2048, 12288) and state["ab_proj"] == (2048, 64)
    assert state["conv_w"] == (4, 8192) and state["A_log"] == (32,) and state["o_norm"] == (128,)
    assert state["router"] == (2048, 512) and state["w_gate"] == (128, 2048, 512)
    assert state["sh_gate"] == (2048, 512) and state["se_gate"] == (2048, 1)
    attention = H.run_shapes(config, "attention", "sparse")
    assert attention["wq"] == attention["wg"] == (2048, 4096) and attention["wk"] == (2048, 512)
    assert attention["q_norm"] == attention["k_norm"] == (256,)
    # one layer of each kind and the top, counted from the shapes the program holds
    per = {k: sum(int(np.prod(s)) for s in H.run_shapes(config, k, "sparse").values())
           for k in ("state", "attention")}
    assert per == {"state": 440_572_096, "attention": 434_117_120}
    top = 2 * 37984 * 2048 + 2048
    assert 9 * per["state"] + 3 * per["attention"] + top == 5_423_084_736
    # both delta kernels tile at these widths (128 x 4096, heads of 128)
    kernels = dataclasses.replace(config, attention_impl="pallas")
    assert H.window_form(kernels, True) == H.step_form(kernels, True) == "pallas"
    # the closed set at the cell's geometry is the dear kind's: six joins, a
    # step's joiners as three rows of 512, three decode chunks and their tails
    from cake_tpu.runtime.shapes import ProgramShapes

    shapes = ProgramShapes.for_model(config, 128, 32)
    assert shapes.widths == (256, 512, 1024, 2048, 3072, 4096)
    assert shapes.one_row_prefill_is_join and shapes.whole_batch
    assert [p for p, _, _ in shapes.programs(64)] == (
        ["join"] * 7 + ["decode"] * 3 + ["decode_tail"] * 3)
    assert shapes.programs(64)[6] == ("join", 3, 512)


@pytest.mark.parametrize("change,message", [
    ({"layer_types": ["linear_attention"] * 3}, "8 entries"),
    ({"layer_types": ["linear_attention", "mamba"] * 4}, "layer_types"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"linear_num_value_heads": 3}, "whole"),
    ({"first_expert": 14}, "must not pass"),
])
def test_what_the_parser_does_not_take_is_an_explicit_error(change, message):
    with pytest.raises(ValueError, match=message):
        LlamaConfig.from_hf_dict({**HF, **change})


@pytest.mark.parametrize("fact,said", [("prefix_cache", "--prefix-cache on"), ("tp", "--tp")])
def test_a_refusal_names_the_feature_and_the_state(fact, said):
    config = LlamaConfig.from_hf_dict(HF)
    with pytest.raises(UnsupportedForCacheKind) as e:
        refuse_unsupported(config, **{fact: True})
    assert said in str(e.value) and "model_type 'qwen3_next'" in str(e.value)
    assert "6 of its 8 layers keep a recurrent state per lane" in str(e.value)


# -------------------------------------------- grouped delta-rule heads


def _delta_layer(key, hidden, key_heads, heads, dk, dv):
    """One delta-rule layer's tree in the program's layout, drawn wide."""
    names = {"in_proj": (hidden, 2 * key_heads * dk + 2 * heads * dv),
             "ab_proj": (hidden, 2 * heads), "conv_w": (4, 2 * key_heads * dk + heads * dv),
             "A_log": (heads,), "dt_bias": (heads,)}
    keys = jax.random.split(key, len(names))
    lp = {n: jax.random.normal(k, s, jnp.float32) * 0.3 for k, (n, s) in zip(keys, names.items())}
    lp["o_norm"] = jnp.ones((dv,), jnp.float32)
    return lp


def test_a_window_equals_steps_one_at_a_time_equals_the_rule_a_position():
    """2 key heads x 2 a group: the chunkwise window, the one-token update
    walked over the same positions, and the reference's position-by-position
    rule in HF's layout agree; value head j reads key head j // 2 (a reading
    by j mod 2, the reference's fault, is far off)."""
    hidden, hk, hv, dk, dv, length = 64, 2, 4, 16, 16, 21  # hidden = Hv dv: out_proj = I
    lp = _delta_layer(jax.random.PRNGKey(0), hidden, hk, hv, dk, dv)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, length, hidden), jnp.float32)
    live = jnp.ones((1, length), bool)
    ssm = jnp.zeros((1, dk, hv * dv), jnp.float32)
    conv = jnp.zeros((3, 1, 2 * hk * dk + hv * dv), jnp.float32)
    kw = dict(eps=1e-6, neg_eigval=False, allow_pallas=False)
    y, s, c = D.mixer_forward(lp, h, ssm, conv, live, jnp.asarray([length]), **kw)
    ys, s1, c1 = [], ssm, conv
    for t in range(length):
        y1, s1, c1 = D.mixer_forward(lp, h[:, t:t + 1], s1, c1, live[:, :1], None, **kw)
        ys.append(y1)
    # float32 sums in another order (a chunk's triangular solve against 21
    # rank-one updates): 6e-6 of values up to 1.4 seen
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y, atol=2e-5)
    np.testing.assert_allclose(s1, s, atol=2e-5)
    np.testing.assert_array_equal(c1, c)
    # the reference's rule, handed the same weights in HF's head-major layouts
    arch = architecture(REPO, HF)
    cfg = LlamaConfig.from_hf_dict({**HF, "hidden_size": hidden})
    from cake_tpu.io import safetensors_io as io

    def hf(key, how):
        run = {key: lp[key][None]}
        return jnp.asarray(io._head_major(run, 0, key, how, cfg, jnp.float32))

    w = {"input_layernorm.weight": jnp.zeros((hidden,)),  # (1 + 0): h goes in as it is
         "post_attention_layernorm.weight": jnp.zeros((hidden,)),
         "linear_attn.in_proj_qkvz.weight": hf("in_proj", "qkvz"),
         "linear_attn.in_proj_ba.weight": hf("ab_proj", "ba"),
         "linear_attn.conv1d.weight": lp["conv_w"].T[:, None, :],
         "linear_attn.A_log": lp["A_log"], "linear_attn.dt_bias": lp["dt_bias"],
         "linear_attn.norm.weight": lp["o_norm"],
         "linear_attn.out_proj.weight": jnp.eye(hv * dv)}
    x = h[0] * 4.0  # any scale: the input norm takes it out...
    unit = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    y_prog, _, _ = D.mixer_forward(lp, unit[None], ssm, conv, live, jnp.asarray([length]), **kw)

    def reference(fault):
        with jax.default_matmul_precision("highest"):
            out, _ = arch._linear_mixer(x, w, hk=hk, hv=hv, dk=dk, dv=dv, eps=1e-6, fault=fault)
        return out - x  # the mixer's own part: out_proj is the identity

    np.testing.assert_allclose(y_prog[0], reference(None), atol=2e-5)
    assert np.abs(np.asarray(reference("keys_not_grouped")) - np.asarray(y_prog[0])).max() > 0.05


def test_a_group_of_one_is_the_one_to_one_rule_bit_for_bit():
    """Where key and value heads are as many (Olmo-Hybrid) nothing is
    repeated and nothing traced: the helper hands back its argument. And the
    grouped model IS the one-to-one model whose key heads are written out a
    value head: same numbers, to the bit."""
    x = jnp.ones((1, 3, 4, 8))
    assert D._to_value_heads(x, 4) is x
    hidden, hk, hv, dk, dv, length = 32, 2, 4, 16, 16, 70
    lp = _delta_layer(jax.random.PRNGKey(2), hidden, hk, hv, dk, dv)
    n_k = hk * dk

    def written_out(a):  # q | k columns of 2 key heads -> 4, each twice
        q, k, rest = a[..., :n_k], a[..., n_k:2 * n_k], a[..., 2 * n_k:]
        twice = lambda t: jnp.repeat(t.reshape(*t.shape[:-1], hk, dk), 2, axis=-2).reshape(
            *t.shape[:-1], hv * dk)
        return jnp.concatenate([twice(q), twice(k), rest], -1)

    one = {**lp, "in_proj": written_out(lp["in_proj"]), "conv_w": written_out(lp["conv_w"])}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, length, hidden), jnp.float32)
    live = jnp.ones((2, length), bool).at[1, :9].set(False)
    ends = jnp.asarray([length, length])
    kw = dict(eps=1e-6, neg_eigval=False, allow_pallas=False)
    ssm = jnp.zeros((2, dk, hv * dv), jnp.float32)
    got = D.mixer_forward(lp, h, ssm, jnp.zeros((3, 2, 2 * n_k + hv * dv)), live, ends, **kw)
    want = D.mixer_forward(one, h, ssm, jnp.zeros((3, 2, 2 * hv * dk + hv * dv)), live, ends, **kw)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ------------------------------------------ against the plain reference


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(config, params as loaded from an HF-named checkpoint, the benchmark's
    reader over the same files, the reference module, the written config)."""
    config = LlamaConfig.from_hf_dict(HF)
    params = H.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    path = tmp_path_factory.mktemp("tiny_qwen3_next")
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


def test_the_loader_reorders_the_head_major_tensors_once(model):
    """HF lays ``in_proj_qkvz`` and ``in_proj_ba`` out a KEY head at a time
    and ``q_proj`` a query head at a time; the tree holds q | k | v | z, a | b
    and ``wq`` beside the gate's matrix ``wg``."""
    config, loaded, reader, *_ = model
    names = set(reader._files)
    assert {"model.embed_tokens.weight", "model.norm.weight", "lm_head.weight"} <= names
    assert {n.split(".", 3)[3] for n in names if n.startswith("model.layers.0.")} >= {
        "input_layernorm.weight", "post_attention_layernorm.weight",
        "linear_attn.in_proj_qkvz.weight", "linear_attn.in_proj_ba.weight",
        "linear_attn.conv1d.weight", "linear_attn.A_log", "linear_attn.dt_bias",
        "linear_attn.norm.weight", "linear_attn.out_proj.weight", "mlp.gate.weight",
        "mlp.shared_expert.gate_proj.weight", "mlp.shared_expert_gate.weight",
        "mlp.experts.4.gate_proj.weight", "mlp.experts.7.down_proj.weight"}
    assert "model.layers.0.mlp.experts.0.gate_proj.weight" not in names  # held: 4..7
    assert reader("model.layers.0.linear_attn.in_proj_qkvz.weight").shape == (192, 64)
    assert reader("model.layers.0.linear_attn.in_proj_ba.weight").shape == (8, 64)
    assert reader("model.layers.0.linear_attn.conv1d.weight").shape == (128, 1, 4)
    assert reader("model.layers.3.self_attn.q_proj.weight").shape == (1024, 64)
    assert reader("model.layers.0.mlp.gate.weight").shape == (16, 64)
    assert reader("model.layers.0.mlp.shared_expert_gate.weight").shape == (1, 64)
    assert [r["wo"].shape[0] for r in loaded["layers"]] == [3, 1, 3, 1]
    state, attention = loaded["layers"][0], loaded["layers"][1]
    assert state["w_gate"].shape == (3, 4, 64, 32) and state["router"].shape == (3, 64, 16)
    # key head 1's rows of the checkpoint: [q 16 | k 16 | v 32 | z 32]
    qkvz = np.asarray(reader("model.layers.0.linear_attn.in_proj_qkvz.weight"), np.float32)
    head1 = qkvz[96:192].T  # [hidden, 96]
    tree = np.asarray(state["in_proj"][0])  # q 32 | k 32 | v 64 | z 64
    np.testing.assert_array_equal(tree[:, 16:32], head1[:, :16])  # q of key head 1
    np.testing.assert_array_equal(tree[:, 48:64], head1[:, 16:32])  # k
    np.testing.assert_array_equal(tree[:, 96:128], head1[:, 32:64])  # v: value heads 2, 3
    np.testing.assert_array_equal(tree[:, 160:192], head1[:, 64:96])  # z
    ba = np.asarray(reader("model.layers.0.linear_attn.in_proj_ba.weight"), np.float32)
    ab = np.asarray(state["ab_proj"][0])  # a 4 | b 4
    np.testing.assert_array_equal(ab[:, 2:4], ba[6:8].T)  # a of value heads 2, 3
    np.testing.assert_array_equal(ab[:, 6:8], ba[4:6].T)  # b of value heads 2, 3
    q_proj = np.asarray(reader("model.layers.3.self_attn.q_proj.weight"), np.float32)
    np.testing.assert_array_equal(np.asarray(attention["wq"][0])[:, 128:256], q_proj[256:384].T)
    np.testing.assert_array_equal(np.asarray(attention["wg"][0])[:, 128:256], q_proj[384:512].T)


def test_prefill_decode_chunks_and_a_join_match_the_reference(model):
    """An epoch's prefill of two rows on FOUR lanes (two dead: a dummy token
    each, no pages), three decode chunks of 8 through the cache, a joiner
    into a lane never used and a chunk more, against the reference's logits
    on the FULL sequences. Float32 on both sides, so only the order of sums
    differs (the chunkwise window against the rule a position, the grouped
    experts against a loop over blocks of them): 7e-7 of a logit spread of
    0.15 seen; the tolerance 2e-5 is Jamba's test's, and the served tokens are
    the reference's argmax at every position. The cache in bfloat16, the
    precision below, misses it a hundredfold (the next test)."""
    config, loaded, reader, arch, cfg = model
    be = backend(config, loaded)
    cache = be.init_kv(4)
    assert cache.ssm.shape == (6, 4, 16, 64) and cache.conv.shape == (6, 3, 4, 128)
    assert cache.kv.k.shape == (2, 48, 2, PAGE, 128)
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
        # 8 steps x 8 sparse layers; two live lanes x 4 experts a token, of
        # which the held quarter takes its share: the dead lanes take no rows
        assert said["dispatches"] == 64 and said["routed"] == 64 * 2 * 4
        assert 0 < said["held"] < said["routed"] and said["touched"] <= said["held"]
        for r in range(2):
            served[r] += toks[r].tolist()
        tok, slot = toks[:, -1], slot + 8
    full = arch.forward_logits(reader, cfg, [p + s[:-1] for p, s in zip(rows, served)])
    for r, p in enumerate(rows):
        lg = full[r][len(p) - 1:]
        assert lg.shape[0] == 25 and (lg.argmax(-1) == served[r]).all()
    # a joiner takes lane 2 at the shared slot, 26 tokens in a window of 64
    # that ends there: its rotary positions count from ITS pad, its state
    # starts from zero
    (joiner,) = prompts(5, 26)
    row = np.zeros((1, 64), np.int32)
    row[0, 64 - len(joiner):] = joiner
    be.allocator.map_range(2, slot - len(joiner), slot)
    j_logits, cache = be.join(
        cache, row, jnp.asarray([slot - len(joiner)], jnp.int32),
        jnp.asarray([slot], jnp.int32), 2, start=slot - 64,
    )
    said = be.absorb_chunk_counters(be.take_chunk_counters(), decode=False)
    assert said["routed"] == 8 * 26 * 4 and 0 < said["held"] < said["routed"]
    (j_want,) = arch.forward_logits(reader, cfg, [joiner])
    np.testing.assert_allclose(j_logits[0], j_want[-1], atol=2e-5)
    pads = np.asarray(pads).copy()
    pads[2] = slot - len(joiner)
    tok = np.concatenate([tok[:2], [j_logits[0].argmax()], tok[3:]]).astype(np.int32)
    toks, cache = decode(be, cache, tok, slot, pads, 8, live=(0, 1, 2))
    be.absorb_chunk_counters(be.take_chunk_counters())
    j_full = arch.forward_logits(reader, cfg, [joiner + [int(tok[2])] + toks[2, :-1].tolist()])
    assert (j_full[0][len(joiner):].argmax(-1) == toks[2]).all()
    # GET /stats engine.moe and engine.state
    facts = be.moe_facts()
    assert (facts["experts_held"], facts["experts_ranked"], facts["first_held"]) == (4, 16, 4)
    assert facts["top_k"] == 4 and facts["join"]["joins"] == 1
    assert facts["dispatches"] == 4 * 64 and facts["held"] < facts["routed"]
    state = be.state_facts()
    assert state["mixer"] == "gated_delta" and state["layers"] == 6
    assert (state["key_heads"], state["value_heads"]) == (2, 4)
    assert state["bytes_per_lane"] == config.state_bytes_per_lane == 6 * (4 * 16 * 64 + 2 * 3 * 128)
    assert state["window_form"] == state["step_form"] == "xla"
    assert be.cache_facts()["bytes_per_token"] == 2 * 2 * 2 * 128 * 4  # 2 layers, K and V, f32


def test_a_cache_in_the_precision_below_misses_the_tolerance(model):
    config, loaded, reader, arch, cfg = model
    be = paged_backend(config, loaded, max_seq_len=128, cache_dtype=jnp.bfloat16,
                       page_size=PAGE, max_pages=48, allow_pallas=False)
    rows = prompts(0, 21, 37)
    cache, tokens, pads = lay_out(be, rows, 4, 48)
    logits, _ = be.prefill(tokens, cache, jnp.asarray(pads))
    want = arch.forward_logits(reader, cfg, rows)
    assert max(np.abs(np.asarray(logits[r]) - want[r][-1]).max() for r in range(2)) > 2e-4


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
    assert facts["dispatches"] > 0 and 0 < facts["held"] < facts["routed"]
    assert facts["touched"] <= 4 * facts["dispatches"] and facts["top_k"] == 4
    assert state["mixer"] == "gated_delta" and state["lane_writes"] >= 2


@pytest.mark.parametrize("fault", ["norm_without_one", "keys_not_grouped",
                                   "rope_over_whole_head", "shared_gate_dropped",
                                   "renorm_over_held"])
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
    # every one moves a logit by a spread or more here (1.0 to 4.5 seen): fifty
    # thousand times the tolerance the served path is held to above
    assert np.abs(faulty - sound).max() / sound.std() > 0.5


# ------------------------------------------------------------ the routing


def test_softmax_then_the_choice_renormalised_over_all_the_chosen():
    """Scores are a softmax over ALL ranked experts, the ten (here four)
    largest are chosen, and their weights divided by the sum over the chosen,
    held here or not: the reference's own routing, and not the rule that
    renormalises over the held alone."""
    arch = architecture(REPO, HF)
    u = jax.random.normal(jax.random.PRNGKey(2), (9, 64))
    gate = jax.random.normal(jax.random.PRNGKey(3), (16, 64)) * 0.3
    topv, topi = moe.route_topk_select(moe.router_logits(u, gate.T), 4, True, "softmax", 1.0)
    p = np.asarray(jax.nn.softmax(u @ gate.T, -1))
    picked = np.take_along_axis(p, np.asarray(topi), -1)
    assert (np.sort(np.asarray(topi), -1) == np.sort(np.argsort(-p, -1)[:, :4], -1)).all()
    np.testing.assert_allclose(topv, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    ours = jnp.sum(jax.nn.one_hot(topi, 16) * topv[..., None], -2)[:, 4:8]
    kw = dict(top_k=4, norm=True, first=4, held=4)
    np.testing.assert_allclose(ours, arch._routing(u, gate, fault=None, **kw), atol=1e-6)
    wrong = arch._routing(u, gate, fault="renorm_over_held", **kw)
    assert np.abs(np.asarray(wrong) - np.asarray(ours)).max() > 0.1


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The share tied to the model: four ranks hold experts 0-3, 4-7, 8-11
    and 12-15 of the 16 the router ranks. Each rank's layer tail (its routed
    part through ``ops/moe`` with ITS ``expert_offset``, the shared expert
    whole) less the residual, summed over the ranks with the shared expert
    counted ONCE, is the uncut reference's feed-forward over all 16."""
    arch = architecture(REPO, HF)
    whole = LlamaConfig.from_hf_dict({**HF, "num_experts": 16, "first_expert": 0})
    shapes = H.run_shapes(whole, "state", "sparse")
    names = ("router", "sh_gate", "sh_up", "sh_down", "se_gate", "w_gate", "w_up", "w_down",
             "ln_mlp", "wo")
    keys = jax.random.split(jax.random.PRNGKey(4), len(names))
    lp = {n: jax.random.normal(k, shapes[n], jnp.float32) * 0.2 for n, k in zip(names, keys)}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 11, 64), jnp.float32)
    mixed = jnp.zeros((1, 11, shapes["wo"][0]), jnp.float32)  # the mixer adds nothing

    def tail(config, tree):
        return M.block_finish(tree, x, mixed, config, moe_dispatch="grouped") - x

    routed = []
    for rank in range(4):
        config = dataclasses.replace(
            whole, num_local_experts=4, router_experts=16, expert_offset=4 * rank)
        held = {n: lp[n][4 * rank:4 * rank + 4] for n in ("w_gate", "w_up", "w_down")}
        no_shared = {n: v for n, v in {**lp, **held}.items() if not n.startswith(("sh_", "se_"))}
        routed.append(tail(config, no_shared))
        if rank == 0:
            shared = tail(config, {**lp, **held}) - routed[0]
    got = sum(routed) + shared
    with jax.default_matmul_precision("highest"):
        u = arch._rms1(x[0], lp["ln_mlp"], 1e-6, None)
        combine = arch._routing(u, lp["router"].T, top_k=4, norm=True, first=0, held=16, fault=None)
        t = lambda n: jnp.swapaxes(lp[n], -1, -2)
        want = arch._add_experts(jnp.zeros_like(u), u, combine, t("w_gate"), t("w_up"), t("w_down"))
        want = want + arch._shared(u, {"gate_proj": t("sh_gate"), "up_proj": t("sh_up"),
                                       "down_proj": t("sh_down"), "gate": t("se_gate")}, None)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # one rank alone is not the layer, and the shared expert four times is not either
    assert np.abs(np.asarray(routed[0] + shared)[0] - np.asarray(want)).max() > 0.01
    assert np.abs(np.asarray(got + 3 * shared)[0] - np.asarray(want)).max() > 0.01


# ------------------------------------------------------- what the judge reads


def test_the_judge_reads_the_mean_deficit_of_the_calls_served_positions(model):
    """``forward_logits`` with ``first_rows`` hands ``bench/reference.py``'s
    judge rows whose worst position reads the MEAN of the served positions'
    deficits, over all probes of the call (LFM2's rule; ``judge.why`` says why
    here too); only the served tokens' logits are moved, and without
    ``first_rows`` the logits are the reference's own."""
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
    assert arch.judged_rows(rows[:1], [[]])[0] is rows[0]  # nothing served: the rows alone


def test_the_reference_in_the_served_type_is_close_and_not_equal(model):
    """``ROUNDING = "bf16"`` (a control: the residual stream and the norms'
    outputs kept as bfloat16 keeps them) moves the logits by thousandths of
    their spread at this size, float8 by far more, and it is off again after."""
    config, loaded, reader, arch, cfg = model
    (ids,) = prompts(11, 40)
    (exact,) = arch.forward_logits(reader, cfg, [ids])
    gaps = {}
    for rounding in ("bf16", "f8"):
        arch.ROUNDING = rounding
        try:
            (rounded,) = arch.forward_logits(reader, cfg, [ids])
        finally:
            arch.ROUNDING = None
        gaps[rounding] = np.abs(rounded - exact).max() / exact.std()
    assert 1e-4 < gaps["bf16"] < 0.2 < gaps["f8"], gaps
    (again,) = arch.forward_logits(reader, cfg, [ids])
    assert (again == exact).all()
