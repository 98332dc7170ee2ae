"""``model_type: sdar_moe``: generation by diffusion over blocks on the plain
K-and-V cache (models/llama/diffusion.py), against the benchmark's plain
reference (``bench/architectures/sdar_moe.py``: full forward passes, no
cache), at tiny widths with seeded float32 weights on the CPU: two layers of
eight softmax-routed experts (two a token, all held) behind grouped heads
with q/k norms, blocks of 4 slots.

One built model a module; the XLA twins, and the Pallas chunk kernels
interpreted.
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
from cake_tpu.models.llama import diffusion as D
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.capability import UnsupportedForCacheKind, refuse_unsupported
from cake_tpu.models.llama.chat import Message, encode_dialog
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.ops.attention import gqa_attention, gqa_attention_hm
from cake_tpu.ops.pallas.paged_prefill import paged_chunk_attention, paged_chunk_attention_xla
from cake_tpu.runtime.batch_backend import paged_backend

from test_hybrid_jamba import GREEDY, collect, engine

REPO = Path(__file__).resolve().parents[1]
PAGE = 16
B = 4
MASK = 300
HF = dict(
    model_type="sdar_moe", hidden_size=64, intermediate_size=96, vocab_size=512,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, rope_theta=1e6, rope_scaling=None, attention_bias=False,
    use_sliding_window=False, sliding_window=None, decoder_sparse_step=1,
    mlp_only_layers=[], num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    norm_topk_prob=True, hidden_act="silu", tie_word_embeddings=False,
    max_position_embeddings=256, block_length=B, mask_token_id=MASK,
    bos_token_id=256, eos_token_id=259, pad_token_id=256,
)
# The catalog row's ``config`` (SDAR-30B-A3B-Chat), key for key.
ROW = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


# ------------------------------------------------------------------ the parser


def test_the_parser_on_the_catalogs_row():
    config = LlamaConfig.from_hf_dict(ROW)
    assert config.generation == "block_diffusion" and config.cache_kind == "kv"
    # ASSUMED where the row is silent: the family's released block and the mask id
    assert (config.block_length, config.mask_token_id, config.denoising_steps) == (4, 151669, 4)
    assert set(config.ff_kinds) == {"sparse"} and len(config.ff_kinds) == 48
    assert (config.num_local_experts, config.n_router_experts, config.expert_offset) == (128, 128, 0)
    assert (config.num_experts_per_tok, config.norm_topk_prob, config.moe_scoring) == (8, True, "softmax")
    assert (config.head_dim, config.qk_norm, config.tie_word_embeddings) == (128, True, False)
    assert config.sliding_window is None and config.rope_theta == 1e6
    assert LlamaConfig.from_hf_dict(config.to_hf_dict()) == config
    assert LlamaConfig.from_hf_dict({**ROW, "model_type": "qwen3_moe"}).generation == "autoregressive"


def test_the_parameter_count_at_the_cut():
    """Stage 0 of eight: six layers with all 128 experts and the whole
    vocabulary. One expert 3 x 2048 x 768 = 4,718,592; a layer's 128:
    603,979,776; attention 18,874,368 + 256; router 262,144; two norms 4,096:
    623,120,640 a layer; embedding and head 311,164,928 each and the final
    norm."""
    arch = architecture(REPO, ROW)
    cut = {**ROW, "num_hidden_layers": 6}
    assert arch.layer_parameters(cut, 0) == 623_120_640
    assert arch.parameters(cut) == 6 * 623_120_640 + 2 * 311_164_928 + 2048 == 4_361_055_744
    assert arch.expert_bytes(cut, "bf16") == 9_437_184 and arch.sparse_layers(cut) == 6
    assert arch.kv_bytes_per_token(cut, "bf16") == 12_288
    config = LlamaConfig.from_hf_dict(cut)
    shapes = jax.eval_shape(lambda: M.init_params(config, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 4_361_055_744


@pytest.mark.parametrize("change,message", [
    ({"rope_scaling": {"rope_type": "linear", "factor": 2.0}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"block_length": 3}, "must divide 16"),
    ({"mask_token_id": 151936}, "outside the vocabulary"),
    ({"denoising_steps": 5}, "1..block_length"),
    ({"decoder_sparse_step": 2}, "per-layer dense/sparse"),
])
def test_what_the_parser_does_not_take_is_an_explicit_error(change, message):
    with pytest.raises(ValueError, match=message):
        LlamaConfig.from_hf_dict({**ROW, **change})


@pytest.mark.parametrize("fact,said", [
    ("speculative_k", "--speculative-k"), ("draft_model", "--draft-model"),
    ("prefix_cache", "--prefix-cache on"), ("kv_mode_dense", "--kv-mode dense"),
    ("tp", "--tp"), ("sp", "--sp"), ("topology", "--topology"),
    ("distributed", "--distributed"), ("single_stream", "single-stream generator"),
    ("repeat_penalty", "--repeat-penalty other than 1.0"),
])
def test_a_refusal_names_the_feature_and_the_generation(fact, said):
    config = LlamaConfig.from_hf_dict(HF)
    with pytest.raises(UnsupportedForCacheKind) as e:
        refuse_unsupported(config, **{fact: True})
    assert said in str(e.value) and "diffusion over blocks of 4 slots" in str(e.value)
    assert "--repeat-penalty 1.0" in str(e.value)
    refuse_unsupported(config, **{fact: False})
    plain = LlamaConfig.from_hf_dict({**HF, "model_type": "qwen3_moe"})
    refuse_unsupported(plain, **{fact: True})  # plain K and V, one token a step: served


# -------------------------------------------------------- the block-causal mask


def _dense_mask_attention(q, k, v, q_pos, k_pos, block):
    """The mask written out: query at p sees keys at positions below
    ``(p // B + 1) * B`` (and at or above 0: a pad's position is negative)."""
    b, t, n_q, d = q.shape
    n_kv = k.shape[2]
    seen = (k_pos[:, None, :] < (q_pos[:, :, None] // block + 1) * block) & (k_pos[:, None, :] >= 0)
    kk = np.repeat(np.asarray(k, np.float64), n_q // n_kv, axis=2)
    vv = np.repeat(np.asarray(v, np.float64), n_q // n_kv, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q, np.float64), kk) * d ** -0.5
    s = np.where(seen[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    b, t, n_q, n_kv, d = 2, 128, 4, 2, 128
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return draw(b, t, n_q, d), draw(b, t, n_kv, d), draw(b, t, n_kv, d)


@pytest.mark.parametrize("block", [4, 8])
def test_the_block_causal_mask_in_the_twins_and_the_paged_kernels(qkv, block):
    """Left pads of whole blocks (16 and 40 slots), a chunk of queries at
    slots [96, 96 + 2 blocks) over the prefix: ``gqa_attention`` (fresh and
    over the cache's prefix, head-major), the paged chunk kernel, its gather
    twin and a pass's folded decode call against the mask written out. (The
    dense chunk kernel has no such mask: ``--kv-mode dense`` is refused for
    this generation.)"""
    q, k, v = qkv
    b, t = q.shape[:2]
    pads = np.asarray([16, 40], np.int32)
    slots = np.arange(t)[None, :] - pads[:, None]  # positions; negative in the pad
    k_pos = jnp.asarray(np.where(slots < 0, 2**30, slots), jnp.int32)
    want_pos = np.where(slots < 0, -1, slots)
    start, width = 96, 2 * block
    want = _dense_mask_attention(q, k, v, np.maximum(slots, 0), want_pos, block)
    got = gqa_attention(q, k, v, jnp.asarray(np.maximum(slots, 0), jnp.int32), k_pos, block=block)
    live = slots >= 0
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=2e-5)
    # the chunk of queries over the cache's prefix
    qc = q[:, start:start + width]
    q_starts = jnp.full((b,), start, jnp.int32)
    lengths = jnp.full((b,), start + width, jnp.int32)
    k_hm, v_hm = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    dense = gqa_attention_hm(
        qc, k_hm, v_hm, jnp.asarray(np.maximum(slots, 0)[:, start:start + width], jnp.int32),
        k_pos, block=block)
    np.testing.assert_allclose(np.asarray(dense), want[:, start:start + width], atol=2e-5)
    # one page of 128 slots a row: the pool [pages, kv heads, page, d]
    tables = jnp.asarray([[0], [1]], jnp.int32)
    paged = paged_chunk_attention(
        qc, k_hm, v_hm, q_starts, lengths, jnp.asarray(pads), tables, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), want[:, start:start + width], atol=2e-5)
    twin = paged_chunk_attention_xla(
        qc, k_hm, v_hm, jnp.asarray(slots[:, start:start + width], jnp.int32), k_pos, tables,
        block=block)
    np.testing.assert_allclose(np.asarray(twin), want[:, start:start + width], atol=2e-5)
    # ONE block's queries as more heads of the paged DECODE kernel (a pass's call)
    from cake_tpu.models.llama.batch import block_pass_attention

    one = block_pass_attention(
        qc[:, :block], k_hm, v_hm, jnp.full((b,), start + block, jnp.int32), tables,
        jnp.asarray(pads), interpret=True)
    np.testing.assert_allclose(np.asarray(one), want[:, start:start + block], atol=2e-5)
    # and it is not the causal mask: the first query of a block sees the block's end
    causal = gqa_attention(q, k, v, jnp.asarray(np.maximum(slots, 0), jnp.int32), k_pos)
    assert np.abs(np.asarray(causal)[live] - want[live]).max() > 1e-2


def test_without_a_block_the_mask_is_todays_bit_for_bit(qkv):
    q, k, v = qkv
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    zeros, full = jnp.zeros((b,), jnp.int32), jnp.full((b,), t, jnp.int32)
    k_hm, v_hm = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    tables = jnp.asarray([[0], [1]], jnp.int32)
    for fn, args in (
        (gqa_attention, (q, k, v, pos, pos)),
        (gqa_attention_hm, (q, k_hm, v_hm, pos, pos)),
        (paged_chunk_attention, (q, k_hm, v_hm, zeros, full, zeros, tables)),
        (paged_chunk_attention_xla, (q, k_hm, v_hm, pos, pos, tables)),
    ):
        assert (np.asarray(fn(*args)) == np.asarray(fn(*args, block=None))).all()
        # a block of one position IS the causal mask
        np.testing.assert_allclose(np.asarray(fn(*args, block=1)), np.asarray(fn(*args)), atol=1e-6)


# ------------------------------------------ against the plain reference


@pytest.fixture(scope="module")
def model(tmp_path_factory):
    """(config, params as loaded from an HF-named checkpoint, the benchmark's
    reader over the same files, the reference module, the written config)."""
    config = LlamaConfig.from_hf_dict(HF)
    params = M.init_params(config, jax.random.PRNGKey(0), jnp.float32)
    # the special ids' head rows zero, as the benchmark's checkpoint has them:
    # no slot is ever revealed AS the mask id
    params["lm_head"] = params["lm_head"].at[:, jnp.asarray([MASK, 256, 259])].set(0)
    path = tmp_path_factory.mktemp("tiny_sdar")
    save_tiny_checkpoint(path, params, config)
    loaded = load_params(path, LlamaConfig.from_model_dir(path), jnp.float32)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), params, loaded))
    with open(path / "config.json") as f:
        cfg = json.load(f)
    assert cfg["model_type"] == "sdar_moe" and cfg["block_length"] == B and cfg["mask_token_id"] == MASK
    arch = architecture(REPO, HF)
    arch.FAULT = None
    return config, loaded, Reader(path), arch, cfg


def backend(config, params, dtype=jnp.float32):
    be = paged_backend(
        config, params, max_seq_len=128, cache_dtype=dtype,
        page_size=PAGE, max_pages=48, allow_pallas=False, lanes=4,
    )
    assert be.cache_kind == "kv" and hasattr(be, "moe_facts") and hasattr(be, "diffusion_facts")
    assert not hasattr(be, "suffix_prefill") and not hasattr(be, "verify_greedy")
    return be


def serve(be, prompts, n_new, lanes=4, with_logits=False):
    """What the engine does, by hand: the first ``P // B * B`` tokens of every
    prompt prefilled into a bucket, then dispatches of two blocks; dead lanes
    beside the rows. (tokens a row cut at ``n_new``, every denoising pass's
    logits [blocks, steps, rows, B, vocab] in order)."""
    config = be.config
    heads = [p[: len(p) // B * B] for p in prompts]
    bucket = -(-max(len(h) for h in heads) // 16) * 16
    cache = be.init_kv(lanes)
    tokens = np.zeros((lanes, bucket), np.int32)
    pads = np.full((lanes,), bucket, np.int32)
    known = np.full((lanes, B), config.mask_token_id, np.int32)
    for r, (p, h) in enumerate(zip(prompts, heads)):
        pads[r] = bucket - len(h)
        tokens[r, pads[r]:] = h
        known[r, : len(p) - len(h)] = p[len(h):]
        be.allocator.map_range(r, int(pads[r]), bucket)
    assert not (pads % B).any()
    _, cache = be.prefill(tokens, cache, jnp.asarray(pads))
    keys = jnp.stack([jax.random.PRNGKey(0)] * lanes)
    ring, idx = jnp.zeros((lanes, 0), jnp.int32), jnp.zeros((lanes,), jnp.int32)
    need = max(-(-(len(p) - len(h) + n_new) // B) * B for p, h in zip(prompts, heads))
    slot, out, logits = bucket, [], []
    known = jnp.asarray(known)
    while slot < bucket + need:
        n = 2 * B
        for r in range(len(prompts)):
            be.allocator.map_range(r, slot, slot + n)
        if with_logits:
            live = jnp.asarray((be.allocator.block_tables[:lanes] >= 0).any(axis=1))
            copy = jax.tree.map(jnp.copy, cache)
            *_, lg = D.block_decode(
                be.params, copy, known, jnp.int32(slot), jnp.asarray(pads), be._tables(),
                live, keys, config, n_steps=n, temperature=0.0, top_k=None, top_p=None,
                allow_pallas=False, with_logits=True)
            logits.append(np.asarray(lg))
        toks, cache, keys, *_ = be.decode(cache, known, slot, jnp.asarray(pads), keys, ring, idx, n, GREEDY)
        be.absorb_chunk_counters(be.take_chunk_counters())
        out.append(np.asarray(toks))
        known = jnp.full((lanes, B), config.mask_token_id, jnp.int32)
        slot += n
    toks = np.concatenate(out, axis=1)
    served = [toks[r, len(p) - len(h):][:n_new].tolist()
              for r, (p, h) in enumerate(zip(prompts, heads))]
    # the known prompt tokens lead a row's tokens, untouched
    for r, (p, h) in enumerate(zip(prompts, heads)):
        assert toks[r, : len(p) - len(h)].tolist() == p[len(h):]
    return served, (np.concatenate(logits) if logits else None)


def prompts(seed, *lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, n).tolist() for n in lengths]


@pytest.mark.parametrize("remask,steps,threshold", [
    ("sequential", 4, 0.9), ("sequential", 2, 0.9), ("sequential", 1, 0.9),
    ("low_confidence_static", 4, 0.9), ("low_confidence_dynamic", 2, 0.004),
])
def test_prefill_then_blocks_match_upstreams_loop(model, remask, steps, threshold):
    """Prefill, then blocks through the paged backend and the cache, against
    ``block_diffusion_generate`` (full forward passes, no cache): the tokens,
    and the logits of every pass the reference made. Prompts of 21 and 30
    tokens (1 and 2 known tokens in their first blocks), 4 lanes for 2 rows
    (dead lanes beside them), answers of 11 tokens (cut inside a block).
    Float32 on both sides, so only the order of sums differs: 3e-5 of a logit
    spread of about 0.25."""
    config, loaded, reader, arch, cfg = model
    config = dataclasses.replace(
        config, denoising_steps=steps, remask=remask, confidence_threshold=threshold)
    be = backend(config, loaded)
    rows = prompts(1, 21, 30)
    served, logits = serve(be, rows, 11, with_logits=True)
    for r, p in enumerate(rows):
        want, passes = arch.block_diffusion_generate(
            reader, cfg, p, 11, steps=steps, remask=remask, threshold=threshold)
        assert served[r] == want, (r, served[r], want)
        # three blocks; the confident reveal (a low threshold) takes fewer passes
        assert (len(passes) < 3 * steps) if threshold < 0.9 else (len(passes) >= 2 * steps + 1)
        first = len(p) // B * B
        for start, t, lg in passes:
            got = logits[(start - first) // B, t, r]
            np.testing.assert_allclose(got, lg, atol=3e-5)
    facts = be.diffusion_facts()
    assert facts["lane_passes"] == (steps + 1) * facts["blocks"]
    assert facts["passes"] == facts["dispatches"] * 2 * (steps + 1)
    assert facts["commit_passes"] == 2 * facts["dispatches"]
    moe = be.moe_facts()
    # a dispatch of the experts is a PASS and sparse layer; dead lanes take no rows
    assert moe["dispatches"] == 2 * facts["passes"]
    assert moe["held"] == moe["routed"] == facts["lane_passes"] * B * 2 * 2
    assert (moe["experts_held"], moe["experts_ranked"], moe["top_k"]) == (8, 8, 2)


def test_a_later_block_reads_the_finished_blocks_k_and_v(model):
    """The commit: block b + 1's passes read block b as FINISHED. A program
    that skipped the commit would leave the last denoising pass's K and V (the
    block's last slot still masked) in the pool: the reference wrong in that
    way (``block_uncommitted``) is far from the served tokens' logits, and the
    sound one is at them."""
    config, loaded, reader, arch, cfg = model
    be = backend(dataclasses.replace(config, remask="sequential"), loaded)
    rows = prompts(2, 16)
    served, _ = serve(be, rows, 16)
    sequences, first = [rows[0] + served[0]], [len(rows[0]) - 1]
    sound = arch.deficits(arch.state_logits(reader, cfg, sequences, first)[0], served[0])
    arch.FAULT = "block_uncommitted"
    try:
        wrong = arch.deficits(arch.state_logits(reader, cfg, sequences, first)[0], served[0])
    finally:
        arch.FAULT = None
    assert sound.max() < 1e-4
    assert wrong[:B].max() < 1e-4 and wrong[B:].max() > 0.05  # the first block has none before it


@pytest.mark.parametrize("fault", ["causal_inside_block", "logits_shifted",
                                   "block_uncommitted", "weights_not_renormalised"])
def test_the_reference_with_one_fault_is_another_model(model, fault):
    config, loaded, reader, arch, cfg = model
    be = backend(dataclasses.replace(config, remask="sequential"), loaded)
    rows = prompts(3, 18, 33)
    served, _ = serve(be, rows, 16)
    sequences = [p + s for p, s in zip(rows, served)]
    first = [len(p) - 1 for p in rows]

    def mean_deficit():
        got = arch.forward_logits(reader, cfg, sequences, first)
        return max(float(np.mean(arch.deficits(g, s))) for g, s in zip(got, served))

    assert mean_deficit() < 1e-4
    arch.FAULT = fault
    try:
        assert mean_deficit() > 0.02
    finally:
        arch.FAULT = None


def test_the_two_stream_form_equals_one_full_forward_a_state(model):
    """What the judge is handed (a clean stream lending K and V to a block of
    rows a state, one set of rows under one mask) against the naive form: a
    full forward of ``[tokens below p] + [mask] * (block's end - p)`` for every
    served position p."""
    config, loaded, reader, arch, cfg = model
    seq = prompts(4, 29)[0]
    fast = arch.state_logits(reader, cfg, [seq], [18])[0]
    slow = arch.naive_state_logits(reader, cfg, seq, 19)
    assert fast.shape == slow.shape == (10, 512)
    np.testing.assert_allclose(fast, slow, atol=2e-5)
    # a state is not the clean sequence: the masks behind p are seen
    clean = arch.forward_logits(reader, cfg, [seq])[0][19:]
    assert min(np.abs(clean[j] - slow[j]).max() for j in range(10)) > 1e-2


def test_softmax_then_the_choice_renormalised_over_the_eight():
    arch = architecture(REPO, HF)
    rng = np.random.default_rng(5)
    u, gate = rng.standard_normal((5, 64)).astype(np.float32), rng.standard_normal((8, 64)).astype(np.float32)
    got = np.asarray(arch._routing(jnp.asarray(u), jnp.asarray(gate), top_k=2, norm=True, fault=None))
    logits = u @ gate.T
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for t in range(5):
        top = np.argsort(-p[t])[:2]
        want = np.zeros(8)
        want[top] = p[t, top] / p[t, top].sum()
        np.testing.assert_allclose(got[t], want, atol=1e-6)
    raw = np.asarray(arch._routing(jnp.asarray(u), jnp.asarray(gate), top_k=2, norm=True,
                                   fault="weights_not_renormalised"))
    assert (raw.sum(-1) <= 1.0 + 1e-6).all() and np.abs(raw.sum(-1) - 1).max() > 0.05


def test_a_cache_in_the_precision_below_misses_the_tolerance(model):
    """The same program with K and V stored in bfloat16 misses the float32
    tolerance of the passes' logits (3e-5) by two orders."""
    config, loaded, reader, arch, cfg = model
    be = backend(dataclasses.replace(config, remask="sequential"), loaded, dtype=jnp.bfloat16)
    rows = prompts(1, 21)
    _, logits = serve(be, rows, 8, with_logits=True)
    _, passes = arch.block_diffusion_generate(reader, cfg, rows[0], 8, remask="sequential")
    worst = max(np.abs(logits[(s - 20) // B, t, 0] - lg).max() for s, t, lg in passes)
    assert worst > 2e-3


# ----------------------------------------------------------- through the engine


def ids_of(config, text):
    from cake_tpu.models.llama.tokenizer import ByteTokenizer

    return ByteTokenizer().encode(encode_dialog([Message.user(text)], config.dialog_template))


def test_through_the_engine_a_joiner_equals_upstreams_loop(model):
    """Through serving.py's loop, the continuous scheduler and the paged
    backend: a request alone, then a late one that joins the running segment
    with a prompt that is no whole number of blocks; each stream is the
    reference's loop on its prompt, exactly ``max_tokens`` long (11 and 10: cut
    inside a block); ``/stats`` engine.diffusion and engine.moe count the
    passes."""
    config, loaded, reader, arch, cfg = model
    config = dataclasses.replace(config, remask="low_confidence_static")
    texts = ["the first, long-running stream of this test", "late joiner!"]
    eng = engine(config, loaded, decode_chunk_size=8)
    assert eng.decode_chunk_size == 8 and eng.shapes.block == B and eng.shapes.whole_batch
    h0 = eng.submit([Message.user(texts[0])], 43, GREEDY)
    first = next(iter(h0.tokens()))
    h1 = eng.submit([Message.user(texts[1])], 10, GREEDY)
    got1 = collect(h1)
    got0 = [first.id, *collect(h0)]
    accounts = json.loads(json.dumps(eng.accounts()))
    eng.stop()
    assert (h0.finish_reason, h1.finish_reason) == ("length", "length")
    assert (len(got0), len(got1)) == (43, 10)
    for text, got in zip(texts, (got0, got1)):
        ids = ids_of(config, text)
        want, _ = arch.block_diffusion_generate(reader, cfg, ids, len(got), remask="low_confidence_static")
        assert got == want
    assert len(ids_of(config, texts[1])) % B and eng.stats["joins"] >= 1
    d, moe = accounts["diffusion"], accounts["moe"]
    assert (d["block_length"], d["denoising_steps"], d["remask"], d["mask_token_id"]) == (
        B, 4, "low_confidence_static", MASK)
    assert d["lane_passes"] == 5 * d["blocks"] and d["passes"] == 10 * d["dispatches"]
    assert d["commit_passes"] == 2 * d["dispatches"]
    assert d["emitted"] == 53 and d["revealed"] + d["known"] == B * d["blocks"]
    assert d["known"] == sum(len(ids_of(config, t)) % B for t in texts)
    assert moe["dispatches"] == 2 * d["passes"] and moe["join"]["joins"] >= 1
    assert moe["held"] == moe["routed"] == d["lane_passes"] * B * 2 * 2
    assert accounts["cache"]["kind"] == "kv"
    assert accounts["period"]["steps"] == 8 * accounts["period"]["count"]


def test_an_end_of_sequence_id_inside_a_block_ends_the_stream_there(model):
    config, loaded, *_ = model
    eng = engine(config, loaded, decode_chunk_size=8)
    whole = collect(eng.submit([Message.user("how does it end")], 14, GREEDY))
    eng.stop()
    # the token served sixth (inside the second block), an end-of-sequence id
    # of a second server over the same weights
    stop = whole[5]
    cut = whole.index(stop)
    assert len(whole) == 14
    from test_hybrid_jamba import BatchEngine, ByteTokenizer, ServeConfig

    ending = dataclasses.replace(config, bos_token_id=256, eos_token_ids=(stop,))
    eng = BatchEngine(
        ending, loaded, ByteTokenizer(), max_seq_len=256, cache_dtype=jnp.float32,
        serve=ServeConfig(max_batch=4, decode_chunk_size=8, admission_window=0.05,
                          scheduler="continuous", kv_mode="paged", page_size=PAGE),
    )
    eng.start()
    handle = eng.submit([Message.user("how does it end")], 14, GREEDY)
    got = collect(handle)
    # a request behind it is served whole: the lane was given back
    after = collect(eng.submit([Message.user("and the next one")], 6, GREEDY))
    eng.stop()
    assert got == whole[: cut + 1] and handle.finish_reason == "stop"
    assert len(after) == 6


def test_the_engine_refuses_what_a_block_step_cannot_serve(model):
    config, loaded, *_ = model
    from test_hybrid_jamba import BatchEngine, ByteTokenizer, ServeConfig

    def build(**kw):
        serve = {"max_batch": 4, "kv_mode": "paged", "page_size": PAGE, "scheduler": "continuous"}
        return BatchEngine(
            config, loaded, ByteTokenizer(), max_seq_len=128, cache_dtype=jnp.float32,
            **{k: v for k, v in kw.items() if k == "speculative_k"},
            serve=ServeConfig(**{**serve, **{k: v for k, v in kw.items() if k != "speculative_k"}}))

    with pytest.raises(UnsupportedForCacheKind, match="--kv-mode dense"):
        build(kv_mode="dense")
    with pytest.raises(UnsupportedForCacheKind, match="--prefix-cache on"):
        build(prefix_cache=True)
    with pytest.raises(UnsupportedForCacheKind, match="--speculative-k"):
        build(speculative_k=3)
    eng = build()
    with pytest.raises(ValueError, match="repeat_penalty"):
        eng.submit([Message.user("x")], 4, dataclasses.replace(GREEDY, repeat_penalty=1.1))


def test_the_flags_override_the_checkpoints_defaults_and_are_refused_elsewhere():
    from cake_tpu.cli import _generation_flags, build_parser

    config = LlamaConfig.from_hf_dict(HF)
    args = build_parser().parse_args(
        ["--model", "x", "--denoise-steps", "2", "--remask", "low_confidence_static",
         "--confidence-threshold", "0.5"])
    got = _generation_flags(args, config)
    assert (got.denoising_steps, got.remask, got.confidence_threshold) == (2, "low_confidence_static", 0.5)
    assert (got.block_length, got.mask_token_id, got.generation) == (B, MASK, "block_diffusion")
    assert _generation_flags(build_parser().parse_args(["--model", "x"]), config) is config
    with pytest.raises(ValueError, match="1..4"):
        _generation_flags(build_parser().parse_args(["--model", "x", "--denoise-steps", "5"]), config)
    plain = LlamaConfig.from_hf_dict({**HF, "model_type": "qwen3_moe"})
    with pytest.raises(ValueError, match="generates one token a step"):
        _generation_flags(args, plain)


def test_every_pad_is_whole_blocks_where_pads_are_set():
    from cake_tpu.models.llama.batch import layout_prompts

    tokens, pads, bucket = layout_prompts([[1] * 20, [], [2] * 4], 256, B)
    assert bucket == 32 and pads.tolist() == [12, 32, 28] and tokens[1].sum() == 0
    assert layout_prompts([[], []], 256, B)[2] == 16  # nothing to prefill: a bucket all the same
    with pytest.raises(AssertionError):
        layout_prompts([[1] * 21], 256, B)
    # every other model: as before
    assert layout_prompts([[1] * 21, [1]], 256)[1].tolist() == [11, 31]


def test_a_warmed_server_compiles_nothing_at_an_epochs_first_live_width(model):
    """``warm_programs`` runs an epoch's prefill with nothing mapped, so every
    group is spare and skipped before its operands are cut; the cut is an
    eager slice that compiles once a shape. Warmed, an epoch's first LIVE
    prefill at each width compiles nothing (``compiles_in_window``)."""
    from cake_tpu.obs import jitwatch

    config, loaded, *_ = model
    be = backend(config, loaded)
    assert be.shapes.whole_batch and jitwatch.install_compile_listener()
    be.warm_programs(4, GREEDY, 2 * B)
    widths = be.shapes.widths
    assert len(widths) > 1
    before = jitwatch.compile_totals()[0]
    for width in widths:
        cache = be.init_kv(4)
        tokens = np.full((4, width), 7, np.int32)
        be.allocator.map_range(0, 0, width)
        be.prefill(tokens, cache, jnp.zeros((4,), jnp.int32))
        be.allocator.reset(batch=1)
    assert jitwatch.compile_totals()[0] == before


def test_the_judge_reads_the_excess_of_the_deficits_over_the_threshold():
    """``judged_rows`` hands ``bench/reference.py``'s worst-position judge rows
    in which every served position reads the call's ``judged_number``: the
    mean excess of the deficits over ``EXCESS_OVER``. Near-ties under it count
    nothing, however many; what is over it counts by how far."""
    from bench import reference

    arch = architecture(REPO, HF)
    rng = np.random.default_rng(5)
    rows = [rng.standard_normal((8, 512)).astype(np.float32) for _ in range(2)]
    served = [r.argmax(-1).tolist() for r in rows]  # the reference's own best tokens

    def judged(rows):
        fake = type("A", (), {"forward_logits": staticmethod(
            lambda reader, cfg, seqs, first, timing: (
                timing.update(load_s=[0.0], layer_s=[0.0]), arch.judged_rows(rows, served))[1])})
        return reference.judge(fake, None, {}, 1.0, [{"context": [0], "served": s} for s in served])

    assert judged(rows)["worst"] == 0.0
    # six near-ties of 0.04 of a spread: no excess; one miss of 0.21: 0.16 over 16 positions
    near = [r.copy() for r in rows]

    def miss(row, tok, by):
        """The served token ``by`` spreads under the row's other logits' largest."""
        row[tok] = -np.inf
        row[tok] = row.max() - by * np.delete(row, tok).std()

    for j in range(6):
        miss(near[0][j], served[0][j], 0.04)
    assert 0.03 < float(arch.deficits(near[0], served[0]).max()) < 0.05
    assert judged(near)["worst"] < 1e-6
    miss(near[1][3], served[1][3], 0.21)
    want = arch.judged_number([arch.deficits(r, s) for r, s in zip(near, served)])
    assert 0.15 / 16 < want < 0.17 / 16
    got = judged(near)
    assert got["worst"] == pytest.approx(want, rel=1e-3) and got["per_probe"][0] == pytest.approx(want, rel=1e-3)
