"""Mixtral sparse-MoE family: HF parity, expert parallelism, quantization.

Expert parallelism is absent from the reference (SURVEY.md §2.7 row "EP:
none — dense Llama only"); this is a beyond-parity family. The oracle
hierarchy mirrors the other families: HF transformers (external truth) for
numerics, then sharded == local for every execution backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from cake_tpu.io.safetensors_io import load_params, save_tiny_checkpoint
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import init_cache
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import LocalForwardStep
from cake_tpu.parallel.tensor import TensorParallelRunner, validate_tp

MAX_SEQ = 64


def make_mixtral_checkpoint(tmp_path, seed=0, n_experts=4, top_k=2):
    cfg = transformers.MixtralConfig(
        hidden_size=64,
        intermediate_size=96,
        vocab_size=512,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=n_experts,
        num_experts_per_tok=top_k,
        rope_theta=10000.0,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        bos_token_id=256,
        eos_token_id=260,
        sliding_window=None,
        attn_implementation="eager",
    )
    torch.manual_seed(seed)
    model = transformers.MixtralForCausalLM(cfg).eval().to(torch.float32)
    model.save_pretrained(tmp_path, safe_serialization=True)
    return model


def hf_greedy(model, prompt_ids, n_steps):
    ids = torch.tensor([prompt_ids], dtype=torch.long)
    out = []
    with torch.no_grad():
        for _ in range(n_steps):
            logits = model(ids).logits[0, -1]
            nxt = int(torch.argmax(logits))
            out.append(nxt)
            ids = torch.cat([ids, torch.tensor([[nxt]])], dim=1)
    return out


def ours_greedy(model_dir, prompt_ids, n_steps):
    cfg = LlamaConfig.from_model_dir(model_dir)
    params = load_params(model_dir, cfg, jnp.float32)
    kv = init_cache(
        cfg.num_hidden_layers, 1, MAX_SEQ, cfg.num_key_value_heads,
        cfg.head_dim, jnp.float32,
    )
    fwd = jax.jit(M.forward, static_argnames=("config",), donate_argnames=("kv",))
    logits, kv = fwd(
        params, jnp.asarray([prompt_ids], jnp.int32), kv, jnp.int32(0),
        jnp.int32(len(prompt_ids)), cfg,
    )
    out = []
    pos = len(prompt_ids)
    for _ in range(n_steps):
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        logits, kv = fwd(
            params, jnp.asarray([[nxt]], jnp.int32), kv, jnp.int32(pos),
            jnp.int32(1), cfg,
        )
        pos += 1
    return out


def test_mixtral_config_parses(tmp_path):
    make_mixtral_checkpoint(tmp_path)
    cfg = LlamaConfig.from_model_dir(tmp_path)
    assert cfg.model_type == "mixtral"
    assert cfg.num_local_experts == 4
    assert cfg.num_experts_per_tok == 2


def test_mixtral_greedy_tokens_match_transformers(tmp_path):
    hf_model = make_mixtral_checkpoint(tmp_path, seed=1)
    prompt = [256, 7, 301, 42, 42, 9, 123, 77]
    assert ours_greedy(tmp_path, prompt, 16) == hf_greedy(hf_model, prompt, 16)


def test_mixtral_prefill_logits_match_transformers(tmp_path):
    """Full-position logits (routing is position-dependent — every token must
    route identically to HF, not just the argmax survive)."""
    hf_model = make_mixtral_checkpoint(tmp_path, seed=2)
    prompt = [256, 11, 205, 499, 3, 3, 64, 90]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    cfg = LlamaConfig.from_model_dir(tmp_path)
    params = load_params(tmp_path, cfg, jnp.float32)
    kv = init_cache(
        cfg.num_hidden_layers, 1, MAX_SEQ, cfg.num_key_value_heads,
        cfg.head_dim, jnp.float32,
    )
    logits, _ = M.forward_all_logits(
        params, jnp.asarray([prompt], jnp.int32), kv, jnp.int32(0), cfg,
        cached_prefill=False,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), hf_logits, atol=3e-4, rtol=3e-4
    )


def test_mixtral_top1_routing(tmp_path):
    """num_experts_per_tok=1: the degenerate top-1 renormalization (weight
    exactly 1.0 on one expert)."""
    hf_model = make_mixtral_checkpoint(tmp_path, seed=3, top_k=1)
    prompt = [256, 5, 77, 140, 9]
    assert ours_greedy(tmp_path, prompt, 10) == hf_greedy(hf_model, prompt, 10)


def _moe_cfg(**kw):
    kw.setdefault("model_type", "mixtral")
    kw.setdefault("num_local_experts", 4)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("intermediate_size", 96)
    return LlamaConfig.tiny(**kw)


def _drive(step, tokens):
    n = tokens.shape[1]
    outs = [step(tokens, 0, n)]
    pos = n
    for _ in range(3):
        nxt = np.argmax(outs[-1], -1).astype(np.int32)[:, None]
        outs.append(step(nxt, pos, 1))
        pos += 1
    return np.stack(outs)


@pytest.mark.parametrize("tp", [2, 4])
def test_moe_expert_parallel_matches_local(tp):
    """Experts sharded over the tp axis == single-device oracle."""
    cfg = _moe_cfg(num_attention_heads=8, num_key_value_heads=4)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 10)
    ).astype(np.int32)
    local = LocalForwardStep(
        cfg, params, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
    )
    ep = TensorParallelRunner(
        cfg, params, tp=tp, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        _drive(ep, tokens), _drive(local, tokens), atol=2e-4, rtol=2e-4
    )


def test_moe_tp_requires_divisible_experts():
    with pytest.raises(ValueError, match="num_local_experts"):
        validate_tp(_moe_cfg(num_local_experts=5), 2)


def test_moe_checkpoint_roundtrip(tmp_path):
    """save_tiny_checkpoint -> load_params preserves MoE numerics exactly."""
    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    save_tiny_checkpoint(tmp_path, params, cfg)
    loaded = load_params(tmp_path, cfg, jnp.float32)
    for k in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(
            np.asarray(loaded["layers"][k]), np.asarray(params["layers"][k]), k
        )


def test_moe_int8_quantization_bounded_drift(tmp_path):
    """int8 expert weights run through the quant-aware einsum path; logits
    stay close to full precision (loose bound: rounding only)."""
    from cake_tpu.ops.quant import quantize_params

    cfg = _moe_cfg(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    qparams = quantize_params(params)
    tokens = jnp.asarray([[256, 4, 9, 33]], jnp.int32)

    def run(p):
        kv = init_cache(
            cfg.num_hidden_layers, 1, MAX_SEQ, cfg.num_key_value_heads,
            cfg.head_dim, jnp.float32,
        )
        logits, _ = M.forward(p, tokens, kv, jnp.int32(0), jnp.int32(4), cfg)
        return np.asarray(logits)

    full, quant = run(params), run(qparams)
    assert np.isfinite(quant).all()
    # Same top token and small absolute drift for a tiny random model.
    assert int(full.argmax()) == int(quant.argmax())
    assert np.abs(full - quant).max() < 0.3


def test_moe_worker_layer_range_load(tmp_path):
    """A worker loading only its block range gets stacked MoE weights for
    exactly those layers (worker.rs:95-108 analogue)."""
    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(4), jnp.float32)
    save_tiny_checkpoint(tmp_path, params, cfg)
    shard = load_params(tmp_path, cfg, jnp.float32, layer_range=(1, 3))
    assert shard["layers"]["w_gate"].shape == (2, 4, 64, 96)
    np.testing.assert_array_equal(
        np.asarray(shard["layers"]["router"]),
        np.asarray(params["layers"]["router"][1:3]),
    )


def test_moe_pipeline_matches_local():
    """MoE layers sharded across ragged pipeline stages == local oracle
    (zero-padded experts inert, router replicated per stage)."""
    from cake_tpu.parallel.pipeline import PipelineRunner

    cfg = _moe_cfg(num_hidden_layers=5)
    params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    tokens = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, 9)
    ).astype(np.int32)
    local = LocalForwardStep(
        cfg, params, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
    )
    pipe = PipelineRunner(
        cfg, params, [(0, 2), (2, 5)], max_seq_len=MAX_SEQ,
        cache_dtype=jnp.float32,
    )
    np.testing.assert_allclose(
        _drive(pipe, tokens), _drive(local, tokens), atol=2e-4, rtol=2e-4
    )


def test_moe_generator_end_to_end(tmp_path):
    """LlamaGenerator.load over a Mixtral checkpoint dir: template dispatch
    ([INST]) + greedy decode + reset determinism."""
    from cake_tpu.models.llama.generator import LlamaGenerator, SamplingConfig
    from cake_tpu.models.llama.chat import Message

    cfg = _moe_cfg(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(6), jnp.float32)
    save_tiny_checkpoint(tmp_path, params, cfg)
    gen = LlamaGenerator.load(
        tmp_path, dtype=jnp.float32, max_seq_len=MAX_SEQ,
        sampling=SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    )
    assert gen.config.num_local_experts == 4
    gen.add_message(Message.user("hello moe"))
    gen.generate(6)
    ids = list(gen.generated_token_ids)
    assert gen._prompt_cache[0].startswith("<s>[INST] hello moe [/INST]")
    gen.reset()
    gen.add_message(Message.user("hello moe"))
    gen.generate(6)
    assert list(gen.generated_token_ids) == ids


def test_moe_sequence_parallel_matches_local():
    """Ring-attention SP serving over a MoE model == local oracle (experts
    replicated over sp; MLP type is orthogonal to the sequence sharding)."""
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.generator import LlamaGenerator, SamplingConfig
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.parallel.sequence import SequenceParallelRunner

    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    cfg = _moe_cfg(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    prompt = "moe over sequence shards needs a longish prompt"

    def run(step):
        gen = LlamaGenerator(cfg, step, ByteTokenizer(), greedy)
        gen.add_message(Message.user(prompt))
        gen.generate(8)
        return gen.generated_token_ids

    ref = run(LocalForwardStep(cfg, params, max_seq_len=256,
                               cache_dtype=jnp.float32))
    got = run(SequenceParallelRunner(cfg, params, sp=4, max_seq_len=256,
                                     cache_dtype=jnp.float32))
    assert got == ref


def test_moe_tcp_workers_match_local(tmp_path):
    """TCP workers serving MoE layer ranges == local oracle (worker-side
    blocks_forward + range loading carry the router/expert weights)."""
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.generator import (
        LlamaGenerator,
        SamplingConfig,
    )
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedForwardStep
    from cake_tpu.runtime.worker import Worker

    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    cfg = _moe_cfg(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(8), jnp.float32)
    model_dir = tmp_path / "model"
    save_tiny_checkpoint(model_dir, params, cfg)
    topo = Topology.from_dict(
        {"w1": {"host": "x", "layers": ["model.layers.1-2"]}}
    )
    w = Worker(
        "w1", model_dir, topo, ("127.0.0.1", 0), dtype=jnp.float32,
        max_seq_len=MAX_SEQ,
    )
    w.start()
    topo.nodes["w1"].host = f"127.0.0.1:{w.address[1]}"
    try:
        def run(step):
            gen = LlamaGenerator(cfg, step, ByteTokenizer(), greedy)
            gen.add_message(Message.user("moe over tcp"))
            gen.generate(6)
            return gen.generated_token_ids

        ref = run(LocalForwardStep(cfg, params, max_seq_len=MAX_SEQ,
                                   cache_dtype=jnp.float32))
        got = run(DistributedForwardStep(
            cfg, model_dir, topo, dtype=jnp.float32, max_seq_len=MAX_SEQ,
        ))
        assert got == ref
    finally:
        w.stop()


# ----------------------------------------------------------------- Qwen2-MoE


def make_qwen2_moe_checkpoint(tmp_path, seed=0, norm_topk=False, top_k=2):
    cfg = transformers.Qwen2MoeConfig(
        hidden_size=64,
        intermediate_size=96,
        moe_intermediate_size=80,
        shared_expert_intermediate_size=112,
        vocab_size=512,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_experts=4,
        num_experts_per_tok=top_k,
        norm_topk_prob=norm_topk,
        rope_theta=10000.0,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        bos_token_id=256,
        eos_token_id=260,
        use_sliding_window=False,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        attn_implementation="eager",
    )
    torch.manual_seed(seed)
    model = transformers.Qwen2MoeForCausalLM(cfg).eval().to(torch.float32)
    model.save_pretrained(tmp_path, safe_serialization=True)
    return model


def test_qwen2_moe_config_parses(tmp_path):
    make_qwen2_moe_checkpoint(tmp_path)
    cfg = LlamaConfig.from_model_dir(tmp_path)
    assert cfg.model_type == "qwen2_moe"
    assert cfg.num_local_experts == 4
    assert cfg.norm_topk_prob is False
    assert cfg.attention_bias  # qwen2-family QKV bias
    assert cfg.moe_intermediate_size == 80
    assert cfg.shared_expert_intermediate_size == 112
    assert cfg.dialog_template == "qwen2_moe"  # -> ChatML encoder


def test_qwen2_moe_greedy_tokens_match_transformers(tmp_path):
    """Shared expert + sigmoid gate + unnormalized top-k routing + QKV bias,
    all pinned against transformers at once."""
    hf_model = make_qwen2_moe_checkpoint(tmp_path, seed=1)
    prompt = [256, 7, 301, 42, 42, 9, 123, 77]
    assert ours_greedy(tmp_path, prompt, 16) == hf_greedy(hf_model, prompt, 16)


def test_qwen2_moe_prefill_logits_match_transformers(tmp_path):
    hf_model = make_qwen2_moe_checkpoint(tmp_path, seed=2, norm_topk=True)
    prompt = [256, 11, 205, 499, 3, 3, 64, 90]
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor([prompt])).logits[0].numpy()
    cfg = LlamaConfig.from_model_dir(tmp_path)
    assert cfg.norm_topk_prob is True
    params = load_params(tmp_path, cfg, jnp.float32)
    kv = init_cache(
        cfg.num_hidden_layers, 1, MAX_SEQ, cfg.num_key_value_heads,
        cfg.head_dim, jnp.float32,
    )
    logits, _ = M.forward_all_logits(
        params, jnp.asarray([prompt], jnp.int32), kv, jnp.int32(0), cfg,
        cached_prefill=False,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), hf_logits, atol=3e-4, rtol=3e-4
    )


def test_qwen2_moe_rejects_mixed_dense_sparse(tmp_path):
    import json

    make_qwen2_moe_checkpoint(tmp_path)
    cfg_path = tmp_path / "config.json"
    d = json.loads(cfg_path.read_text())
    d["decoder_sparse_step"] = 2
    cfg_path.write_text(json.dumps(d))
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        LlamaConfig.from_model_dir(tmp_path)


def _qwen2_moe_cfg(**kw):
    kw.setdefault("model_type", "qwen2_moe")
    kw.setdefault("num_local_experts", 4)
    kw.setdefault("num_experts_per_tok", 2)
    kw.setdefault("norm_topk_prob", False)
    kw.setdefault("attention_bias", True)
    kw.setdefault("moe_intermediate_size", 80)
    kw.setdefault("shared_expert_intermediate_size", 112)
    return LlamaConfig.tiny(**kw)


def test_qwen2_moe_expert_parallel_matches_local():
    """Experts AND the shared expert shard over tp (experts on the expert
    axis, shared on its intermediate) == single-device oracle."""
    cfg = _qwen2_moe_cfg(num_attention_heads=8, num_key_value_heads=4)
    params = M.init_params(cfg, jax.random.PRNGKey(10), jnp.float32)
    tokens = np.random.default_rng(5).integers(
        0, cfg.vocab_size, (1, 10)
    ).astype(np.int32)
    local = LocalForwardStep(
        cfg, params, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
    )
    ep = TensorParallelRunner(
        cfg, params, tp=2, max_seq_len=MAX_SEQ, cache_dtype=jnp.float32
    )
    np.testing.assert_allclose(
        _drive(ep, tokens), _drive(local, tokens), atol=2e-4, rtol=2e-4
    )


def test_qwen2_moe_checkpoint_roundtrip_and_quant(tmp_path):
    cfg = _qwen2_moe_cfg(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    save_tiny_checkpoint(tmp_path, params, cfg)
    loaded = load_params(tmp_path, cfg, jnp.float32)
    for k in ("router", "w_gate", "sh_gate", "sh_down", "se_gate", "bq"):
        np.testing.assert_array_equal(
            np.asarray(loaded["layers"][k]), np.asarray(params["layers"][k]), k
        )

    from cake_tpu.ops.quant import quantize_params

    qparams = quantize_params(loaded)
    tokens = jnp.asarray([[256, 4, 9, 33]], jnp.int32)
    kv = init_cache(
        cfg.num_hidden_layers, 1, MAX_SEQ, cfg.num_key_value_heads,
        cfg.head_dim, jnp.float32,
    )
    logits, _ = M.forward(
        qparams, tokens, kv, jnp.int32(0), jnp.int32(4), cfg
    )
    assert np.isfinite(np.asarray(logits)).all()


def test_qwen2_moe_windowed_roundtrip_and_topk_default():
    """Review findings: the window must survive to_hf/from_hf, and an
    omitted num_experts_per_tok must follow HF's per-family default (4)."""
    import dataclasses

    cfg = _qwen2_moe_cfg(sliding_window=16)
    back = LlamaConfig.from_hf_dict(cfg.to_hf_dict())
    assert back.sliding_window == 16

    d = _qwen2_moe_cfg().to_hf_dict()
    del d["num_experts_per_tok"]
    assert LlamaConfig.from_hf_dict(d).num_experts_per_tok == 4
    d2 = dataclasses.replace(
        LlamaConfig.tiny(model_type="mixtral", num_local_experts=4)
    ).to_hf_dict()
    del d2["num_experts_per_tok"]
    assert LlamaConfig.from_hf_dict(d2).num_experts_per_tok == 2


@pytest.mark.parametrize("norm_topk,quantized", [
    (True, False), (False, False), (True, True),
])
def test_moe_grouped_dispatch_matches_dense(norm_topk, quantized):
    """The sorted/grouped ragged_dot dispatch (prefill chunks) must reproduce
    the dense masked-combine path bit-near-exactly for both weight
    representations and both renorm conventions. The transformers
    cross-checks above exercise the grouped path end-to-end (prefill chunks
    are >= GROUPED_MIN_TOKENS); this pins the two internal paths against
    each other directly."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.ops.quant import quantize_weight

    rng = np.random.default_rng(11)
    b, t, h, inter, e, k = 2, 16, 32, 64, 8, 2
    x = jnp.asarray(rng.standard_normal((b, t, h)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((h, e)) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, h, inter)) * h**-0.5, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, h, inter)) * h**-0.5, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, inter, h)) * inter**-0.5, jnp.float32)
    if quantized:
        wg, wu, wd = quantize_weight(wg), quantize_weight(wu), quantize_weight(wd)

    old = moe.GROUPED_MIN_TOKENS
    try:
        moe.GROUPED_MIN_TOKENS = 10**9
        dense = moe.moe_swiglu(x, router, wg, wu, wd, k, norm_topk=norm_topk)
        moe.GROUPED_MIN_TOKENS = 0
        grouped = moe.moe_swiglu(x, router, wg, wu, wd, k, norm_topk=norm_topk)
    finally:
        moe.GROUPED_MIN_TOKENS = old
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(dense), atol=2e-6, rtol=2e-6
    )


# ---------------------------------------------------------- expert capacity


def test_capacity_dispatch_flops_scale_with_capacity():
    """The point of the capacity path: tp-sharded prefill MLP FLOPs ∝ the
    per-expert budget (~ k/tp of the dense all-experts combine), measured on
    the compiled per-device program."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.parallel.tensor import TP_AXIS, checked_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = _moe_cfg(
        num_local_experts=8, num_experts_per_tok=2, intermediate_size=256,
        hidden_size=128,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    lp = params["layers"]
    mesh = Mesh(np.array(jax.devices()[:2]), (TP_AXIS,))
    x = jnp.ones((1, 64, cfg.hidden_size), jnp.float32)

    def flops_with(min_tokens):
        old = moe.GROUPED_MIN_TOKENS
        moe.GROUPED_MIN_TOKENS = min_tokens
        try:
            def body(x, router, wg, wu, wd):
                return moe.moe_swiglu(
                    x, router, wg, wu, wd, cfg.num_experts_per_tok,
                    tp_axis=TP_AXIS,
                )

            mapped = checked_shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P(TP_AXIS), P(TP_AXIS), P(TP_AXIS)),
                out_specs=P(),
            )
            lowered = jax.jit(mapped).lower(
                x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
                lp["w_down"][0],
            )
            a = lowered.compile().cost_analysis()
            if isinstance(a, list):
                a = a[0]
            return float(a["flops"])
        finally:
            moe.GROUPED_MIN_TOKENS = old

    dense = flops_with(10**9)  # force the dense all-experts combine
    capacity = flops_with(8)  # the capacity path (64 tokens >= 8)
    # Ideal MLP ratio = cf*k/E = 2*2/8 = 0.5; routing/scatter overhead eats
    # some of it — require a solid margin.
    assert capacity < 0.7 * dense, (capacity, dense)


def test_capacity_dispatch_drop_free_parity():
    """With the budget at or above the worst-case per-expert load (cap >= n,
    since each token selects an expert at most once), the capacity path must
    match the dense tp combine to reduction-order tolerance."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.parallel.tensor import TP_AXIS, checked_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(6), jnp.float32)
    lp = params["layers"]
    mesh = Mesh(np.array(jax.devices()[:2]), (TP_AXIS,))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 12, cfg.hidden_size))

    def run(min_tokens):
        old = moe.GROUPED_MIN_TOKENS
        moe.GROUPED_MIN_TOKENS = min_tokens
        try:
            def body(x, router, wg, wu, wd):
                part = moe.moe_swiglu(
                    x, router, wg, wu, wd, cfg.num_experts_per_tok,
                    tp_axis=TP_AXIS,
                )
                return jax.lax.psum(part, TP_AXIS)

            mapped = checked_shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), P(), P(TP_AXIS), P(TP_AXIS), P(TP_AXIS)),
                out_specs=P(),
            )
            return np.asarray(
                jax.jit(mapped)(
                    x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
                    lp["w_down"][0],
                )
            )
        finally:
            moe.GROUPED_MIN_TOKENS = old

    # n = 24 tokens, E = 4, k = 2 -> cap = ceil(2*48/4) = 24 = n: drop-free
    # by construction (a token contributes at most one row per expert).
    np.testing.assert_allclose(run(8), run(10**9), atol=2e-5, rtol=2e-5)


def test_capacity_dispatch_overflow_drops_are_bounded():
    """Forcing a tiny budget (EP_CAPACITY_FACTOR < 1) must stay finite and
    close to the dense result in norm — the documented routing-drop trade."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.parallel.tensor import TP_AXIS, checked_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(8), jnp.float32)
    lp = params["layers"]
    mesh = Mesh(np.array(jax.devices()[:2]), (TP_AXIS,))
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 32, cfg.hidden_size))

    def run_once():
        # Built FRESH per run: EP_CAPACITY_FACTOR is read at trace time, and
        # jax caches traces on the underlying callable.
        def body(x, router, wg, wu, wd):
            part = moe.moe_swiglu(
                x, router, wg, wu, wd, cfg.num_experts_per_tok,
                tp_axis=TP_AXIS,
            )
            return jax.lax.psum(part, TP_AXIS)

        mapped = checked_shard_map(
            body,
            mesh=mesh,
            in_specs=(P(), P(), P(TP_AXIS), P(TP_AXIS), P(TP_AXIS)),
            out_specs=P(),
        )
        return np.asarray(
            jax.jit(mapped)(
                x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
                lp["w_down"][0],
            )
        )

    full = run_once()
    old = moe.EP_CAPACITY_FACTOR
    moe.EP_CAPACITY_FACTOR = 0.5
    try:
        tight = run_once()
    finally:
        moe.EP_CAPACITY_FACTOR = old
    assert np.isfinite(tight).all()
    # Drops remove SOME contributions; the outputs stay in the same regime.
    rel = np.linalg.norm(tight - full) / np.linalg.norm(full)
    assert 0.0 < rel < 1.0, rel


def test_capacity_dispatch_pads_do_not_consume_capacity():
    """Left-pad slots (sentinel-position rows in lockstep batches) must not
    eat the expert budget ahead of real tokens: with the valid mask, the
    capacity output at real positions matches the dense combine; without it,
    a pad pile-up evicts real contributions."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.parallel.tensor import TP_AXIS, checked_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(10), jnp.float32)
    lp = params["layers"]
    mesh = Mesh(np.array(jax.devices()[:2]), (TP_AXIS,))
    h = cfg.hidden_size
    # 8 identical "pad" vectors (they all route to the same top-2 experts)
    # followed by 8 real tokens; budget cf=1.0 -> cap = 8 per expert, so the
    # pads alone can fill their experts' budgets.
    pad_vec = jnp.ones((1, 1, h)) * 0.7
    real = jax.random.normal(jax.random.PRNGKey(11), (1, 8, h))
    x = jnp.concatenate([jnp.tile(pad_vec, (1, 8, 1)), real], axis=1)
    valid = jnp.asarray([[False] * 8 + [True] * 8])

    def run(use_mask, min_tokens):
        old_mt, old_cf = moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR
        moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR = min_tokens, 1.0
        try:
            def body(x, router, wg, wu, wd):
                part = moe.moe_swiglu(
                    x, router, wg, wu, wd, cfg.num_experts_per_tok,
                    tp_axis=TP_AXIS, valid=valid if use_mask else None,
                )
                return jax.lax.psum(part, TP_AXIS)

            mapped = checked_shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(TP_AXIS), P(TP_AXIS), P(TP_AXIS)),
                out_specs=P(),
            )
            return np.asarray(
                jax.jit(mapped)(
                    x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
                    lp["w_down"][0],
                )
            )[0, 8:]  # real positions only
        finally:
            moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR = old_mt, old_cf

    dense = run(False, 10**9)  # dense combine = the drop-free oracle
    masked = run(True, 8)
    np.testing.assert_allclose(masked, dense, atol=2e-5, rtol=2e-5)


def test_dispatch_dense_forces_drop_free_even_with_min_tokens_zero():
    """dispatch="dense" (speculative verify chunks) must bypass BOTH grouped
    branches even under the documented GROUPED_MIN_TOKENS=0 forcing knob —
    output equals the dense combine exactly, never the droppy capacity path."""
    import cake_tpu.ops.moe as moe
    from cake_tpu.parallel.tensor import TP_AXIS, checked_shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = _moe_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(12), jnp.float32)
    lp = params["layers"]
    mesh = Mesh(np.array(jax.devices()[:2]), (TP_AXIS,))
    x = jax.random.normal(jax.random.PRNGKey(13), (1, 16, cfg.hidden_size))

    def run(dispatch, min_tokens, cf):
        old_mt, old_cf = moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR
        moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR = min_tokens, cf
        try:
            def body(x, router, wg, wu, wd):
                part = moe.moe_swiglu(
                    x, router, wg, wu, wd, cfg.num_experts_per_tok,
                    tp_axis=TP_AXIS, dispatch=dispatch,
                )
                return jax.lax.psum(part, TP_AXIS)

            mapped = checked_shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P(TP_AXIS), P(TP_AXIS), P(TP_AXIS)),
                out_specs=P(),
            )
            return np.asarray(
                jax.jit(mapped)(
                    x, lp["router"][0], lp["w_gate"][0], lp["w_up"][0],
                    lp["w_down"][0],
                )
            )
        finally:
            moe.GROUPED_MIN_TOKENS, moe.EP_CAPACITY_FACTOR = old_mt, old_cf

    oracle = run("auto", 10**9, 2.0)  # dense combine (width below threshold)
    # A tight capacity factor WOULD drop if the capacity path ran; "dense"
    # with GROUPED_MIN_TOKENS=0 must still match the oracle bit-for-bit.
    forced = run("dense", 0, 0.25)
    np.testing.assert_array_equal(forced, oracle)


# ------------------------------------------- the rule that chooses the path


def _cell_dispatches():
    """Every routed dispatch the benchmark's cells can run, from their
    configurations' own flags and the closed shapes those make: (case id,
    rows in the dispatch, a row's chunk, experts a token, experts ranked,
    the path ``dispatch="auto"`` must take). A decode step at every lane
    count an epoch can have, every join, every group of an epoch's prefill."""
    import glob
    import json
    import os

    from cake_tpu.runtime.shapes import ProgramShapes

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cases = {}
    for path in sorted(glob.glob(os.path.join(root, "bench/configs/*.json"))):
        with open(path) as f:
            hf = json.load(f)
        config = LlamaConfig.from_hf_dict(hf)
        if config.n_router_experts <= 1:
            continue  # Mistral's, Jamba's, Olmo-Hybrid's: no router
        flags = hf["server_flags"]
        flag = lambda name: int(flags[flags.index(name) + 1])  # noqa: E731
        page, lanes = flag("--page-size"), flag("--api-batch")
        shapes = ProgramShapes.for_model(config, page, flag("--max-seq-len") // page)
        epochs = sorted({shapes.lanes(seeds, lanes) for seeds in range(1, lanes + 1)})
        # (a pass of a block-diffusion model is a block's rows a lane)
        dispatches = {("decode", b * max(1, config.block_length), 1) for b in epochs}
        for width in shapes.widths:
            dispatches.add(("join", width, width))
            for b in epochs:
                group = shapes.prefill_group(b, width)
                if not (shapes.one_row_prefill_is_join and group == 1):
                    dispatches.add(("prefill", group * width, width))
        for op, rows, chunk in sorted(dispatches):
            dense = config.model_type == "lfm2_moe" and op == "decode"
            cases[f"{config.model_type}.{op}.{rows}"] = (
                rows, chunk, config.num_experts_per_tok, config.n_router_experts,
                "dense" if dense else "grouped",
            )
    return cases


_CELLS = _cell_dispatches()
_RULE = {
    **_CELLS,
    # (rows, chunk, top_k, ranked, path[, more])
    "mixtral.decode.16": (16, 1, 2, 8, "grouped"),  # 0.75 ** 16 = 1.002%
    "mixtral.decode.17": (17, 1, 2, 8, "dense"),
    "lfm2.a_tile_of_rows": (128, 1, 4, 32, "dense"),
    "lfm2.a_row_past_a_tile": (129, 1, 4, 32, "grouped"),
    "lfm2.too_few_rows_touch_every_expert": (34, 1, 4, 32, "grouped"),  # 1.07%
    "tp.prefill_chunk": (32, 16, 2, 8, "capacity", dict(tp=True)),
    "tp.decode_step": (32, 1, 2, 8, "dense", dict(tp=True)),
    "tp.decode_step_few_rows": (8, 1, 2, 8, "grouped", dict(tp=True)),
    "tp.verify_chunk_forced_dense": (32, 16, 2, 8, "dense", dict(tp=True, dispatch="dense")),
    "forced.dense": (4096, 4096, 8, 256, "dense", dict(dispatch="dense")),
    "forced.grouped": (64, 1, 4, 32, "grouped", dict(dispatch="grouped")),
}


def test_the_cells_dispatches_are_all_there():
    families = {name.split(".")[0] for name in _CELLS}
    assert families == {"lfm2_moe", "pangu_ultra_moe", "laguna", "deepseek_v32", "qwen3_next",
                        "sdar_moe"}
    assert [n for n, c in _CELLS.items() if c[-1] == "dense"] == ["lfm2_moe.decode.64"]
    # the narrowest and the widest join of each, and Pangu's 128-slot one
    for name in ("lfm2_moe.join.256", "lfm2_moe.join.4096", "pangu_ultra_moe.join.64",
                 "pangu_ultra_moe.join.128", "pangu_ultra_moe.join.4096",
                 "pangu_ultra_moe.decode.64", "laguna.decode.32", "laguna.join.1536",
                 "laguna.join.24576", "deepseek_v32.decode.16", "deepseek_v32.join.2688",
                 "deepseek_v32.join.21504",
                 # 64 rows that choose 10 of 512 leave 28% untouched: GROUPED (PR 53)
                 "qwen3_next.decode.64", "qwen3_next.join.256", "qwen3_next.join.4096",
                 # a pass's 256 rows that choose 8 of 128 touch every expert and
                 # are wider than a tile: GROUPED (PR 57)
                 "sdar_moe.decode.256", "sdar_moe.join.256", "sdar_moe.join.4096"):
        assert name in _CELLS, sorted(_CELLS)


@pytest.mark.parametrize("case", sorted(_RULE))
def test_the_rule_takes_the_path_the_shapes_say(case):
    """``dispatch_path`` at every dispatch the cells run (LFM2's decode chunk
    alone takes the dense combine: 64 rows that choose 4 of 32 leave 0.02% of
    the experts untouched; Pangu's, Laguna's and DeepSeek's decode steps
    leave 13 to 97%, and every join and prefill but Pangu's two narrowest is
    wider than a tile), at the rule's edges, under ``--tp`` and forced."""
    import cake_tpu.ops.moe as moe

    rows, chunk, top_k, ranked, path, *more = _RULE[case]
    assert moe.dispatch_path(rows, chunk, top_k, ranked, **(more[0] if more else {})) == path


@pytest.mark.parametrize("min_tokens,path", [(0, "grouped"), (10**9, "dense")])
@pytest.mark.parametrize("case", ["lfm2_moe.decode.64", "lfm2_moe.join.256",
                                  "pangu_ultra_moe.decode.64", "pangu_ultra_moe.join.128"])
def test_a_test_forces_either_path_process_wide(monkeypatch, case, min_tokens, path):
    import cake_tpu.ops.moe as moe

    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", min_tokens)
    assert moe.dispatch_path(*_CELLS[case][:4]) == path
    # a --tp prefill chunk keeps its buckets under the grouped forcing
    assert moe.dispatch_path(32, 16, 2, 8, tp=True) == ("capacity" if min_tokens == 0 else "dense")


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.05)])
def test_the_dense_combine_at_lfm2s_shape_is_the_grouped_path(dtype, tol):
    """LFM2's decode dispatch: 64 rows of one token, 4 of 32 experts by
    sigmoid scores behind a selection bias, every third row dead. The rule
    takes the dense combine (no grouped product in the program); on the live
    rows it is the grouped path within the seam's tolerance (in units of the
    result's spread), on the dead rows both are zero, and the account's
    counts are the same digits whichever path ran."""
    import cake_tpu.ops.moe as moe

    rng = np.random.default_rng(50)
    n, h, inter, e, k = 64, 64, 32, 32, 4
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((n, 1, h)), dt)
    router = jnp.asarray(rng.standard_normal((h, e)) * 0.1, dt)
    bias = jnp.asarray(rng.standard_normal((e,)) * 0.02, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, h, inter)) * h**-0.5, dt)
    wu = jnp.asarray(rng.standard_normal((e, h, inter)) * h**-0.5, dt)
    wd = jnp.asarray(rng.standard_normal((e, inter, h)) * inter**-0.5, dt)
    valid = (np.arange(n) % 3 != 2)[:, None]

    def run(dispatch):
        def fn(*a):
            return moe.moe_swiglu(
                *a, k, scoring="sigmoid", router_bias=bias, valid=jnp.asarray(valid),
                dispatch=dispatch, with_counts=True)

        out, counts = jax.jit(fn)(x, router, wg, wu, wd)
        program = str(jax.make_jaxpr(fn)(x, router, wg, wu, wd))
        return np.asarray(out, np.float32)[:, 0], np.asarray(counts), "ragged_dot" in program

    auto, auto_counts, auto_grouped = run("auto")
    dense, dense_counts, _ = run("dense")
    grouped, grouped_counts, is_grouped = run("grouped")
    assert is_grouped and not auto_grouped
    np.testing.assert_array_equal(auto, dense)
    live = valid[:, 0]
    assert not dense[~live].any() and not grouped[~live].any()
    assert np.abs(dense[live] - grouped[live]).max() <= tol * grouped[live].std()
    np.testing.assert_array_equal(dense_counts, grouped_counts)
    np.testing.assert_array_equal(auto_counts, grouped_counts)
    routed, held, touched, _ = (int(c) for c in auto_counts)
    assert routed == held == int(live.sum()) * k and e - 1 <= touched <= e
