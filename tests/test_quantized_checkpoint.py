"""Offline checkpoint quantizer (io/quantizer.py) + quantized loading.

The contract: a quantized checkpoint loads to EXACTLY the tree
quantize_params builds in memory (bit-identical leaves), so every runtime
quantization oracle transfers to the offline path; and the quantized
checkpoint stays a drop-in directory (workers, splitter, generator.load).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    LlamaGenerator,
    LocalForwardStep,
    SamplingConfig,
)
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.io.quantizer import quantize_checkpoint
from cake_tpu.io.safetensors_io import load_params, save_tiny_checkpoint
from cake_tpu.ops.quant import (
    Quant4Weight,
    QuantWeight,
    quantize_params,
    tree_quantization,
)

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)


def _trees_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves_with_path(a)
    lb = dict(jax.tree_util.tree_leaves_with_path(b))
    if len(la) != len(lb):
        return False
    return all(
        path in lb and np.array_equal(np.asarray(leaf), np.asarray(lb[path]))
        for path, leaf in la
    )


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_checkpoint_roundtrips_bitwise(tmp_path, mode):
    cfg = LlamaConfig.tiny(num_hidden_layers=2, tie_word_embeddings=False)
    params = M.init_params(cfg, jax.random.PRNGKey(80), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q", mode, dtype=jnp.float32)

    loaded = load_params(dst, cfg, jnp.float32)
    want = quantize_params(load_params(src, cfg, jnp.float32), mode)
    assert tree_quantization(loaded) == mode
    assert _trees_equal(loaded, want)
    # config carries the informational stamp
    import json

    assert json.load(open(dst / "config.json"))["cake_quantization"] == {
        "mode": mode
    }


def test_quantized_checkpoint_generation_matches_runtime_quantize(tmp_path):
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(81), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q4", "int4", dtype=jnp.float32)

    def run(gen):
        gen.add_message(Message.user("offline quantized"))
        gen.generate(9)
        return list(gen.generated_token_ids)

    got = run(
        LlamaGenerator.load(
            dst, dtype=jnp.float32, max_seq_len=128, sampling=GREEDY
        )
    )
    want = run(
        LlamaGenerator.load(
            src, dtype=jnp.float32, max_seq_len=128, sampling=GREEDY,
            quantize="int4",
        )
    )
    assert got == want


def test_quantized_checkpoint_worker_range_load(tmp_path):
    """A worker loads only its block range from a quantized checkpoint —
    and serving from it matches the local quantized oracle."""
    from cake_tpu.parallel.topology import Topology
    from cake_tpu.runtime.master import DistributedForwardStep
    from cake_tpu.runtime.worker import Worker

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(82), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q8", "int8", dtype=jnp.float32)

    shard = load_params(dst, cfg, jnp.float32, layer_range=(0, 2))
    assert isinstance(shard["layers"]["wq"], QuantWeight)

    topo = Topology.from_dict(
        {"w1": {"host": "placeholder", "layers": ["model.layers.0-1"]}}
    )
    w = Worker(
        "w1", dst, topo, ("127.0.0.1", 0), dtype=jnp.float32, max_seq_len=128
    )
    w.start()
    topo.nodes["w1"].host = f"127.0.0.1:{w.address[1]}"
    try:
        step = DistributedForwardStep(
            cfg, dst, topo, dtype=jnp.float32, max_seq_len=128
        )
        try:
            gen = LlamaGenerator(cfg, step, ByteTokenizer(), GREEDY)
            gen.add_message(Message.user("quantized checkpoint worker"))
            gen.generate(8)
            got = list(gen.generated_token_ids)
        finally:
            step.close()
    finally:
        w.stop()

    oracle = dict(params)
    oracle["layers"] = quantize_params(params, "int8")["layers"]
    ref = LlamaGenerator(
        cfg,
        LocalForwardStep(cfg, oracle, max_seq_len=128, cache_dtype=jnp.float32),
        ByteTokenizer(),
        GREEDY,
    )
    ref.add_message(Message.user("quantized checkpoint worker"))
    ref.generate(8)
    assert got == list(ref.generated_token_ids)


def test_quantized_checkpoint_splits(tmp_path):
    """The splitter carves a quantized checkpoint exactly like a plain one
    (suffixed names keep their layer prefixes) and the bundle loads."""
    from cake_tpu.io.splitter import split_model

    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(83), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q", "int4", dtype=jnp.float32)

    topo_path = tmp_path / "topology.yml"
    topo_path.write_text(
        "w0:\n  host: h0:1\n  layers:\n    - model.layers.0-1\n"
        "w1:\n  host: h1:1\n  layers:\n    - model.layers.2-3\n"
    )
    split_model(dst, topo_path, tmp_path / "splits")
    bundle = tmp_path / "splits" / "w1-node" / "model"
    shard = load_params(bundle, cfg, jnp.float32, layer_range=(2, 4))
    want = quantize_params(load_params(src, cfg, jnp.float32), "int4")
    want_slice = jax.tree.map(lambda a: a[2:4], want["layers"])
    assert _trees_equal(shard["layers"], want_slice)


def test_phi3_source_canonicalized(tmp_path):
    """A fused-storage (Phi-3) source quantizes into standard per-projection
    names; the quantized checkpoint reloads without the fused-split path."""
    from cake_tpu.io.safetensors_io import hf_tensor_dict, write_safetensors

    cfg = LlamaConfig.tiny(num_hidden_layers=2, model_type="phi3")
    params = M.init_params(cfg, jax.random.PRNGKey(84), jnp.float32)
    src = tmp_path / "src"
    # Write a REAL fused checkpoint the way Phi-3 ships.
    import json

    src.mkdir(parents=True)
    tensors = hf_tensor_dict(params, cfg)
    fused = {}
    for i in range(2):
        q = tensors.pop(f"model.layers.{i}.self_attn.q_proj.weight")
        k = tensors.pop(f"model.layers.{i}.self_attn.k_proj.weight")
        v = tensors.pop(f"model.layers.{i}.self_attn.v_proj.weight")
        fused[f"model.layers.{i}.self_attn.qkv_proj.weight"] = (
            np.concatenate([q, k, v], axis=0)
        )
        g = tensors.pop(f"model.layers.{i}.mlp.gate_proj.weight")
        u = tensors.pop(f"model.layers.{i}.mlp.up_proj.weight")
        fused[f"model.layers.{i}.mlp.gate_up_proj.weight"] = (
            np.concatenate([g, u], axis=0)
        )
    tensors.update(fused)
    write_safetensors(src / "model.safetensors", tensors)
    with open(src / "config.json", "w") as f:
        json.dump(cfg.to_hf_dict(), f)

    dst = quantize_checkpoint(src, tmp_path / "q", "int4", dtype=jnp.float32)
    loaded = load_params(dst, cfg, jnp.float32)
    assert isinstance(loaded["layers"]["wq"], Quant4Weight)
    want = quantize_params(load_params(src, cfg, jnp.float32), "int4")
    assert _trees_equal(loaded, want)


def test_moe_mixed_mode_roundtrip(tmp_path):
    """qwen2_moe under int4: expert stacks store .q8, shared expert .q4."""
    cfg = LlamaConfig.tiny(
        num_hidden_layers=2, model_type="qwen2_moe",
        num_local_experts=4, num_experts_per_tok=2,
        shared_expert_intermediate_size=32,
    )
    params = M.init_params(cfg, jax.random.PRNGKey(85), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q", "int4", dtype=jnp.float32)
    loaded = load_params(dst, cfg, jnp.float32)
    assert isinstance(loaded["layers"]["w_gate"], QuantWeight)  # experts int8
    assert isinstance(loaded["layers"]["sh_gate"], Quant4Weight)
    want = quantize_params(load_params(src, cfg, jnp.float32), "int4")
    assert _trees_equal(loaded, want)


def test_requantizing_quantized_checkpoint_fails_clearly(tmp_path):
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(86), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(src, tmp_path / "q", "int8", dtype=jnp.float32)
    with pytest.raises(ValueError, match="already quantized"):
        quantize_checkpoint(dst, tmp_path / "qq", "int4", dtype=jnp.float32)
    with pytest.raises(ValueError, match="already quantized"):
        LlamaGenerator.load(
            dst, dtype=jnp.float32, max_seq_len=64, sampling=GREEDY,
            quantize="int8",
        )


def test_streaming_chunks_and_shards_match_whole_tree(tmp_path):
    """The streaming path (layer chunks through the incremental shard
    writer, uneven tail chunk, multi-file output) produces EXACTLY the
    whole-tree quantization — and leaves no tmp shards behind."""
    cfg = LlamaConfig.tiny(num_hidden_layers=5, tie_word_embeddings=False)
    params = M.init_params(cfg, jax.random.PRNGKey(87), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    dst = quantize_checkpoint(
        src, tmp_path / "q", "int8", dtype=jnp.float32,
        max_shard_bytes=64 << 10, layers_per_chunk=2,
    )
    shards = sorted(dst.glob("model-*.safetensors"))
    assert len(shards) > 1  # the shard writer actually flushed mid-stream
    assert not list(dst.glob(".model-part-*.tmp"))
    loaded = load_params(dst, cfg, jnp.float32)
    want = quantize_params(load_params(src, cfg, jnp.float32), "int8")
    assert _trees_equal(loaded, want)


def test_shard_writer_abort_and_stale_tmp_sweep(tmp_path):
    """abort() (and the context manager's exception path) deletes flushed
    tmp shards; a fresh writer sweeps stale tmp files from a died run."""
    from cake_tpu.io.safetensors_io import ShardedCheckpointWriter

    out = tmp_path / "out"
    with pytest.raises(RuntimeError, match="mid-stream"):
        with ShardedCheckpointWriter(out, max_shard_bytes=64) as w:
            w.add({"a": np.zeros((64,), np.float32)})
            w.add({"b": np.zeros((64,), np.float32)})  # forces a tmp flush
            assert list(out.glob(".model-part-*.tmp"))
            raise RuntimeError("mid-stream")
    assert not list(out.glob(".model-part-*.tmp"))
    assert not list(out.glob("model-*.safetensors"))

    # A stale tmp from a killed process is swept by the next writer.
    stale = out / ".model-part-00042.tmp"
    stale.write_bytes(b"stale")
    w = ShardedCheckpointWriter(out, max_shard_bytes=1 << 20)
    assert not stale.exists()
    w.add({"c": np.ones((4,), np.float32)})
    (path,) = w.finish()
    assert path.name == "model-00001-of-00001.safetensors"


def test_quantizer_bad_mode_writes_nothing(tmp_path):
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(88), jnp.float32)
    src = tmp_path / "src"
    save_tiny_checkpoint(src, params, cfg)
    with pytest.raises(ValueError, match="unknown quantize mode"):
        quantize_checkpoint(src, tmp_path / "bad", "int2")
    assert not (tmp_path / "bad").exists()
