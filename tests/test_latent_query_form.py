"""PR 56: a latent model's projections that are reshaped into heads.

The checkpoint's ``self_attn.q_b_proj.weight`` is one matrix, a head's
``nope`` rows then its ``rope`` rows, head after head; the published layer
multiplies by it once, reshapes the result to heads and slices a head's 192
numbers into 128 and 64. The tree holds that matrix as it is published
(``wq_b``, [in, out]) and ``latent.into_heads`` puts an optimisation barrier
between the product and the reshape, so that the chip's compiler reads a
layer's matrix where it lies in the run's stack (``tests/
test_paged_pool_carry.py`` and ``test_deepseek_v32_v5e_compile.py`` hold the
compiled decode chunks to that). Here, at tiny Pangu and DeepSeek
configurations on the CPU: the barrier changes no number (bf16 and int8
weights, one token a lane and a window, DeepSeek's index queries too), a
window's walk over two groups of heads is the reference's layer, a published
checkpoint loads into the tree and the name table writes it back, and the
loader, the random initialiser and ``run_shapes`` agree on the tree.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.checkpoint import Reader, write_checkpoint
from bench.manifest import architecture
from cake_tpu.io.safetensors_io import (
    hf_tensor_dict, load_params, open_checkpoint, save_tiny_checkpoint, write_safetensors,
)
from cake_tpu.models.llama import latent as L
from cake_tpu.models.llama import latent_index as LI
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.pool_audit import weight_ops_in_hlo
from cake_tpu.ops.norm import rms_norm
from cake_tpu.ops.quant import qmat, quantize_weight
from cake_tpu.ops.rope import apply_rope, kind_rope_rows, rope_table

from test_deepseek_v32 import TINY as DEEPSEEK
from test_latent_pangu import TINY as PANGU
from zbench.conftest import REPO

MODELS = {"pangu": PANGU, "deepseek": DEEPSEEK}
# 32 heads: two of the window's head groups (``latent_index._HEAD_GROUP``)
WIDE = {**DEEPSEEK, "num_attention_heads": 32, "num_key_value_heads": 32}
Q_B = "model.layers.{i}.self_attn.q_b_proj.weight"
DTYPE = jnp.bfloat16


def _matrix(seed, shape, int8):
    """A projection [in, out]: bf16, or int8 with a scale an output channel."""
    w = (jax.random.normal(jax.random.PRNGKey(seed), shape) * 0.3).astype(DTYPE)
    return quantize_weight(w) if int8 else w


def _rope_rows(config, positions):
    if config.index_topk:
        return kind_rope_rows(config.latent_rope, positions)
    cos, sin = rope_table(config.qk_rope_head_dim, 64, config.rope_theta)
    return cos[positions], sin[positions]


def _same(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype == DTYPE
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


SHAPES = pytest.mark.parametrize("rows, width", [(3, 1), (2, 8)], ids=["decode", "window"])
WEIGHTS = pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])


@SHAPES
@WEIGHTS
@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_queries_are_the_published_layers(model, int8, rows, width):
    """One product with ``q_b_proj``, reshaped to heads and sliced: what
    ``mla_project`` and ``attention_queries`` give behind the barrier."""
    config = LlamaConfig.from_hf_dict(MODELS[model])
    n, nope, rope = config.num_attention_heads, config.qk_nope_head_dim, config.qk_rope_head_dim
    wq_b = _matrix(1, (config.q_lora_rank, n * (nope + rope)), int8)
    positions = jnp.arange(rows * width, dtype=jnp.int32).reshape(rows, width) % 40
    cos, sin = _rope_rows(config, positions)
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    if model == "deepseek":
        cq = jax.random.normal(keys[0], (rows, width, config.q_lora_rank)).astype(DTYPE)
        q_nope, q_rope = jax.jit(lambda w: LI.attention_queries(w, cq, cos, sin, config))(wq_b)
    else:  # the layer's own way to its query latent, then the same product
        eps, shapes = config.rms_norm_eps, L.run_shapes(config, config.ff_runs[0][0])
        lp = {name: jnp.ones(shapes[name], DTYPE) for name in ("ln_attn", "q_a_ln", "kv_a_ln")}
        lp.update(wq_a=_matrix(3, shapes["wq_a"], False), wkv_a=_matrix(4, shapes["wkv_a"], False),
                  wq_b=wq_b)
        x = jax.random.normal(keys[0], (rows, width, config.hidden_size)).astype(DTYPE)
        q_nope, q_rope, _ = jax.jit(lambda lp: L.mla_project(lp, x, cos, sin, None, config))(lp)
        cq = rms_norm(qmat(rms_norm(x, lp["ln_attn"], eps), lp["wq_a"]), lp["q_a_ln"], eps)
    q = qmat(cq, wq_b).reshape(rows, width, n, nope + rope)
    _same(q_nope, q[..., :nope])
    _same(q_rope, apply_rope(q[..., nope:], cos, sin, None))


@SHAPES
@WEIGHTS
def test_the_index_queries_are_the_published_layers(int8, rows, width):
    config = LlamaConfig.from_hf_dict(DEEPSEEK)
    heads, dim = config.index_n_heads, config.index_head_dim
    lp = {"wi_q": _matrix(5, (config.q_lora_rank, heads * dim), int8)}
    positions = jnp.arange(rows * width, dtype=jnp.int32).reshape(rows, width)
    cos, sin = _rope_rows(config, positions)
    cq = jax.random.normal(jax.random.PRNGKey(6), (rows, width, config.q_lora_rank)).astype(DTYPE)
    got = jax.jit(lambda lp: LI.index_queries(lp, cq, cos, sin, config))(lp)
    _same(got, apply_rope(qmat(cq, lp["wi_q"]).reshape(rows, width, heads, dim), cos, sin, None))


def test_a_window_over_two_groups_of_heads_is_the_reference(tmp_path):
    """32 heads: the window's attention walks two groups of 16, each with its
    columns of ``wq_b``. The last token's logits of a prompt of 24 in a window
    of 32 slots against the plain reference over the published checkpoint."""
    arch = architecture(REPO, WIDE)
    arch.FAULT = None
    write_checkpoint(tmp_path, WIDE, "f32", 11, arch)
    config = LlamaConfig.from_model_dir(tmp_path)
    assert config.num_attention_heads == 2 * LI._HEAD_GROUP
    params = load_params(tmp_path, config, jnp.float32)
    seq = [int(t) for t in np.random.default_rng(1).integers(5, 200, size=24)]
    want = arch.forward_logits(Reader(tmp_path), WIDE, [seq])[0]
    width, page = 32, 16
    tokens = np.zeros((1, width), np.int32)
    tokens[0, width - len(seq):] = seq
    one = lambda v: jnp.asarray([v], jnp.int32)  # noqa: E731
    logits, _, _ = LI.latent_index_prefill(
        params, jnp.asarray(tokens), LI.init_cache(config, 8, page, jnp.float32),
        one(width - len(seq)), one(width), jnp.arange(4, dtype=jnp.int32)[None, :], config,
        allow_pallas=False)
    np.testing.assert_allclose(np.asarray(logits)[0], want[len(seq) - 1], atol=2e-4)


@pytest.fixture(scope="module", params=sorted(MODELS))
def checkpoint(request, tmp_path_factory):
    """(config, the tensors of a checkpoint whose ``q_b_proj`` are fresh
    published-form matrices, the tree loaded from it)."""
    config = LlamaConfig.from_hf_dict(MODELS[request.param])
    params = L.init_params(config, jax.random.PRNGKey(5), jnp.float32, std=0.1)
    path = tmp_path_factory.mktemp(f"q_b_{request.param}")
    save_tiny_checkpoint(path, params, config)
    tensors = dict(hf_tensor_dict(params, config))
    rng = np.random.default_rng(6)
    for i in range(config.num_hidden_layers):
        tensors[Q_B.format(i=i)] = rng.standard_normal(
            tensors[Q_B.format(i=i)].shape).astype(np.float32)
    write_safetensors(path / "model.safetensors", tensors)
    return config, tensors, load_params(path, config, jnp.float32)


def test_a_published_checkpoint_loads_into_the_form_the_product_reads(checkpoint):
    """[heads * (nope + rope), q_lora_rank] on disk, its transpose in the
    tree: the same numbers once, a head's 192 columns side by side."""
    config, tensors, loaded = checkpoint
    n, nope, rope = config.num_attention_heads, config.qk_nope_head_dim, config.qk_rope_head_dim
    assert tensors[Q_B.format(i=0)].shape == (n * (nope + rope), config.q_lora_rank)
    for run, (_, lo, hi) in zip(loaded["layers"], config.ff_runs):
        assert run["wq_b"].shape == (hi - lo, config.q_lora_rank, n * (nope + rope))
        for k, i in enumerate(range(lo, hi)):
            np.testing.assert_array_equal(run["wq_b"][k], tensors[Q_B.format(i=i)].T)


def test_the_name_table_writes_the_loaded_tree_back(checkpoint, tmp_path):
    config, tensors, loaded = checkpoint
    back = hf_tensor_dict(loaded, config)
    assert sorted(back) == sorted(tensors)
    for name, a in tensors.items():
        np.testing.assert_array_equal(back[name], a, err_msg=name)
    save_tiny_checkpoint(tmp_path, loaded, config)
    reader = open_checkpoint(tmp_path)
    for i in range(config.num_hidden_layers):
        np.testing.assert_array_equal(reader.numpy(Q_B.format(i=i)), tensors[Q_B.format(i=i)])


def test_init_params_and_run_shapes_agree_with_the_loader(checkpoint):
    config, _, loaded = checkpoint
    fresh = L.init_params(config, jax.random.PRNGKey(7), jnp.float32)
    for run, new, (kind, lo, hi) in zip(loaded["layers"], fresh["layers"], config.ff_runs):
        shapes = L.run_shapes(config, kind)
        assert sorted(run) == sorted(new) == sorted(shapes)
        for name, shape in shapes.items():
            assert run[name].shape == new[name].shape == (hi - lo, *shape), name


# ------------------------------------- the reading of a compiled program's text

# What the chip's compiler made of PR 56's parent, cut to its bones: the stack
# transposed at the entry, a layer sliced out of it every layer-step, and
# beside them what is NOT a second stream of a weight.
HLO = """HloModule jit_decode_chunk, is_scheduled=true

%fused_computation.308 (param_0.1: bf16[4,1536,24576], param_1.2: s32[]) -> bf16[1,1536,24576] {
  %param_0.1 = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic_slice.316 = bf16[1,1536,24576]{1,2,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.2), dynamic_slice_sizes={1,1536,24576}
}

%fused_computation.258 (param_0.3: bf16[4,7680,1536], param_1.4: s32[], param_2.5: bf16[64,7680]) -> bf16[64,1536] {
  %param_0.3 = bf16[4,7680,1536]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.4 = s32[]{:T(128)} parameter(1)
  %dynamic_slice.341 = bf16[1,7680,1536]{2,1,0:T(8,128)(2,1)} dynamic-slice(%param_0.3, %param_1.4), dynamic_slice_sizes={1,7680,1536}
  %bitcast.9 = bf16[7680,1536]{1,0:T(8,128)(2,1)} bitcast(%dynamic_slice.341)
  %param_2.5 = bf16[64,7680]{1,0:T(8,128)(2,1)} parameter(2)
  ROOT %convolution.81 = bf16[64,1536]{1,0:T(8,128)(2,1)} convolution(%param_2.5, %bitcast.9), dim_labels=bf_io->bf
}

%body.5 (arg_tuple.5: (s32[], bf16[64,7680], bf16[4,1536,24576], bf16[4,7680,1536])) -> (s32[], bf16[64,7680], bf16[4,1536,24576], bf16[4,7680,1536]) {
  %arg_tuple.5 = (s32[]{:T(128)}, bf16[64,7680]{1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[4,7680,1536]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = s32[]{:T(128)} get-tuple-element(%arg_tuple.5), index=0
  %get-tuple-element.2 = bf16[64,7680]{1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.5), index=1
  %get-tuple-element.3 = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.5), index=2
  %get-tuple-element.4 = bf16[4,7680,1536]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg_tuple.5), index=3
  %constant_dynamic-slice_fusion.3 = bf16[1,1536,24576]{1,2,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.3, %get-tuple-element.1), kind=kLoop, calls=%fused_computation.308
  %bitcast.681 = bf16[128,192,1536]{2,1,0:T(8,128)(2,1)S(1)} bitcast(%constant_dynamic-slice_fusion.3)
  %fusion.502 = bf16[64,1536]{1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.4, %get-tuple-element.1, %get-tuple-element.2), kind=kOutput, calls=%fused_computation.258
  ROOT %tuple.1 = (s32[]{:T(128)}, bf16[64,7680]{1,0:T(8,128)(2,1)}, bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)}, bf16[4,7680,1536]{2,1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.1, %get-tuple-element.2, %get-tuple-element.3, %get-tuple-element.4)
}

ENTRY %main.142 (wq_b.1: bf16[4,1536,24576], wq_a.1: bf16[4,7680,1536], wq_b0.1: bf16[1,1536,24576]) -> bf16[64,7680] {
  %wq_b.1 = bf16[4,1536,24576]{2,1,0:T(8,128)(2,1)} parameter(0)
  %wq_a.1 = bf16[4,7680,1536]{2,1,0:T(8,128)(2,1)} parameter(1)
  %wq_b0.1 = bf16[1,1536,24576]{2,1,0:T(8,128)(2,1)} parameter(2)
  %copy.103 = bf16[4,1536,24576]{1,2,0:T(8,128)(2,1)} copy(%wq_b.1)
  %copy-start.25 = (bf16[1,1536,24576]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,1536,24576]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%wq_b0.1)
  %copy-done.25 = bf16[1,1536,24576]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.25)
  %copy.101 = bf16[1,24576,1536]{2,1,0:T(8,128)(2,1)} copy(%copy-done.25)
}
"""


def test_what_writes_a_weight_out_again_is_found_in_compiled_text():
    found = weight_ops_in_hlo(HLO, [(4, 1536, 24576), (1, 1536, 24576)])
    # the layer-step's slice, the entry's transposes (in any order of the
    # sides): not the slice fused into a product, not a view, not the
    # compiler's own prefetch of an operand
    assert [f["op"] for f in found] == [
        "constant_dynamic-slice_fusion.3 bf16[1,1536,24576] fusion",
        "copy.103 bf16[4,1536,24576] copy", "copy.101 bf16[1,24576,1536] copy"]
    # the offending fusion's own text comes with it
    assert "dynamic-slice(%param_0.1, %param_1.2)" in found[0]["text"]
    assert found[1]["text"].strip().startswith("%copy.103 = ")
    # ``wq_a`` is read where it lies: nothing of it is found
    assert weight_ops_in_hlo(HLO, [(4, 7680, 1536)]) == []
    assert weight_ops_in_hlo(HLO, []) == []
