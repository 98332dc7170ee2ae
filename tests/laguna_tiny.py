"""A tiny ``model_type: laguna`` for the CPU tests: the published layout at
toy widths (full and sliding layers of different head counts in groups of 6
and 9 on 2 KV heads, YaRN over half a head beside a plain rope, the gate a
head, a dense layer then sparse ones of which this rank holds a share), a
seeded checkpoint, and the plain reference beside it."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.io.safetensors_io import save_tiny_checkpoint
from cake_tpu.models.llama import kinds as K
from cake_tpu.models.llama.config import LlamaConfig

ROOT = Path(__file__).resolve().parents[1]
WINDOW, PAGE = 16, 8

HF = {
    "model_type": "laguna", "vocab_size": 96, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 4096, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_total": 16,
    "first_expert": 4, "num_experts_per_tok": 6, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
    "mlp_only_layers": [0], "tie_word_embeddings": False,
    "gating": "per-head", "sliding_window": WINDOW,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
    },
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention",
                    "full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads_per_layer": [12, 18, 18, 12, 18],
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False, "bos_token_id": 1,
    "eos_token_id": 2,
}


def reference_module():
    path = ROOT / "bench" / "architectures" / "laguna.py"
    spec = importlib.util.spec_from_file_location("bench_architecture_laguna", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def checkpoint(tmp_path: Path, hf: dict = HF, seed: int = 0, std: float = 0.1, mutate=None):
    """(config, params in float32, the reference's reader and its cfg) of a
    seeded tiny model written as a checkpoint. ``std`` 0.1: wide enough for
    the attention, the gate and the router to matter at a width of 64."""
    from bench.checkpoint import Reader

    config = LlamaConfig.from_hf_dict(hf)
    params = K.init_params(config, jax.random.PRNGKey(seed), jnp.float32, std=std)
    if mutate is not None:
        params = mutate(params)
    save_tiny_checkpoint(tmp_path, params, config)
    with open(tmp_path / "config.json") as f:
        cfg = json.load(f)
    return config, params, Reader(tmp_path), cfg


class Lanes:
    """A few lanes served by hand through ``kinds``' programs and a
    ``PagePools``: what the engine does, a step at a time, so that a test
    can read the logits."""

    def __init__(self, config, params, lanes, table_pages, full_pages, sliding_pages):
        from cake_tpu.models.llama.paged_cache import PageAllocator, PagePools
        from cake_tpu.ops.fuse import fuse_params

        self.config, self.params = config, fuse_params(params)
        self.pools = PagePools({
            kind: PageAllocator(
                pages, PAGE, batch=lanes, max_pages_per_seq=table_pages,
                reserve_pages=0, window=config.kind_window(kind))
            for kind, pages in zip(config.attention_kinds, (full_pages, sliding_pages))
        })
        self.cache = K.init_cache(
            config, (full_pages, sliding_pages), PAGE, jnp.float32)
        self.pads = np.zeros((lanes,), np.int32)

    def tables(self, rows=slice(None)):
        return tuple(jnp.asarray(a.block_tables[rows].copy())
                     for a in self.pools.kinds.values())

    def join(self, lane, ids, slot, width):
        """``ids`` into ``lane`` so that they end at ``slot``, in a window
        ``width`` wide that ends there too; the logits after the last."""
        pad = slot - len(ids)
        self.pads[lane] = pad
        self.pools.map_range(lane, pad, slot)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, width - len(ids):] = ids
        logits, self.cache, _ = K._kinds_join_fn(self.config, width, False)(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray([pad], jnp.int32), jnp.asarray([slot], jnp.int32),
            self.tables(slice(lane, lane + 1)), jnp.int32(slot - width),
        )
        return np.asarray(logits[0])

    def step(self, toks, slot):
        """One decode step at ``slot`` of every lane (``toks`` [lanes]);
        logits [lanes, vocab]."""
        self.pools.free_behind(slot)
        live = [lane for lane in range(len(toks)) if self.pools.lane_mapped(lane)]
        for lane in live:
            self.pools.map_range(lane, slot, slot + 1)
        valid = np.zeros((len(toks), 1), bool)
        valid[live] = True
        logits, self.cache, _ = _STEP(
            self.params, jnp.asarray(toks, jnp.int32)[:, None], self.cache,
            jnp.int32(slot), jnp.asarray(self.pads), self.tables(),
            jnp.asarray(valid), self.config, allow_pallas=False,
        )
        return np.asarray(logits)


_STEP = jax.jit(
    K.kinds_decode_step, static_argnames=("config", "allow_pallas"), donate_argnums=(2,),
)
