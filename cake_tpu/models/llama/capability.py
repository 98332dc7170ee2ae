"""What a model's cache kind cannot be served with: the one capability check.

Most of the program restores, shares, rewinds, shards or re-packs K and V a
KV head: the dense cache, the prefix cache's copy-on-write chains,
speculative verify and its rewind, tensor / sequence / pipeline parallelism,
the distributed workers' block ranges, weight quantisation's expert and
projection tables, the single-stream generator. A model whose lanes keep
something else (``config.cache_kind``: a recurrent state beside K and V,
whichever mixer keeps it (Jamba's Mamba-1, Olmo-Hybrid's gated delta rule), one
latent a token in place of them (Pangu's; beside the key of a learned index
that chooses what attention reads: DeepSeek-V3.2's), or K and V in a pool a KIND of attention layer
with a windowed kind's pages freed behind the window: Laguna's) is served by its
own paged leaf
(``runtime/batch_backend.paged_backend``) on one chip, and everything else is
refused HERE, with one message, before a weight is read: over such a cache
each would serve wrong tokens silently, and there is no fallback to serve
instead. A model that GENERATES otherwise (``config.generation``: by
diffusion over blocks, ``sdar_moe``) keeps plain K and V and is refused the
same features, and a penalty ring beside them, for its step's sake: each
restores, rewinds, verifies or shards a ONE-TOKEN step. Every caller fills in the facts it knows (``cli.main``, the engine
for programmatic use, the loader, the splitter, the single-stream step) and
the message names the feature as a user would have written it.
"""

from __future__ import annotations

from cake_tpu.models.llama.config import (
    BLOCK_DIFFUSION, CACHE_KV, CACHE_KV_KINDS, CACHE_KV_STATE, CACHE_LATENT_INDEX,
    SLIDING, STATE, LlamaConfig,
)


class UnsupportedForCacheKind(ValueError):
    """A feature was asked for that this model's cache kind cannot serve."""


# Fact -> the feature as a user would have written it. THE one list.
REFUSED = {
    "single_stream":
        "the single-stream generator (no --api with --api-batch > 1)",
    "kv_mode_dense": "--kv-mode dense",
    "prefix_cache": "--prefix-cache on",
    "draft_model": "--draft-model",
    "speculative_k": "--speculative-k",
    "tp": "--tp",
    "sp": "--sp",
    "topology": "--topology (pipeline and distributed backends)",
    "distributed": "--distributed",
    "quantize": "--quantize",
    "other_backend":
        "a backend other than the local paged one (--tp, pipeline, distributed)",
    "layer_range": "a worker's layer range (--topology)",
    "split_model": "cake-split-model",
}
# Refused over one cache kind only: a pool narrower than the activations
# would round the index keys too, and with them which tokens are chosen.
REFUSED_BY_KIND = {
    CACHE_LATENT_INDEX: {"kv_dtype_narrow": "--kv-dtype narrower than --dtype"},
}


# Refused over one generation kind only: a repeat penalty is a ring of the
# last tokens SAMPLED one by one, which a block's passes do not keep.
REFUSED_BY_GENERATION = {
    BLOCK_DIFFUSION: {"repeat_penalty": "--repeat-penalty other than 1.0"},
}


def _why(config: LlamaConfig) -> str:
    if config.generation == BLOCK_DIFFUSION:
        return (
            f"it generates by diffusion over blocks of {config.block_length} "
            f"slots (several passes over a block under a mask that is "
            "bidirectional inside it, then a commit of its K and V), and this "
            "feature restores, rewinds, verifies, penalises or shards a "
            "one-token step"
        )
    if config.cache_kind == CACHE_KV_STATE and config.state_shape is None:
        kept, channels = config.conv_window
        return (
            f"{len(config.layers_of(STATE))} of its "
            f"{config.num_hidden_layers} layers keep the window of a short "
            f"convolution per lane (its last {kept} inputs of {channels} "
            "channels) in place of K and V, and this feature restores, "
            "rewinds, shares or shards K and V only"
        )
    if config.cache_kind == CACHE_KV_STATE:
        return (
            f"{len(config.layers_of(STATE))} of its "
            f"{config.num_hidden_layers} layers keep a recurrent state "
            "per lane, and this feature restores, rewinds, shares or "
            "shards K and V only"
        )
    if config.cache_kind == CACHE_KV_KINDS:
        return (
            f"{len(config.kind_layers(SLIDING))} of its "
            f"{config.num_hidden_layers} attention layers keep K and V in a "
            f"pool of their own whose pages are freed {config.sliding_window} "
            "tokens behind a lane's position, and this feature restores, "
            "rewinds, shares, shards or re-packs one pool that holds every "
            "token of every layer"
        )
    if config.cache_kind == CACHE_LATENT_INDEX:
        return (
            f"its {config.num_hidden_layers} layers keep one latent of "
            f"{config.kv_lora_rank} + {config.qk_rope_head_dim} numbers a token "
            f"and, behind the same block table, an index key of "
            f"{config.index_head_dim} by which each query chooses the "
            f"{config.index_topk} cached tokens it attends, and this feature "
            "restores, rewinds, shares, shards or re-packs K and V a KV head "
            "(a shared prefix would need both pools copied and a verify chunk "
            "a choice a drafted position)"
        )
    return (
        f"its {config.num_hidden_layers} layers keep one latent of "
        f"{config.kv_lora_rank} + {config.qk_rope_head_dim} numbers a token "
        "in a latent page pool, and this feature restores, rewinds, shares, "
        "shards or re-packs K and V a KV head"
    )


def refuse_unsupported(config: LlamaConfig, **facts: bool) -> None:
    """``facts`` maps names of ``REFUSED`` to whether the caller was asked
    for that feature; the first that was raises, for a model whose cache is
    not plain K and V (a fact of ``REFUSED_BY_KIND`` for its kind alone)."""
    if config.cache_kind == CACHE_KV and config.generation != BLOCK_DIFFUSION:
        return
    refused = {**REFUSED, **REFUSED_BY_KIND.get(config.cache_kind, {}),
               **REFUSED_BY_GENERATION.get(config.generation, {})}
    for fact, on in facts.items():
        if on and fact in refused:
            raise UnsupportedForCacheKind(
                f"{refused[fact]} is not supported for model_type "
                f"{config.model_type!r}: {_why(config)}. Serve it with --api "
                "HOST:PORT --api-batch N (N > 1) --kv-mode paged "
                "--prefix-cache off on one chip, unquantized"
                + (", --repeat-penalty 1.0." if config.generation == BLOCK_DIFFUSION else ".")
            )

