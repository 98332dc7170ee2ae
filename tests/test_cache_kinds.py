"""A cache kind is one record (``models/llama/programs.KINDS``) and the paged
backend drives any of them: every record is complete, ``paged_backend``
gives a kind that is not plain K and V a backend WITHOUT the operations only
plain K and V has (the engine's capability gates read that absence), and a
family's ``GET /stats`` sections keep exactly the keys they had on PR 46's
parent, where a leaf a kind wrote them out (the key sets below were written
down from that parent's backends before the leaves went)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import programs
from cake_tpu.models.llama.config import (
    CACHE_KV, CACHE_KV_KINDS, CACHE_KV_STATE, CACHE_LATENT, CACHE_LATENT_INDEX,
    LlamaConfig,
)
from cake_tpu.runtime.batch_backend import PagedLocalBackend, paged_backend
from cake_tpu.runtime.shapes import ProgramShapes

from laguna_tiny import HF as LAGUNA
from test_program_parts import FAMILIES as FIVE

FAMILIES = {**FIVE, "laguna": LlamaConfig.from_hf_dict(LAGUNA)}
# What only plain K and V can do: the engine reads the ABSENCE of these.
PLAIN_ONLY = ("suffix_prefill", "suffix_join", "cow_copy", "verify_greedy",
              "verify_sampled", "attach_prefix_cache", "retain_kv")

CACHE = {"kind", "bytes_per_token", "bytes_per_token_needed", "page_size", "pages",
         "bytes", "pool_write"}
STATE = {"layers", "mixer", "window_form", "step_form", "bytes_per_lane", "bytes",
         "lane_writes", "decode_dispatches", "decode_rows", "decode_lanes"}
# (``dense_dispatches``: PR 50's, the one key a later PR added to a section)
MOE = {"dispatches", "dense_dispatches", "routed", "held", "touched", "max_load",
       "experts_held", "experts_ranked", "first_held", "top_k", "join"}
SPARSE = {"index_topk", "dispatches", "rows", "scanned", "chosen", "scores_form",
          "select_form", "traced", "join"}
NESTED = {
    ("moe", "join"): {"joins", "routed", "held"},
    ("sparse", "traced"): {"index_topk", "dispatches", "rows", "scanned", "chosen"},
    ("sparse", "join"): {"dispatches", "rows", "scanned", "chosen"},
    ("cache", "bytes_per_token_by"): {"latent", "index"},
    ("cache", "kinds"): {"full", "sliding"},
}
POOL = {"window", "pages_total", "pages_mapped", "bytes_per_page", "freed_behind_window"}
DELTA_HEADS = {"key_heads", "value_heads"}
# family -> section -> its keys on the parent (None: the backend has no such
# method, and ``runtime/api.py`` reports no such section)
PARENT_FACTS = {
    "dense": {"cache": CACHE, "state": STATE, "moe": None, "sparse": None},
    "jamba": {"cache": CACHE, "state": STATE, "moe": None, "sparse": None},
    # (PR 53: a delta rule's two head counts beside ``mixer``)
    "olmo_hybrid": {"cache": CACHE, "state": STATE | DELTA_HEADS, "moe": None, "sparse": None},
    "latent_moe": {"cache": CACHE, "state": STATE, "moe": MOE, "sparse": None},
    "latent_index": {"cache": CACHE | {"bytes_per_token_by"}, "state": STATE,
                     "moe": MOE, "sparse": SPARSE},
    "laguna": {"cache": CACHE | {"kinds", "cached_tokens"}, "state": STATE,
               "moe": MOE, "sparse": None},
    # PR 48's own (no parent had it): the hybrids' sections and, since the
    # model has sparse layers, Pangu's expert account
    "lfm2_moe": {"cache": CACHE, "state": STATE, "moe": MOE, "sparse": None},
    # PR 53's own: grouped delta-rule heads beside a share of routed experts
    "qwen3_next": {"cache": CACHE, "state": STATE | DELTA_HEADS, "moe": MOE, "sparse": None},
    # PR 57's own: plain K and V under generation by diffusion over blocks: the
    # expert account and the dispatches' own (``diffusion.DiffusionAccount``)
    "sdar": {"cache": CACHE, "state": STATE, "moe": MOE, "sparse": None, "diffusion": {
        "block_length", "denoising_steps", "remask", "mask_token_id", "confidence_threshold",
        "dispatches", "blocks", "passes", "commit_passes", "lane_passes", "revealed",
        "emitted", "known"}},
}


def tiny_backend(config):
    kind = programs.kind_of(config)
    # zeros in the tree's shapes: no fact of the construction reads a weight
    params = jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype),
        jax.eval_shape(lambda: kind.init_params(config, jax.random.PRNGKey(0), jnp.float32)),
    )
    return paged_backend(
        config, params, max_seq_len=128, cache_dtype=jnp.float32, page_size=16,
        max_pages=32, allow_pallas=False, lanes=3,
    )


@pytest.mark.parametrize(
    "name", [CACHE_KV, CACHE_KV_STATE, CACHE_LATENT, CACHE_LATENT_INDEX, CACHE_KV_KINDS])
def test_the_record_is_complete_and_the_backend_is_the_kinds(name):
    kind = programs.KINDS[name]
    assert kind.name == name and dataclasses.is_dataclass(kind) and hash(kind)
    for field in ("init_params", "init_cache", "window", "forward_one", "token_bytes",
                  "pools", "cache_facts"):
        assert callable(getattr(kind, field)), field
    assert kind.label and kind.module.startswith("paged")
    assert set(kind.window_operands) <= {"start", "lane"}
    for account in kind.accounts:
        assert account.section in ("moe", "sparse") and account.names and callable(account.add)
    assert {key for _, key, _ in kind.forms} >= {"window_form", "step_form"}
    config = next(c for c in FAMILIES.values() if c.cache_kind == name)
    be = tiny_backend(config)
    assert be.cache_kind == name and be.kind is kind and be.kv_mode == "paged"
    assert be.shapes == ProgramShapes.for_model(config, 16, 8)
    # the three programs are found at construction, and are the makers' own
    assert be._join_program(config, 64, False) is programs.join_program(
        kind, config, 64, False)
    plain = [m for m in PLAIN_ONLY if hasattr(be, m)]
    if name == CACHE_KV:
        assert type(be) is PagedLocalBackend and plain == list(PLAIN_ONLY)
    else:
        assert not plain and not isinstance(be, PagedLocalBackend)
    # what rides back beside the tokens is the record's accounts', and only a
    # kind with accounts has a section for them
    # (of THIS model's: a ``kv+state`` model without a sparse layer has none)
    assert [s for s in ("moe", "sparse") if hasattr(be, f"{s}_facts")] == [
        a.section for a in kind.accounts_of(config)]
    needed, stored = kind.token_bytes(config, jnp.float32)
    assert 0 < needed <= stored == be.cache_facts()["bytes_per_token"]
    cache = be.init_kv(2)
    assert kind.pools(cache) and (kind.lane_state is None or kind.lane_state(cache))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_stats_sections_keep_the_parents_keys(family):
    """At the backend and without an engine: ``runtime/api.py`` builds
    ``/stats`` from ``getattr(backend, f"{key}_facts", None)``."""
    be = tiny_backend(FAMILIES[family])
    for section, want in PARENT_FACTS[family].items():
        facts = getattr(be, f"{section}_facts", None)
        if want is None:
            assert facts is None, section
            continue
        got = facts()
        assert set(got) == want, (section, set(got) ^ want)
        for (where, key), inner in NESTED.items():
            if where == section and key in got:
                assert set(got[key]) == inner, (section, key)
        for pool in got.get("kinds", {}).values():
            assert set(pool) == POOL
    assert be.cache_facts()["kind"] == FAMILIES[family].cache_kind
