"""Every operation of a served program sits under one PART scope.

``obs/taxonomy.PROGRAM_PARTS`` names eight parts of a device program
(``embed`` .. ``sample``); the model's functions enter them with
``jax.named_scope``, and the benchmark reads a part's device time from the
trace's ``op_name`` metadata (``bench/parts.py``). Here the served programs
of the five families (dense GQA, a Jamba-like and an Olmo-Hybrid-like hybrid,
latent attention with sparse experts, the same behind a learned index) are LOWERED at a tiny size, never run:
the operations' names are read from the lowered module, and the same
programs lower to the same text, locations aside, with the scopes taken
away: a scope is metadata and nothing else.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import batch as B
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import latent as L
from cake_tpu.models.llama import latent_index as LI
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.paged_cache import PagedKVCache
from cake_tpu.obs.taxonomy import (
    FEED_FORWARD, MIXER, PROGRAM_PARTS, SAMPLE,
)
from cake_tpu.ops.fuse import fuse_params

from test_hybrid_jamba import HF as JAMBA
from test_hybrid_olmo import HF as OLMO_HYBRID
from test_deepseek_v32 import TINY as LATENT_INDEX
from test_latent_pangu import SHARE as LATENT_MOE

FAMILIES = {
    "dense": LlamaConfig.tiny(),
    "jamba": LlamaConfig.from_hf_dict(JAMBA),
    "olmo_hybrid": LlamaConfig.from_hf_dict(OLMO_HYBRID),
    "latent_moe": LlamaConfig.from_hf_dict(LATENT_MOE),
    "latent_index": LlamaConfig.from_hf_dict(LATENT_INDEX),
}
PROGRAMS = ("decode", "join", "prefill")
# A tiny server's shapes: lanes, pages of 16 slots, a table of 8 pages a
# row, a decode chunk of 4 steps, a window of 64 slots, an epoch's group of
# 2 rows.
GEOMETRY = dict(
    n_pages=32, page_size=16, lanes=3, table_pages=8, n_steps=4, width=64,
    prefill_rows=2, dtype=jnp.float32,
)
# The scopes older than the parts, and the part each must sit inside.
OLDER = {
    "gated_delta_step": MIXER, "gated_delta_rule": MIXER,
    "selective_scan_xla": MIXER, "moe_experts_grouped": FEED_FORWARD,
    "moe_experts_dense": FEED_FORWARD,
    # the learned index's three (PR 43): what a decode step or a window's
    # block scores, chooses and attends
    "index_scores": MIXER, "index_select": MIXER, "sparse_attention": MIXER,
}
# Which of them a family's program holds at these shapes (the XLA forms:
# tiny widths tile no kernel; ``moe_experts_dense`` is a ``--tp`` verify
# chunk's, which no paged server dispatches).
HOLDS = {
    ("jamba", "join"): {"selective_scan_xla"},
    ("jamba", "prefill"): {"selective_scan_xla"},
    ("olmo_hybrid", "decode"): {"gated_delta_step"},
    ("olmo_hybrid", "join"): {"gated_delta_rule"},
    ("olmo_hybrid", "prefill"): {"gated_delta_rule"},
    ("latent_moe", "decode"): {"moe_experts_grouped"},
    ("latent_moe", "join"): {"moe_experts_grouped"},
    ("latent_moe", "prefill"): {"moe_experts_grouped"},
    **{("latent_index", p): {"moe_experts_grouped", "index_scores", "index_select",
                             "sparse_attention"} for p in ("decode", "join", "prefill")},
}
WEIGHTY = ("dot_general", "convolution", "custom_call", "scatter")


def served_program(
    config: LlamaConfig, program: str, *, n_pages, page_size, lanes,
    table_pages, n_steps, width, prefill_rows, dtype,
    allow_pallas: bool = False, sharding=None,
):
    """One program a paged server dispatches (``runtime/batch_backend.py``'s
    leaf by ``config.cache_kind``), traced from abstract arguments: a decode
    chunk over ``lanes`` rows, one joining row's window, or one group of an
    epoch's prefill. Greedy, as the benchmark's cells serve."""

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def abstract(build):
        return jax.tree.map(
            lambda a: spec(a.shape, a.dtype), jax.eval_shape(build)
        )

    key = jax.random.PRNGKey(0)
    rows = 1 if program == "join" else prefill_rows
    table = spec((lanes if program == "decode" else rows, table_pages))
    decode_tail = (
        spec((lanes, 2), jnp.uint32), spec((lanes, 0)), spec((lanes,)),
    )
    sampling = (n_steps, 0.0, None, None, 1.0)
    if config.cache_kind == "kv":
        params = abstract(
            lambda: fuse_params(M.init_params(config, key, dtype)))
        shape = (
            config.num_hidden_layers, n_pages, config.num_key_value_heads,
            page_size, config.head_dim,
        )
        kv = PagedKVCache(k=spec(shape, dtype), v=spec(shape, dtype))
        if program == "decode":
            fn = B._paged_decode_fn(
                config, table_pages * page_size, *sampling,
                allow_pallas=allow_pallas,
            )
            return fn._jitted.trace(
                params, kv, spec((lanes,)), spec(()), spec((lanes,)), table,
                *decode_tail,
            )
        # with the prefix cache on an epoch's prefill is the suffix program
        fn = B._paged_suffix_join_jit if program == "join" else B._paged_suffix_jit
        return fn._jitted.trace(
            params, spec((rows, width)), kv, spec((rows,)), spec((rows,)),
            table, config, spec(()), allow_pallas=allow_pallas,
        )
    if config.cache_kind == "kv+state":
        params = abstract(
            lambda: fuse_params(H.init_params(config, key, dtype)))
        cache = abstract(
            lambda: H.init_hybrid_cache(config, lanes, n_pages, page_size, dtype))
        if program == "decode":
            fn = H._hybrid_decode_fn(
                config, table_pages * page_size, *sampling,
                allow_pallas=allow_pallas,
            )
            return fn._jitted.trace(
                params, cache, spec((lanes,)), spec(()), spec((lanes,)), table,
                spec((lanes,), jnp.bool_), *decode_tail,
            )
        if program == "join":
            return H._hybrid_join_fn(config, width, allow_pallas)._jitted.trace(
                params, cache, spec((1, width)), spec((1,)), spec((1,)), table,
                spec(()), spec(()),
            )
        return H._hybrid_prefill_jit._jitted.trace(
            params, spec((rows, width)), cache, spec((rows,)), spec((rows,)),
            table, config, spec(()), spec(()), allow_pallas=allow_pallas,
        )
    params = abstract(lambda: fuse_params(L.init_params(config, key, dtype)))
    if config.cache_kind == "latent+index":
        cache = abstract(lambda: LI.init_cache(config, n_pages, page_size, dtype))
        if program == "decode":
            fn = LI._decode_fn(config, *sampling, allow_pallas=allow_pallas)
            return fn._jitted.trace(
                params, cache, spec((lanes,)), spec(()), spec((lanes,)), table,
                spec((lanes,), jnp.bool_), *decode_tail,
            )
        if program == "join":
            return LI._join_fn(config, width, allow_pallas)._jitted.trace(
                params, cache, spec((1, width)), spec((1,)), spec((1,)), table,
                spec(()),
            )
        return LI._prefill_jit._jitted.trace(
            params, spec((rows, width)), cache, spec((rows,)), spec((rows,)),
            table, config, spec(()), allow_pallas=allow_pallas,
        )
    cache = abstract(lambda: L.init_cache(config, n_pages, page_size, dtype))
    if program == "decode":
        fn = L._latent_decode_fn(config, *sampling, allow_pallas=allow_pallas)
        return fn._jitted.trace(
            params, cache, spec((lanes,)), spec(()), spec((lanes,)), table,
            spec((lanes,), jnp.bool_), *decode_tail,
        )
    if program == "join":
        return L._latent_join_fn(config, width, allow_pallas)._jitted.trace(
            params, cache, spec((1, width)), spec((1,)), spec((1,)), table,
            spec(()),
        )
    return L._latent_prefill_jit._jitted.trace(
        params, spec((rows, width)), cache, spec((rows,)), spec((rows,)),
        table, config, spec(()), allow_pallas=allow_pallas,
    )


_NAME = re.compile(r'^loc\("([^"]*)"')


def operation_names(module) -> list[tuple[str, str]]:
    """(operation, its whole ``op_name``) of every operation of a lowered
    module, as a device trace's metadata will hold it: an operation of a
    private function (a jitted helper, lowered once) is named from its call
    sites, the caller's path in front, as XLA names it when it inlines the
    call."""
    functions = {
        op.attributes["sym_name"].value: op for op in module.body.operations
    }
    out: list[tuple[str, str]] = []

    def walk(op, prefix):
        m = _NAME.match(str(op.location))
        name = "/".join(p for p in (prefix, m.group(1) if m else "") if p)
        kind = op.operation.name
        if not kind.endswith("return"):  # a return is located at its function
            out.append((kind, name))
        if kind in ("func.call", "call"):
            callee = functions[op.attributes["callee"].value]
            inside(callee, name)
        inside(op, prefix)

    def inside(op, prefix):
        for region in op.regions:
            for block in region.blocks:
                for child in block.operations:
                    walk(child, prefix)

    inside(functions["main"], "")
    return out


def parts_of(name: str) -> list[str]:
    return [p for p in name.split("/") if p in PROGRAM_PARTS]


@functools.lru_cache(maxsize=None)
def lowered(family: str, program: str):
    return served_program(FAMILIES[family], program, **GEOMETRY).lower()


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("family", FAMILIES)
def test_every_operation_sits_under_one_part(family, program):
    names = operation_names(lowered(family, program).compiler_ir())
    found = {p for _, name in names for p in parts_of(name)}
    # a prefill's first token is sampled by a program of its own, below
    want = set(PROGRAM_PARTS) - ({SAMPLE} if program != "decode" else set())
    assert found == want
    twice = [name for _, name in names if len(parts_of(name)) > 1]
    assert not twice, twice[:5]
    bare = [
        (kind, name) for kind, name in names
        if kind.split(".")[-1] in WEIGHTY and not parts_of(name)
    ]
    assert not bare, bare[:5]
    older = set()
    for _, name in names:
        path = name.split("/")
        for scope, part in OLDER.items():
            if scope in path:
                older.add(scope)
                assert parts_of(name) == [part], name
                assert path.index(part) < path.index(scope), name
    assert older == HOLDS.get((family, program), set())


def test_the_in_place_step_sits_inside_mixer():
    """Jamba's decode program at widths that tile (``d_state`` 8 is a sublane
    tile, ``d_inner`` 128 a lane tile) with the kernel switch on: the
    one-token update is ``selective_step`` (PR 42), every operation of it
    under ``mixer`` and no other part, and the twin's update is not there."""
    config = dataclasses.replace(
        LlamaConfig.from_hf_dict({**JAMBA, "mamba_d_state": 8}),
        attention_impl="pallas",
    )
    geometry = {**GEOMETRY, "page_size": 128, "n_pages": 8, "table_pages": 2}
    traced = served_program(config, "decode", **geometry, allow_pallas=True)
    names = operation_names(traced.lower().compiler_ir())
    step = [name for _, name in names if "jit(selective_step)" in name.split("/")]
    assert step and {tuple(parts_of(name)) for name in step} == {(MIXER,)}
    assert H.step_form(config, True) == "pallas"
    twin = served_program(config, "decode", **geometry, allow_pallas=False)
    assert not [
        name for _, name in operation_names(twin.lower().compiler_ir())
        if "selective_step" in name
    ]
    assert H.step_form(config, False) == "xla"


def test_the_first_token_is_sampled_under_sample():
    fn = B._first_sample_fn(0.7, 40, None, 1.1, True)
    traced = fn._jitted.trace(
        jax.ShapeDtypeStruct((3, 512), jnp.float32),
        jax.ShapeDtypeStruct((3, 8), jnp.int32),
        jax.ShapeDtypeStruct((3, 2), jnp.uint32),
    )
    names = operation_names(traced.lower().compiler_ir())
    weighty = [n for kind, n in names if kind.split(".")[-1] in WEIGHTY]
    assert weighty and all(parts_of(n) == [SAMPLE] for n in weighty)
    assert {p for _, n in names for p in parts_of(n)} == {SAMPLE}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_scope_is_metadata_and_nothing_else(family, monkeypatch):
    """With ``jax.named_scope`` a null context the three programs lower to
    the same text without locations; with locations no part is left in it
    (the second lowering is a new trace, not the first one's cache)."""
    with_scopes = {p: lowered(family, p).as_text() for p in PROGRAMS}
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        for program in PROGRAMS:
            bare = served_program(FAMILIES[family], program, **GEOMETRY).lower()
            assert bare.as_text() == with_scopes[program], program
            names = operation_names(bare.compiler_ir())
            assert not [n for _, n in names if parts_of(n)], program
    finally:
        jax.clear_caches()
