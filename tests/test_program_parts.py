"""Every operation of a served program sits under one PART scope.

``obs/taxonomy.PROGRAM_PARTS`` names eight parts of a device program
(``embed`` .. ``sample``); the model's functions enter them with
``jax.named_scope``, and the benchmark reads a part's device time from the
trace's ``op_name`` metadata (``bench/parts.py``). Here the served programs
of the six families (dense GQA, a Jamba-like and an Olmo-Hybrid-like hybrid,
latent attention with sparse experts, the same behind a learned index, an
LFM2-like hybrid of short convolutions with routed experts, a Qwen3-Next-like
hybrid of grouped delta-rule heads, gated attention and a share of routed experts
beside a gated shared one) are LOWERED at a tiny size, never run:
the operations' names are read from the lowered module, and the same
programs lower to the same text, locations aside, with the scopes taken
away: a scope is metadata and nothing else.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import batch as B
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.programs import served_programs
from cake_tpu.obs.taxonomy import (
    FEED_FORWARD, MIXER, PROGRAM_PARTS, SAMPLE,
)

from test_hybrid_jamba import HF as JAMBA
from test_hybrid_olmo import HF as OLMO_HYBRID
from test_deepseek_v32 import TINY as LATENT_INDEX
from test_latent_pangu import SHARE as LATENT_MOE
from test_lfm2_moe import HF as LFM2_MOE
from test_qwen3_next import HF as QWEN3_NEXT
from test_sdar import HF as SDAR

FAMILIES = {
    "dense": LlamaConfig.tiny(),
    "jamba": LlamaConfig.from_hf_dict(JAMBA),
    "olmo_hybrid": LlamaConfig.from_hf_dict(OLMO_HYBRID),
    "latent_moe": LlamaConfig.from_hf_dict(LATENT_MOE),
    "latent_index": LlamaConfig.from_hf_dict(LATENT_INDEX),
    "lfm2_moe": LlamaConfig.from_hf_dict(LFM2_MOE),
    "qwen3_next": LlamaConfig.from_hf_dict(QWEN3_NEXT),
    # plain K and V, generating by diffusion over blocks of 4: the decode
    # program is one block of four denoising passes and a commit
    "sdar": LlamaConfig.from_hf_dict(SDAR),
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
    # the gated short convolution's three multiply-adds a channel (PR 48)
    "short_conv": MIXER,
}
# Which of them a family's program holds at these shapes (the XLA forms:
# tiny widths tile no kernel). The routed experts' path follows the dispatch's
# shape (``ops/moe.dispatch_path``, PR 50): a decode step of 3 rows is
# grouped, a join's 64 rows and a prefill's 128 that choose 2 of 4 experts (of
# 8) touch every one and take the dense combine; no cell's join does.
_EXPERTS = {"decode": "moe_experts_grouped", "join": "moe_experts_dense",
            "prefill": "moe_experts_dense"}
HOLDS = {
    ("jamba", "join"): {"selective_scan_xla"},
    ("jamba", "prefill"): {"selective_scan_xla"},
    ("olmo_hybrid", "decode"): {"gated_delta_step"},
    ("olmo_hybrid", "join"): {"gated_delta_rule"},
    ("olmo_hybrid", "prefill"): {"gated_delta_rule"},
    **{("latent_moe", p): {experts} for p, experts in _EXPERTS.items()},
    **{("latent_index", p): {experts, "index_scores", "index_select", "sparse_attention"}
       for p, experts in _EXPERTS.items()},
    **{("lfm2_moe", p): {experts, "short_conv"} for p, experts in _EXPERTS.items()},
    **{("qwen3_next", p): {experts, "gated_delta_step" if p == "decode" else "gated_delta_rule"}
       for p, experts in _EXPERTS.items()},
    **{("sdar", p): {experts} for p, experts in _EXPERTS.items()},
}
WEIGHTY = ("dot_general", "convolution", "custom_call", "scatter")


def served_program(config: LlamaConfig, program: str, *, allow_pallas: bool = False, **geometry):
    """One program a paged server dispatches, traced from abstract arguments
    (``models/llama/programs.served_programs``): a decode chunk over
    ``lanes`` rows, one joining row's window, or one group of an epoch's
    prefill. Greedy, as the benchmark's cells serve; with the prefix cache
    on where the kind has one (a window is then its suffix program)."""
    served = served_programs(config, **geometry, allow_pallas=allow_pallas).programs
    return served.get(f"suffix_{program}", served[program])()


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


def unscoped(family: str, program: str) -> list[str]:
    """The operations under no part, constants aside (a constant takes no
    time on a device, and their number follows the bodies' size)."""
    names = operation_names(lowered(family, program).compiler_ir())
    return [kind for kind, name in names
            if not parts_of(name) and not kind.endswith("constant")]


def test_the_short_convolutions_programs_leave_no_more_unscoped_than_jambas():
    """What sits under no part (the loops, the carries' plumbing: a device
    trace's ``decode_unscoped_pct``): the window-only state adds nothing to
    it, and the expert stacks outside the scanned trees add no operation.
    The projections are ``mixer_in``'s and ``mixer_out``'s, the window's
    write ``cache_write``'s."""
    for program in PROGRAMS:
        assert len(unscoped("lfm2_moe", program)) <= len(unscoped("jamba", program)), program
    names = operation_names(lowered("lfm2_moe", "decode").compiler_ir())
    conv = [name for _, name in names if "short_conv" in name.split("/")]
    assert conv and {tuple(parts_of(n)) for n in conv} == {(MIXER,)}
    dots = [parts_of(name) for kind, name in names if kind.endswith("dot_general")]
    assert ["mixer_in"] in dots and ["mixer_out"] in dots
    updates = [parts_of(name) for kind, name in names if kind.endswith("dynamic_update_slice")]
    # (the one under no part is the chunk's own: a step's tokens into the scan's output)
    assert ["cache_write"] in updates and all(p in ([], ["cache_write"]) for p in updates)


def test_qwen3_nexts_programs_leave_nothing_new_unscoped():
    """What the model adds sits under a part: the head groups' repeat of q and
    k under ``mixer_in``, the attention output's gate (a product with ``wg``
    and a sigmoid) under ``mixer_out``, the shared expert's three products and
    its gate's under ``feed_forward/shared_expert`` and nowhere else."""
    from cake_tpu.obs.taxonomy import MIXER_OUT, SHARED_EXPERT

    def plumbing(family, program):
        """Unscoped operations by kind, the scan's own read of a layer's leaf
        aside (a ``dynamic_slice`` and a ``squeeze`` a leaf a loop: they follow
        the tree's size, which is the model's, and take no time on a device)."""
        kinds = collections.Counter(unscoped(family, program))
        return kinds - collections.Counter(
            {"stablehlo.dynamic_slice": 10**6, "stablehlo.reshape": 10**6})

    for program in PROGRAMS:
        # what is left is the loops' and the expert account's plumbing: no more
        # of any kind than the hybrid that has sparse runs already (LFM2-like,
        # more runs), and beyond Olmo-Hybrid's only what that account brings
        ours, lfm2, olmo = (plumbing(f, program) for f in ("qwen3_next", "lfm2_moe", "olmo_hybrid"))
        assert not ours - lfm2, (program, ours - lfm2)
        assert set(ours - olmo) <= set(lfm2 - olmo), program
    names = operation_names(lowered("qwen3_next", "decode").compiler_ir())
    shared = [(kind, name) for kind, name in names if SHARED_EXPERT in name.split("/")]
    assert {tuple(parts_of(name)) for _, name in shared} == {(FEED_FORWARD,)}
    assert all(name.split("/").index(FEED_FORWARD) < name.split("/").index(SHARED_EXPERT)
               for _, name in shared)
    # its gate, up and down products, in the body of each of the four runs
    assert sum(kind.endswith("dot_general") for kind, _ in shared) == 3 * 4
    # ``wo`` in each of the four runs' bodies, the gate's ``wg`` beside it in
    # the two attention runs': the gate is ``mixer_out``'s
    out = [kind for kind, name in names if parts_of(name) == [MIXER_OUT]]
    assert sum(kind.endswith("dot_general") for kind in out) == 4 + 2
    # Laguna's and Pangu's shared experts (no gate) take the scope too
    names = operation_names(lowered("latent_moe", "decode").compiler_ir())
    assert [n for _, n in names if SHARED_EXPERT in n.split("/")]


def test_the_block_programs_leave_no_more_unscoped_than_the_plain_kinds():
    """A decode dispatch of blocks (``diffusion.block_decode``): the reveal
    under ``sample/unmask`` and nowhere else, the head in the denoising pass
    alone (the commit runs none), every weighty operation under a part, and
    under no part only the loops' plumbing: no more kinds of it than the
    dense family's decode chunk (Mistral's cell's) and LFM2's (the expert
    stacks outside the scanned tree and their account) leave."""
    from cake_tpu.obs.taxonomy import HEAD, UNMASK

    names = operation_names(lowered("sdar", "decode").compiler_ir())
    unmask = [name for _, name in names if UNMASK in name.split("/")]
    assert unmask and {tuple(parts_of(n)) for n in unmask} == {(SAMPLE,)}
    assert all(n.split("/").index(SAMPLE) < n.split("/").index(UNMASK) for n in unmask)
    heads = [kind for kind, name in names if parts_of(name) == [HEAD] and kind.endswith("dot_general")]
    assert len(heads) == 1  # the denoising pass's body; the commit's has none
    ours = collections.Counter(unscoped("sdar", "decode"))
    known = collections.Counter(unscoped("dense", "decode")) + collections.Counter(
        unscoped("lfm2_moe", "decode"))
    assert set(ours) <= set(known), set(ours) - set(known)
    for program in ("join", "prefill"):
        ours = collections.Counter(unscoped("sdar", program))
        assert set(ours) <= set(collections.Counter(unscoped("lfm2_moe", program))), program


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
