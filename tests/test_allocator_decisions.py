"""The pin PR 40 lacked: a model whose attention layers are all of ONE kind
is admitted, mapped, extended, preempted and released exactly as the commit
before the page pool learned of kinds did it.

``tests/allocator_decisions.py`` drives a seeded script of requests through
the engine's admission and its ``PageAllocator`` on the CPU, for tiny Mistral-,
Jamba-, Olmo-Hybrid- and Pangu-shaped models, and logs every call that
changes the allocator with the period it fell in and the free pages it left.
``tests/data/allocator_decisions_pr40.json`` is that log made ON PR 41's
PARENT (the file's docstring has the command). PR 40's change of the same
mechanism moved Pangu's cell by moving these decisions, not its programs
(PERF.md section 6); a later change that moves them on purpose records the
log again and says so.
"""

import json
from pathlib import Path

import pytest

from allocator_decisions import FAMILIES, decisions

RECORDED = json.loads(
    (Path(__file__).parent / "data" / "allocator_decisions_pr40.json").read_text()
)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_kind_decisions_are_the_parents(family):
    want, got = RECORDED[family], decisions(family)
    assert got["stats"] == want["stats"] and got["served"] == want["served"]
    assert want["stats"]["joins"] >= 4 and want["stats"]["preemptions"] >= 1
    assert got["calls"] == want["calls"]


def test_the_engine_sweeps_no_window_it_does_not_have():
    """A one-kind model's allocator is the ``PageAllocator`` itself, it has
    no window, and the engine's per-period path holds no sweep for it."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama.paged_cache import PageAllocator
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    cfg, init = FAMILIES["mistral"]()
    eng = BatchEngine(
        cfg, init(cfg, jax.random.PRNGKey(0), jnp.float32), ByteTokenizer(),
        max_seq_len=128, cache_dtype=jnp.float32,
        serve=ServeConfig(max_batch=2, scheduler="continuous", kv_mode="paged",
                          page_size=16, max_pages=8),
    )
    assert type(eng._alloc) is PageAllocator and eng._alloc.window is None
    assert eng._free_behind is None
