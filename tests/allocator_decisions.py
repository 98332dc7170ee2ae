"""A seeded script of requests through the serving engine's admission and a
``PageAllocator``, on the CPU, for tiny models of the four one-kind families
the benchmark serves: every call that changes the allocator, with the period
it fell in and the free pages it left, is the sequence of DECISIONS.

    PYTHONPATH=tests python tests/allocator_decisions.py > tests/data/allocator_decisions_pr40.json

run on the commit BEFORE the page pool learned of kinds (PR 41's parent)
made the recording that ``tests/test_allocator_decisions.py`` holds every
later tree to: a model whose attention layers are all of one kind must be
admitted, mapped, extended, preempted and released exactly as it was. This
file uses nothing a later PR added, so it runs on that parent unchanged.
"""

from __future__ import annotations

import json
import random
import sys

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import latent as L
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.paged_cache import PageAllocator
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.runtime.serving import BatchEngine, ServeConfig

from test_continuous_serving import TINY_JAMBA, collect
from test_hybrid_olmo import HF as TINY_OLMO_HYBRID
from test_latent_pangu import SHARE as TINY_PANGU

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
FAMILIES = {
    "mistral": lambda: (LlamaConfig.tiny(num_hidden_layers=2, model_type="mistral"), M.init_params),
    "jamba": lambda: (LlamaConfig.from_hf_dict(TINY_JAMBA), H.init_params),
    "olmo_hybrid": lambda: (LlamaConfig.from_hf_dict(TINY_OLMO_HYBRID), H.init_params),
    "pangu": lambda: (LlamaConfig.from_hf_dict(TINY_PANGU), L.init_params),
}
WATCHED = ("map_range", "release", "reset", "release_lanes")


def script(seed: int = 41, n: int = 9) -> list[tuple[str, int]]:
    """Arrivals: (prompt, new tokens), a byte a token; the ends follow from
    the budgets (and an end-of-sequence id where the weights draw one)."""
    rng = random.Random(seed)
    return [
        ("".join(rng.choice("abcdefgh ") for _ in range(rng.randrange(4, 150))),
         rng.randrange(3, 40))
        for _ in range(n)
    ]


def decisions(family: str) -> list:
    """[[period, call, arguments, pages free after], ...] of one session:
    every request queued before the engine starts, three lanes, a pool of 24
    pages of 16 slots, so that admission, joins, growth a chunk at a time
    and preemption all happen."""
    cfg, init = FAMILIES[family]()
    params = init(cfg, jax.random.PRNGKey(5), jnp.float32)
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=512, cache_dtype=jnp.float32,
        serve=ServeConfig(
            max_batch=3, decode_chunk_size=4, admission_window=0.0,
            scheduler="continuous", kv_mode="paged", page_size=16, max_pages=24,
        ),
    )
    log, plain = [], {name: getattr(PageAllocator, name) for name in WATCHED}

    def watched(name):
        def call(self, *args, **kw):
            out = plain[name](self, *args, **kw)
            period = eng.periods.snapshot()["period"]["count"]
            log.append([period, name, [int(a) for a in (*args, *kw.values())], self.pages_free])
            return out
        return call

    for name in WATCHED:
        setattr(PageAllocator, name, watched(name))
    try:
        handles = [eng.submit([Message.user(p)], n, GREEDY) for p, n in script()]
        eng.start()
        try:
            served = [len(collect(h)) for h in handles]
        finally:
            eng.stop()
    finally:
        for name in WATCHED:
            setattr(PageAllocator, name, plain[name])
    stats = {k: eng.stats[k] for k in ("joins", "preemptions", "restores")}
    return {"calls": log, "stats": stats, "served": served}


if __name__ == "__main__":
    families = sys.argv[1:] or list(FAMILIES)
    json.dump({f: decisions(f) for f in families}, sys.stdout, indent=None)
