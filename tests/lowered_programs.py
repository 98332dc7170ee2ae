"""The served programs of the two cells whose modules PR 43 touched, lowered
at a tiny size and digested: Pangu-shaped (``models/llama/latent.py``,
``ops/moe.py``) and Laguna-shaped (``models/llama/kinds.py``, which imports
``latent.py``'s account and shares ``ops/moe.py`` and ``ops/rope.py``).

    python tests/lowered_programs.py > tests/data/lowered_programs_pr42.json

run IN A CHECKOUT OF THE PARENT (this file copied into its ``tests/``) made
the recording; ``tests/test_lowered_programs.py`` holds the tree that stands
to it. The text is ``Lowered.as_text()``: StableHLO without locations, so a
scope or a moved line changes nothing and a changed operation does. Tiny
widths tile no kernel, so no Mosaic payload (which carries its callers' line
numbers) is in it.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

GEOMETRY = dict(n_pages=32, page_size=16, lanes=3, table_pages=8, n_steps=4, width=64,
                prefill_rows=2, dtype=jnp.float32)


def laguna_programs():
    """Laguna's three programs traced from abstract arguments
    (``pool_audit.audit_kinds_programs``' operands, never compiled)."""
    from cake_tpu.models.llama import kinds as K
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.ops.fuse import fuse_params

    from laguna_tiny import HF

    config = LlamaConfig.from_hf_dict(HF)
    g = GEOMETRY
    spec = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
    abstract = lambda build: jax.tree.map(  # noqa: E731
        lambda a: spec(a.shape, a.dtype), jax.eval_shape(build))
    params = abstract(lambda: fuse_params(K.init_params(config, jax.random.PRNGKey(0), g["dtype"])))
    cache = abstract(lambda: K.init_cache(config, (g["n_pages"], 12), g["page_size"], g["dtype"]))
    tables = lambda rows: tuple(spec((rows, g["table_pages"])) for _ in cache.pools)  # noqa: E731
    lanes, width, rows = g["lanes"], g["width"], g["prefill_rows"]
    decode = K._kinds_decode_fn(config, g["n_steps"], 0.0, None, None, 1.0, allow_pallas=False)
    return {
        "decode": decode._jitted.trace(
            params, cache, spec((lanes,)), spec(()), spec((lanes,)), tables(lanes),
            spec((lanes,), jnp.bool_), spec((lanes, 2), jnp.uint32), spec((lanes, 0)),
            spec((lanes,))),
        "join": K._kinds_join_fn(config, width, False)._jitted.trace(
            params, cache, spec((1, width)), spec((1,)), spec((1,)), tables(1), spec(())),
        "prefill": K._kinds_prefill_jit._jitted.trace(
            params, spec((rows, width)), cache, spec((rows,)), spec((rows,)), tables(rows),
            config, spec(()), allow_pallas=False),
    }


def digests() -> dict[str, str]:
    from test_program_parts import FAMILIES, PROGRAMS, served_program

    texts = {
        f"pangu.{p}": served_program(FAMILIES["latent_moe"], p, **GEOMETRY).lower().as_text()
        for p in PROGRAMS
    }
    texts.update({f"laguna.{p}": t.lower().as_text() for p, t in laguna_programs().items()})
    return {k: hashlib.sha256(t.encode()).hexdigest() for k, t in texts.items()}


if __name__ == "__main__":
    json.dump(digests(), sys.stdout, indent=1)
