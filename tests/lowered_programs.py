"""The served programs of the six families the benchmark's cells serve,
lowered at a tiny size and digested: dense GQA (Mistral-shaped: with the
prefix cache on ``decode`` and the two suffix programs, and the plain join
and prefill a server with ``--prefix-cache off`` dispatches), a Jamba-like
and an Olmo-Hybrid-like hybrid, Pangu-shaped latent attention, the same
behind a learned index, and Laguna-shaped pools a kind.

    python tests/lowered_programs.py > tests/data/lowered_programs_pr45.json

(with the argument ``grouped``: the routed experts' dense combine never taken
by shape, ``ops/moe.GROUPED_MIN_TOKENS`` 0, which is how a tree since PR 50
stands to a recording made before it: tiny joins and prefills of two experts
a token in four fall under that rule where no cell's do)
(with ``unbarred``: ``jax.lax.optimization_barrier`` taken out, which is how
a tree since PR 56 stands to a recording made before it: the latent models'
projections that are reshaped into heads go behind one, ``latent.into_heads``,
and nothing else of their programs changed)
run IN A CHECKOUT OF THE PARENT (this file copied into its ``tests/``) makes
a recording; ``tests/test_lowered_programs.py`` holds the tree that stands to
it. (PR 46's own recording was made on ITS parent by that parent's helpers,
which built each kind's programs by hand: CHANGES.md, PR 46, says how.) The
programs are built by the one constructor, ``models/llama/programs.
served_programs``. The text is ``Lowered.as_text()``: StableHLO without
locations, so a scope or a moved line changes nothing and a changed operation
does. Tiny widths tile no kernel, so no Mosaic payload (which carries its
callers' line numbers) is in it.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax.numpy as jnp  # noqa: E402

GEOMETRY = dict(n_pages=32, page_size=16, lanes=3, table_pages=8, n_steps=4, width=64,
                prefill_rows=2, dtype=jnp.float32)
PROGRAMS = ("decode", "join", "prefill")
# What a family records beside the three: recorded name -> the program's.
MORE = {"dense": {"join_plain": "join", "prefill_plain": "prefill"}}


def families() -> dict:
    from cake_tpu.models.llama.config import LlamaConfig

    from laguna_tiny import HF
    from test_program_parts import FAMILIES

    return {
        # (the families the recording holds: a later family's programs have
        # no parent text to be held to)
        **{"pangu" if name == "latent_moe" else name: (config, GEOMETRY)
           for name, config in FAMILIES.items() if name not in ("lfm2_moe", "qwen3_next", "sdar")},
        # the windowed pool as the first recording held it (12 pages; a
        # server's would be 9 at 3 lanes)
        "laguna": (LlamaConfig.from_hf_dict(HF), {**GEOMETRY, "n_pages": (32, 12)}),
    }


def digests() -> dict[str, str]:
    from cake_tpu.models.llama.programs import served_programs

    from test_program_parts import served_program

    texts = {}
    for family, (config, geometry) in families().items():
        for program in PROGRAMS:
            texts[f"{family}.{program}"] = served_program(config, program, **geometry)
        plain = served_programs(config, **geometry, allow_pallas=False).programs
        for name, program in MORE.get(family, {}).items():
            texts[f"{family}.{name}"] = plain[program]()
    return {k: hashlib.sha256(t.lower().as_text().encode()).hexdigest()
            for k, t in sorted(texts.items())}


def dear_digests() -> dict[str, str]:
    """The family whose programs PR 52 adds to (``lfm2_moe``: state layers
    beside routed experts, which no recording before it held), as served: the
    three every tree has, and ``join_rows`` (a step's joiners as one program
    of three rows) where the tree has it. ``python tests/lowered_programs.py
    dear`` in a checkout of PR 52's parent recorded the three of ``tests/
    data/lowered_programs_pr52.json``; the fourth is PR 52's own."""
    from cake_tpu.models.llama import programs

    from test_program_parts import FAMILIES

    more = {"join_rows": 3} if hasattr(programs, "join_rows_program") else {}
    served = programs.served_programs(
        FAMILIES["lfm2_moe"], **GEOMETRY, **more, allow_pallas=False).programs
    return {f"lfm2_moe.{name}": hashlib.sha256(
        thunk().lower().as_text().encode()).hexdigest() for name, thunk in sorted(served.items())}


def block_digests() -> dict[str, str]:
    """The family PR 57 adds (``sdar_moe``: plain K and V, generating by
    diffusion over blocks): its three served programs as that tree lowers them
    (``python tests/lowered_programs.py blocks`` there recorded ``tests/data/
    lowered_programs_pr57.json``; no parent had them)."""
    from cake_tpu.models.llama import programs

    from test_program_parts import FAMILIES

    served = programs.served_programs(FAMILIES["sdar"], **GEOMETRY, allow_pallas=False).programs
    return {f"sdar.{name}": hashlib.sha256(
        thunk().lower().as_text().encode()).hexdigest() for name, thunk in sorted(served.items())}


if __name__ == "__main__":
    if sys.argv[1:] == ["dear"]:
        json.dump(dear_digests(), sys.stdout, indent=1)
        sys.exit(0)
    if sys.argv[1:] == ["blocks"]:
        json.dump(block_digests(), sys.stdout, indent=1)
        sys.exit(0)
    if "grouped" in sys.argv[1:]:
        from cake_tpu.ops import moe

        moe.GROUPED_MIN_TOKENS = 0
    if "unbarred" in sys.argv[1:]:
        import jax

        jax.lax.optimization_barrier = lambda x: x
    json.dump(digests(), sys.stdout, indent=1)
