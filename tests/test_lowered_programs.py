"""PR 41's second guard, kept by PR 43: the served programs of Pangu's and
Laguna's cells lower, at a tiny size, to the text they lowered to on PR 43's
PARENT (``tests/data/lowered_programs_pr42.json``, recorded there by
``tests/lowered_programs.py``). PR 43 edits modules both run
(``models/llama/latent.py``, ``ops/moe.py``, ``ops/rope.py``,
``models/llama/model.py block_finish``, ``paged_cache.py``): what the new
model needs there is chosen by the config in Python, so an older model's
program holds not one operation more."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

RECORDED = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr42.json").read_text())


@pytest.fixture(scope="module")
def digests():
    """As the recording was made: the script in a process of its own (this
    suite's conftest pins the CPU's matmul precision, which is in the text)."""
    script = Path(__file__).parent / "lowered_programs.py"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script)], env=env, capture_output=True,
                         text=True, timeout=300, check=True)
    return json.loads(out.stdout)


@pytest.mark.parametrize("program", sorted(RECORDED))
def test_the_program_lowers_to_the_parents_text(digests, program):
    assert digests[program] == RECORDED[program]


def test_every_program_is_held():
    assert sorted(RECORDED) == [f"{m}.{p}" for m in ("laguna", "pangu")
                                for p in ("decode", "join", "prefill")]
