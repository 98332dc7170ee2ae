"""PR 41's second guard, widened by PR 46 to every served family: the 20
programs of the six families the cells serve lower, at a tiny size, to the
text they lowered to on PR 46's PARENT (``tests/data/lowered_programs_pr45
.json``: recorded there from the parent's own per-kind makers, before they
went). Here they are built by the one constructor of a cache kind's programs
(``models/llama/programs.py``): what a kind needs is chosen by its record in
Python, so no served program holds one operation more or fewer. The six
digests ``lowered_programs_pr42.json`` held (Pangu's and Laguna's, recorded
on PR 43's parent) are in the new file unchanged.

Since PR 50 the routed experts take the dense combine where a dispatch
touches every held expert and is at most a row tile wide (``ops/moe.
dispatch_path``). No cell's join or prefill does, but THESE tiny ones do (64
and 128 rows that choose 2 of 4 experts, or of 8): six programs' text changed
for that reason alone. So the recording is held twice: with the shape rule
off (``GROUPED_MIN_TOKENS`` 0: the script's ``grouped``) all 20 lower to the
parent's text, to the digit; as served the six lower to this PR's own
recording (``lowered_programs_pr50.json``) and the other 14 to the parent's.

Since PR 56 the latent models' projections that are reshaped into heads stand
behind an optimisation barrier (``latent.into_heads``: on the chip the
compiler otherwise lays a layer's ``wq_b`` out again every layer-step). That
is one operation more in six programs, Pangu's and DeepSeek's, and the first
holding says that it is ALL: with the barrier taken out (the script's
``unbarred``) all 20 still lower to the parent's text, to the digit. As
served the six lower to PR 56's own recording (``lowered_programs_pr56.json``:
``python tests/lowered_programs.py`` on that tree)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

RECORDED = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr45.json").read_text())
FAMILIES = ("dense", "jamba", "laguna", "latent_index", "olmo_hybrid", "pangu")


# The tiny joins and prefills that the dense combine's rule moves, as served.
MOVED = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr50.json").read_text())


def _digests(*argv: str) -> dict[str, str]:
    """As the recording was made: the script in a process of its own (this
    suite's conftest pins the CPU's matmul precision, which is in the text)."""
    script = Path(__file__).parent / "lowered_programs.py"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, str(script), *argv], env=env,
                         capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout)


# The latent families' programs as served behind ``latent.into_heads``' barrier.
BARRED = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr56.json").read_text())


@pytest.fixture(scope="module")
def digests():
    return _digests("grouped", "unbarred")


@pytest.fixture(scope="module")
def served_digests():
    return _digests()


@pytest.mark.parametrize("program", sorted(RECORDED))
def test_the_program_lowers_to_the_parents_text(digests, program):
    assert digests[program] == RECORDED[program]


@pytest.mark.parametrize("program", sorted(RECORDED))
def test_the_program_as_served_lowers_to_its_recording(served_digests, program):
    assert served_digests[program] == {**RECORDED, **MOVED, **BARRED}[program]
    assert (program in MOVED or program in BARRED) == (served_digests[program] != RECORDED[program])


def test_the_moved_programs_are_tiny_joins_and_prefills_with_a_router():
    assert sorted(MOVED) == [
        f"{family}.{program}" for family in ("laguna", "latent_index", "pangu")
        for program in ("join", "prefill")]


def test_the_barred_programs_are_the_latent_families():
    assert sorted(BARRED) == [
        f"{family}.{program}" for family in ("latent_index", "pangu")
        for program in ("decode", "join", "prefill")]


# PR 52: the dear kind (``lfm2_moe``: no recording before it held its
# programs): the three every tree serves, recorded on PR 52's PARENT
# (``python tests/lowered_programs.py dear`` there), and the program PR 52
# adds, a step's joiners as three rows, as this tree lowers it.
DEAR = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr52.json").read_text())


@pytest.fixture(scope="module")
def dear_digests():
    return _digests("dear")


@pytest.mark.parametrize("program", sorted(DEAR))
def test_the_dear_kinds_programs_lower_to_their_recording(dear_digests, program):
    """The decode chunk, the one-row join and an epoch's group are the
    parent's text; the group of joining rows is held to its first lowering."""
    assert dear_digests[program] == DEAR[program]
    assert sorted(dear_digests) == sorted(DEAR) == [
        f"lfm2_moe.{p}" for p in ("decode", "join", "join_rows", "prefill")]


# PR 57: the family that generates by diffusion over blocks (``sdar_moe``),
# its three served programs held to their first lowering.
BLOCKS = json.loads(
    (Path(__file__).parent / "data" / "lowered_programs_pr57.json").read_text())


@pytest.fixture(scope="module")
def block_digests():
    return _digests("blocks")


@pytest.mark.parametrize("program", sorted(BLOCKS))
def test_the_block_programs_lower_to_their_recording(block_digests, program):
    assert block_digests[program] == BLOCKS[program]
    assert sorted(block_digests) == sorted(BLOCKS) == [
        f"sdar.{p}" for p in ("decode", "join", "prefill")]


def test_every_program_is_held(digests):
    held = sorted([f"{m}.{p}" for m in FAMILIES for p in ("decode", "join", "prefill")]
                  + ["dense.join_plain", "dense.prefill_plain"])
    assert sorted(RECORDED) == held == sorted(digests)
