"""Qwen3-Next's cell at its published widths, compiled for a described v5e
(no chip: ``tests/test_paged_pool_carry.py`` says how): the decode chunk, a
join and a step's joiners as three rows of ``qwen3-next-80b-a3b-ep4-d12``
carry the page pool (heads of 256 on 2 KV heads: every paged kernel compiles
at a group of 8 query heads) and the float32 matrix states without a copy,
both delta kernels lower through Mosaic at 32 value heads of 128 x 128 handed
q and k repeated from the 16 key heads, every run's three grouped products
take the run's stacked experts whole (128 held of 512: the decode chunk keeps
the GROUPED path, 28% of the held experts untouched at 64 rows), and all fit
the chip beside 10.85 GB of weights, 1.24 GB of state and 0.81 GB of pool."""

import dataclasses
import json
import os

import jax
import pytest

from cake_tpu.models.llama import pool_audit
from cake_tpu.models.llama.config import LlamaConfig

from test_paged_pool_carry import one_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "bench/configs/qwen3-next-80b-a3b-ep4-d12.json")) as _f:
    CELL_CONFIG = json.load(_f)
FLAGS = CELL_CONFIG["server_flags"]
TABLE_PAGES = 32  # --max-seq-len 4096 over --page-size 128
PAGES = int(FLAGS[FLAGS.index("--max-pages") + 1])


@pytest.fixture(scope="module")
def qwen3_next():
    return dataclasses.replace(
        LlamaConfig.from_hf_dict(CELL_CONFIG), attention_impl="pallas"
    )


@pytest.fixture(scope="module")
def cell_reports(qwen3_next, one_chip):  # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                qwen3_next, n_pages=PAGES, page_size=128, lanes=64, n_steps=8,
                table_pages=TABLE_PAGES, width=512, sharding=one_chip,
                join_rows=3, only=("decode", "join", "join_rows"),
            )


@pytest.mark.parametrize("program", ["decode", "join", "join_rows"])
def test_the_cell_compiles_for_v5e_without_pool_or_state_copies(program, cell_reports):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["state_scans"] == [], report
    # the float32 state is never copied (a group's rows are placed in their
    # lanes a row at a time: ``hybrid._place_rows``); the bf16 window changes
    # layout at a decode program's two ends and is placed whole by a group
    # (two copies of 28 MB: Olmo-Hybrid's ``window_copies``), a join copies none
    assert not [c for c in report["state_copies"] if " f32[" in c], report
    windows = [c.split(" ")[1] for c in report["state_copies"]]
    assert windows == ([] if program == "join" else ["bf16[9,3,64,8192]"] * 2), report
    # PAGES pages x 128 tokens x 3 attention layers x 2 KV heads x 256 numbers in bf16, K (V as much)
    assert report["pool_bytes"] == PAGES * 128 * 3 * 2 * 256 * 2
    # 64 lanes of nine layers' [128, 4096] float32 states and [3, 8192] windows
    assert report["state_bytes"] == 64 * 19_316_736 == 1_236_271_104
    # weights 10.85 GB + state 1.24 + pool 0.81: the chip's 15.75 GB hold the program
    assert 12.8e9 < report["argument_bytes"] < 13.0e9, report
    assert report["temp_bytes"] < 300e6, report
    # a pool write and an attention kernel an attention run (3 + 3), a delta
    # kernel a state run (3: the step's in a decode chunk, the window's in a
    # join), three grouped products a run (6 x 3): the decode chunk keeps them
    assert report["grouped_products"] == 18 and report["pool_writes"] == 3, report
    assert report["kernels"] == 27, report
    assert report["code_bytes"] < 32e6, report  # six runs' bodies: code by the run


def test_the_cells_closed_shapes(qwen3_next):
    """What ``--max-seq-len 4096 --page-size 128`` makes of the CLOSED
    instance: the dear kind's thirteen programs (six joins, a step's joiners
    as three rows of 512 slots, three decode chunks and their tails)."""
    from cake_tpu.runtime.shapes import ProgramShapes

    assert FLAGS[FLAGS.index("--max-seq-len") + 1] == str(128 * TABLE_PAGES)
    shapes = ProgramShapes.for_model(qwen3_next, 128, TABLE_PAGES)
    assert shapes.widths == (256, 512, 1024, 2048, 3072, 4096)
    assert len(shapes.programs(64)) == 13 and shapes.whole_batch and shapes.join_rows == 3
    assert not [p for p in shapes.programs(64) if p[0] == "prefill"]
    # the longest prompt with its template, and the probes
    assert shapes.program_width(3000 + 6) == 3072 and shapes.program_width(1206) == 2048
    assert shapes.program_width(70) == 256 and shapes.program_width(306) == 512
