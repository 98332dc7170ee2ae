"""LFM2-8B-A1B's cell at its published widths, compiled for a described v5e
(no chip: ``tests/test_paged_pool_carry.py`` says how): the decode chunk and a
join of ``lfm2-8b-a1b-d16`` carry the page pool (heads of 64 two a row of 128:
every paged kernel compiles at 128 lanes) and the convolutions' windows
without a copy, every sparse run's three grouped products of a join lower
through Mosaic with the run's stacked experts whole, the decode chunk's sparse
layers take the dense combine (no grouped product in it), and both fit the
chip beside 10.80 GB of weights and 2.15 GB of pool."""

import dataclasses
import json
import os

import jax
import pytest

from cake_tpu.models.llama import pool_audit
from cake_tpu.models.llama.config import LlamaConfig

from test_paged_pool_carry import one_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "bench/configs/lfm2-8b-a1b-d16.json")) as _f:
    CELL_CONFIG = json.load(_f)
FLAGS = CELL_CONFIG["server_flags"]
TABLE_PAGES = 32  # --max-seq-len 4096 over --page-size 128


@pytest.fixture(scope="module")
def lfm2():
    return dataclasses.replace(
        LlamaConfig.from_hf_dict(CELL_CONFIG), attention_impl="pallas"
    )


@pytest.fixture(scope="module")
def cell_reports(lfm2, one_chip):  # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                lfm2, n_pages=2048, page_size=128, lanes=64, n_steps=8,
                table_pages=TABLE_PAGES, width=512, sharding=one_chip,
                join_rows=3, only=("decode", "join", "join_rows"),
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_the_cell_compiles_for_v5e_without_pool_or_window_copies(program, cell_reports):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["state_scans"] == [] and report["state_copies"] == [], report
    # 2048 pages x 128 tokens x 4 attention layers x 4 packed KV heads x 128 numbers in bf16, K (V as much)
    assert report["pool_bytes"] == 2048 * 128 * 4 * 4 * 128 * 2 == 1_073_741_824
    # the windows alone: 12 layers x 2 taps x 64 lanes x 2048 channels in bf16
    assert report["state_bytes"] == 64 * 98_304 == 6_291_456
    # weights 10.80 GB + pool 2.15: the chip's 15.75 GB hold the program
    assert 12.9e9 < report["argument_bytes"] < 13.0e9, report
    assert report["temp_bytes"] < 64e6, report
    # a pool write and an attention kernel an attention run (4 + 4); a join's
    # window (512 rows) takes three grouped products a sparse run (8 x 3), the
    # decode chunk's dispatch (64 rows, 4 of 32 experts: every one touched) the
    # dense combine and holds none (``ops/moe.dispatch_path``, PR 50)
    grouped = 0 if program == "decode" else 24
    assert report["grouped_products"] == grouped, report
    assert report["pool_writes"] == 4 and report["kernels"] == 8 + grouped, report
    assert report["code_bytes"] < 24e6, report  # nine runs' bodies: code by the run


def test_a_steps_joiners_as_three_rows_compile_for_v5e(cell_reports):
    """PR 52's program at the cell's geometry: three rows of 512 slots. The
    pool is carried like the one-row join's, the grouped products are the
    join's 24 (the experts are read once for the three rows), and the rows'
    windows are placed in their lanes by a gather and a select over the lane
    state WHOLE: two copies of its 6 MB (15 us each at the HBM's peak, of a
    program of 40 ms), which is what no update-slice at a lane costs."""
    report, one = cell_reports["join_rows"], cell_reports["join"]
    assert report["scans"] == [] and report["pool_ops"] == [] and report["state_scans"] == []
    assert [c.split(" ")[1] for c in report["state_copies"]] == ["bf16[12,2,64,2048]"] * 2
    assert report["grouped_products"] == one["grouped_products"] == 24
    assert report["pool_writes"] == 4 and report["kernels"] == one["kernels"] == 32
    assert report["argument_bytes"] - one["argument_bytes"] < 16_384  # two more rows' operands
    assert report["temp_bytes"] < 64e6 and report["code_bytes"] < 24e6, report


def test_the_cells_closed_shapes(lfm2):
    """What ``--max-seq-len 4096 --page-size 128`` makes of the CLOSED
    instance: six joins, three decode chunks and their tails, no program for
    an epoch's groups (its rows go one a program through the join's)."""
    from cake_tpu.runtime.shapes import ProgramShapes

    assert FLAGS[FLAGS.index("--max-seq-len") + 1] == str(128 * TABLE_PAGES)
    assert FLAGS[FLAGS.index("--max-pages") + 1] == str(64 * TABLE_PAGES)  # every lane backed
    shapes = ProgramShapes.for_model(lfm2, 128, TABLE_PAGES)
    assert shapes.widths == (256, 512, 1024, 2048, 3072, 4096)
    # and, since PR 52, a step's joiners together as three rows of 512 slots
    assert len(shapes.programs(64)) == 12 + 1 and shapes.whole_batch
    assert not [p for p in shapes.programs(64) if p[0] == "prefill"]
    # the longest prompt with its template, and the probes
    assert shapes.program_width(3000 + 7) == 3072 and shapes.program_width(1207) == 2048
    assert shapes.program_width(71) == 256 and shapes.program_width(307) == 512
