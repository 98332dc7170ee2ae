"""Laguna's cell at its published widths, compiled for a described v5e (no
chip: ``tests/test_paged_pool_carry.py`` says how): the decode chunk and the
joins of ``laguna-s-2.1-ep8-d9`` lower through Mosaic at query groups of 6
and 9, carry BOTH pools a kind without a copy, write each through the pool's
kernel, and fit the chip beside 6.40 GB of weights."""

import dataclasses
import json
import os

import jax
import pytest

from cake_tpu.models.llama import pool_audit
from cake_tpu.models.llama.config import LlamaConfig

from test_paged_pool_carry import one_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


with open(os.path.join(ROOT, "bench/configs/laguna-s-2.1-ep8-d9.json")) as _f:
    CELL_CONFIG = json.load(_f)
FLAGS = CELL_CONFIG["server_flags"]
TABLE_PAGES = 192  # --max-seq-len 24576 over --page-size 128


@pytest.fixture(scope="module")
def laguna():
    return dataclasses.replace(
        LlamaConfig.from_hf_dict(CELL_CONFIG), attention_impl="pallas"
    )


def _reports(config, sharding, **kw):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                config, n_pages=(2048, 192), page_size=128, lanes=32,
                n_steps=8, sharding=sharding, **kw,
            )


@pytest.fixture(scope="module")
def cell_reports(laguna, one_chip):  # noqa: F811
    return _reports(laguna, one_chip, table_pages=TABLE_PAGES, width=6144)


@pytest.mark.parametrize("program", ["decode", "join"])
def test_laguna_cell_compiles_for_v5e_without_pool_copies(program, cell_reports):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["pool_writes"] == 5, report  # one a run of layers
    # 32 held of 256 at 10 a token, 32 rows or 6,144: the grouped path, three
    # products a sparse run
    assert report["grouped_products"] >= 3 and report["grouped_products"] % 3 == 0, report
    # 2048 pages x 3 layers and 192 x 6, K and V, 1 MB a page a layer
    assert report["pool_bytes"] == 2 * (3 * 2048 + 6 * 192) * 8 * 128 * 128 * 2
    # weights 6.40 GB + the pools 3.83: the chip's 16 GB hold the program
    assert report["argument_bytes"] + report["temp_bytes"] < 13.0e9, report


def test_the_widest_join_fits(laguna, one_chip):  # noqa: F811
    """A 24,576-slot window (the widest a 192-page table cuts; the start-up
    pass runs it, the cell's traffic reaches 18,432): its temporaries (4.09
    GB) beside the arguments (10.22) stay under the chip's 16 GB."""
    report = _reports(
        laguna, one_chip, table_pages=TABLE_PAGES, width=24576, only=("join",)
    )["join"]
    assert report["pool_ops"] == [] and report["scans"] == [], report
    assert report["argument_bytes"] + report["temp_bytes"] < 14.5e9, report


def test_the_cells_closed_shapes(laguna):
    """What ``--max-seq-len 24576 --page-size 128`` makes of the CLOSED
    instance: six widths and three capacities, shares of the lane's table."""
    from cake_tpu.runtime.shapes import ProgramShapes

    assert FLAGS[FLAGS.index("--max-seq-len") + 1] == str(128 * TABLE_PAGES)
    shapes = ProgramShapes.for_model(laguna, 128, TABLE_PAGES)
    assert shapes.widths == (1536, 3072, 6144, 12288, 18432, 24576)
    assert shapes.capacities == (6144, 12288, 24576)
    assert len(shapes.programs(32)) == 15
    # the first caller's epoch (prompt 3193, answer 1444) takes the smallest capacity, the
    # 32-seed epoch behind it (a 12,288-token prompt among its seeds) the whole table
    assert shapes.capacity(3200 + 1444, 24576) == 6144
    assert shapes.program_width(12304) == 18432 and shapes.capacity(12304 + 2394, 24576) == 24576
