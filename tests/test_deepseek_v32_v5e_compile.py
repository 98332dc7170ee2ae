"""DeepSeek-V3.2-Exp's cell at its published widths, compiled for a described
v5e (no chip: ``tests/test_paged_pool_carry.py`` says how): the decode chunk
and a join of ``deepseek-v3.2-exp-ep16-d5`` carry BOTH pools (the latents and
the index's keys) without a copy, the join's attention lowers through Mosaic
(``ops/pallas/masked_prefill.py``) and so do the decode step's index scores
(``ops/pallas/index_scores.py``: the pool of index keys read in place, no
gathered copy of a row's table among the temporaries), and both fit the chip
beside 9.27 GB of weights and 2.64 GB of pools."""

import dataclasses
import json
import os

import jax
import pytest

from cake_tpu.models.llama import pool_audit
from cake_tpu.models.llama.config import LlamaConfig

from test_paged_pool_carry import one_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "bench/configs/deepseek-v3.2-exp-ep16-d5.json")) as _f:
    CELL_CONFIG = json.load(_f)
FLAGS = CELL_CONFIG["server_flags"]
TABLE_PAGES = 168  # --max-seq-len 21504 over --page-size 128


@pytest.fixture(scope="module")
def deepseek():
    return dataclasses.replace(
        LlamaConfig.from_hf_dict(CELL_CONFIG), attention_impl="pallas"
    )


@pytest.fixture(scope="module")
def cell_reports(deepseek, one_chip):  # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                deepseek, n_pages=2688, page_size=128, lanes=16, n_steps=8,
                table_pages=TABLE_PAGES, width=8064, sharding=one_chip,
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_the_cell_compiles_for_v5e_without_pool_copies(program, cell_reports):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    # 2688 pages x 128 tokens x 5 layers x (640 + 128) numbers in bf16
    assert report["pool_bytes"] == 2688 * 128 * 5 * 768 * 2 == 2_642_411_520
    # weights 9.27 GB + the pools 2.64: the chip's 15.75 GB hold the program
    assert 11.8e9 < report["argument_bytes"] < 12.0e9, report
    assert report["argument_bytes"] + report["temp_bytes"] < 14.5e9, report
    # the grouped experts' three products a sparse run; the decode step's index
    # scores and its choice's search (ops/pallas/kth_largest.py) a run (the dense
    # run and the sparse one); a join's attention kernel a run
    assert report["kernels"] == (7 if program == "decode" else 8), report
    # 16 rows, or a join's blocks of 2,048 under either of a share's two row
    # budgets (``moe._row_budget``), 8 of 256: never dense
    assert report["grouped_products"] == (3 if program == "decode" else 6), report
    if program == "decode":
        # PR 43's chunk held a layer's gathered index keys (16 rows x 21,504
        # slots x 128 in bf16: 88 MB) among its 640,564,224 bytes of temporaries
        assert report["temp_bytes"] <= 640_564_224 - 44e6, report
    assert report["code_bytes"] < 40e6, report  # one block's code whatever the width


def test_the_decode_chunk_reads_the_query_projections_where_they_lie(cell_reports):
    """PR 56: neither ``wq_b`` (75.5 MB a layer) nor the index's ``wi_q``
    (25 MB) is written out again by the decode chunk, a layer a layer-step or
    the stack at the chunk's entry (``latent.into_heads``); the transposed
    stacks were 504 MB of the parent's 553 MB of temporaries."""
    report = cell_reports["decode"]
    assert report["weight_ops"] == [], "\n".join(
        f"{f['op']}\n{f['text']}" for f in report["weight_ops"])
    assert report["temp_bytes"] < 64e6, report["temp_bytes"]


def test_the_cells_closed_shapes(deepseek):
    """What ``--max-seq-len 21504 --page-size 128`` makes of the CLOSED
    instance: six widths in whole 128s and three capacities."""
    from cake_tpu.models.llama.latent_index import window_block
    from cake_tpu.ops.sparse_index import window_kernel_supported
    from cake_tpu.runtime.shapes import ProgramShapes

    assert FLAGS[FLAGS.index("--max-seq-len") + 1] == str(128 * TABLE_PAGES)
    assert FLAGS[FLAGS.index("--max-pages") + 1] == str(16 * TABLE_PAGES)  # every lane backed
    shapes = ProgramShapes.for_model(deepseek, 128, TABLE_PAGES)
    assert shapes.widths == (2688, 5376, 8064, 13440, 18816, 21504)
    assert shapes.capacities == (5376, 10752, 21504)
    # a one-row group of an epoch's prefill is the join's program: six joins and three decode chunks
    assert len(shapes.programs(16)) == 9 and shapes.prefill_tokens == 2048
    assert not [p for p in shapes.programs(16) if p[0] == "prefill"]
    # the longest prompt with its template, and the probes
    assert shapes.program_width(16384 + 3) == 18816 and shapes.program_width(8003) == 8064
    assert shapes.program_width(303) == 2688 and shapes.program_width(3003) == 5376
    # a row wider than a block is a program of its own
    assert shapes.prefill_group(16, 2688) == 1 and shapes.prefill_group(16, 64) == 16
    for width in shapes.widths:
        assert window_kernel_supported(width, 128, 64, 128), width
        block = window_block(1, width)
        assert width % block == 0 and 1024 < block <= 2048 and block % 16 == 0, (width, block)
    step = int(FLAGS[FLAGS.index("--step-prefill") + 1])
    assert step >= 16384 + 3  # without it no prompt of this mix ever joins (PERF.md row 23)
