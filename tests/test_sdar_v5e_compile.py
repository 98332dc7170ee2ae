"""SDAR's cell at its published widths, compiled for a described v5e (no chip:
``tests/test_paged_pool_carry.py`` says how): the decode dispatch (two blocks
of five passes: a scan of blocks over a scan of denoising passes and a commit)
and a join of ``sdar-30b-a3b-chat-pp8-d6`` carry the page pool without a copy
(ten passes write the same slots in place), the paged chunk kernel and the
pool's write compile under the block-causal mask at 4 queries a lane, both
pass bodies' three grouped products take the stacked experts whole (128 held
of 128: no expert stack written out again a pass), and all fits the chip
beside 8.72 GB of weights and 1.61 GB of pool."""

import dataclasses
import json
import os

import jax
import pytest

from cake_tpu.models.llama import pool_audit
from cake_tpu.models.llama.config import LlamaConfig

from test_paged_pool_carry import one_chip  # noqa: F401  (a fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(ROOT, "bench/configs/sdar-30b-a3b-chat-pp8-d6.json")) as _f:
    CELL_CONFIG = json.load(_f)
FLAGS = CELL_CONFIG["server_flags"]
TABLE_PAGES = 32  # --max-seq-len 4096 over --page-size 128
PAGES = int(FLAGS[FLAGS.index("--max-pages") + 1])
STACKS = ("wq", "wk", "wv", "wo", "w_qkv", "w_gate", "w_up", "w_down", "router")


@pytest.fixture(scope="module")
def sdar():
    return dataclasses.replace(LlamaConfig.from_hf_dict(CELL_CONFIG), attention_impl="pallas")


@pytest.fixture(scope="module")
def cell_reports(sdar, one_chip):  # noqa: F811
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                sdar, n_pages=PAGES, page_size=128, lanes=64, n_steps=8,
                table_pages=TABLE_PAGES, width=512, sharding=one_chip,
                only=("decode", "join"), watch=STACKS,
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_the_cell_compiles_for_v5e_without_a_copy_of_the_pool_or_a_weight(program, cell_reports):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    # PAGES pages x 128 tokens x 6 layers x 4 KV heads x 128 numbers in bf16, K (V as much)
    assert report["pool_bytes"] == PAGES * 128 * 6 * 4 * 128 * 2 == 805_306_368
    # weights 8.72 GB + K and V 1.61: the chip's 15.75 GB hold the program
    assert 10.3e9 < report["argument_bytes"] < 10.4e9, report
    # a pass's logits are [256, 151936] float32 (156 MB), twice over with the softmax
    assert report["temp_bytes"] < 1.5e9, report
    # a pass body holds a pool write, the chunk kernel and three grouped
    # products; the decode dispatch two bodies (a denoising pass, the commit)
    bodies = 2 if program == "decode" else 1
    assert report["pool_writes"] == bodies and report["grouped_products"] == 3 * bodies, report
    assert report["kernels"] == 5 * bodies, report
    # no stacked matrix is written out again (PERF.md section 7 row 45): a
    # layer's 1.2 GB of experts laid out again a pass would double its stream
    experts = [f["op"] for f in report["weight_ops"] if "128,2048,768" in f["op"]
               or "128,768,2048" in f["op"]]
    assert experts == [], experts
    if program == "decode":
        assert report["weight_ops"] == [], [f["op"] for f in report["weight_ops"]]
    assert report["code_bytes"] < 32e6, report


def test_the_cells_closed_shapes(sdar):
    """What ``--max-seq-len 4096 --page-size 128`` makes of the CLOSED
    instance for a model that generates by blocks: six joins (an epoch's rows
    go one a program, the join's), three decode dispatches and their tails,
    every width and dispatch whole blocks."""
    from cake_tpu.runtime.shapes import ProgramShapes

    assert FLAGS[FLAGS.index("--max-seq-len") + 1] == str(128 * TABLE_PAGES)
    shapes = ProgramShapes.for_model(sdar, 128, TABLE_PAGES)
    assert shapes.widths == (256, 512, 1024, 2048, 3072, 4096) and shapes.block == 4
    assert not [w for w in shapes.widths + shapes.capacities if w % 4]
    assert len(shapes.programs(64)) == 12 and shapes.whole_batch and shapes.join_rows == 1
    assert not [p for p in shapes.programs(64) if p[0] == "prefill"]
    assert shapes.one_row_prefill_is_join and shapes.lanes(1, 64) == 64
    # a dispatch is whole blocks from the first unwritten slot; none is left under 4
    assert shapes.decode_steps(8, 1024, 1016) == 8 and shapes.decode_steps(8, 1024, 1020) == 4
    assert shapes.decode_steps(8, 1024, 1022) == 0 and not shapes.more(1024, 1022)
    assert shapes.more(1024, 1020) and shapes.program_width(3000 + 6) == 3072
    # Mistral's instance stays the open one
    plain = LlamaConfig.from_hf_dict({**CELL_CONFIG, "model_type": "qwen3_moe"})
    assert ProgramShapes.for_model(plain, 128, TABLE_PAGES) == ProgramShapes()
