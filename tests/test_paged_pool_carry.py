"""The paged pool as a scan carry: same bytes, and no program that moves it.

Two properties of models/llama/batch.batched_blocks_forward's paged branch:

  * **Equivalence.** The scan that carries the whole pool and writes it in
    place gives, bit for bit, the logits and EVERY byte of the pool that a
    Python loop over layers gives when it calls the single-layer functions
    (``paged_write_layer`` and the kernels on one layer's 4-D pages) — for a
    decode chunk, a fresh prefill, a suffix prefill under ``write_starts``
    and a join, under the Pallas kernels (interpret mode) and the XLA
    fallback. The pool starts full of stale bytes (every page "recycled"),
    one lane's pad pages are unmapped and one lane is wholly unmapped, so a
    write that should have dropped shows as a changed byte.
  * **Structure.** No ``scan`` of the served programs takes the pool, or a
    layer of it, as a scanned input or returns one as a stacked output, and
    the compiled programs hold no pool-sized temporary (pool_audit.py). The
    same check on a described v5e, at the benchmark cell's geometry, also
    finds the copies that only the TPU's layout assignment makes.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import batch as B
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama import pool_audit, programs
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.paged_cache import (
    UNMAPPED,
    PagedKVCache,
    paged_write_layer,
)
from cake_tpu.ops.attention import gqa_attention
from cake_tpu.ops.fuse import fuse_params, resolve_fusion
from cake_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
)
from cake_tpu.ops.pallas.paged_prefill import (
    paged_chunk_attention,
    paged_chunk_attention_xla,
)
from cake_tpu.ops.rope import model_rope_tables

PS = 128  # the kernels' page: one 128-lane tile
N_P = 3  # pages a row's table holds -> 384 slots
N_PAGES = 14
LANES = 3
CONFIG = LlamaConfig.tiny(num_hidden_layers=3, attention_impl="pallas")


@functools.lru_cache(maxsize=1)
def model():
    params = M.init_params(CONFIG, jax.random.PRNGKey(0), jnp.float32)
    return fuse_params(params)


def stale_pool(seed):
    """A pool in which every page holds somebody's old bytes."""
    rng = np.random.default_rng(seed)
    shape = (
        CONFIG.num_hidden_layers, N_PAGES, CONFIG.num_key_value_heads, PS,
        CONFIG.head_dim,
    )
    return PagedKVCache(
        k=jnp.asarray(rng.normal(size=shape), jnp.float32),
        v=jnp.asarray(rng.normal(size=shape), jnp.float32),
    )


def tables(pads, ends, dead=()):
    """Row r maps the pages its window [pads[r], ends[r]) touches, scattered
    over the pool (highest pages first); pages wholly under the pad stay
    UNMAPPED, and so does every page of a row in ``dead``."""
    t = np.full((len(pads), N_P), UNMAPPED, np.int32)
    free = list(range(N_PAGES))
    for r, (lo, hi) in enumerate(zip(pads, ends)):
        if r in dead:
            continue
        for p in range(lo // PS, -(-hi // PS)):
            t[r, p] = free.pop()
    return jnp.asarray(t)


# ------------------------------------------------------------------ oracle


def oracle_blocks(
    params, x, kv, bt, *, kind, q_pos, k_pos, pads, lengths, write_pos,
    write_starts, kernel,
):
    """batched_blocks_forward's paged branch as a Python loop over layers,
    each layer's pages taken out of the pool, written and read by the
    single-layer functions, and stacked back."""
    cos, sin = model_rope_tables(CONFIG, N_P * PS)
    fusion = resolve_fusion(CONFIG, kernel)
    kw = dict(
        window=CONFIG.sliding_window, scale=CONFIG.attn_scale,
        softcap=CONFIG.attn_logit_softcap,
    )
    b = x.shape[0]
    if kind == "suffix":
        q_pos = jnp.maximum(q_pos, 0)
    if kind == "decode":
        cos, sin = cos[q_pos], sin[q_pos]
    q_starts = (
        jnp.broadcast_to(write_pos, (b,)).astype(jnp.int32)
        if kind == "suffix" else jnp.zeros((b,), jnp.int32)
    )
    k_layers, v_layers = [], []
    for i in range(CONFIG.num_hidden_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        flag = lp.get("win_flag")
        if kind == "fresh":
            q, k, v = M.block_qkv(
                lp, x, cos, sin, q_pos, CONFIG, k_positions=k_pos,
                fusion=fusion,
            )
        else:
            q, k, v = M.block_qkv(lp, x, cos, sin, q_pos, CONFIG, fusion=fusion)
        k_l, v_l = paged_write_layer(
            kv.k[i], kv.v[i], k, v, write_pos, bt, starts=write_starts
        )
        if kind == "decode" and kernel:
            attn = paged_decode_attention(
                q, k_l, v_l, lengths, bt, pads, flag, **kw
            )
        elif kind == "decode":
            attn = paged_decode_attention_xla(
                q, k_l, v_l, q_pos, k_pos, bt, window_flag=flag, **kw
            )
        elif kernel:
            attn = paged_chunk_attention(
                q, k_l, v_l, q_starts, lengths, pads, bt, flag, **kw
            )
        elif kind == "suffix":
            attn = paged_chunk_attention_xla(
                q, k_l, v_l, q_pos, k_pos, bt, window_flag=flag, **kw
            )
        else:
            attn = gqa_attention(q, k, v, q_pos, k_pos, window_flag=flag, **kw)
        x = M.block_finish(lp, x, attn, CONFIG, fusion=fusion)
        k_layers.append(k_l)
        v_layers.append(v_l)
    return x, PagedKVCache(k=jnp.stack(k_layers), v=jnp.stack(v_layers))


@functools.partial(jax.jit, static_argnames=("kernel",))
def oracle_prefill(params, tokens, kv, pads, bt, ends, write_starts, kernel):
    b, l = tokens.shape
    x = M.embed_tokens(params, tokens, CONFIG)
    q_pos, k_pos = B.prefill_positions(l, pads, ends)
    x, kv = oracle_blocks(
        params, x, kv, bt, kind="fresh", q_pos=q_pos, k_pos=k_pos, pads=pads,
        lengths=ends, write_pos=jnp.int32(0), write_starts=write_starts,
        kernel=kernel,
    )
    return M.head_forward(params, x, ends[0], CONFIG), kv


@functools.partial(jax.jit, static_argnames=("kernel",))
def oracle_suffix(params, tokens, kv, pads, write_starts, bt, start, kernel):
    w = tokens.shape[1]
    x = M.embed_tokens(params, tokens, CONFIG)
    q_pos, k_pos, lengths = B.verify_positions(w, pads, start, N_P * PS)
    x, kv = oracle_blocks(
        params, x, kv, bt, kind="suffix", q_pos=q_pos, k_pos=k_pos, pads=pads,
        lengths=lengths, write_pos=start, write_starts=write_starts,
        kernel=kernel,
    )
    return M.head_forward(params, x, jnp.int32(w), CONFIG), kv


@functools.partial(jax.jit, static_argnames=("kernel", "n_steps"))
def oracle_decode(params, tok, kv, slot, pads, bt, kernel, n_steps):
    """Greedy steps one after another; (tokens [B, n], every step's logits,
    pool)."""
    toks, all_logits = [], []
    for _ in range(n_steps):
        x = M.embed_tokens(params, tok[:, None], CONFIG)
        q_pos, k_pos, lengths = B.decode_positions(slot, pads, N_P * PS)
        x, kv = oracle_blocks(
            params, x, kv, bt, kind="decode", q_pos=q_pos, k_pos=k_pos, pads=pads,
            lengths=lengths, write_pos=slot, write_starts=None, kernel=kernel,
        )
        logits = M.head_forward(params, x, jnp.int32(1), CONFIG)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        slot = slot + 1
        toks.append(tok)
        all_logits.append(logits)
    return jnp.stack(toks, 1), jnp.stack(all_logits, 1), kv


# ------------------------------------------------------------------- cases


def run_decode(kernel, kv):
    pads = np.asarray([5, 150, 0], np.int32)  # row 1: page 0 under its pad
    slot, n = 255, 3  # the chunk crosses from page 1 into page 2
    bt = tables(pads, [slot + n] * LANES, dead={2})
    tok = jnp.asarray([7, 300, 11], jnp.int32)
    want_toks, want_logits, want_kv = oracle_decode(
        model(), tok, kv, jnp.int32(slot), jnp.asarray(pads), bt, kernel, n
    )
    # The program returns tokens only; the first step's logits come from
    # the one-token forward it scans over (before the program is given the
    # pool, which it takes for good).
    one = B.paged_forward_one(
        model(), jnp.asarray(pads), bt, CONFIG, N_P * PS, allow_pallas=kernel
    )
    got_logits, _ = jax.jit(one)(tok[:, None], kv, jnp.int32(slot))
    fn = programs.decode_program(
        programs.KINDS[CONFIG.cache_kind], CONFIG, N_P * PS, n, 0.0, None, None,
        1.0, kernel,
    )
    keys = jnp.zeros((LANES, 2), jnp.uint32)
    got_toks, got_kv, *_ = fn(
        model(), kv, tok, jnp.int32(slot), jnp.asarray(pads), bt, keys,
        jnp.zeros((LANES, 0), jnp.int32), jnp.zeros((LANES,), jnp.int32),
    )
    np.testing.assert_array_equal(np.asarray(got_toks), np.asarray(want_toks))
    return got_logits, got_kv, want_logits[:, 0], want_kv


def run_fresh(kernel, kv):
    pads = np.asarray([3, 140, 20], np.int32)
    width = 272
    ends = np.asarray([width, width, 200], np.int32)  # row 2: a dead tail
    bt = tables(pads, ends)
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, 256, (LANES, width)), jnp.int32)
    # Row 0 rides the prefill warm: its first 64 slots must not be written.
    ws = jnp.asarray([64, 0, 0], jnp.int32)
    want = oracle_prefill(
        model(), tokens, kv, jnp.asarray(pads), bt, jnp.asarray(ends), ws, kernel
    )
    got = B._paged_prefill_jit(
        model(), tokens, kv, jnp.asarray(pads), bt, CONFIG,
        ends=jnp.asarray(ends), seq_len=jnp.asarray(ends)[0],
        write_starts=ws, allow_pallas=kernel,
    )
    return *got, *want


def run_suffix(kernel, kv):
    pads = np.asarray([130, 4, 200], np.int32)  # rows 0, 2: page 0 unmapped
    start, w = 192, 64  # window [192, 256): ends on a page boundary
    bt = tables(pads, [start + w] * LANES, dead={1})
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 256, (LANES, w)), jnp.int32)
    ws = jnp.asarray([224, 192, 200], jnp.int32)
    args = (tokens, kv, jnp.asarray(pads), ws, bt)
    want = oracle_suffix(model(), *args, jnp.int32(start), kernel)
    got = B._paged_suffix_jit(
        model(), *args, CONFIG, jnp.int32(start), allow_pallas=kernel
    )
    return *got, *want


def run_join(kernel, kv):
    """One row joins a pool that other lanes live in: its own table row, a
    window of 128 slots ending at the running batch's slot."""
    pad, start, w = 170, 128, 128
    others = tables([0, 0], [300, 260])
    lane = np.full((1, N_P), UNMAPPED, np.int32)
    lane[0, 1] = 0  # pages the others did not take, out of order
    bt = jnp.asarray(lane)
    assert not set(lane[lane >= 0]) & set(np.asarray(others).ravel())
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, 256, (1, w)), jnp.int32)
    args = (
        tokens, kv, jnp.asarray([pad], jnp.int32),
        jnp.asarray([pad], jnp.int32), bt,
    )
    want = oracle_suffix(model(), *args, jnp.int32(start), kernel)
    got = B._paged_suffix_join_jit(
        model(), *args, CONFIG, jnp.int32(start), allow_pallas=kernel
    )
    return *got, *want


CASES = {
    "decode": run_decode, "fresh": run_fresh, "suffix": run_suffix,
    "join": run_join,
}


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("case", CASES)
def test_carried_pool_equals_layer_loop(case, kernel, monkeypatch):
    # Here the pool's write is the kernel too (ops/pallas/paged_write.py),
    # interpreted like the attention kernels; a server on the CPU takes the
    # scatter (``compiled_here``), which the layer loop beside it calls.
    monkeypatch.setattr(B, "compiled_here", lambda: True)
    seed = list(CASES).index(case)
    got_logits, got_kv, want_logits, want_kv = CASES[case](
        kernel, stale_pool(seed)
    )
    np.testing.assert_array_equal(
        np.asarray(got_logits), np.asarray(want_logits)
    )
    before = stale_pool(seed)  # the decode program kept the one it got
    for name in ("k", "v"):
        got = np.asarray(getattr(got_kv, name))
        np.testing.assert_array_equal(got, np.asarray(getattr(want_kv, name)))
        # The case wrote something, and left most stale bytes alone.
        changed = (got != np.asarray(getattr(before, name))).mean()
        assert 0 < changed < 0.5, changed


# --------------------------------------------------------------- structure


@functools.lru_cache(maxsize=2)
def tiny_reports(kernel):
    # Abstract shapes: a pool far larger than the tiny model's temporaries.
    return pool_audit.audit_programs(
        CONFIG, n_pages=64, page_size=PS, lanes=LANES, table_pages=N_P,
        n_steps=4, width=64, dtype=jnp.float32, allow_pallas=kernel,
        only=("decode", "suffix_join"),
    )


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("program", ["decode", "suffix_join"])
def test_no_scan_moves_the_pool(program, kernel):
    report = tiny_reports(kernel)[program]
    assert report["scans"] == []
    # The compiled half holds for the XLA twins alone here: the Pallas
    # INTERPRETER stages every operand of a kernel through copies of its
    # own. The kernels' program is compiled for the v5e below.
    if not kernel:
        assert report["pool_ops"] == [], report
        if report["temp_bytes"] is not None:
            assert report["temp_bytes"] < report["pool_bytes"], report


def test_the_guard_sees_the_scanned_form():
    """The dense cache still goes through the scan as a scanned input and a
    stacked output (ROADMAP Queue 1): the same reader must say so, or the
    guard above proves nothing."""
    from cake_tpu.models.llama.cache import init_cache

    kv = jax.eval_shape(lambda: init_cache(3, LANES, 256, 2, 16, jnp.float32))
    fn = B._decode_fn(CONFIG, 256, 4, 0.0, None, None, 1.0)._jitted
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    traced = fn.trace(
        jax.eval_shape(model), kv, i32((LANES,)), i32(()), i32((LANES,)),
        jax.ShapeDtypeStruct((LANES, 2), jnp.uint32), i32((LANES, 0)),
        i32((LANES,)),
    )
    found = pool_audit.scans_moving_pool(traced.jaxpr, tuple(kv.k.shape))
    assert any("scanned input" in f for f in found), found
    assert any("stacked output" in f for f in found), found


def test_hlo_reader_names_pool_movers():
    hlo = """
  %copy.110 = bf16[16,256,8,128,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p.1)
  %fusion.9 = bf16[16,256,8,128,128]{4,3,2,1,0} fusion(%a, %b), kind=kLoop
  %bitcast_dynamic-update-slice_fusion.5 = bf16[16,256,8,128,128]{4,3,2,1,0} fusion(%a, %b), kind=kLoop
  ROOT %dynamic-slice_bitcast_fusion.5 = bf16[256,8,128,128]{3,2,1,0} fusion(%a), kind=kLoop
  %copy.3 = bf16[8,1,4096]{2,1,0} copy(%x)
  %copy.4 = f32[16,256,8,128,128]{4,3,2,1,0} copy(%y)
"""
    assert pool_audit.pool_ops_in_hlo(
        hlo, (16, 256, 8, 128, 128), jnp.bfloat16
    ) == [
        "copy.110 bf16[16,256,8,128,128]",
        "bitcast_dynamic-update-slice_fusion.5 bf16[16,256,8,128,128]",
        "dynamic-slice_bitcast_fusion.5 bf16[256,8,128,128]",
    ]


# ------------------------------ the cell's geometry, compiled for a v5e
#
# The TPU's compiler is installed here and compiles for a chip that is
# described, not attached (nothing runs). It is the only place short of the
# chip where the copies that the TPU's LAYOUT assignment makes can be seen:
# a scatter whose window holds the KV head has the pool laid out token-major
# and converted back, whole, for every kernel call, with a jaxpr as clean as
# the right one's.


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cell_reports(one_chip):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench/configs/mistral-7b-v0.1-d16.json")) as f:
        config = dataclasses.replace(
            LlamaConfig.from_hf_dict(json.load(f)), attention_impl="pallas"
        )
    with pytest.MonkeyPatch.context() as mp:
        # The wrappers pick interpret mode from the default backend, which
        # stays the CPU here. conftest pins f32 matmuls for the CPU's
        # oracles; the served program runs at the default, and Mosaic
        # refuses an fp32-precision bf16 dot.
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                config, n_pages=256, page_size=128, lanes=8, table_pages=8,
                n_steps=8, width=256, sharding=one_chip,
                only=("decode", "suffix_join"),
            )


@pytest.mark.parametrize("program", ["decode", "suffix_join"])
def test_cell_geometry_compiles_for_v5e_without_pool_copies(
    program, cell_reports
):
    report = cell_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    # the layer scan's write is the kernel (ops/pallas/paged_write.py): at 8
    # KV heads, 8 rows of one token or one row's window of 256
    assert report["pool_writes"] == 1, report
    assert report["temp_bytes"] < report["pool_bytes"] // 8, report


# The pool's write alone (ops/pallas/paged_write.py), compiled for a v5e at
# the shapes the cells' servers run ahead: a decode step, a join, an epoch's
# group and the blank rows of start-up, at each cell's KV heads. How many
# slabs a group holds follows (KV heads, rows, width), and Mosaic counts what
# they take: 32 rows x 64 slots at ONE head made a group of 160 slabs, 960 DMA
# semaphores for a core's 512, and Jamba's server did not start (PR 39).
WRITE_SHAPES = [
    (rows, n_kv, width)
    for n_kv in (1, 8, 30)
    for rows, width in ((32, 1), (64, 1), (32, 64), (64, 40), (1, 512), (8, 2560))
]


@pytest.mark.parametrize("rows,n_kv,width", WRITE_SHAPES)
def test_pool_write_compiles_for_v5e(rows, n_kv, width, one_chip):
    from cake_tpu.ops.pallas.paged_write import paged_pool_write

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = spec((2, 64, n_kv, PS, 128), jnp.bfloat16)
    new = spec((rows, width, n_kv, 128), jnp.bfloat16)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        compiled = paged_pool_write.trace(
            pool, pool, spec(()), new, new, spec(()), spec((rows, 24)),
            spec((rows,)),
        ).lower().compile()
    assert len(pool_audit._POOL_WRITE.findall(compiled.as_text())) == 1


# ------------- a model with state layers, at its cell's geometry, for a v5e
#
# jamba2-3b-chat-closed (bench/configs/ai21-jamba2-3b.json): the page pool of
# the 2 attention layers AND the lane state of the 26 state layers ride the
# scans' carries (models/llama/hybrid.py). The same compile also shows that
# Mosaic takes the paged kernels at one KV head under a group of 20 query
# heads (a block of 20 rows): both are in each program; and (PR 42) the
# one-token update's kernel (ops/pallas/selective_step.py) at [16, 5120] a
# row, eight rows a grid step, handed the layer stack's state whole.


@pytest.fixture(scope="module")
def hybrid_reports(one_chip):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench/configs/ai21-jamba2-3b.json")) as f:
        config = dataclasses.replace(
            LlamaConfig.from_hf_dict(json.load(f)), attention_impl="pallas"
        )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                config, n_pages=1024, page_size=128, lanes=32, table_pages=8,
                n_steps=8, width=512, sharding=one_chip,
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_hybrid_cell_compiles_for_v5e_without_pool_or_state_copies(
    program, hybrid_reports
):
    report = hybrid_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["state_scans"] == [] and report["state_copies"] == [], report
    # the pool's write and the attention, one each a layer of attention (4);
    # each of the three scanned runs of state layers holds one more: a join's
    # prefill scan, and since PR 42 a decode step's one-token update
    # (``selective_step``; 4 before it, the update then in XLA)
    assert report["pool_writes"] == 2, report
    assert report["kernels"] == 7, report
    # 298 MB of state, 134 MB of pool: a second copy of either would show
    assert report["state_bytes"] == 32 * 9_318_400
    assert report["temp_bytes"] < report["state_bytes"] // 2, report


def test_hybrid_audit_names_a_state_that_is_scanned():
    """The audit's own reading, on a walk that does it wrong: the state as
    a scanned input and a stacked output."""
    ssm = jnp.zeros((6, 4, 4, 128), jnp.float32)

    def wrong(ssm):
        return jax.lax.scan(lambda c, s: (c, s + 1.0), 0.0, ssm)[1]

    found = pool_audit.scans_moving_pool(jax.make_jaxpr(wrong)(ssm), ssm.shape)
    assert len(found) == 2 and "scanned input" in found[0]


# ---- a model whose state layers run the gated delta rule, at its cell's
# geometry, for a v5e
#
# olmo-hybrid-7b-chat-closed (bench/configs/olmo-hybrid-7b-d16.json): the
# page pool of the 4 full-attention layers (30 KV heads, no grouping: the
# heaviest K and V a layer in the benchmark) and the lane state of the 12
# delta-rule layers ride the scans' carries. The same compile shows that
# Mosaic takes the paged kernels at 30 KV heads, the one-token update's
# kernel (ops/pallas/delta_step.py) at [96, 5760] a row, handed the layer
# stack's state whole, and a window's kernel (ops/pallas/delta_rule.py) at
# 30 heads of 96 keys and 192 values.


@pytest.fixture(scope="module")
def delta_reports(one_chip):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench/configs/olmo-hybrid-7b-d16.json")) as f:
        config = dataclasses.replace(
            LlamaConfig.from_hf_dict(json.load(f)), attention_impl="pallas"
        )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                config, n_pages=320, page_size=128, lanes=32, table_pages=8,
                n_steps=8, width=512, sharding=one_chip,
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_delta_rule_cell_compiles_for_v5e_without_pool_or_state_copies(
    program, delta_reports
):
    report = delta_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["state_scans"] == [], report
    # The float32 state (876 MB) is never copied. The convolution's window
    # (bf16, 26.5 MB) changes layout once at each end of a decode program:
    # two copies of it a chunk of 8 steps, 0.1 ms of 100.
    assert not [op for op in report["state_copies"] if "f32[" in op], report
    assert len(report["state_copies"]) <= 2, report
    # each of the four periods' scans holds the pool's write (30 KV heads a
    # slab: ops/pallas/paged_write.py), an attention kernel and a rule
    # kernel: decode the one-token update's (ops/pallas/delta_step.py), a
    # join the window's (ops/pallas/delta_rule.py, [96, 1152] of state a
    # grid step in VMEM over the chunks)
    assert report["pool_writes"] == 4, report
    assert report["kernels"] == 12, report
    assert report["state_bytes"] == 32 * 27_371_520
    assert report["temp_bytes"] < report["state_bytes"] // 2, report


# -------- a model with latent attention, at its cell's geometry, for a v5e
#
# pangu-ultra-ep16-chat-closed (bench/configs/openpangu-ultra-moe-718b-ep16
# .json): the latent pool rides the carries of the two runs' scans
# (models/llama/latent.py). The same compile shows that Mosaic takes the
# absorbed decode kernel at 128 heads x 640 against pages of 128 x 640 and
# the chunk kernel at heads padded to 256, and what the programs need beside
# their 11.5 GB of arguments.


@pytest.fixture(scope="module")
def latent_reports(one_chip):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench/configs/openpangu-ultra-moe-718b-ep16.json")) as f:
        config = dataclasses.replace(
            LlamaConfig.from_hf_dict(json.load(f)), attention_impl="pallas"
        )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        with jax.default_matmul_precision("default"):
            return pool_audit.audit_programs(
                config, n_pages=2048, page_size=128, lanes=64, table_pages=8,
                n_steps=8, width=512, sharding=one_chip,
            )


@pytest.mark.parametrize("program", ["decode", "join"])
def test_latent_cell_compiles_for_v5e_without_pool_copies(program, latent_reports):
    report = latent_reports[program]
    assert report["scans"] == [] and report["pool_ops"] == [], report
    assert report["kernels"] >= 2, report  # one a run of layers
    # 16 held of 256 at 8 a token: a decode chunk's 64 rows and a join's 512
    # stay on the grouped path: three products the sparse run, under each of
    # the two row budgets a share chooses between (``moe._row_budget``)
    assert report["grouped_products"] == 6, report
    assert report["pool_bytes"] == 5 * 2048 * 128 * 640 * 2  # 1.68 GB: what is stored
    # weights 9.84 GB + the pool: the chip's 16 GB hold the program
    assert report["argument_bytes"] + report["temp_bytes"] < 14.0e9, report
    assert report["temp_bytes"] < report["pool_bytes"], report


def test_latent_decode_chunk_reads_wq_b_where_it_lies(latent_reports):
    """PR 56: no operation of the decode chunk writes the queries'
    up-projection out again, a layer of it (75.5 MB a layer-step, as the
    parent's ``constant_dynamic-slice_fusion``) or the run's stack (its
    transposes at the chunk's entry): ``latent.into_heads``. With them went
    the 377 MB of transposed stacks among the program's temporaries (424 MB
    before). A join lays its one layer out as it likes: reported, not held."""
    report = latent_reports["decode"]
    assert report["weight_ops"] == [], "\n".join(
        f"{f['op']}\n{f['text']}" for f in report["weight_ops"])
    assert report["temp_bytes"] < 64e6, report["temp_bytes"]
