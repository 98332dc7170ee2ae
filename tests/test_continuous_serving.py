"""Continuous scheduler (ISSUE 15): kill the lockstep epoch.

The contract under test (README "Continuous scheduling"):

  * Streams are BIT-IDENTICAL to epoch mode given the same admission order
    — greedy and sampled, dense and paged — because both schedulers walk
    the same per-row arithmetic (batch.first_sample / join / decode), each
    of which is already pinned bit-identical to a solo run.
  * Page pressure PREEMPTS instead of force-finishing: the victim lane's
    page chain spills host-side (history + sampling state at the chunk
    boundary — the _migrate_kv invariant) and a later restore re-attaches
    it through the join/suffix-join arithmetic, bit-identically.
  * The spill table honors the whole request lifecycle: cancel and
    deadline reach spilled lanes, stop() closes them, quiesce sees no
    leaked pages (a spilled lane holds none).
  * Convoy attribution drops to ~0 by construction: finished lanes retire
    immediately and empty lanes are admission headroom, not lockstep tax.
  * Zero steady-state retraces under the armed jit watchdog: lane-count
    churn, joins, spills and restores ride traced operands and the same
    64-bucketed window families epoch mode compiles.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.runtime.admission import StepBudget
from cake_tpu.runtime.serving import (
    BatchEngine,
    ServeConfig,
    _RowState,
    _SpilledLane,
)
from cake_tpu.utils import metrics

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
SAMPLED = SamplingConfig(temperature=0.8, top_k=20, repeat_penalty=1.0, seed=7)

# Mixed prompt lengths: the workload shape the continuous scheduler exists
# for (short requests must not pay for long co-batched ones).
MIXED = [
    "short",
    "a medium prompt with some more words in it",
    "the long prompt of this batch, padded out with further words so its "
    "bucket is clearly taller than the short one's",
]


def setup(n_layers=2, seed=31):
    cfg = LlamaConfig.tiny(num_hidden_layers=n_layers)
    params = M.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    return cfg, params


def make_engine(cfg, params, start=True, **serve_kw):
    serve_kw.setdefault("max_batch", 4)
    serve_kw.setdefault("decode_chunk_size", 4)
    serve_kw.setdefault("admission_window", 0.05)
    eng = BatchEngine(
        cfg, params, ByteTokenizer(),
        max_seq_len=256, cache_dtype=jnp.float32,
        serve=ServeConfig(**serve_kw),
    )
    if start:  # else: queue first, so the schedule is the queue's alone
        eng.start()
    return eng


def collect(handle):
    return [tok.id for tok in handle.tokens()]


def serve_all(eng, prompts, n, sampling):
    handles = [eng.submit([Message.user(p)], n, sampling) for p in prompts]
    return [collect(h) for h in handles], handles


# ------------------------------------------------- epoch-vs-continuous parity


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_continuous_dense_streams_match_epoch(sampling):
    cfg, params = setup()
    got = {}
    for sched in ("epoch", "continuous"):
        eng = make_engine(cfg, params, scheduler=sched)
        got[sched], handles = serve_all(eng, MIXED, 10, sampling)
        assert all(
            h.finish_reason in ("stop", "length") for h in handles
        )
        eng.stop()
    assert got["continuous"] == got["epoch"]


@pytest.mark.parametrize("prefix", [False, True], ids=["plain", "prefix"])
def test_continuous_paged_streams_match_epoch(prefix):
    cfg, params = setup(seed=32)
    got = {}
    for sched in ("epoch", "continuous"):
        eng = make_engine(
            cfg, params, scheduler=sched, kv_mode="paged", page_size=16,
            prefix_cache=prefix,
        )
        got[sched], _ = serve_all(eng, MIXED, 10, GREEDY)
        assert eng.quiesce()
        eng.stop()
    assert got["continuous"] == got["epoch"]


def test_continuous_late_submission_joins_bit_exact():
    """A request submitted while the segment is decoding joins it and is
    still bit-identical to its epoch-mode stream."""
    cfg, params = setup(seed=33)
    got = {}
    for sched in ("epoch", "continuous"):
        eng = make_engine(cfg, params, scheduler=sched)
        h0 = eng.submit([Message.user("the first, long-running stream")],
                        24, GREEDY)
        deadline = time.time() + 30
        while h0.completion_tokens < 2 and time.time() < deadline:
            time.sleep(0.005)
        h1 = eng.submit([Message.user("late joiner")], 8, GREEDY)
        got[sched] = (collect(h0), collect(h1))
        eng.stop()
    assert got["continuous"] == got["epoch"]
    # (both joined mid-flight; the join machinery is pinned bit-exact
    # against solo runs by test_serving.py)


# ------------------------------------------------------- preemption/restore


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
@pytest.mark.parametrize("prefix", [False, True], ids=["plain", "prefix"])
def test_preemption_spill_restore_bit_identical(prefix, sampling):
    """Page pressure preempts (spills) instead of force-finishing, and the
    restored stream is bit-identical to an unpressured run — greedy AND
    sampled (the PRNG key and penalty ring ride the spill), with and
    without the prefix cache (the restore walks the suffix arithmetic)."""
    cfg, params = setup()
    prompts = [
        "alpha prompt padded out to be long " * 2,
        "row two also made quite long here " * 2,
    ]

    def run(max_pages):
        eng = make_engine(
            cfg, params, scheduler="continuous", kv_mode="paged",
            page_size=16, max_pages=max_pages, prefix_cache=prefix,
        )
        out, handles = serve_all(eng, prompts, 48, sampling)
        stats = dict(eng.stats)
        assert eng.quiesce()
        with eng._cv:
            assert not eng._spilled  # no leaked spilled chains
        alloc = eng.backend.allocator
        held = eng._prefix.stats()["pages"] if eng._prefix else 0
        assert alloc.pages_free == alloc.pages_total - held
        eng.stop()
        return out, stats, [h.finish_reason for h in handles]

    want, st_big, fin_big = run(64)
    got, st_small, fin_small = run(14)
    assert st_big["preemptions"] == 0
    assert st_small["preemptions"] >= 1 and st_small["restores"] >= 1
    assert got == want  # spill/restore round trip is bit-identical
    # Nobody was force-finished by the pressure: same finish reasons.
    assert fin_small == fin_big


def test_preemption_victim_is_lowest_priority():
    cfg, params = setup()
    eng = make_engine(
        cfg, params, scheduler="continuous", kv_mode="paged",
        page_size=16, max_pages=14,
    )
    lo = eng.submit(
        [Message.user("alpha prompt padded out to be long " * 2)], 48,
        GREEDY, priority=0,
    )
    hi = eng.submit(
        [Message.user("row two also made quite long here " * 2)], 48,
        GREEDY, priority=2,
    )
    collect(lo), collect(hi)
    assert eng.stats["preemptions"] >= 1
    preempted = {
        e["request_id"]
        for e in metrics.flight.snapshot()
        if e["event"] == "preempted"
    }
    assert lo.request_id in preempted
    assert hi.request_id not in preempted
    eng.stop()


def test_spilled_lane_restores_via_spill_seeded_segment():
    """A spill that cannot re-attach inside its segment (the remaining
    budget no longer fits the segment's bounded capacity) waits out the
    drain and restores as the SEED of a fresh spill-seeded segment —
    bit-identical to the unpressured run, across the segment boundary."""
    cfg, params = setup()

    def run(max_pages):
        eng = BatchEngine(
            cfg, params, ByteTokenizer(),
            max_seq_len=512, cache_dtype=jnp.float32,
            serve=ServeConfig(
                max_batch=4, decode_chunk_size=4, admission_window=0.1,
                scheduler="continuous", kv_mode="paged", page_size=16,
                max_pages=max_pages,
            ),
        )
        eng.start()
        h1 = eng.submit(
            [Message.user("alpha prompt padded out to be long " * 2)],
            140, GREEDY, priority=2,
        )
        h2 = eng.submit(
            [Message.user("row two also made quite long here " * 2)],
            48, GREEDY, priority=0,
        )
        out = (collect(h1), collect(h2))
        stats = dict(eng.stats)
        assert eng.quiesce()
        with eng._cv:
            assert not eng._spilled
        eng.stop()
        return out, stats

    want, st_big = run(64)
    got, st = run(15)
    assert st["preemptions"] >= 1 and st["restores"] >= 1
    assert st["page_truncations"] == 0  # preemption REPLACED force-finish
    # The restore rode a second, spill-seeded segment (the in-segment
    # path is covered by test_preemption_spill_restore_bit_identical).
    assert st["batches"] > st_big["batches"]
    assert got == want


def test_cancel_reaches_spilled_lane():
    """cancel() on a spilled rid finishes the stream immediately — no
    pages to free, the spill table entry is gone, cancel is idempotent."""
    cfg, params = setup()
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=256,
        cache_dtype=jnp.float32,
        serve=ServeConfig(max_batch=2, scheduler="continuous"),
    )
    # Engine NOT started: forge the spill state deterministically.
    h = eng.submit([Message.user("park me")], 8, GREEDY)
    with eng._cv:
        req = next(iter(eng._queue))
        eng._queue.remove(req)
    row = _RowState(req, set(), ByteTokenizer(), lane=0, engine=eng)
    row.history.append(5)  # the pending token
    with eng._cv:
        eng._spilled[req.rid] = _SpilledLane(
            row=row, key=np.zeros((2,), np.uint32), ring=None, ring_idx=0,
        )
    assert eng.cancel(req.rid) is True
    assert collect(h) == []
    assert h.finish_reason == "cancelled"
    with eng._cv:
        assert not eng._spilled
    assert eng.cancel(req.rid) is False


def test_deadline_reaches_spilled_lane():
    cfg, params = setup()
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=256,
        cache_dtype=jnp.float32,
        serve=ServeConfig(max_batch=2, scheduler="continuous"),
    )
    h = eng.submit([Message.user("expire me")], 8, GREEDY, deadline_s=0.01)
    with eng._cv:
        req = next(iter(eng._queue))
        eng._queue.remove(req)
    row = _RowState(req, set(), ByteTokenizer(), lane=0, engine=eng)
    row.history.append(5)
    with eng._cv:
        eng._spilled[req.rid] = _SpilledLane(
            row=row, key=np.zeros((2,), np.uint32), ring=None, ring_idx=0,
        )
    time.sleep(0.02)
    eng._apply_deadlines([])  # the chunk-boundary sweep reaches spills
    assert collect(h) == []
    assert h.finish_reason == "deadline"
    with eng._cv:
        assert not eng._spilled


# ------------------------------------------------------- convoy + step obs


def test_continuous_convoy_frac_below_epoch():
    """The headline A/B: on a mixed-length workload the continuous
    scheduler's measured convoy fraction is strictly below epoch mode's
    (finished lanes retire; empty lanes are headroom, not tax)."""
    cfg, params = setup()
    frac = {}
    for sched in ("epoch", "continuous"):
        eng = make_engine(cfg, params, scheduler=sched)
        budgets = [24, 6, 6]
        handles = [
            eng.submit([Message.user(p)], n, GREEDY)
            for p, n in zip(MIXED, budgets)
        ]
        for h in handles:
            collect(h)
        # Streams close BEFORE the epoch's finally runs the convoy meter
        # (the documented quiesce race) — poll for the meter.
        deadline = time.time() + 30
        while time.time() < deadline:
            with eng._phase_lock:
                cv = dict(eng.convoy_stats)
            if cv["epochs"] >= 1:
                break
            time.sleep(0.01)
        assert cv["epochs"] >= 1
        frac[sched] = cv["frac_sum"] / cv["epochs"]
        eng.stop()
    assert frac["continuous"] < frac["epoch"]


def test_continuous_emits_segment_and_step_spans():
    from cake_tpu.obs.timeline import timeline

    cfg, params = setup()
    eng = make_engine(cfg, params, scheduler="continuous")
    h = eng.submit([Message.user("spans please")], 8, GREEDY)
    collect(h)
    eng.stop()
    names = {e["name"] for e in timeline.snapshot()}
    assert "segment" in names and "step" in names
    assert "epoch" not in names  # step spans REPLACE epoch spans


def test_restore_phase_reaches_explain():
    """A preempted request's /explain decomposition carries the restore
    phase (the price its spill cost it) and still sums to the wall."""
    from cake_tpu.obs import critpath
    from cake_tpu.obs.timeline import timeline

    cfg, params = setup()
    eng = make_engine(
        cfg, params, scheduler="continuous", kv_mode="paged",
        page_size=16, max_pages=14,
    )
    prompts = [
        "alpha prompt padded out to be long " * 2,
        "row two also made quite long here " * 2,
    ]
    _, handles = serve_all(eng, prompts, 48, GREEDY)
    assert eng.stats["restores"] >= 1
    events = timeline.snapshot()
    restored_rids = {
        e["rid"] for e in events if e["name"] == "restore" and e.get("rid")
    }
    assert restored_rids
    rid = next(iter(restored_rids))
    res = critpath.explain(events, rid)
    assert res is not None
    assert res["phases"]["restore"] > 0.0
    # The structural pin of the merged-span decomposition: preemption
    # split the lane into (at least) a pre-spill and a post-restore
    # request span, and the explained wall covers FIRST open to LAST
    # close — before spans merged, latest-wins dropped the pre-spill
    # compute and the parked gap from the wall entirely.
    opens = [
        e for e in events
        if e.get("ph") == "B" and e.get("name") == "request"
        and e.get("rid") == rid
    ]
    closes = {
        e["id"]: e for e in events if e.get("ph") == "E" and "id" in e
    }
    assert len(opens) >= 2
    t0 = min(float(e["mono"]) for e in opens)
    t1 = max(
        float(closes[e["id"]]["mono"])
        for e in opens
        if e.get("id") in closes
    )
    assert res["wall_s"] >= (t1 - t0) * 0.99
    # Sanity on the attribution quality (host slop on a loaded CPU keeps
    # this below the synthetic-span 0.95 gate).
    assert res["coverage"] >= 0.5
    eng.stop()


# ------------------------------------------------------------- step budget


def test_step_budget_slo_feedback():
    """The SLO-aware prefill grant (runtime/admission.StepBudget): doubled
    under burn, quartered under running-deadline pressure, floored."""
    b = StepBudget()
    base = b.grant()
    assert base == StepBudget.AUTO_TOKENS
    assert b.grant(burning=True) == 2 * base
    # No chunk clock yet: slack cannot be priced, grant unchanged.
    assert b.grant(tightest_slack_s=0.001) == base
    b.observe_chunk(0.1)
    assert b.grant(tightest_slack_s=0.1) == max(
        StepBudget.MIN_TOKENS, base // 4
    )
    assert b.grant(tightest_slack_s=100.0) == base
    explicit = StepBudget(base_tokens=128)
    assert explicit.grant() == 128
    assert explicit.grant(burning=True) == 256


def test_step_budget_defers_joins_to_later_steps():
    """A tiny explicit step budget still serves everyone — candidates over
    the grant wait a step, they are not starved."""
    cfg, params = setup(seed=34)
    eng = make_engine(
        cfg, params, scheduler="continuous", step_prefill_tokens=64,
    )
    out, handles = serve_all(eng, MIXED, 8, GREEDY)
    assert all(h.finish_reason in ("stop", "length") for h in handles)
    # Oracle: same streams as an unbudgeted continuous engine.
    eng2 = make_engine(cfg, params, scheduler="continuous")
    want, _ = serve_all(eng2, MIXED, 8, GREEDY)
    assert out == want
    eng.stop()
    eng2.stop()


# --------------------------------------------------------- zero retraces


def test_continuous_steady_state_never_retraces():
    """Armed jitwatch: once the shape set is warm, a further continuous
    round (admission + joins + decode + retirement) traces NOTHING — lane
    churn stays a traced operand.

    A round queues its three requests BEFORE its engine starts, on two
    lanes: an epoch of two, and the third joins when the second ends.
    Which lane that is, and at which slot, is then the queue's doing and
    not the threads' timing. Every round has an engine of its own; the
    programs are the process's."""
    from cake_tpu.obs import jitwatch as _jw

    cfg, params = setup(seed=35)

    def round_():
        eng = make_engine(
            cfg, params, start=False, scheduler="continuous",
            kv_mode="paged", page_size=16, max_batch=2, admission_window=0.0,
        )
        handles = [
            eng.submit([Message.user(p)], n, GREEDY)
            for p, n in zip(reversed(MIXED), (24, 8, 4))  # longest first
        ]
        eng.start()
        try:
            out = [collect(h) for h in handles]
            assert eng.quiesce()
            assert eng.stats["joins"] == 1 and eng.stats["batches"] == 1
            return out
        finally:
            eng.stop()

    want = round_()  # traces what a round needs
    t0 = _jw.watch.snapshot()
    assert round_() == want
    assert _jw.watch.snapshot() == t0  # the same round again: nothing new
    r0 = _jw.retrace_total()
    _jw.watch.arm()
    try:
        got = round_()
    finally:
        _jw.watch.disarm()
    assert _jw.retrace_total() == r0
    assert got == want


# ------------------------------------------------------ the period account


def test_period_account_closes_on_its_spans():
    """``engine.period`` (obs/period.py) and the timeline's spans are one
    set of clock reads: the phases' self times sum to the periods' seconds
    with next to nothing unnamed, the count is the number of dispatching
    ``period`` spans, and each such span is covered by its children: also
    where the wait it holds is for the chunk BEFORE the one it enqueued.

    The five requests are queued before the engine starts: an epoch of four
    lanes, and the fifth joins when the fourth (8 tokens) ends; submitted to
    a running engine, the threads' timing would decide who joins where."""
    from cake_tpu.obs.timeline import timeline

    cfg, params = setup()
    timeline.clear()
    eng = make_engine(
        cfg, params, start=False, scheduler="continuous", kv_mode="paged",
        page_size=16, prefix_cache=True, admission_window=0.0,
    )
    handles = [eng.submit([Message.user(p)], 64, GREEDY) for p in MIXED]
    handles += [
        eng.submit([Message.user("joiner " + p)], 8, GREEDY) for p in MIXED[:2]
    ]
    eng.start()
    for h in handles:
        collect(h)
    deadline = time.monotonic() + 10  # the segment closes on its own
    while time.monotonic() < deadline and not eng.periods.snapshot()["segment"]["count"]:
        time.sleep(0.01)
    eng.stop()
    snap = eng.periods.snapshot()
    period, segment = snap["period"], snap["segment"]

    assert period["count"] >= 6 and segment["count"] == 1
    phases = period["phase_seconds"]
    assert sum(phases.values()) == pytest.approx(period["seconds"], rel=1e-9)
    # under 1% unnamed; on this tiny model a period is a few milliseconds, so
    # allow the loop's fixed unspanned cost (a gauge, two counts) its floor
    assert phases["other"] < max(0.01 * period["seconds"], 5e-4 * period["count"])
    assert phases["dispatch"] > 0 and phases["readback"] > 0 and phases["emit"] > 0
    assert sum(period["hist"]["counts"]) == period["count"]
    assert len(period["hist"]["counts"]) == len(period["hist"]["edges_s"]) + 1
    assert eng.stats["joins"] == 1
    assert 1 <= period["with_join"]["count"] <= period["joins"] == eng.stats["joins"]
    assert period["with_join"]["seconds"] <= period["seconds"]
    # a join's span is the host's work to enqueue it; the wait for its first
    # token is the boundary's, read behind the next chunk's enqueue
    assert 0 < period["join_seconds"] and 0 <= period["join_readback_seconds"]
    lanes = period["lane_seconds"]
    assert 0 < lanes["live"] <= lanes["offered"] == pytest.approx(
        eng.max_batch * period["seconds"]
    )
    assert 0 <= lanes["idle_queued"] <= lanes["offered"] - lanes["live"] + 1e-9
    assert segment["prefill_seconds"] < segment["seconds"]
    assert 0 < segment["between_seconds"]
    # every chunk but the segment's first was enqueued ahead of the host
    assert period["ahead"] + sum(period["serial"].values()) == period["count"]
    assert period["serial"]["segment-start"] == 1
    assert period["ahead"] == period["count"] - 1

    events = timeline.snapshot()
    spans = [e for e in events if e.get("ph") == "X"]
    periods = [s for s in spans if s["name"] == "period"]
    assert sum(s["args"]["dispatched"] for s in periods) == period["count"]
    assert len(periods) == period["count"] + period["undispatched"]["count"]
    ahead, bare = 0, []
    for root in periods:
        if not root["args"]["dispatched"]:
            continue
        kids = [s for s in spans if s.get("parent") == root["id"]]
        assert {"sweep", "step", "decode-chunk"} <= {k["name"] for k in kids}
        bare.append(root["dur"] - sum(k["dur"] for k in kids))
        chunk = next(k for k in kids if k["name"] == "decode-chunk")
        assert set(chunk["args"]) == {
            "lanes", "capacity", "slot", "n", "live", "ahead",
        }
        inside = {s["name"] for s in spans if s.get("parent") == chunk["id"]}
        assert root["args"]["order"] == (
            "ahead" if chunk["args"]["ahead"] else "segment-start"
        )
        if chunk["args"]["ahead"]:
            # the enqueue of this chunk, then the wait for the one before
            # it, whose tokens the period goes on to emit
            ahead += 1
            assert inside == {"dispatch", "readback"}
            assert "emit" in {k["name"] for k in kids}
        else:
            assert inside == {"dispatch"}  # nothing to wait for yet
    assert ahead == period["ahead"]
    # what of a period no child covers: under a millisecond, but for the odd
    # period in which this thread lost the interpreter between two spans (a
    # consumer woken by ``emit`` takes it for up to a switch interval)
    assert sorted(bare)[len(bare) * 8 // 10] < 1e-3
    assert sum(bare) < max(0.02 * period["seconds"], 5e-4 * period["count"])
    seg = next(s for s in spans if s["name"] == "segment")
    assert seg["args"]["ended"] in ("capacity", "drained")
    assert {"lanes", "bucket", "capacity", "queue_depth"} <= set(seg["args"])


# ------------------------------------------- which programs a session compiles

# (prompt, new tokens), all queued BEFORE the engine starts: the schedule is
# then a function of the queue alone. On two lanes: an epoch of the first
# two; the second ends at once and the third joins; the fourth, with the
# longest prompt, joins a bucket later; the pool is short of what the first
# and the fourth reach together, so one is preempted and restored.
SCRIPT = [
    ("the first stream outlives every other one of the session", 180),
    ("short", 6),
    ("a joiner of middling length, here", 40),
    ("the last joiner has the longest prompt of the three that join, "
     "longer than the first", 30),
]
# A config of its own each (no other test's traces are in the way) and the
# pool that makes the preemption.
SESSIONS = {
    "dense": dict(eps=1.01e-5),
    "paged": dict(eps=1.02e-5, kv_mode="paged", page_size=16, max_pages=18),
    "prefix": dict(eps=1.03e-5, kv_mode="paged", page_size=16, max_pages=18,
                   prefix_cache=True),
    "hybrid": dict(eps=1.04e-6, kv_mode="paged", page_size=16, max_pages=22),
}
TINY_JAMBA = dict(
    model_type="jamba", hidden_size=64, intermediate_size=128, vocab_size=512,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, mamba_conv_bias=True,
    mamba_proj_bias=False, num_experts=1, num_experts_per_tok=1,
    tie_word_embeddings=True, bos_token_id=1, eos_token_id=2, pad_token_id=0,
    max_position_embeddings=512, sliding_window=None,
)


def scripted_session(case):
    """(jitwatch's ``compile`` spans of the served programs, in order, as
    [fn, shapes]; the engine's stats)."""
    import dataclasses

    from cake_tpu.models.llama import hybrid as H
    from cake_tpu.obs.timeline import timeline

    kw = dict(SESSIONS[case])
    eps = kw.pop("eps")
    if case == "hybrid":
        cfg = LlamaConfig.from_hf_dict({**TINY_JAMBA, "rms_norm_eps": eps})
        params = H.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    else:
        cfg = dataclasses.replace(
            LlamaConfig.tiny(num_hidden_layers=2), rms_norm_eps=eps
        )
        params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=512, cache_dtype=jnp.float32,
        serve=ServeConfig(
            max_batch=2, decode_chunk_size=4, admission_window=0.0,
            scheduler="continuous", **kw,
        ),
    )
    timeline.clear()
    handles = [eng.submit([Message.user(p)], n, GREEDY) for p, n in SCRIPT]
    eng.start()
    try:
        for h in handles:
            collect(h)
        stats = dict(eng.stats)
    finally:
        eng.stop()
    events = timeline.snapshot()
    assert len(events) < timeline.capacity  # nothing fell off the ring
    compiles = [
        [e["args"]["fn"], e["args"]["shapes"]] for e in events
        if e.get("name") == "compile" and e.get("ph") in ("B", "X")
        # the served programs; ``first_sample`` is keyed by the sampling
        # knobs alone, so another test's trace of it would hide this one's
        and e["args"]["fn"].startswith("batch.")
        and not e["args"]["fn"].startswith("batch.first_sample")
    ]
    return compiles, stats


@pytest.mark.parametrize("case", sorted(SESSIONS))
def test_a_scripted_session_compiles_the_recorded_programs(case):
    """The guard that an edit of ``runtime/shapes.py`` (or of who asks it)
    changes which programs get compiled only on purpose: an epoch, two
    joins of different lengths and a preemption with its restore compile
    exactly the programs, at exactly the operand shapes, that the commit
    before ``shapes.py`` compiled (recorded there, ``tests/data/``). When a
    change of programs IS the purpose (ROADMAP S2), record the list again
    and say so."""
    import json
    from pathlib import Path

    want = json.loads(
        (Path(__file__).parent / "data" / "served_programs_pr29.json").read_text()
    )["programs"][case]
    got, stats = scripted_session(case)
    assert stats["joins"] == 2
    if case != "dense":  # dense lanes hold no pages: nothing to preempt
        assert stats["preemptions"] >= 1 and stats["restores"] >= 1
    assert got == want
