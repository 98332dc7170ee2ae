"""One decode chunk in flight ahead of the host (ISSUE 31).

The step loop (runtime/serving.py ``_run_epoch``) never reads a value back
from the device before it has enqueued the next program that does not depend
on that read. What is pinned here, on the CPU at tiny widths:

  * the ORDER: chunk k+1's ``decode`` is called before chunk k's tokens are
    read; a join's prefill, the next chunk, and only then the joiner's first
    token;
  * the STREAMS: token for token what the serial order serves, for the paged
    and the hybrid backend. The serial order is reached through the fact the
    backend states (``lookahead``), as the pipeline and distributed backends
    state it, not through an option;
  * what lags and what does not: a budget's end frees the lane at the same
    boundary as before and a segment whose rows all end by budget enqueues
    nothing past its last chunk; an EOS id is seen one chunk late, the row
    keeps its pages through that chunk, and the joiner that recycles them
    reads none of what the dead lane wrote;
  * the account: ``engine.period`` counts ``ahead`` and ``serial`` by reason.

Every session queues its requests BEFORE the engine starts: the schedule is
then a function of the queue alone, not of the threads' timing.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.obs.period import SERIAL_WHY
from cake_tpu.runtime.serving import BatchEngine, ServeConfig

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
SAMPLED = SamplingConfig(temperature=0.8, top_k=20, repeat_penalty=1.2, seed=11)
# The tiny hybrid model's greedy stream is one id repeated (a tied head over
# a handful of layers): its sessions sample, seeded, so that a wrong token
# has somewhere to show. ``SAMPLED`` carries a repeat penalty: the ring's
# update at a join runs on the device too.
HOT = SamplingConfig(temperature=1.0, top_k=40, repeat_penalty=1.0, seed=3)
PLAIN = {"paged": GREEDY, "hybrid": SAMPLED}
OTHER = {"paged": SAMPLED, "hybrid": HOT}
CHUNK = 4

TINY_JAMBA = dict(
    model_type="jamba", hidden_size=64, intermediate_size=128, vocab_size=512,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=4, attn_layer_offset=2, mamba_d_state=4, mamba_d_conv=4,
    mamba_expand=2, mamba_dt_rank=4, mamba_conv_bias=True,
    mamba_proj_bias=False, num_experts=1, num_experts_per_tok=1,
    tie_word_embeddings=True, bos_token_id=256, eos_token_id=259,
    pad_token_id=0, max_position_embeddings=512, sliding_window=None,
    rms_norm_eps=1.31e-6,
)

# (prompt, max_tokens): two lanes, so the third and fourth JOIN. The second
# ends at once (its budget ends inside the first chunk), the first outlives
# everyone.
SESSION = [
    ("the first stream outlives every other one of this session", 41),
    ("short", 6),
    ("a joiner of middling length, here", 19),
    ("the last joiner, with a prompt longer than the one before it", 10),
]


@pytest.fixture(scope="module")
def models():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, rms_norm_eps=1.31e-5)
    hyb = LlamaConfig.from_hf_dict(TINY_JAMBA)
    return {
        "paged": (cfg, M.init_params(cfg, jax.random.PRNGKey(31), jnp.float32)),
        "hybrid": (hyb, H.init_params(hyb, jax.random.PRNGKey(31), jnp.float32)),
    }


def make(models, kind, *, serial=False, eos=None, **serve_kw):
    """An engine that has NOT started. ``serial``: the backend states that it
    cannot run ahead. ``eos``: the ids that end a stream, for the engine
    alone (the backend's programs are keyed by its own config: no compile)."""
    cfg, params = models[kind]
    serve_kw = {
        "max_batch": 2, "decode_chunk_size": CHUNK, "admission_window": 0.0,
        "scheduler": "continuous", "kv_mode": "paged", "page_size": 16,
        **serve_kw,
    }
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=256,
        cache_dtype=jnp.float32, serve=ServeConfig(**serve_kw),
    )
    if serial:
        eng.backend.lookahead = 0
    if eos is not None:
        eng.config = dataclasses.replace(eng.config, eos_token_ids=tuple(eos))
    return eng


def serve(eng, session=SESSION, sampling=GREEDY):
    """Queue the whole session (request ids r0, r1, ...), start, drain every
    stream. Returns ([(token ids, finish reason)], stats, the period
    account)."""
    handles = [
        eng.submit([Message.user(p)], n, sampling, request_id=f"r{i}")
        for i, (p, n) in enumerate(session)
    ]
    eng.start()
    try:
        out = [([tok.id for tok in h.tokens()], h.finish_reason) for h in handles]
        assert eng.quiesce()
        return out, dict(eng.stats), eng.periods.snapshot()["period"]
    finally:
        eng.stop()


def on_read(eng, fn):
    """Call ``fn(entry)`` on the engine's thread whenever the loop is about
    to wait for a value it has enqueued (a chunk's tokens: ``entry.n`` > 0)."""
    read = eng._read

    def read_(entry):
        if entry.host is None:
            fn(entry)
        return read(entry)

    eng._read = read_


def record(eng):
    """The order of what the loop enqueues and reads, as
    ("decode", slot) / ("join", slot) / ("read", slot, n), with n 0 for a
    joiner's first token."""
    log = []
    decode, join = eng.backend.decode, eng.backend.join

    def decode_(kv, tok, slot, *a, **kw):
        log.append(("decode", int(slot)))
        return decode(kv, tok, slot, *a, **kw)

    def join_(kv, row_tokens, pads1, ends1, lane, *a, **kw):
        log.append(("join", int(np.asarray(ends1)[0])))
        return join(kv, row_tokens, pads1, ends1, lane, *a, **kw)

    eng.backend.decode, eng.backend.join = decode_, join_
    on_read(eng, lambda e: log.append(("read", int(e.slot), int(e.n))))
    return log


# ------------------------------------------------------------- (a) the order


@pytest.mark.parametrize("kind", ["paged", "hybrid"])
def test_the_next_chunk_is_enqueued_before_the_last_is_read(models, kind):
    eng = make(models, kind)
    log = record(eng)
    out, stats, _ = serve(eng)
    assert stats["joins"] == 2 and stats["batches"] == 1
    decodes = [e[1] for e in log if e[0] == "decode"]
    assert decodes == sorted(decodes) and len(decodes) >= 8
    for before, after in zip(decodes, decodes[1:]):
        # chunk k+1 is enqueued, and only then chunk k's tokens are read
        assert log.index(("decode", after)) < log.index(("read", before, CHUNK))
    joins = [e[1] for e in log if e[0] == "join"]
    assert len(joins) == 2
    for slot in joins:
        # the join's prefill, the chunk at its slot, then its first token
        assert (
            log.index(("join", slot))
            < log.index(("decode", slot))
            < log.index(("read", slot, 0))
        )
        # and the chunk in front of it is still unread when it is enqueued
        assert log.index(("join", slot)) < log.index(("read", slot - CHUNK, CHUNK))
    assert [len(ids) for ids, _ in out] == [n for _, n in SESSION]


def test_a_backend_that_cannot_run_ahead_keeps_the_serial_order(models):
    eng = make(models, "paged", serial=True)
    log = record(eng)
    _, stats, period = serve(eng)
    assert stats["joins"] == 2
    # every value is read before anything else is enqueued
    for a, b in zip(log, log[1:]):
        if a[0] in ("decode", "join"):
            assert b == ("read", a[1], CHUNK if a[0] == "decode" else 0)
    assert period["ahead"] == 0
    assert period["serial"]["backend"] == period["count"] - 1
    assert period["serial"]["segment-start"] == 1


# ----------------------------------------------------------- (b) the streams


def eos_mid_chunk(streams):
    """An id that ends the FIRST stream in the middle of its third chunk (a
    stream's token 0 is its prefill's; chunk c holds tokens 4c-3 .. 4c) and
    that the stream has not sampled before."""
    ids = streams[0][0]
    for at in (10, 11, 9):
        if ids[at] not in ids[:at]:
            return ids[at], at
    raise AssertionError("the tiny model repeats itself: pick another seed")


def scenario(models, kind, name, serial, baseline):
    """One session under ``name``'s conditions; ([(ids, reason)], stats)."""
    kw = {}
    session = SESSION
    if name == "eos":
        kw["eos"] = [eos_mid_chunk(baseline)[0]]
    elif name == "budgets":
        # ends at a chunk's first, middle and last token, and a budget of one
        session = [(p, n) for (p, _), n in zip(SESSION, (14, 1, 9, 12))]
    elif name == "pool":
        # the first cannot reach its end: the epoch scheduler truncates
        # where the continuous one would preempt
        kw.update(
            max_pages=POOL[kind] + 2 * (kind == "hybrid"), scheduler="epoch",
            eos=(),  # no early end: the pool decides
        )
        session = [(p, n) for (p, _), n in zip(SESSION, (150, 6, 40, 30))]
    elif name == "preempt":
        kw.update(max_pages=POOL[kind], eos=())
        session = [(p, n) for (p, _), n in zip(SESSION, (180, 6, 40, 30))]
    eng = make(models, kind, serial=serial, **kw)
    seen = {"chunks": 0}
    if name == "cancel":
        def hang_up(entry):  # as the third chunk's tokens are awaited
            seen["chunks"] += bool(entry.n)
            if entry.n and seen["chunks"] == 3:
                assert eng.cancel("r0")
        on_read(eng, hang_up)
    elif name == "deadline":
        def run_out(entry):  # the joiner's deadline passes in its 2nd chunk
            for _, row in entry.rows:
                if entry.n and row.req.rid == "r2":
                    seen["chunks"] += 1
                    if seen["chunks"] == 2:
                        row.req.deadline = 1e-9
        on_read(eng, run_out)
    out, stats, _ = serve(
        eng, session, OTHER[kind] if name == "sampled" else PLAIN[kind]
    )
    return out, stats


# pools in which the first and the fourth stream cannot both reach their ends
POOL = {"paged": 17, "hybrid": 20}
SCENARIOS = [
    "joins", "sampled", "eos", "budgets", "cancel", "deadline", "pool",
    "preempt",
]


@pytest.fixture(scope="module")
def baselines(models):
    """The plain session in the serial order, per backend: what ``eos``
    picks its id from and what a cut stream must be a prefix of."""
    return {
        kind: serve(make(models, kind, serial=True), SESSION, PLAIN[kind])[0]
        for kind in ("paged", "hybrid")
    }


@pytest.mark.parametrize("name", SCENARIOS)
@pytest.mark.parametrize("kind", ["paged", "hybrid"])
def test_streams_are_token_for_token_the_serial_order_s(
    models, baselines, kind, name
):
    base = baselines[kind]
    ahead, stats_a = scenario(models, kind, name, False, base)
    serial, stats_s = scenario(models, kind, name, True, base)
    assert ahead == serial
    for key in ("joins", "page_truncations", "preemptions", "restores",
                "cancelled", "deadline_expired"):
        assert stats_a[key] == stats_s[key], key
    if name == "joins":
        assert ahead == base and stats_a["joins"] == 2
    elif name == "eos":
        tid, at = eos_mid_chunk(base)
        assert ahead[0] == (base[0][0][: at + 1], "stop")
    elif name == "budgets":
        assert [len(ids) for ids, _ in ahead] == [14, 1, 9, 12]
    elif name == "cancel":
        # the boundary after the third chunk: its tokens were streamed, the
        # fourth chunk's (in flight when the flag is seen) never are
        assert ahead[0] == (base[0][0][: 1 + 3 * CHUNK], "cancelled")
        assert ahead[1:] == base[1:]
    elif name == "deadline":
        assert ahead[2] == (base[2][0][: 1 + 2 * CHUNK], "deadline")
        assert ahead[:2] + ahead[3:] == base[:2] + base[3:]
    elif name == "pool":
        assert stats_a["page_truncations"] >= 1
    elif name == "preempt":
        assert stats_a["preemptions"] >= 1 and stats_a["restores"] >= 1


# ----------------------------------------- (c) an EOS id is seen a chunk late


@pytest.mark.parametrize("kind", ["paged", "hybrid"])
def test_an_eos_row_keeps_its_pages_through_the_chunk_in_flight(
    models, baselines, kind
):
    """The first stream ends on an EOS id in the middle of chunk 3. The
    host sees it when it reads chunk 3, with chunk 4 enqueued: chunk 4 ran
    with the dead row's pages still mapped (its writes land in pages the row
    holds), the pages go back at that boundary, and the joiner that takes
    them streams what it streams alone."""
    base = baselines[kind]
    tid, at = eos_mid_chunk(base)
    assert 9 <= at <= 11
    # lane 0: the stream that stops; lane 1: one that outlives it; queued:
    # a joiner for which only the stopped row's pages are left
    session = [SESSION[0], (SESSION[2][0], 60), (SESSION[3][0], 24)]
    alone = serve(make(models, kind, eos=[tid]), [session[2]], PLAIN[kind])[0][0]

    got = {}
    for serial in (False, True):
        eng = make(models, kind, serial=serial, eos=[tid])
        log = record(eng)
        decode, join = eng.backend.decode, eng.backend.join
        release = eng._alloc.release
        mapped, pages = {}, {}

        def held(lane, _eng=eng):
            row = _eng._alloc.block_tables[lane]
            return set(row[row >= 0].tolist())

        def decode_(kv, tok, slot, *a, _decode=decode, _m=mapped):
            _m[int(slot)] = held(0)
            return _decode(kv, tok, slot, *a)

        def join_(kv, row_tokens, pads1, ends1, lane, *a, _join=join, _p=pages):
            _p["joiner"] = held(lane)
            return _join(kv, row_tokens, pads1, ends1, lane, *a)

        def release_(lane, _release=release, _log=log, _p=pages):
            if lane == 0:
                _p.setdefault("stopped", held(0))
            _log.append(("release", lane))
            return _release(lane)

        eng.backend.decode, eng.backend.join = decode_, join_
        eng._alloc.release = release_
        out, stats, _ = serve(eng, session, PLAIN[kind])
        got[serial] = out
        assert out[0] == (base[0][0][: at + 1], "stop") and stats["joins"] == 1
        c3, c4 = sorted(mapped)[2:4]
        if not serial:  # chunk 4 ran over the pages the dead row holds
            assert mapped[c4] == pages["stopped"] >= mapped[c3] > set()
        # the pages go back once the chunk that held the EOS is read: in
        # the ahead order that is behind chunk 4's enqueue
        gone = log.index(("release", 0))
        assert log.index(("read", c3, CHUNK)) < gone
        assert (log.index(("decode", c4)) < gone) is (not serial)
        # and the joiner's prefill, which takes those very pages (the free
        # list is last in, first out), is enqueued behind both
        joined = next(i for i, e in enumerate(log) if e[0] == "join")
        assert gone < joined
        assert pages["stopped"] & pages["joiner"]
    assert got[False] == got[True]
    assert got[False][2] == alone  # none of the dead lane's writes in it


# ------------------------------------ (d) what the host can count does not lag


@pytest.mark.parametrize("budgets,chunks", [
    ((9, 9), 2), ((5, 9), 2), ((8, 2), 2), ((1, 1), 0), ((13, 4), 3),
])
def test_a_segment_that_ends_by_budget_enqueues_nothing_past_its_last_chunk(
    models, budgets, chunks
):
    session = [(p, n) for (p, _), n in zip(SESSION, budgets)]
    counts = {}
    for serial in (False, True):
        eng = make(models, "paged", serial=serial)
        log = record(eng)
        out, _, period = serve(eng, session)
        assert [len(ids) for ids, _ in out] == list(budgets)
        counts[serial] = sum(e[0] == "decode" for e in log)
        assert period["count"] == counts[serial]
    assert counts[False] == counts[True] == chunks


def test_a_budget_s_end_frees_the_lane_at_the_boundary_it_did(models):
    """The joins land at the slots the serial order gives them: a row that
    ends by budget is known to end before its tokens are read."""
    slots = {}
    for serial in (False, True):
        eng = make(models, "hybrid", serial=serial)
        log = record(eng)
        serve(eng)
        slots[serial] = [e[1] for e in log if e[0] == "join"]
    assert slots[False] == slots[True] and len(slots[True]) == 2


# ----------------------------------------------------------- (e) the account


@pytest.mark.parametrize("kind", ["paged", "hybrid"])
def test_the_period_account_says_how_often_the_loop_ran_ahead(models, kind):
    _, stats, period = serve(make(models, kind))
    assert set(period["serial"]) == set(SERIAL_WHY)
    assert period["ahead"] + sum(period["serial"].values()) == period["count"]
    assert period["serial"]["segment-start"] == stats["batches"] == 1
    assert period["ahead"] == period["count"] - 1 >= 8
    assert sum(period["phase_seconds"].values()) == pytest.approx(
        period["seconds"], rel=1e-9
    )
    assert period["joins"] == stats["joins"] == 2
    assert 0 <= period["join_readback_seconds"] and 0 < period["join_seconds"]


def test_a_short_pool_and_a_restore_are_read_first_and_counted(models):
    eng = make(models, "paged", max_pages=POOL["paged"])
    session = [(p, n) for (p, _), n in zip(SESSION, (180, 6, 40, 30))]
    _, stats, period = serve(eng, session)
    assert stats["preemptions"] >= 1 and stats["restores"] >= 1
    assert period["ahead"] + sum(period["serial"].values()) == period["count"]
    assert period["serial"]["pages"] >= 1
    assert period["serial"]["restore"] >= 1
    assert period["ahead"] > period["count"] // 2


def test_stop_closes_a_row_that_waits_for_tokens_in_flight(models):
    """A row whose budget ends inside the chunk in flight has left its lane;
    stop() reaches it all the same."""
    eng = make(models, "paged")
    waiting = []

    def stop_with_a_row_in_flight(entry):
        for later in list(eng._unread)[1:]:
            for _, row in later.rows:
                if row.n + row.inflight >= row.req.max_tokens and not waiting:
                    waiting.append(row.req.rid)
                    eng._stop = True  # what stop() sets, seen at the boundary

    on_read(eng, stop_with_a_row_in_flight)
    handles = [
        eng.submit([Message.user(p)], n, GREEDY, request_id=f"r{i}")
        for i, (p, n) in enumerate(((SESSION[0][0], 120), (SESSION[2][0], 13)))
    ]
    ends = {}

    def drain(h):
        try:
            ends[h.request_id] = len([t for t in h.tokens()])
        except RuntimeError as e:
            ends[h.request_id] = str(e)

    threads = [threading.Thread(target=drain, args=(h,)) for h in handles]
    for t in threads:
        t.start()
    eng.start()
    for t in threads:
        t.join(timeout=60)
    eng.stop()
    assert waiting == ["r1"]
    assert not any(t.is_alive() for t in threads)
    assert ends == {"r0": "engine stopped", "r1": "engine stopped"}
