"""The engine's accounts at a profiler's two edges (ISSUE 55).

A device trace's times are of the dispatches a profiler recorded; what a
reader divides them by has to be of the same dispatches. What is pinned here,
on the CPU at tiny widths:

  * ``obs/timeline.recording()`` is the tree's one test of whether a profiler
    is recording, and a timeline tells its listener of a flip at the next
    boundary of a span on the engine's track;
  * ``GET /stats`` engine.profiled holds ``accounts()`` as it stood at the two
    flips of the LAST session: ``close - open`` is of the periods that ended
    between them, ``engine.period.steps`` the decode steps they were
    dispatched for (a segment's last chunk is a program of fewer);
  * through the CLOSING of a trace (the session object lives until
    ``stop_trace`` returns) nothing is counted, in ``engine.profiled`` and in
    ``engine.sparse.traced`` alike;
  * a real session around the tiny engine: the ``period`` events on the
    written trace's ``/host:CPU`` plane against ``close - open``.

The flag is asked through one module attribute, so a stand-in takes its place;
every session queues its requests BEFORE the engine starts, so the schedule is
a function of the queue alone (tests/test_lookahead.py).
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import json
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.obs.timeline import PROFILED_TRACK, Timeline
from cake_tpu.runtime.serving import BatchEngine, ServeConfig

# The MODULE: the package exports its ``timeline`` instance under the same name.
TL = sys.modules["cake_tpu.obs.timeline"]

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
CHUNK = 4
# (prompt, max_tokens) on two lanes of 96 slots: the first stream runs into
# the segment's ceiling (its last chunk is a ``decode_tail`` of fewer steps),
# the third joins.
SESSION = [("the first", 200), ("short", 6), ("third one joins", 9)]
NEVER = {"sessions": 0, "open": None, "close": None}


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(num_hidden_layers=2, rms_norm_eps=1.31e-5)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(31), jnp.float32)


def make(model, **kw):
    cfg, params = model
    serve = ServeConfig(
        max_batch=2, decode_chunk_size=CHUNK, admission_window=0.0,
        scheduler="continuous", kv_mode="paged", page_size=16,
    )
    kw = {"max_seq_len": 96, "cache_dtype": jnp.float32, "serve": serve, **kw}
    return BatchEngine(cfg, params, ByteTokenizer(), **kw)


def at_periods(eng, then):
    """Call ``then(k)`` on the engine's thread when the k-th dispatching
    period has been committed, INSIDE its ``period`` span (whose exit is the
    next boundary that can notice a flip). Returns the list the periods'
    ``steps`` are appended to."""
    end, steps_seen = eng.periods.end, []

    def end_(live, order="", cached=0, steps=0):
        end(live, order, cached, steps)
        if live is not None:
            steps_seen.append(steps)
            then(len(steps_seen))

    eng.periods.end = end_
    return steps_seen


def serve(eng, session=SESSION):
    handles = [
        eng.submit([Message.user(p)], n, GREEDY, request_id=f"r{i}")
        for i, (p, n) in enumerate(session)
    ]
    eng.start()
    try:
        for h in handles:
            list(h.tokens())
        assert eng.quiesce()
    finally:
        eng.stop()


def flipped(model, monkeypatch, flips, **kw):
    """Serve the session with a stand-in for ``timeline.recording`` that
    flips once the k-th dispatching period has been committed, for each k
    of ``flips``. (engine, the steps of every dispatching period)."""
    flag = [False]
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    eng = make(model, **kw)

    def then(k):
        if k in flips:
            flag[0] = not flag[0]

    steps = at_periods(eng, then)
    serve(eng)
    return eng, steps


@pytest.fixture(scope="module")
def schedule(model):
    """The steps of the session's dispatching periods, no recorder about."""
    eng = make(model)
    steps = at_periods(eng, lambda k: None)
    serve(eng)
    assert eng.profiled() == NEVER
    return steps


def less(close: dict, open_: dict, *path):
    a, b = close["engine"], open_["engine"]
    for key in path:
        a, b = a[key], b[key]
    return a - b


# --------------------------------------------------- the flag and its listener


class Heard:
    def __init__(self):
        self.flips = []

    def hear(self, recording):
        self.flips.append(recording)


@pytest.mark.parametrize("boundary", ["span-entry", "span-exit", "begin", "end"])
def test_a_flip_is_noticed_at_the_next_boundary_of_a_profiled_span(monkeypatch, boundary):
    flag = [False]
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    tl, heard = Timeline(), Heard()
    tl.listen(heard.hear)
    if boundary == "span-entry":
        flag[0] = True
        with tl.span("period", track=PROFILED_TRACK):
            assert heard.flips == [True]
    elif boundary == "span-exit":
        with tl.span("period", track=PROFILED_TRACK):
            flag[0] = True
            assert heard.flips == []
    elif boundary == "begin":
        flag[0] = True
        sid = tl.begin("step", track=PROFILED_TRACK)
        assert heard.flips == [True]
        tl.end(sid)
    else:
        sid = tl.begin("step", track=PROFILED_TRACK)
        flag[0] = True
        assert heard.flips == []
        tl.end(sid)
    assert heard.flips == [True]
    with tl.span("period", track=PROFILED_TRACK):  # nothing flipped: no call
        flag[0] = False
    assert heard.flips == [True, False]


def test_other_tracks_spans_notice_nothing_and_a_listener_is_held_weakly(monkeypatch):
    flag = [True]
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    tl, heard = Timeline(), Heard()
    tl.listen(heard.hear)
    with tl.span("request", track="lane0"):
        pass
    tl.end(tl.begin("request", track="lane1", parent=None))
    assert heard.flips == []
    with tl.span("period", track=PROFILED_TRACK):
        pass
    assert heard.flips == [True]
    # a later listener replaces the first and hears of the recording under way
    second = Heard()
    tl.listen(second.hear)
    with tl.span("period", track=PROFILED_TRACK):
        pass
    assert (heard.flips, second.flips) == ([True], [True])
    del second
    gc.collect()
    flag[0] = False
    with tl.span("period", track=PROFILED_TRACK):  # the listener is gone: no error
        pass


def test_recording_is_the_recorders_switch_and_false_through_a_traces_closing(tmp_path):
    """``recording()`` against the private session test it replaced, polled
    by one thread through a real ``stop_trace``: the session object outlives
    the call, the recorder's switch does not."""
    from jax._src import profiler as private

    assert TL.recording() is False
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as bench/child.py opens its window
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    samples, done = [], threading.Event()
    try:
        assert TL.recording() is True
        for i in range(20000):  # something to export: the closing takes a while
            with jax.profiler.TraceAnnotation("filler", i=i):
                pass

        def poll():
            while not done.is_set():
                samples.append(
                    (TL.recording(), private._profile_state.profile_session is not None)
                )

        poller = threading.Thread(target=poll)
        poller.start()
        while len(samples) < 10:  # the poller runs before the stop is called
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
        done.set()
        poller.join()
    assert TL.recording() is False
    assert samples[0] == (True, True)
    recorded = [r for r, _ in samples]
    assert recorded == sorted(recorded, reverse=True)  # once false, false
    assert (True, False) not in samples
    # THE CLOSING: the session still stands and nothing is being recorded.
    assert (False, True) in samples


# ------------------------------------------- (a) the copies at the two edges


@pytest.mark.parametrize("where", ["head", "tail"])
def test_close_less_open_holds_the_periods_that_ended_under_the_recorder(
    model, monkeypatch, schedule, where,
):
    """open -> three dispatching periods -> close -> the rest: the three and
    not the rest, their steps as dispatched, a ``decode_tail`` among them."""
    total = len(schedule)
    k0 = 2 if where == "head" else total - 3
    eng, steps = flipped(model, monkeypatch, {k0, k0 + 3})
    assert steps == schedule and total >= 8
    got = eng.profiled()
    assert got["sessions"] == 1
    opened, closed = got["open"], got["close"]
    assert opened["engine"]["period"]["count"] == k0
    assert less(closed, opened, "period", "count") == 3
    assert less(closed, opened, "period", "steps") == sum(steps[k0:k0 + 3])
    assert less(closed, opened, "period", "seconds") > 0
    assert opened["mono"] < closed["mono"] < time.perf_counter()
    # the account went on past the close: the copy did not
    now = eng.accounts()["period"]
    assert now["count"] == total and now["steps"] == sum(steps)
    if where == "head":
        assert closed["engine"]["period"]["count"] == 5 < total
        assert sum(steps[k0:k0 + 3]) == 3 * CHUNK
    else:
        assert closed["engine"]["period"] == now
        assert 0 < steps[-1] < CHUNK  # the segment's last chunk: a tail
        assert sum(steps[k0:]) == 2 * CHUNK + steps[-1]


def test_a_second_session_replaces_the_first(model, monkeypatch, schedule):
    eng, _ = flipped(model, monkeypatch, {1, 3, 6, 8})
    got = eng.profiled()
    assert got["sessions"] == 2
    assert got["open"]["engine"]["period"]["count"] == 6
    assert got["close"]["engine"]["period"]["count"] == 8


def test_close_is_null_while_recording_and_a_new_open_clears_it(model, monkeypatch, schedule):
    eng, _ = flipped(model, monkeypatch, {2})
    got = eng.profiled()
    assert got["sessions"] == 0 and got["close"] is None
    assert got["open"]["engine"]["period"]["count"] == 2
    eng, _ = flipped(model, monkeypatch, {1, 3, 6})
    got = eng.profiled()
    assert got["sessions"] == 1 and got["close"] is None
    assert got["open"]["engine"]["period"]["count"] == 6


def test_an_idle_engines_close_is_noticed_by_the_reader(model, monkeypatch, schedule):
    """The recorder stops after the last stream has ended: the step loop
    passes no more boundary, so ``profiled()`` looks itself. No period has
    ended since: the copy is what the loop's own notice would have kept."""
    flag = [False]
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    eng = make(model)
    at_periods(eng, lambda k: flag.__setitem__(0, flag[0] or k == 2))
    serve(eng)
    assert eng.profiled()["close"] is None and eng.profiled()["sessions"] == 0
    flag[0] = False
    got = eng.profiled()
    assert got["sessions"] == 1 and eng.profiled() is got  # one flip, one copy
    assert got["open"]["engine"]["period"]["count"] == 2
    assert got["close"]["engine"]["period"] == eng.accounts()["period"]
    assert got["close"]["engine"]["period"]["count"] == len(schedule)


def test_steps_are_summed_over_every_dispatching_period(model, monkeypatch, schedule):
    """With a join or without: the third request joined, and its period's
    chunk was dispatched for the steps of any other."""
    eng, steps = flipped(model, monkeypatch, set())
    period = eng.accounts()["period"]
    assert period["steps"] == sum(steps) and period["count"] == len(steps)
    assert period["with_join"]["count"] >= 1
    assert set(steps) == {CHUNK, steps[-1]}


class Clock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


def test_the_time_in_periods_between_two_reads_counts_an_open_period_for_its_part(monkeypatch):
    """A period's wall is committed whole at its end; ``open_seconds`` is how
    long the open one has run at the read. Periods [0, 10], [10, 15] and one
    from 15 on; read at 4 and at 18: 14 s, all in periods."""
    from cake_tpu.obs import period as P

    clock = Clock()
    monkeypatch.setattr(P, "time", clock)
    account = P.PeriodAccount(2)
    assert account.snapshot()["period"]["open_seconds"] == 0.0
    account.begin(False)
    clock.now = 4.0
    first = account.snapshot(4.0)["period"]
    assert (first["seconds"], first["open_seconds"]) == (0.0, 4.0)
    clock.now = 10.0
    account.end(2, "ahead", steps=4)
    assert account.snapshot()["period"]["open_seconds"] == 0.0  # between two iterations
    account.begin(False)
    clock.now = 15.0
    account.end(2, "ahead", steps=4)
    account.begin(False)
    clock.now = 18.0
    second = account.snapshot()["period"]
    assert (second["seconds"], second["open_seconds"]) == (15.0, 3.0)
    assert second["seconds"] - first["seconds"] == 15.0  # more than the 14 s between the reads
    between = second["seconds"] + second["open_seconds"] - first["seconds"] - first["open_seconds"]
    assert between == 14.0
    # a look that dispatched nothing closes the iteration too
    account.end(None)
    last = account.snapshot()["period"]
    assert last["open_seconds"] == 0.0 and last["undispatched"]["count"] == 1
    assert account.snapshot(now=1.0)["period"]["open_seconds"] == 0.0


def test_an_edge_inside_a_period_keeps_how_long_it_had_run(model, monkeypatch, schedule):
    """The recorder starts while the third period waits for its chunk and
    stops inside the sixth: each copy holds the open period's seconds so far,
    and the time in periods between the notices is no more than the time
    between them (``seconds`` alone holds all of the third)."""
    flag, walls = [False], []
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    eng = make(model)
    begin, pop = eng.periods.begin, eng.periods.pop

    def begin_(queued):
        walls.append(None)
        begin(queued)

    def pop_():
        if len(walls) in (3, 6):
            time.sleep(0.02)
            flag[0] = len(walls) == 3
        return pop()

    eng.periods.begin, eng.periods.pop = begin_, pop_
    serve(eng)
    got = eng.profiled()
    assert got["sessions"] == 1
    opened, closed = got["open"], got["close"]
    at_open = opened["engine"]["period"]["open_seconds"]
    at_close = closed["engine"]["period"]["open_seconds"]
    assert at_open >= 0.02 and at_close >= 0.02
    between = closed["mono"] - opened["mono"]
    in_periods = less(closed, opened, "period", "seconds") + at_close - at_open
    assert 0 < in_periods <= between + 1e-9
    assert eng.accounts()["period"]["open_seconds"] == 0.0  # the loop has ended


def test_a_speculative_round_counts_the_positions_it_verified(model):
    cfg, params = model
    eng = BatchEngine(
        cfg, params, ByteTokenizer(), max_seq_len=128, cache_dtype=jnp.float32,
        max_batch=2, decode_chunk_size=CHUNK, admission_window=0.0, speculative_k=3,
    )
    steps = at_periods(eng, lambda k: None)
    serve(eng, [("abc abc abc abc abc abc", 16)])
    assert eng.stats["spec_rounds"] >= 1
    assert steps.count(3 + 1) >= eng.stats["spec_rounds"]
    assert eng.accounts()["period"]["steps"] == sum(steps)


# ---------------------------------------------------------- (d) GET /stats


def test_stats_engine_is_the_accounts_and_profiled_and_the_copies_hold_none(
    model, monkeypatch, schedule,
):
    from cake_tpu.models.llama.generator import LlamaGenerator, LocalForwardStep
    from cake_tpu.runtime.api import ApiServer

    eng, _ = flipped(model, monkeypatch, {2, 5})
    cfg, params = model
    step = LocalForwardStep(cfg, params, max_seq_len=96, cache_dtype=jnp.float32)
    gen = LlamaGenerator(cfg, step, ByteTokenizer(), GREEDY)
    httpd = ApiServer(gen, model_name="tiny", engine=eng).make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{httpd.server_address[1]}/stats"
        served = json.loads(urllib.request.urlopen(url, timeout=30).read())["engine"]
    finally:
        httpd.shutdown()
    accounts = json.loads(json.dumps(eng.accounts()))
    assert {k: v for k, v in served.items() if k != "profiled"} == accounts
    assert served["profiled"] == json.loads(json.dumps(eng.profiled()))
    assert set(served["profiled"]) == {"sessions", "open", "close"}
    for edge in ("open", "close"):
        copy = served["profiled"][edge]
        assert set(copy) == {"mono", "engine"}
        assert set(copy["engine"]) == set(accounts) and "profiled" not in copy["engine"]
    assert {"period", "segment", "cache", "state", "scheduler", "spilled"} <= set(accounts)
    assert {"steps", "open_seconds", "with_join"} <= set(accounts["period"])


# ------------------------------------------------ (b) the closing of a trace


@pytest.fixture(scope="module")
def deepseek(tmp_path_factory):
    """The tiny ``deepseek_v32`` of tests/test_deepseek_v32.py: its decode
    programs count what the index scanned and chose (``engine.sparse``)."""
    from bench.checkpoint import write_checkpoint
    from bench.manifest import architecture
    from cake_tpu.io.safetensors_io import load_params
    from test_deepseek_v32 import PAGE, TINY
    from zbench.conftest import REPO

    arch = architecture(REPO, TINY)
    arch.FAULT = None
    path = tmp_path_factory.mktemp("tiny_deepseek_v32")
    write_checkpoint(path, TINY, "f32", 7, arch)
    config = LlamaConfig.from_model_dir(path)
    config = dataclasses.replace(config, bos_token_id=256, eos_token_ids=(287,))
    return (config, load_params(path, config, jnp.float32)), PAGE


SPARSE = ("dispatches", "rows", "scanned", "chosen")
INDEXED = [("the first, long-running stream", 80), ("late joiner", 30)]


def indexed_engine(deepseek):
    model, page = deepseek
    serve = ServeConfig(
        max_batch=2, decode_chunk_size=CHUNK, admission_window=0.0,
        scheduler="continuous", kv_mode="paged", page_size=page,
    )
    return make(model, max_seq_len=256, serve=serve)


def test_through_the_closing_nothing_is_counted(deepseek, monkeypatch):
    """The flag false while the private session object still stands (what
    ``stop_trace`` leaves until it returns): no edge, no traced dispatch."""
    from jax._src import profiler as private

    monkeypatch.setattr(private._profile_state, "profile_session", object())
    monkeypatch.setattr(TL, "recording", lambda: False)
    eng = indexed_engine(deepseek)
    serve(eng, INDEXED)
    sparse = eng.accounts()["sparse"]
    assert sparse["dispatches"] > 0
    assert sparse["traced"] == {"index_topk": 8, **dict.fromkeys(SPARSE, 0)}
    assert eng.profiled() == NEVER


def test_traced_counts_are_of_the_dispatches_between_the_two_edges(deepseek, monkeypatch):
    """``engine.sparse.traced`` asks the same flag where a chunk is
    dispatched; the copies are taken where a period ends: they agree to the
    chunk in flight at each edge."""
    flag = [False]
    monkeypatch.setattr(TL, "recording", lambda: flag[0])
    eng = indexed_engine(deepseek)

    def then(k):
        if k in (2, 6):
            flag[0] = not flag[0]

    at_periods(eng, then)
    serve(eng, INDEXED)
    got = eng.profiled()
    assert got["sessions"] == 1 and less(got["close"], got["open"], "period", "count") == 4
    traced = eng.accounts()["sparse"]["traced"]
    a_chunk = CHUNK * 3  # decode steps x layers
    assert traced["dispatches"] > 0
    for key, slack in zip(SPARSE, (a_chunk, 2 * a_chunk, None, None)):
        between = less(got["close"], got["open"], "sparse", key)
        assert between > 0 and traced[key] > 0
        if slack is not None:
            assert abs(traced[key] - between) <= 2 * slack
    # the copies' own ``traced`` say the same: nothing before, all of it after
    assert got["open"]["engine"]["sparse"]["traced"]["dispatches"] == 0
    assert got["close"]["engine"]["sparse"]["traced"]["dispatches"] in (
        traced["dispatches"], traced["dispatches"] - a_chunk,
    )


# ------------------------------------------------------ (c) a real session


def host_events(trace_dir) -> list:
    from jax.profiler import ProfileData

    (trace,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    planes = [p for p in ProfileData.from_file(trace).planes if p.name == "/host:CPU"]
    return [e.name for p in planes for line in p.lines for e in line.events]


def traced_session(eng, session, trace_dir, k0, k1):
    """A real ``jax.profiler`` session from the k0-th dispatching period's
    end to the k1-th's, started and stopped on the engine's thread."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1

    def then(k):
        if k == k0:
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        elif k == k1:
            jax.profiler.stop_trace()

    at_periods(eng, then)
    try:
        serve(eng, session)
    finally:
        if TL.recording():  # the session ended short of k1: leave none open
            jax.profiler.stop_trace()


def test_a_real_sessions_period_events_against_close_less_open(model, tmp_path, schedule):
    assert TL.recording() is False
    eng = make(model)
    traced_session(eng, SESSION, tmp_path, 3, 9)
    got = eng.profiled()
    assert got["sessions"] == 1
    between = less(got["close"], got["open"], "period", "count")
    on_the_plane = host_events(tmp_path).count("period")
    assert between == 6  # the periods that ENDED between the two notices
    # a period the recorder's start or stop fell in is no event of the trace
    assert 0 < on_the_plane <= between and between - on_the_plane <= 2
    assert less(got["close"], got["open"], "period", "steps") == sum(schedule[3:9])


def test_a_real_sessions_traced_counts_against_close_less_open(deepseek, tmp_path):
    eng = indexed_engine(deepseek)
    traced_session(eng, INDEXED, tmp_path, 2, 6)
    got = eng.profiled()
    assert got["sessions"] == 1 and less(got["close"], got["open"], "period", "count") == 4
    traced = eng.accounts()["sparse"]["traced"]
    between = less(got["close"], got["open"], "sparse", "dispatches")
    a_chunk = CHUNK * 3
    assert traced["dispatches"] > 0 and abs(traced["dispatches"] - between) <= 2 * a_chunk
    assert 0 < host_events(tmp_path).count("decode-chunk") <= 4
