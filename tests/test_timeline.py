"""Timeline profiler (cake_tpu/obs/timeline.py): span trees, Perfetto export
schema, bounded-ring eviction, flow arrows, concurrent JSONL streams.

The export contract these tests pin is what Perfetto/chrome://tracing depend
on: valid trace-event JSON, every "B" matched by an "E" on its track, flow
events that land inside real slices. No jax needed anywhere here.
"""

import json
import threading

from cake_tpu.obs.timeline import (
    Timeline,
    export_events,
    load_jsonl,
    validate_export,
)

# ------------------------------------------------------------- span trees


def test_nested_spans_record_parent_ids():
    tl = Timeline()
    with tl.span("outer") as outer_id:
        with tl.span("inner") as inner_id:
            pass
    events = tl.snapshot()
    inner = next(e for e in events if e["name"] == "inner")
    outer = next(e for e in events if e["name"] == "outer")
    assert inner["parent"] == outer_id
    assert "parent" not in outer or outer["parent"] is None
    assert inner["id"] == inner_id
    # Both clocks on every event.
    for e in events:
        assert "wall" in e and "mono" in e


def test_span_attrs_and_request_id_ride_along():
    tl = Timeline()
    with tl.span("work", rid="req-1", track="lane0", args={"k": 3}):
        pass
    (ev,) = tl.snapshot()
    assert ev["rid"] == "req-1"
    assert ev["track"] == "lane0"
    assert ev["args"] == {"k": 3}
    assert ev["dur"] >= 0


def test_begin_end_pairs_by_id():
    tl = Timeline()
    sid = tl.begin("request", rid="r", track="lane1")
    tl.instant("first-token", rid="r", track="lane1")
    tl.end(sid, args={"finish_reason": "stop"})
    trace = tl.export()
    assert validate_export(trace) == []
    phases = [e["ph"] for e in trace["traceEvents"] if e["ph"] != "M"]
    assert phases.count("B") == 1 and phases.count("E") == 1
    b = next(e for e in trace["traceEvents"] if e["ph"] == "B")
    e = next(e for e in trace["traceEvents"] if e["ph"] == "E")
    assert b["name"] == e["name"] == "request"
    assert e["ts"] >= b["ts"]


def test_open_span_is_not_half_exported():
    """A B without its E yet (request still running) must not emit a lone
    "B" — the schema contract is every exported B has a matching E."""
    tl = Timeline()
    tl.begin("request", track="lane0")
    trace = tl.export()
    assert validate_export(trace) == []
    assert all(e["ph"] not in ("B", "E") for e in trace["traceEvents"])


def test_aggregate_total_and_self_time():
    tl = Timeline()
    import time

    with tl.span("outer"):
        time.sleep(0.01)
        with tl.span("inner"):
            time.sleep(0.01)
    agg = tl.aggregate()
    assert agg["outer"]["count"] == 1
    assert agg["inner"]["count"] == 1
    # Outer total covers inner; outer SELF excludes it.
    assert agg["outer"]["total_s"] >= agg["inner"]["total_s"]
    assert agg["outer"]["self_s"] < agg["outer"]["total_s"]


# ------------------------------------------------------------- exporter


def test_export_assigns_pids_by_node_and_tids_by_track():
    tl = Timeline(node="master")
    with tl.span("a", track="engine"):
        pass
    with tl.span("b", track="wire"):
        pass
    with tl.span("c", node="worker0", track="ops"):
        pass
    trace = tl.export()
    assert validate_export(trace) == []
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    procs = {
        e["args"]["name"] for e in meta if e["name"] == "process_name"
    }
    assert procs == {"master", "worker0"}
    threads = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    assert {"engine", "wire", "ops"} <= threads
    a = next(e for e in trace["traceEvents"] if e.get("name") == "a")
    c = next(e for e in trace["traceEvents"] if e.get("name") == "c")
    assert a["pid"] != c["pid"]


def test_flow_events_pair_and_validate():
    tl = Timeline()
    with tl.span("wire.w0", track="wire"):
        tl.flow_start(42, "hop", track="wire")
    with tl.span("worker.chunk", node="w0", track="ops"):
        tl.flow_end(42, "hop", node="w0", track="ops")
    trace = tl.export()
    assert validate_export(trace) == []
    s = next(e for e in trace["traceEvents"] if e["ph"] == "s")
    f = next(e for e in trace["traceEvents"] if e["ph"] == "f")
    assert s["id"] == f["id"] == 42
    assert f["bp"] == "e"
    # The two ends live on different pids: the cross-node arrow.
    assert s["pid"] != f["pid"]


def test_validator_catches_orphan_flow_and_unpaired_b():
    bad = {
        "traceEvents": [
            {"ph": "B", "name": "x", "pid": 1, "tid": 1, "ts": 0.0},
            {"ph": "f", "name": "hop", "pid": 1, "tid": 1, "ts": 1.0,
             "id": 7, "bp": "e"},
        ]
    }
    problems = validate_export(bad)
    assert any("never closed" in p for p in problems)
    assert any("no 's'" in p for p in problems)


def test_validator_reports_idless_flow_instead_of_crashing():
    problems = validate_export(
        {"traceEvents": [{"ph": "s", "name": "hop", "pid": 1, "tid": 1,
                          "ts": 0.0}]}
    )
    assert any("lacks an id" in p for p in problems)


def test_validator_catches_flow_outside_any_slice():
    # An arrow anchored in empty space on its track renders detached.
    bad = {
        "traceEvents": [
            {"ph": "X", "name": "op", "pid": 1, "tid": 1, "ts": 0.0,
             "dur": 5.0},
            {"ph": "s", "name": "hop", "pid": 1, "tid": 1, "ts": 2.0,
             "id": 1},           # inside the slice: fine
            {"ph": "f", "name": "hop", "pid": 1, "tid": 1, "ts": 99.0,
             "id": 1, "bp": "e"},  # way past it: flagged
        ]
    }
    problems = validate_export(bad)
    assert any("lands in no slice" in p and "99.0" in p for p in problems)
    assert not any("2.0" in p for p in problems)


def test_request_id_filter_keeps_the_requests_pairs():
    tl = Timeline()
    sid = tl.begin("request", rid="want", track="lane0")
    tl.begin("request", rid="other", track="lane1")
    tl.end(sid)
    events = tl.snapshot(request_id="want")
    assert {e.get("rid") for e in events if e.get("ph") == "B"} == {"want"}
    # The E (which carries no rid itself) is retained through its B's id.
    assert any(e["ph"] == "E" for e in events)
    trace = tl.export(request_id="want")
    assert validate_export(trace) == []
    assert any(e["ph"] == "B" for e in trace["traceEvents"])


# ------------------------------------------------------------- bounded ring


def test_ring_eviction_bounds_and_export_stays_valid():
    tl = Timeline(capacity=16)
    # Far more spans than capacity: the ring keeps the newest 16 events and
    # the exporter drops eviction orphans (an E whose B was evicted) rather
    # than emitting an unpaired end.
    for i in range(100):
        sid = tl.begin(f"s{i}")
        tl.end(sid)
    assert len(tl.snapshot()) == 16
    trace = tl.export()
    assert validate_export(trace) == []
    names = [e["name"] for e in trace["traceEvents"] if e["ph"] == "B"]
    assert names and all(n >= "s92" for n in names)  # newest survive


def test_eviction_orphan_end_is_dropped():
    tl = Timeline(capacity=4)
    sid = tl.begin("victim")
    for i in range(4):  # push the B out of the ring; keep the E
        tl.instant(f"i{i}")
    tl.end(sid)
    ring = tl.snapshot()
    assert any(e["ph"] == "E" for e in ring)  # orphan E is IN the ring
    trace = tl.export()
    assert validate_export(trace) == []
    assert all(e["ph"] not in ("B", "E") for e in trace["traceEvents"])


# ------------------------------------------------------------- JSONL sink


def test_concurrent_streams_write_valid_jsonl(tmp_path):
    """N threads spanning concurrently while the JSONL sink is attached:
    every line must parse (whole-line appends), and the rebuilt export must
    validate — the `--trace-jsonl` + `cake-tpu trace --jsonl` path."""
    path = str(tmp_path / "trace.jsonl")
    tl = Timeline(capacity=64)  # smaller than the event count: sink >> ring
    tl.attach_jsonl(path)

    def work(t):
        for i in range(50):
            with tl.span(f"t{t}.work", track=f"lane{t}", args={"i": i}):
                pass

    threads = [threading.Thread(target=work, args=(t,)) for t in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tl.attach_jsonl(None)

    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln]
    assert len(lines) == 6 * 50
    events = [json.loads(ln) for ln in lines]  # every line valid JSON
    assert events == load_jsonl(path)
    trace = export_events(events)
    assert validate_export(trace) == []
    assert len([e for e in trace["traceEvents"] if e["ph"] == "X"]) == 300


def test_export_events_roundtrips_through_json():
    tl = Timeline()
    with tl.span("a", rid="r", args={"n": 1}):
        tl.counter("hbm", {"bytes_in_use": 123.0}, track="mem")
    trace = json.loads(json.dumps(tl.export()))
    assert validate_export(trace) == []
    c = next(e for e in trace["traceEvents"] if e["ph"] == "C")
    assert c["args"] == {"bytes_in_use": 123.0}


# ------------------------------------------------------------- integrations


def test_trace_spans_bridge_into_timeline():
    """utils/trace.py's global registry feeds the timeline (the satellite:
    hop/stage spans merge into the Perfetto view with both clocks)."""
    from cake_tpu.obs.timeline import timeline
    from cake_tpu.utils import trace

    with trace.span("hop.test-node"):
        pass
    assert trace.spans.snapshot()["hop.test-node"]["count"] == 1
    names = {e["name"] for e in timeline.snapshot()}
    assert "hop.test-node" in names


def test_eight_stream_paged_serving_exports_connected_trace():
    """Acceptance: the PR 4 capacity scenario (8 concurrent short streams
    through a paged pool at HALF the dense footprint) exports ONE
    Perfetto-loadable trace: per-lane request tracks from admission to
    finish, engine prefill/decode/page-extend spans, and the memory counter
    track — all schema-valid."""
    import jax
    import jax.numpy as jnp

    from cake_tpu.models.llama import model as M
    from cake_tpu.models.llama.chat import Message
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.generator import SamplingConfig
    from cake_tpu.models.llama.tokenizer import ByteTokenizer
    from cake_tpu.obs.timeline import timeline
    from cake_tpu.runtime.serving import BatchEngine, ServeConfig

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(21), jnp.float32)
    pages_per_seq = 256 // 16
    eng = BatchEngine(
        cfg, params, ByteTokenizer(),
        max_seq_len=256, cache_dtype=jnp.float32,
        serve=ServeConfig(
            max_batch=8, decode_chunk_size=4, admission_window=0.1,
            kv_mode="paged", page_size=16,
            max_pages=4 * pages_per_seq,  # half the dense 8-lane footprint
        ),
    )
    eng.start()
    greedy = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
    try:
        handles = [
            eng.submit([Message.user(f"stream number {i}")], 20, greedy)
            for i in range(8)
        ]
        rids = [h.request_id for h in handles]
        for h in handles:
            assert sum(1 for _ in h.tokens()) >= 1
    finally:
        eng.stop()

    trace = timeline.export()
    assert validate_export(trace) == []
    events = trace["traceEvents"]
    names = {e["name"] for e in events if e["ph"] != "M"}
    assert {"epoch", "prefill", "decode-chunk", "page-extend"} <= names
    # Per-lane tracks: every admitted request renders as a closed B/E pair
    # on a laneN thread, admission -> finish.
    lane_tracks = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
        and e["args"]["name"].startswith("lane")
    }
    assert len(lane_tracks) == 8
    req_b = [e for e in events if e["ph"] == "B" and e["name"] == "request"]
    assert {e["args"]["request_id"] for e in req_b} == set(rids)
    assert len([e for e in events if e["ph"] == "E"]) == len(req_b)
    # The memory counter track (host RSS on CPU; HBM on real devices) and
    # the paged-pool occupancy counters line up on the same clock.
    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert "host_rss" in counters and "kv_pages" in counters
    # The raw ring events carry the sampling phase tag (chart args stay
    # numeric); "prefill" fires unthrottled so it always survives the ring.
    tags = {
        e.get("tag") for e in timeline.snapshot()
        if e.get("ph") == "C" and e["name"] == "host_rss"
    }
    assert "prefill" in tags or "epoch-end" in tags
    # Perfetto-loadable: serializes as strict JSON.
    json.dumps(trace)


def test_flight_events_carry_mono_and_span_id():
    """FlightRecorder events gain a monotonic clock and, when a timeline
    span is open, its id (the satellite's /events <-> trace link)."""
    from cake_tpu.obs.timeline import timeline
    from cake_tpu.utils import metrics

    with timeline.span("epoch") as sid:
        ev = metrics.flight.record("admitted", "req-x", lane=2)
    assert ev["span"] == sid
    assert "mono" in ev and "ts" in ev
    outside = metrics.flight.record("finished", "req-x")
    assert "span" not in outside


# ------------------------------------------- the profiler as a second sink


def test_engine_track_spans_land_on_the_profilers_host_plane(tmp_path):
    """A span on the engine track, lexical or begin()/end(), opened while a
    profiler window is open is an event of the same name on the trace's
    ``/host:CPU`` plane; a lane-track span and a span that began before the
    window are not."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from cake_tpu.obs.timeline import Timeline

    tl = Timeline()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as bench/child.py opens its window
    opts.host_tracer_level = 1
    with tl.span("before-the-window", track="engine"):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tl.span("period", track="engine", args={"slot": 3}):
                with tl.span("sweep", track="engine"):
                    pass
                sid = tl.begin("step", track="engine", args={"live": 2})
                tl.end(sid, args={"joins": 0})
            lane = tl.begin("request", rid="r1", track="lane0", parent=None)
            tl.end(lane)
        finally:
            jax.profiler.stop_trace()
    (trace,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    host = [p for p in ProfileData.from_file(trace).planes if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert {"period", "sweep", "step"} <= names
    assert not {"request", "before-the-window"} & names
    # the ring holds them all, whatever the profiler kept
    assert {e["name"] for e in tl.snapshot()} >= {
        "period", "sweep", "step", "request", "before-the-window",
    }


def test_an_epochs_prefill_in_groups_has_a_span_a_group_and_one_for_the_logits():
    """``batch_backend._PagedBackend``'s two helpers of an epoch's prefill in
    groups: one engine-track span a program and one around the logits'
    ``concatenate``, none for a single group's (nothing is concatenated)."""
    import jax.numpy as jnp

    from cake_tpu.obs.timeline import PROFILED_TRACK, timeline
    from cake_tpu.runtime.batch_backend import _PagedBackend

    before = len(timeline.snapshot())
    logits = []
    for index, rows in enumerate([slice(0, 2), slice(2, 3)]):
        with _PagedBackend._group_span(index, rows, 64):
            logits.append(jnp.zeros((rows.stop - rows.start, 8)))
    assert _PagedBackend._group_logits(logits).shape == (3, 8)
    assert _PagedBackend._group_logits(logits[:1]) is logits[0]
    mine = [e for e in timeline.snapshot()[before:] if e.get("track") == PROFILED_TRACK]
    assert [(e["name"], e["args"]) for e in mine] == [
        ("prefill-group", {"group": 0, "rows": 2, "slots": 64}),
        ("prefill-group", {"group": 1, "rows": 1, "slots": 64}),
        ("prefill-logits", {"groups": 2}),
    ]


def test_obs_imports_and_plain_spans_cost_no_jax_import():
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from cake_tpu import obs\n"
        "with obs.timeline.span('x', track='lane0', args={'a': 1}):\n"
        "    pass\n"
        "obs.timeline.end(obs.timeline.begin('y', track='wire'))\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
    )
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
