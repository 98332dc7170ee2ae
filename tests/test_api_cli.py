"""API server and CLI tests."""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cake_tpu.io.safetensors_io import save_tiny_checkpoint
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import (
    LlamaGenerator,
    LocalForwardStep,
    SamplingConfig,
)
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.runtime.api import CHAT_ROUTE, ApiServer


@pytest.fixture(scope="module")
def server():
    cfg = LlamaConfig.tiny(num_hidden_layers=4)
    params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    step = LocalForwardStep(cfg, params, max_seq_len=96, cache_dtype=jnp.float32)
    gen = LlamaGenerator(
        cfg,
        step,
        ByteTokenizer(),
        SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    )
    api = ApiServer(gen, model_name="tiny-test", default_max_tokens=6)
    httpd = api.make_server("127.0.0.1", 0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def post(url, body, raw=False):
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    resp = urllib.request.urlopen(req, timeout=120)
    data = resp.read()
    return data if raw else json.loads(data)


def test_chat_completion_response_shape(server):
    out = post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 4},
    )
    # Reference response shape (api/mod.rs:26-62) + usage extension.
    assert out["object"] == "chat.completion"
    assert out["id"].startswith("chatcmpl-")
    assert out["model"] == "tiny-test"
    choice = out["choices"][0]
    assert choice["index"] == 0
    assert choice["message"]["role"] == "assistant"
    assert isinstance(choice["message"]["content"], str)
    assert out["usage"]["completion_tokens"] >= 1
    assert (
        out["usage"]["total_tokens"]
        == out["usage"]["prompt_tokens"] + out["usage"]["completion_tokens"]
    )


def test_chat_deterministic_across_requests(server):
    body = {"messages": [{"role": "user", "content": "same prompt"}]}
    a = post(server + CHAT_ROUTE, body)
    b = post(server + CHAT_ROUTE, body)
    # Greedy + per-request reset => identical output (exercises state isolation).
    assert a["choices"][0]["message"]["content"] == b["choices"][0]["message"]["content"]


def test_streaming_sse(server):
    raw = post(
        server + CHAT_ROUTE,
        {
            "messages": [{"role": "user", "content": "stream it"}],
            "stream": True,
            "max_tokens": 4,
        },
        raw=True,
    ).decode()
    events = [
        json.loads(line[len("data: ") :])
        for line in raw.splitlines()
        if line.startswith("data: ") and line != "data: [DONE]"
    ]
    assert raw.rstrip().endswith("data: [DONE]")
    assert all(e["object"] == "chat.completion.chunk" for e in events)
    assert events[0]["choices"][0]["delta"].get("role") == "assistant"
    assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    streamed = "".join(
        e["choices"][0]["delta"].get("content", "") for e in events
    )
    # Streamed concatenation equals the non-streaming result for the same prompt.
    full = post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "stream it"}], "max_tokens": 4},
    )
    assert streamed == full["choices"][0]["message"]["content"]


def test_concurrent_requests_both_valid(server):
    results = {}

    def hit(key, prompt):
        results[key] = post(
            server + CHAT_ROUTE,
            {"messages": [{"role": "user", "content": prompt}], "max_tokens": 3},
        )

    threads = [
        threading.Thread(target=hit, args=(i, f"prompt {i}")) for i in range(3)
    ]
    [t.start() for t in threads]
    [t.join(timeout=120) for t in threads]
    assert len(results) == 3
    for r in results.values():
        assert r["object"] == "chat.completion"


def test_per_request_sampling_override_takes_effect(server):
    # Server default is greedy (temperature=0). A high-temperature request must
    # actually change sampling (regression: jit once baked the first config's
    # constants into the sampler forever).
    body_greedy = {
        "messages": [{"role": "user", "content": "override test"}],
        "max_tokens": 6,
    }
    greedy = post(server + CHAT_ROUTE, body_greedy)["choices"][0]["message"][
        "content"
    ]
    hot_outputs = {
        post(
            server + CHAT_ROUTE,
            {**body_greedy, "temperature": 5.0, "seed": seed},
        )["choices"][0]["message"]["content"]
        for seed in range(5)
    }
    assert len(hot_outputs) > 1 or hot_outputs != {greedy}
    # And greedy again afterwards: defaults restored.
    assert (
        post(server + CHAT_ROUTE, body_greedy)["choices"][0]["message"]["content"]
        == greedy
    )


def test_null_sampling_fields_treated_as_unset(server):
    out = post(
        server + CHAT_ROUTE,
        {
            "messages": [{"role": "user", "content": "nulls"}],
            "temperature": None,
            "top_p": None,
            "seed": None,
            "max_tokens": 3,
        },
    )
    assert out["object"] == "chat.completion"


def test_finish_reason_length_on_truncation(server):
    out = post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "long"}], "max_tokens": 2},
    )
    assert out["choices"][0]["finish_reason"] == "length"


def test_unknown_route_404(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server + "/api/v1/other", {})
    assert e.value.code == 404


def test_empty_messages_400(server):
    with pytest.raises(urllib.error.HTTPError) as e:
        post(server + CHAT_ROUTE, {"messages": []})
    assert e.value.code == 400


def test_malformed_body_400(server):
    req = urllib.request.Request(
        server + CHAT_ROUTE,
        data=b"{not json",
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_health(server):
    with urllib.request.urlopen(server + "/health", timeout=30) as r:
        out = json.loads(r.read())
    assert out["status"] == "ok"


def test_stats_endpoint(server):
    from cake_tpu.utils import trace

    with trace.span("test.stats.probe"):
        pass
    with urllib.request.urlopen(server + "/stats", timeout=30) as r:
        out = json.loads(r.read())
    assert out["spans"]["test.stats.probe"]["count"] >= 1
    assert out["memory"].get("host_peak_rss_bytes", 0) > 0


# ---------------------------------------------------------------- CLI


def test_cli_parser_covers_reference_flags():
    from cake_tpu.cli import build_parser

    p = build_parser()
    args = p.parse_args(
        [
            "--model", "/m",
            "--mode", "worker",
            "--name", "w1",
            "--address", "0.0.0.0:10128",
            "--topology", "/t.yml",
            "--prompt", "hello",
            "--system-prompt", "sys",
            "--seed", "7",
            "-n", "50",
            "--temperature", "0.7",
            "--top-p", "0.9",
            "--top-k", "40",
            "--repeat-penalty", "1.3",
            "--repeat-last-n", "64",
            "--dtype", "f32",
            "--cpu",
            "--device", "1",
        ]
    )
    assert args.mode == "worker" and args.seed == 7 and args.sample_len == 50
    assert args.top_k == 40 and args.dtype == "f32" and args.cpu
    assert args.device == 1


def test_cli_distributed_flag_validation(capsys):
    """--distributed parses COORD,N,I and demands the mesh backend (the
    joining itself is covered by tests/test_multihost.py)."""
    from cake_tpu.cli import main

    rc = main(["--model", "/nope", "--distributed", "bad-spec"])
    assert rc == 2
    assert "COORDINATOR" in capsys.readouterr().err

    rc = main(
        ["--model", "/nope", "--distributed", "127.0.0.1:1,2,0", "--backend", "tcp"]
    )
    assert rc == 2
    assert "--backend mesh" in capsys.readouterr().err


def test_cli_device_ordinal_pins_and_validates(tmp_path, capsys):
    """--device N places single-device compute on jax.devices()[N]; an
    out-of-range ordinal is a clean error (utils/mod.rs:15-30 parity)."""
    from cake_tpu.cli import main

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    save_tiny_checkpoint(tmp_path / "model", params, cfg)
    common = [
        "--model", str(tmp_path / "model"),
        "--prompt", "hi",
        "-n", "2",
        "--temperature", "0",
        "--dtype", "f32",
        "--max-seq-len", "96",
    ]
    try:
        assert main(common + ["--device", "3"]) == 0
        capsys.readouterr()
        # The pinned default device now hosts fresh computations.
        assert jax.numpy.zeros(()).devices() == {jax.devices()[3]}

        rc = main(common + ["--device", "99"])
        assert rc == 2
        assert "out of range" in capsys.readouterr().err
    finally:
        jax.config.update("jax_default_device", None)


def test_cli_one_shot_generation(tmp_path, capsys):
    from cake_tpu.cli import main

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    save_tiny_checkpoint(tmp_path / "model", params, cfg)
    rc = main(
        [
            "--model", str(tmp_path / "model"),
            "--prompt", "hi",
            "-n", "3",
            "--temperature", "0",
            "--dtype", "f32",
            "--max-seq-len", "96",
        ]
    )
    assert rc == 0


def test_cli_stats_subcommand_renders_table(server, capsys):
    """``cake-tpu stats --count 1`` polls /stats and renders the table
    without demanding --model (it is a thin HTTP poller)."""
    from cake_tpu.cli import main
    from cake_tpu.utils import metrics

    post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "table"}], "max_tokens": 2},
    )
    metrics.registry.counter("cake_probe_total").inc(7)
    rc = main(["stats", "--url", server, "--count", "1", "--no-clear"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "model=tiny-test" in out
    assert "cake_prefill_seconds" in out
    assert "p99_ms" in out
    assert "cake_probe_total" in out


def test_cli_stats_subcommand_unreachable_server(capsys):
    from cake_tpu.cli import main

    rc = main(["stats", "--url", "http://127.0.0.1:9", "--count", "1"])
    assert rc == 1
    assert "poll" in capsys.readouterr().err


def test_cli_worker_requires_topology(tmp_path, capsys):
    from cake_tpu.cli import main

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(1), jnp.float32)
    save_tiny_checkpoint(tmp_path / "model", params, cfg)
    rc = main(["--model", str(tmp_path / "model"), "--mode", "worker"])
    assert rc == 2


def test_models_endpoint(server):
    """OpenAI SDK discovery surface: GET /api/v1/models lists the loaded
    model in the list-envelope shape."""
    with urllib.request.urlopen(server + "/api/v1/models", timeout=30) as r:
        out = json.loads(r.read())
    assert out["object"] == "list"
    (entry,) = out["data"]
    assert entry["object"] == "model"
    assert entry["id"]
    assert isinstance(entry["created"], int)


def test_metrics_endpoint(server):
    """Prometheus text exposition at /metrics: span summaries (count/sum
    pairs) that scrapers can point at the serving port."""
    from cake_tpu.utils import trace

    with trace.span("test.metrics.probe"):
        pass
    with urllib.request.urlopen(server + "/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        body = r.read().decode()
    assert "# TYPE cake_span_seconds summary" in body
    assert 'cake_span_seconds_count{span="test.metrics.probe"}' in body
    assert 'cake_span_seconds_sum{span="test.metrics.probe"}' in body


def _scrape(server: str) -> str:
    with urllib.request.urlopen(server + "/metrics", timeout=30) as r:
        return r.read().decode()


def test_metrics_exposition_contract(server):
    """Parse /metrics line-by-line: label escaping, TYPE correctness, HELP
    presence, monotone cumulative histogram buckets, build info + uptime."""
    from cake_tpu.utils import metrics, trace

    nasty = 'quo"te\\slash\nnewline'
    with trace.span(nasty):
        pass
    metrics.registry.histogram(
        "cake_probe_seconds", "probe latency", buckets=(0.01, 1.0)
    ).observe(0.005)
    metrics.registry.histogram("cake_probe_seconds").observe(0.5)
    metrics.registry.histogram("cake_probe_seconds").observe(9.0)
    metrics.registry.counter("cake_probe_total", "probe counter").inc(3)
    metrics.registry.gauge("cake_probe_level", "probe gauge").set(2)
    body = _scrape(server)

    # Every line is a comment or a `series value` pair — no raw newlines
    # from the nasty label broke the line discipline.
    types: dict[str, str] = {}
    series: dict[str, str] = {}
    for line in body.splitlines():
        assert line, "blank line in exposition"
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
        elif not line.startswith("#"):
            name, val = line.rsplit(" ", 1)
            float(val)  # parseable value
            series[name] = val

    # Label escaping: backslash, quote, and newline all escaped in-place.
    assert (
        'cake_span_seconds_count{span="quo\\"te\\\\slash\\nnewline"}' in series
    )

    # TYPE correctness per family.
    assert types["cake_probe_total"] == "counter"
    assert types["cake_probe_level"] == "gauge"
    assert types["cake_probe_seconds"] == "histogram"
    assert types["cake_build_info"] == "gauge"
    assert types["cake_uptime_seconds"] == "gauge"
    assert types["cake_span_seconds"] == "summary"

    # Self-describing scrape: a HELP line for every TYPE'd family.
    helps = {
        line.split(" ", 3)[2]
        for line in body.splitlines()
        if line.startswith("# HELP ")
    }
    assert set(types) <= helps

    # Histogram contract: cumulative monotone buckets, +Inf == _count.
    buckets = [
        int(series[f'cake_probe_seconds_bucket{{le="{le}"}}'])
        for le in ("0.01", "1", "+Inf")
    ]
    assert buckets == sorted(buckets) == [1, 2, 3]
    assert buckets[-1] == int(series["cake_probe_seconds_count"])
    assert float(series["cake_probe_seconds_sum"]) == pytest.approx(9.505)

    # Build info + uptime (satellite: self-describing scrapes).
    assert 'model="tiny-test"' in body
    info_line = next(
        l for l in body.splitlines() if l.startswith("cake_build_info")
    )
    assert info_line.endswith(" 1")
    assert float(series["cake_uptime_seconds"]) >= 0.0


def test_request_latency_histogram_on_metrics(server):
    """Acceptance: a served request surfaces at least one cake_*_seconds
    histogram with cumulative _bucket/_sum/_count series on /metrics."""
    post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "measured"}], "max_tokens": 3},
    )
    body = _scrape(server)
    assert "# TYPE cake_prefill_seconds histogram" in body
    assert 'cake_prefill_seconds_bucket{le="+Inf"}' in body
    assert "cake_prefill_seconds_sum" in body
    assert "cake_prefill_seconds_count" in body
    assert "# TYPE cake_decode_step_seconds histogram" in body


def test_events_endpoint_serialized_path(server):
    """GET /events: the flight recorder's ring, filterable by the chat
    response id (the serialized path records submitted/finished)."""
    out = post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "flight"}], "max_tokens": 3},
    )
    rid = out["id"]
    with urllib.request.urlopen(server + "/events", timeout=30) as r:
        all_events = json.loads(r.read())
    assert all_events["capacity"] > 0
    assert all_events["count"] == len(all_events["events"])
    with urllib.request.urlopen(
        server + "/events?request_id=" + rid, timeout=30
    ) as r:
        mine = json.loads(r.read())["events"]
    assert [e["event"] for e in mine] == ["submitted", "finished"]
    assert mine[0]["prompt_tokens"] == out["usage"]["prompt_tokens"]
    assert mine[1]["completion_tokens"] == out["usage"]["completion_tokens"]


def test_stats_includes_metrics_snapshot(server):
    post(
        server + CHAT_ROUTE,
        {"messages": [{"role": "user", "content": "snap"}], "max_tokens": 2},
    )
    with urllib.request.urlopen(server + "/stats", timeout=30) as r:
        out = json.loads(r.read())
    assert out["uptime_s"] >= 0
    hists = {h["name"] for h in out["metrics"]["histograms"]}
    assert "cake_prefill_seconds" in hists
    for h in out["metrics"]["histograms"]:
        assert {"count", "sum", "mean", "p50", "p90", "p99"} <= set(h)


def test_trace_endpoint_and_cli_export(server, tmp_path):
    """GET /trace returns Perfetto-loadable trace-event JSON and the
    `cake-tpu trace` subcommand (thin HTTP + stdlib, no --model/jax) fetches,
    writes, and schema-validates it."""
    from cake_tpu.cli import main
    from cake_tpu.obs.timeline import timeline, validate_export

    # The server shares this process's global timeline: land a span tree the
    # route must render (the serving engine does this for real requests).
    with timeline.span("epoch", rid="chatcmpl-trace-test", track="engine"):
        with timeline.span("prefill", track="engine"):
            pass
    with urllib.request.urlopen(server + "/trace", timeout=30) as r:
        trace = json.loads(r.read())
    assert validate_export(trace) == []
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] != "M"}
    assert {"epoch", "prefill"} <= names
    # Filtered fetch: only the tagged request's spans.
    with urllib.request.urlopen(
        server + "/trace?request_id=chatcmpl-trace-test", timeout=30
    ) as r:
        mine = json.loads(r.read())
    assert validate_export(mine) == []
    assert any(
        e.get("args", {}).get("request_id") == "chatcmpl-trace-test"
        for e in mine["traceEvents"]
    )

    out = tmp_path / "t.json"
    rc = main(["trace", "--url", server, "--out", str(out), "--validate"])
    assert rc == 0
    assert validate_export(json.loads(out.read_text())) == []


def test_trace_cli_offline_jsonl_mode(tmp_path, capsys):
    """`cake-tpu trace --jsonl` renders a --trace-jsonl stream offline."""
    from cake_tpu.cli import main
    from cake_tpu.obs.timeline import Timeline, validate_export

    jsonl = tmp_path / "t.jsonl"
    tl = Timeline()
    tl.attach_jsonl(str(jsonl))
    with tl.span("decode-chunk", rid="req-1", track="engine"):
        pass
    out = tmp_path / "t.json"
    rc = main(["trace", "--jsonl", str(jsonl), "--out", str(out),
               "--validate"])
    assert rc == 0
    trace = json.loads(out.read_text())
    assert validate_export(trace) == []
    assert any(e.get("name") == "decode-chunk" for e in trace["traceEvents"])
    assert "wrote" in capsys.readouterr().out


def test_cli_stats_spans_view(server, capsys):
    """`cake-tpu stats --spans`: top spans by total/self time from the
    timeline aggregate in /stats."""
    from cake_tpu.cli import main
    from cake_tpu.obs.timeline import timeline

    with timeline.span("epoch", track="engine"):
        with timeline.span("decode-chunk", track="engine"):
            pass
    rc = main(["stats", "--url", server, "--count", "1", "--no-clear",
               "--spans"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "model=tiny-test" in out
    assert "epoch" in out and "decode-chunk" in out
    assert "self_ms" in out


# ----------------------------------------------------- failure-semantics API
# Cancellation route + load-shedding 503: the engine seam is duck-typed, so
# a stub engine pins the HTTP contract without spinning a real decode loop
# (tests/test_chaos.py covers the real engine behavior).


class _StubEngine:
    """Duck-typed BatchEngine surface the ApiServer touches."""

    def __init__(self, overloaded=False):
        self.overloaded = overloaded
        self.over_quota = False
        self.cancelled: list[str] = []
        self.priorities: list[int | None] = []
        self.tenants: list[str | None] = []
        self.deadlines: list[float | None] = []
        self.stats = {"batches": 0}

    def start(self):
        pass

    def accounts(self):  # ``GET /stats`` engine, and ``profiled`` beside it
        return dict(self.stats)

    def profiled(self):
        return {"sessions": 0, "open": None, "close": None}

    def submit(
        self, messages, max_tokens, sampling, request_id=None, priority=None,
        tenant=None, deadline_s=None,
    ):
        from cake_tpu.runtime.admission import QuotaExceeded
        from cake_tpu.runtime.serving import EngineOverloaded

        self.priorities.append(priority)
        self.tenants.append(tenant)
        self.deadlines.append(deadline_s)
        if self.over_quota:
            raise QuotaExceeded(
                "tenant 'abuser' over its token rate", retry_after_s=2.4,
                tenant="abuser", kind="rate",
            )
        if self.overloaded:
            raise EngineOverloaded(
                "engine overloaded: queue depth 8 >= 8", retry_after_s=2.0
            )
        raise AssertionError("stub engine only tests refusal paths")

    def tenant_stats(self):
        return {"abuser": {"active_streams": 1, "quota_refusals": 2}}

    def cancel(self, request_id: str) -> bool:
        self.cancelled.append(request_id)
        return request_id.startswith("chatcmpl-")


@pytest.fixture()
def stub_server():
    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    step = LocalForwardStep(cfg, params, max_seq_len=96, cache_dtype=jnp.float32)
    gen = LlamaGenerator(
        cfg, step, ByteTokenizer(),
        SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    )
    engine = _StubEngine()
    api = ApiServer(gen, model_name="tiny-test", engine=engine)
    httpd = api.make_server("127.0.0.1", 0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{port}", engine
    httpd.shutdown()


def test_cancel_route_hits_engine(stub_server):
    url, engine = stub_server
    out = post(url + "/api/v1/cancel", {"id": "chatcmpl-abc"})
    assert out == {"id": "chatcmpl-abc", "cancelled": True}
    assert engine.cancelled == ["chatcmpl-abc"]
    # Unknown ids answer honestly instead of 404-ing (cancel is idempotent).
    out = post(url + "/api/v1/cancel", {"request_id": "nope"})
    assert out == {"id": "nope", "cancelled": False}


def test_cancel_route_requires_id_and_engine(stub_server, server):
    url, _ = stub_server
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(url + "/api/v1/cancel", {})
    assert ei.value.code == 400
    # The serialized (no-engine) server refuses with a clear message.
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(server + "/api/v1/cancel", {"id": "chatcmpl-abc"})
    assert ei.value.code == 400
    assert "engine" in json.loads(ei.value.read())["error"]


def test_shed_maps_to_503_with_retry_after(stub_server):
    url, engine = stub_server
    engine.overloaded = True
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(url + CHAT_ROUTE, {"messages": [{"role": "user", "content": "x"}]})
    assert ei.value.code == 503
    assert ei.value.headers["Retry-After"] == "2"
    assert "overloaded" in json.loads(ei.value.read())["error"]


def test_priority_field_reaches_engine_and_validates(stub_server):
    """The ``priority`` request field threads into engine.submit; values
    outside 0/1/2 are a 400 BEFORE the engine sees anything."""
    url, engine = stub_server
    engine.overloaded = True  # refusal path: submit records then raises
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(
            url + CHAT_ROUTE,
            {"messages": [{"role": "user", "content": "x"}], "priority": 0},
        )
    assert ei.value.code == 503
    assert engine.priorities == [0]
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(
            url + CHAT_ROUTE,
            {"messages": [{"role": "user", "content": "x"}], "priority": 7},
        )
    assert ei.value.code == 400
    assert "priority" in json.loads(ei.value.read())["error"]
    assert engine.priorities == [0]  # the bad request never reached submit


def post_h(url, body, headers=None):
    req = urllib.request.Request(
        url,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    return json.loads(urllib.request.urlopen(req, timeout=120).read())


def test_quota_maps_to_429_with_retry_after(stub_server):
    """Per-tenant quota refusal is a 429 (caller over budget, Retry-After
    from their own bucket) — deliberately distinct from the 503 shed."""
    url, engine = stub_server
    engine.over_quota = True
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(url + CHAT_ROUTE, {"messages": [{"role": "user", "content": "x"}]})
    assert ei.value.code == 429
    assert ei.value.headers["Retry-After"] == "3"  # ceil(2.4)
    assert "token rate" in json.loads(ei.value.read())["error"]


def test_tenant_field_and_header_reach_engine(stub_server):
    """The explicit body field wins over X-Cake-Tenant; the header is the
    fallback; whitespace-only fields are a 400."""
    url, engine = stub_server
    engine.overloaded = True  # refusal path: submit records then raises
    msgs = {"messages": [{"role": "user", "content": "x"}]}
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_h(
            url + CHAT_ROUTE, dict(msgs, tenant="alice"),
            headers={"X-Cake-Tenant": "bob"},
        )
    assert ei.value.code == 503
    with pytest.raises(urllib.error.HTTPError):
        post_h(url + CHAT_ROUTE, msgs, headers={"X-Cake-Tenant": "bob"})
    with pytest.raises(urllib.error.HTTPError):
        post_h(url + CHAT_ROUTE, msgs)
    assert engine.tenants == ["alice", "bob", None]
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_h(url + CHAT_ROUTE, dict(msgs, tenant="   "))
    assert ei.value.code == 400
    assert engine.tenants == ["alice", "bob", None]  # 400 before submit


def test_deadline_field_reaches_engine_and_validates(stub_server):
    url, engine = stub_server
    engine.overloaded = True
    msgs = {"messages": [{"role": "user", "content": "x"}]}
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_h(url + CHAT_ROUTE, dict(msgs, deadline_s=2.5))
    assert ei.value.code == 503
    assert engine.deadlines == [2.5]
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_h(url + CHAT_ROUTE, dict(msgs, deadline_s=0))
    assert ei.value.code == 400
    assert "deadline_s" in json.loads(ei.value.read())["error"]
    assert engine.deadlines == [2.5]  # the bad one never reached submit


def test_stats_exposes_tenants_block(stub_server):
    url, _ = stub_server
    body = json.loads(
        urllib.request.urlopen(url + "/stats", timeout=30).read()
    )
    assert body["tenants"] == {
        "abuser": {"active_streams": 1, "quota_refusals": 2}
    }


def test_oversized_tenant_id_is_400(stub_server):
    from cake_tpu.runtime.api import MAX_TENANT_ID_LEN

    url, engine = stub_server
    engine.overloaded = True
    n0 = len(engine.tenants)
    msgs = {"messages": [{"role": "user", "content": "x"}]}
    with pytest.raises(urllib.error.HTTPError) as ei:
        post_h(
            url + CHAT_ROUTE, msgs,
            headers={"X-Cake-Tenant": "t" * (MAX_TENANT_ID_LEN + 1)},
        )
    assert ei.value.code == 400
    assert len(engine.tenants) == n0  # never reached submit


def _sse_events(raw: str) -> list[dict]:
    return [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ") and line != "data: [DONE]"
    ]


def test_stream_include_usage_final_chunk(server):
    """stream_options {"include_usage": true}: one usage chunk with empty
    choices between the finish chunk and [DONE], counts matching the
    non-streaming response for the same prompt."""
    body = {
        "messages": [{"role": "user", "content": "count me"}],
        "max_tokens": 4,
    }
    raw = post(
        server + CHAT_ROUTE,
        dict(body, stream=True, stream_options={"include_usage": True}),
        raw=True,
    ).decode()
    assert raw.rstrip().endswith("data: [DONE]")
    events = _sse_events(raw)
    usage_events = [e for e in events if e.get("usage")]
    assert len(usage_events) == 1
    last = events[-1]
    assert last is usage_events[0], "usage chunk must be the final chunk"
    assert last["choices"] == []
    assert last["object"] == "chat.completion.chunk"
    u = last["usage"]
    assert u["completion_tokens"] >= 1
    assert u["total_tokens"] == u["prompt_tokens"] + u["completion_tokens"]
    # The chunk before it carries the finish_reason as usual.
    assert events[-2]["choices"][0]["finish_reason"] in ("stop", "length")
    # Exact agreement with the non-streaming usage for the same prompt.
    full = post(server + CHAT_ROUTE, body)
    assert u == full["usage"]


def test_stream_without_include_usage_has_no_usage_chunk(server):
    for opts in ({}, {"stream_options": {"include_usage": False}},
                 {"stream_options": {}}):
        raw = post(
            server + CHAT_ROUTE,
            {
                "messages": [{"role": "user", "content": "no usage"}],
                "stream": True, "max_tokens": 3, **opts,
            },
            raw=True,
        ).decode()
        events = _sse_events(raw)
        assert not any(e.get("usage") for e in events)
        assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")


def test_stream_options_must_be_an_object(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        post(
            server + CHAT_ROUTE,
            {
                "messages": [{"role": "user", "content": "x"}],
                "stream": True, "stream_options": ["include_usage"],
            },
        )
    assert ei.value.code == 400
    assert "stream_options" in json.loads(ei.value.read())["error"]


def test_requests_and_timeseries_routes_gate_on_engine(server, stub_server):
    """/requests and /timeseries 404 cleanly without an engine-side ring
    (the serialized server, or an engine predating the request log), and
    serve the filtered ring when one is attached."""
    from cake_tpu.obs.requestlog import RequestLog
    from cake_tpu.obs.timeseries import SliTimeseries

    for base in (server, stub_server[0]):
        for route in ("/requests", "/timeseries"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + route, timeout=30)
            assert ei.value.code == 404

    url, engine = stub_server
    engine.requestlog = RequestLog()
    engine.timeseries = SliTimeseries()
    engine.requestlog.record(
        request_id="r1", tenant="alice", finish_reason="stop",
        prompt_tokens=9,
    )
    engine.requestlog.record(
        request_id="r2", tenant="bob", finish_reason="quota",
    )
    engine.timeseries.observe_tokens(3)
    engine.timeseries.observe_finish("stop")

    body = json.loads(
        urllib.request.urlopen(url + "/requests", timeout=30).read()
    )
    assert body["count"] == 2 and body["last_seq"] == 2
    assert [r["request_id"] for r in body["requests"]] == ["r1", "r2"]
    body = json.loads(
        urllib.request.urlopen(
            url + "/requests?tenant=bob&finish=quota&since=1&limit=5",
            timeout=30,
        ).read()
    )
    assert [r["request_id"] for r in body["requests"]] == ["r2"]
    ts = json.loads(
        urllib.request.urlopen(url + "/timeseries", timeout=30).read()
    )
    assert ts["points"] and ts["points"][-1]["finished"] == 1


# ------------------------------------------------------------ POST /profile


@pytest.fixture()
def profiled_server(tmp_path):
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(5), jnp.float32)
    step = LocalForwardStep(cfg, params, max_seq_len=64, cache_dtype=jnp.float32)
    gen = LlamaGenerator(
        cfg, step, ByteTokenizer(),
        SamplingConfig(temperature=0.0, repeat_penalty=1.0),
    )
    api = ApiServer(gen, model_name="tiny-test", profile_dir=str(tmp_path))
    httpd = api.make_server("127.0.0.1", 0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}", tmp_path
    httpd.shutdown()


def _post_profile(url, query):
    req = urllib.request.Request(f"{url}/profile{query}", data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_profile_route_writes_one_window(profiled_server, server):
    url, trace_dir = profiled_server
    status, out = _post_profile(url, "?seconds=0.2")
    assert status == 200 and out["seconds"] >= 0.2
    assert out["path"].endswith(".xplane.pb")
    assert out["path"].startswith(str(trace_dir))
    assert _post_profile(url, "?seconds=31")[0] == 400
    assert _post_profile(url, "")[0] == 400
    assert _post_profile(server, "?seconds=1")[0] == 404  # no --trace-dir


def test_profile_route_answers_409_while_a_window_is_open(profiled_server):
    url, _ = profiled_server
    first: list = []
    t = threading.Thread(
        target=lambda: first.append(_post_profile(url, "?seconds=2")[0])
    )
    t.start()
    time.sleep(0.5)  # the first request is in: its window is open
    second, _ = _post_profile(url, "?seconds=1")
    t.join(60)
    assert not t.is_alive()
    # two overlapping requests: one records, the other is refused (which of
    # them arrived first is the machine's business)
    assert sorted([first[0], second]) == [200, 409]
    assert _post_profile(url, "?seconds=0.05")[0] == 200  # and it is free again
