"""The client against a scripted SSE server: its clock, its counts, its cut."""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from bench import stats
from bench.client import Load
from bench.traffic import Request

from conftest import TINY_MODEL, vocabulary

VOCAB = vocabulary(TINY_MODEL)
word, TEMPLATE_TOKENS = VOCAB.word, len(VOCAB.chat_ids([]))


class Scripted(BaseHTTPRequestHandler):
    """Streams ``max_tokens`` words, ``GAP`` apart after ``FIRST`` seconds;
    a prompt that starts with w13 is refused, w14 never finishes."""

    FIRST, GAP = 0.15, 0.03

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        words = body["messages"][0]["content"].split()
        if words[0] == "w13":
            self.send_response(503)
            self.end_headers()
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(obj):
            data = f"data: {obj if isinstance(obj, str) else json.dumps(obj)}\n\n".encode()
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
            self.wfile.flush()

        try:
            chunk({"id": "r1", "choices": [{"delta": {"role": "assistant", "content": ""}}]})
            time.sleep(self.FIRST)
            for i in range(body["max_tokens"]):
                text = word(100 + i) if i == 0 else " " + word(100 + i)
                chunk({"id": "r1", "choices": [{"delta": {"content": text}, "finish_reason": None}]})
                time.sleep(self.GAP)
            if words[0] == "w14":
                time.sleep(30)
            chunk({"id": "r1", "choices": [{"delta": {}, "finish_reason": "length"}]})
            chunk({"id": "r1", "choices": [], "usage": {
                "prompt_tokens": len(words) + TEMPLATE_TOKENS,
                "completion_tokens": body["max_tokens"]}})
            chunk("[DONE]")
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            self.server.hung_up.append(words[0])


@pytest.fixture
def base():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), Scripted)
    srv.hung_up = []
    srv.daemon_threads = True
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", srv
    srv.shutdown()
    srv.server_close()


def req(i, due, first_word, n_new=4):
    return Request(i, due, (first_word, 6, 7), n_new)


def test_open_loop_times_from_due_and_keeps_every_gap(base):
    url, _ = base
    load = Load(url, VOCAB)
    t0 = time.perf_counter()
    load.run_open([req(0, 0.0, 20), req(1, 0.2, 21), req(2, 0.25, 13)], t0)
    assert load.finish(drain_s=5.0) < 5.0
    ok0, ok1, refused = load.outcomes
    assert ok0.failure() is None and ok1.failure() is None
    assert refused.failure() == "status 503"
    assert ok0.served_ids() == [100, 101, 102, 103]
    assert ok1.due == pytest.approx(t0 + 0.2)
    assert 0 <= ok1.sent - ok1.due < 0.1          # lateness is kept, and small
    assert ok1.arrivals[0] - ok1.due >= Scripted.FIRST
    e = stats.end_to_end(load.outcomes, t0, 1.0, "open")
    assert e["attempted"] == 3 and e["failed"] == 1
    assert e["samples"] == {"requests": 3, "ttft": 2, "gaps": 6, "tokens": 8}
    assert e["values"]["gap_p95_ms"] == pytest.approx(30, abs=25)
    assert e["values"]["tokens_per_s"] == 8.0


def test_what_is_not_finished_by_the_drains_end_is_cut_and_failed(base):
    url, srv = base
    load = Load(url, VOCAB)
    t0 = time.perf_counter()
    load.run_open([req(0, 0.0, 14), req(1, 0.0, 22)], t0)
    waited = load.finish(drain_s=1.0)
    assert 0.9 < waited < 3.0
    stuck, ok = load.outcomes
    assert ok.failure() is None
    assert stuck.failure() == "unfinished" and len(stuck.arrivals) == 4


def test_closed_loop_sends_the_next_when_the_last_ends(base):
    url, _ = base
    load = Load(url, VOCAB)
    stream = (req(i, 0.0, 30 + i, n_new=2) for i in range(1000))
    t0 = time.perf_counter() + 0.3                # the loop leads in for 0.3 s
    load.run_closed(stream, clients=2, t_end=t0 + 0.9, min_send_gap_s=0.05)
    assert load.finish(drain_s=0.0) < 0.5         # what is in flight is cut
    outs = load.outcomes
    assert 6 <= len(outs) <= 14                   # 2 callers, about 0.21 s a request
    assert all(o.failure() is None for o in outs[:-2])
    assert sum(not o.done for o in outs) <= 2
    assert all(abs(o.sent - o.due) < 0.1 for o in outs)  # due when its caller is ready
    sent = [o.sent for o in outs]
    assert sent == sorted(sent) and sent[1] - sent[0] >= 0.04  # in order, spaced
    assert [o.request.index for o in outs] == list(range(len(outs)))
    e = stats.end_to_end(outs, t0, 0.9, "closed")
    assert e["failed"] == 0 and 3 <= e["attempted"] < len(outs)  # some ended in the lead-in
    assert all(t0 <= o.ended < t0 + 0.9 for o in e["counted"])


def test_warm_up_hangs_up_after_the_tokens_it_wants(base):
    url, srv = base
    out, = Load(url, VOCAB).run_each([req(0, 0.0, 40, n_new=50)], cut_after=3)
    assert len(out.arrivals) == 3 and out.finish == "cut" and out.status == 200
    deadline = time.time() + 5
    while not srv.hung_up and time.time() < deadline:
        time.sleep(0.05)
    assert srv.hung_up == ["w40"]                 # the server saw the client go
