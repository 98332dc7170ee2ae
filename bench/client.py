"""The load generator's client: streaming chat completions over HTTP, timed
at the client on one clock (``time.perf_counter``).

What ``cake_tpu/loadgen/client.py`` got wrong for a benchmark is put right
here: time to first token counts from when the request was *due*, not from
when it was sent, so a stall that delays later sends is charged to them;
every arrival time of every token is kept, not one mean gap a request; and
how late each send was is kept too, so a starved generator is not read as a
fast server. Stdlib only; this process never imports JAX.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import socket
import threading
import time
from collections.abc import Iterator
from urllib.parse import urlparse

from bench.tokens import Vocabulary
from bench.traffic import Request

CHAT_ROUTE = "/api/v1/chat/completions"


@dataclasses.dataclass
class Outcome:
    request: Request
    due: float  # perf_counter at which the request was due
    vocab: Vocabulary  # the cell's words and template
    sent: float = 0.0
    status: int = 0  # HTTP status; 0 = transport error or never answered
    finish: str | None = None
    request_id: str | None = None
    arrivals: list = dataclasses.field(default_factory=list)  # one per token
    text: str = ""
    usage: dict | None = None
    error: str | None = None
    done: bool = False
    ended: float = 0.0  # perf_counter when the stream ended or was cut

    def failure(self) -> str | None:
        """None for a request that was answered whole; else why it failed."""
        if not self.done:
            return "unfinished"
        if self.error:
            return f"transport: {self.error}"
        if self.status != 200:
            return f"status {self.status}"
        if self.finish not in ("stop", "length"):
            return f"finish_reason {self.finish!r}"
        want = len(self.vocab.chat_ids(self.request.prompt_ids))
        if not self.usage or self.usage.get("prompt_tokens") != want:
            return f"usage {self.usage} for a prompt of {want} tokens"
        # An end-of-sequence token is counted and not streamed.
        n, made = len(self.arrivals), self.usage.get("completion_tokens")
        if made not in (n, n + 1) or n == 0:
            return f"{n} tokens streamed, usage says {made}"
        return None

    def served_ids(self) -> list[int]:
        return self.vocab.ids_from_text(self.text)


class Load:
    """Runs requests against ``base_url`` from threads of this process and
    collects their outcomes. One thread a request in flight: each spends its
    life blocked on a socket."""

    def __init__(self, base_url: str, vocab: Vocabulary, timeout_s: float = 300.0):
        url = urlparse(base_url)
        self._vocab = vocab
        self._addr = (url.hostname, url.port)
        self._timeout = timeout_s
        self._lock = threading.Lock()
        self._open: dict[int, socket.socket] = {}
        self._threads: list[threading.Thread] = []
        self.outcomes: list[Outcome] = []
        self._cancelled = False

    # ------------------------------------------------------------ one request

    def _stream(self, out: Outcome, cut_after: int = 0) -> None:
        body = json.dumps({
            "model": "bench", "stream": True, "max_tokens": out.request.max_tokens,
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user",
                          "content": self._vocab.prompt_text(list(out.request.prompt_ids))}],
        })
        conn = http.client.HTTPConnection(*self._addr, timeout=self._timeout)
        try:
            conn.connect()
            with self._lock:
                if self._cancelled:
                    return
                self._open[id(out)] = conn.sock
            out.sent = time.perf_counter()
            conn.request("POST", CHAT_ROUTE, body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out.status = resp.status
            if resp.status != 200:
                out.error = resp.read(500).decode("utf-8", "replace")
                out.done = True
                return
            pieces = []
            for raw in resp:
                if not raw.startswith(b"data: "):
                    continue
                now = time.perf_counter()
                data = raw[6:].strip()
                if data == b"[DONE]":
                    out.done = True
                    break
                evt = json.loads(data)
                if "error" in evt and "choices" not in evt:
                    out.error, out.finish = str(evt["error"]), "error"
                    continue
                out.request_id = evt.get("id", out.request_id)
                if evt.get("usage"):
                    out.usage = evt["usage"]
                for choice in evt.get("choices", ()):
                    if choice.get("finish_reason"):
                        out.finish = choice["finish_reason"]
                    piece = choice.get("delta", {}).get("content")
                    if piece:
                        pieces.append(piece)
                        out.arrivals.append(now)
                if cut_after and len(out.arrivals) >= cut_after:
                    out.done, out.finish = True, "cut"
                    break
            out.text = "".join(pieces)
        except (OSError, ValueError, http.client.HTTPException) as e:
            if not self._cancelled:
                out.error, out.done = repr(e), True
        finally:
            out.ended = time.perf_counter()
            with self._lock:
                self._open.pop(id(out), None)
            conn.close()

    def _start(self, req: Request, due: float) -> Outcome:
        out = Outcome(req, due, self._vocab)
        th = threading.Thread(target=self._stream, args=(out,), daemon=True)
        with self._lock:
            self.outcomes.append(out)
            self._threads.append(th)
        th.start()
        return out

    # ------------------------------------------------------------------ loops

    def run_open(self, requests: list[Request], t0: float) -> None:
        """Send each request when it is due (``t0 + due_s``), whatever
        happened to the ones before. Returns after the last send."""
        for req in requests:
            due = t0 + req.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self._start(req, due)

    def run_closed(self, stream: Iterator[Request], clients: int, t_end: float,
                   min_send_gap_s: float = 0.0) -> None:
        """``clients`` callers, each sending its next request when its last
        one ended, until ``t_end``; what is in flight then is for ``finish``
        to drain or cut. A request is due when its caller is ready for it.
        Sends are ``min_send_gap_s`` apart at least, so that callers that are
        ready together reach the server in the stream's order and not in the
        order their threads happened to run."""
        gate = threading.Lock()
        last_send = [0.0]

        def caller() -> None:
            while time.perf_counter() < t_end and not self._cancelled:
                ready = time.perf_counter()
                with gate:
                    wait = last_send[0] + min_send_gap_s - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    out = Outcome(next(stream), ready, self._vocab)
                    last_send[0] = time.perf_counter()
                    with self._lock:
                        self.outcomes.append(out)
                self._stream(out)

        callers = [threading.Thread(target=caller, daemon=True) for _ in range(clients)]
        with self._lock:
            self._threads.extend(callers)
        for th in callers:
            th.start()
        time.sleep(max(0.0, t_end - time.perf_counter()))

    def run_each(self, requests: list[Request], cut_after: int = 0) -> list[Outcome]:
        """One at a time, each after the last has ended (warm-up, probes).
        With ``cut_after`` the client hangs up after that many tokens, which
        cancels the request: warm-up wants the programs, not the answers."""
        outs = []
        for req in requests:
            out = Outcome(req, time.perf_counter(), self._vocab)
            self._stream(out, cut_after)
            outs.append(out)
        return outs

    def finish(self, drain_s: float) -> float:
        """Wait up to ``drain_s`` for what is in flight, then cut the rest
        (they stay ``done = False``). Returns the seconds waited."""
        t0 = time.perf_counter()
        deadline = t0 + drain_s
        for th in list(self._threads):
            th.join(max(0.0, deadline - time.perf_counter()))
        waited = time.perf_counter() - t0
        with self._lock:
            self._cancelled = True
            socks = list(self._open.values())
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for th in list(self._threads):
            th.join(10.0)
        return waited
