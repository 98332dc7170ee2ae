"""Loadgen request clients: the HTTP/SSE front door and the in-proc engine.

``HttpTarget`` is the real-world path: one ``chat()`` call issues one
streaming ``POST /api/v1/chat/completions`` with
``stream_options: {"include_usage": true}`` and measures CLIENT-side
SLIs off the SSE stream — TTFT at the first content chunk, TPOT from
inter-chunk gaps, exact token counts from the final usage chunk (content
chunks undercount: a token with empty text emits none). Refusals keep
the server's taxonomy: HTTP 429 = the caller's quota, HTTP 503 = load
shed; transport failures are status 0. Stdlib only — this class runs
from any machine with no jax installed.

``EngineTarget`` is the same interface over an in-process
``BatchEngine`` (the serving funnel without socket noise); it imports
engine types lazily so this
module stays importable jax-free.
"""

from __future__ import annotations

import dataclasses
import json
import time
import urllib.error
import urllib.request

CHAT_ROUTE = "/api/v1/chat/completions"


@dataclasses.dataclass
class Result:
    """One request's client-side record (the loadgen's measurement unit)."""

    tenant: str
    status: int                 # HTTP status; 0 = transport error
    prompt_units: int
    max_tokens: int
    t_offset: float = 0.0       # scheduled send offset (runner fills)
    finish_reason: str | None = None
    prompt_tokens: int = 0      # exact, from the usage chunk
    completion_tokens: int = 0
    ttft_s: float | None = None
    tpot_s: float | None = None
    wall_s: float = 0.0
    deadline_s: float | None = None
    retry_after_s: float | None = None
    error: str | None = None


class HttpTarget:
    """Streaming SSE client against a serving master's ``--api`` address."""

    def __init__(self, base_url: str, timeout_s: float = 60.0,
                 model: str = "loadgen"):
        self.base_url = base_url.rstrip("/")
        self.timeout_s = timeout_s
        self.model = model

    def chat(
        self,
        prompt: str,
        max_tokens: int,
        tenant: str | None = None,
        priority: int | None = None,
        deadline_s: float | None = None,
        prompt_units: int = 0,
    ) -> Result:
        res = Result(
            tenant=tenant or "default", status=0,
            prompt_units=prompt_units, max_tokens=max_tokens,
            deadline_s=deadline_s,
        )
        body: dict = {
            "model": self.model,
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens,
            "stream": True,
            "stream_options": {"include_usage": True},
        }
        if tenant is not None:
            body["tenant"] = tenant
        if priority is not None:
            body["priority"] = priority
        if deadline_s is not None:
            body["deadline_s"] = deadline_s
        req = urllib.request.Request(
            self.base_url + CHAT_ROUTE,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        t0 = time.perf_counter()
        t_first = t_last = None
        n_chunks = 0
        try:
            with urllib.request.urlopen(req, timeout=self.timeout_s) as r:
                res.status = r.status
                for raw in r:
                    line = raw.decode("utf-8", "replace").strip()
                    if not line.startswith("data: "):
                        continue
                    data = line[len("data: "):]
                    if data == "[DONE]":
                        break
                    try:
                        evt = json.loads(data)
                    except json.JSONDecodeError:
                        continue
                    if "error" in evt and "choices" not in evt:
                        res.error = str(evt["error"])
                        res.finish_reason = "error"
                        continue
                    usage = evt.get("usage")
                    if usage:  # the include_usage final chunk
                        res.prompt_tokens = int(
                            usage.get("prompt_tokens", 0)
                        )
                        res.completion_tokens = int(
                            usage.get("completion_tokens", 0)
                        )
                    for choice in evt.get("choices", []):
                        if choice.get("finish_reason"):
                            res.finish_reason = choice["finish_reason"]
                        if choice.get("delta", {}).get("content"):
                            now = time.perf_counter()
                            if t_first is None:
                                t_first = now
                            t_last = now
                            n_chunks += 1
        except urllib.error.HTTPError as e:
            # The refusal taxonomy: 429 = caller quota, 503 = load shed.
            res.status = e.code
            res.finish_reason = (
                "quota" if e.code == 429
                else "shed" if e.code == 503 else "error"
            )
            ra = e.headers.get("Retry-After") if e.headers else None
            try:
                res.retry_after_s = float(ra) if ra else None
            except ValueError:
                pass
            try:
                res.error = json.loads(e.read() or b"{}").get("error")
            except (OSError, json.JSONDecodeError):
                pass
        except (OSError, ValueError) as e:
            res.status = 0
            res.finish_reason = "error"
            res.error = str(e)
        res.wall_s = time.perf_counter() - t0
        if t_first is not None:
            res.ttft_s = t_first - t0
            # Inter-token gap from chunk times; the usage chunk's exact
            # completion count is the denominator when present (tokens
            # with empty text emit no content chunk).
            n = res.completion_tokens or n_chunks
            if n >= 2 and t_last is not None:
                res.tpot_s = (t_last - t_first) / (n - 1)
        return res

    def get(self, route: str) -> dict:
        """GET a JSON observability route (/requests, /timeseries, ...)."""
        with urllib.request.urlopen(
            self.base_url + route, timeout=self.timeout_s
        ) as r:
            return json.load(r)


class EngineTarget:
    """Same ``chat()`` interface over an in-process BatchEngine (no
    sockets, no server thread). Lazy engine imports keep
    the module stdlib-importable."""

    def __init__(self, engine):
        self.engine = engine

    def chat(
        self,
        prompt: str,
        max_tokens: int,
        tenant: str | None = None,
        priority: int | None = None,
        deadline_s: float | None = None,
        prompt_units: int = 0,
    ) -> Result:
        from cake_tpu.models.llama.chat import Message
        from cake_tpu.models.llama.generator import SamplingConfig
        from cake_tpu.runtime.admission import QuotaExceeded
        from cake_tpu.runtime.serving import EngineOverloaded

        res = Result(
            tenant=tenant or "default", status=0,
            prompt_units=prompt_units, max_tokens=max_tokens,
            deadline_s=deadline_s,
        )
        sampling = SamplingConfig(temperature=0.0, repeat_penalty=1.0)
        t0 = time.perf_counter()
        try:
            h = self.engine.submit(
                [Message.user(prompt)], max_tokens, sampling,
                priority=priority, tenant=tenant, deadline_s=deadline_s,
            )
        except QuotaExceeded as e:
            res.status, res.finish_reason = 429, "quota"
            res.retry_after_s = e.retry_after_s
            res.wall_s = time.perf_counter() - t0
            return res
        except EngineOverloaded as e:
            res.status, res.finish_reason = 503, "shed"
            res.retry_after_s = e.retry_after_s
            res.wall_s = time.perf_counter() - t0
            return res
        t_first = t_last = None
        for tok in h.tokens():
            now = time.perf_counter()
            if t_first is None:
                t_first = now
            t_last = now
        res.status = 200
        res.finish_reason = h.finish_reason
        res.prompt_tokens = h.prompt_tokens
        res.completion_tokens = h.completion_tokens
        res.wall_s = time.perf_counter() - t0
        if t_first is not None:
            res.ttft_s = t_first - t0
            if res.completion_tokens >= 2 and t_last is not None:
                res.tpot_s = (
                    (t_last - t_first) / (res.completion_tokens - 1)
                )
        return res
