"""Jit retrace/compile watchdog (cake_tpu/obs/jitwatch.py).

Pins the runtime complement of the static jit lints: tracked functions count
exactly one trace per signature, rebuilt wrappers recompiling an old
signature are flagged, the armed watchdog turns ANY steady-state trace into a
counter + flight event (+ a raise under CAKE_RETRACE_FATAL=1), and — the PR 4
promise, now a tier-1 invariant — steady-state paged lockstep decode performs
ZERO retraces after warmup, with page growth, release, and a same-shape
second request all hitting the compiled entry.
"""

import jax
import jax.numpy as jnp
import pytest

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.chat import Message
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.generator import SamplingConfig
from cake_tpu.models.llama.tokenizer import ByteTokenizer
from cake_tpu.obs import jitwatch
from cake_tpu.runtime.serving import BatchEngine, ServeConfig
from cake_tpu.utils import metrics

GREEDY = SamplingConfig(temperature=0.0, repeat_penalty=1.0)


def wait_epochs_closed(n: int, timeout: float = 10.0) -> None:
    """Block until n epoch spans have CLOSED on the timeline — i.e. the
    engine fully drained them. Submitting the steady-state request before
    the warm epoch exits would continuous-batching-JOIN it (a different,
    legitimately cold code path) instead of starting a same-shape epoch."""
    import time

    from cake_tpu.obs.timeline import timeline

    deadline = time.time() + timeout
    while time.time() < deadline:
        done = sum(1 for e in timeline.snapshot() if e["name"] == "epoch")
        if done >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"epoch {n} never closed")


def retrace_events():
    return [
        e for e in metrics.flight.snapshot() if e["event"] == "jit-retrace"
    ]


# ------------------------------------------------------------- tracked_jit


def test_one_trace_per_signature():
    f = jitwatch.tracked_jit(lambda x: x * 2, name="t.double")
    f(jnp.ones(3))
    f(jnp.ones(3))
    f(jnp.ones(3))
    assert jitwatch.watch.trace_count("t.double") == 1
    f(jnp.ones(5))  # new shape: a legitimate new compile, not a retrace
    assert jitwatch.watch.trace_count("t.double") == 2
    assert jitwatch.retrace_total() == 0
    assert (
        metrics.registry.counter("cake_jit_traces_total").value(fn="t.double")
        == 2
    )
    snap = jitwatch.snapshot()["t.double"]
    assert snap["traces"] == 2 and snap["retraces"] == 0
    assert snap["compile_s"] > 0  # the tracing calls were wall-timed


def test_rebuilt_wrapper_same_signature_is_a_retrace():
    """An evicted-and-rebuilt wrapper recompiling the SAME program is the
    waste the watchdog exists to surface (lru churn, jit-in-loop bugs)."""
    for _ in range(2):
        # The in-loop rebuild IS the defect under test (the runtime watchdog
        # catching what the static rule catches at review time).
        f = jitwatch.tracked_jit(  # cake-lint: disable=jit-in-hot-loop
            lambda x: x + 1, name="t.rebuilt"
        )
        f(jnp.ones(4))
    assert jitwatch.watch.trace_count("t.rebuilt") == 2
    assert jitwatch.retrace_total() == 1
    events = retrace_events()
    assert events and events[0]["fn"] == "t.rebuilt"
    assert events[0]["reason"] == "duplicate-signature"


def test_armed_watchdog_flags_any_trace_and_fatal_raises(monkeypatch):
    f = jitwatch.tracked_jit(lambda x: x - 1, name="t.armed")
    f(jnp.ones(2))  # warmup
    with jitwatch.expect_no_retrace():
        f(jnp.ones(2))  # cache hit: no trace, no complaint
        assert jitwatch.retrace_total() == 0
        f(jnp.ones(7))  # traces while armed -> retrace (non-fatal: counted)
        assert jitwatch.retrace_total() == 1
        assert retrace_events()[0]["reason"] == "armed"
        monkeypatch.setenv("CAKE_RETRACE_FATAL", "1")
        with pytest.raises(jitwatch.RetraceError):
            f(jnp.ones(9))
    assert not jitwatch.watch.armed  # context manager disarms


# ----------------------------------------------- paged decode: no retraces


def setup_engine(serve=None, **kw):
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(11), jnp.float32)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("cache_dtype", jnp.float32)
    serve = serve or ServeConfig(
        max_batch=4, decode_chunk_size=4, admission_window=0.03,
        kv_mode="paged", page_size=16,
    )
    eng = BatchEngine(cfg, params, ByteTokenizer(), serve=serve, **kw)
    eng.start()
    return eng


def test_paged_steady_state_zero_retraces_fatal(monkeypatch):
    """Tier-1 pin of the PR 4 claim: after one warmup request, a second
    same-shape request — prefill, decode chunks, page growth at boundaries,
    release on finish — performs ZERO jit traces, enforced in FATAL mode
    (any retrace raises inside the engine and fails the stream)."""
    eng = setup_engine()
    try:
        prompt = "steady state prompt!"
        # Warmup: compiles paged prefill + every decode-chunk variant this
        # shape sequence needs (24 tokens cross page boundaries of 16).
        h = eng.submit([Message.user(prompt)], 24, GREEDY)
        warm = [t.id for t in h.tokens()]
        assert len(warm) >= 1
        wait_epochs_closed(1)
        monkeypatch.setenv("CAKE_RETRACE_FATAL", "1")
        with jitwatch.expect_no_retrace():
            h2 = eng.submit([Message.user(prompt)], 24, GREEDY)
            again = [t.id for t in h2.tokens()]  # a raise lands here
        assert again == warm  # greedy, same seed: bit-identical
        assert jitwatch.retrace_total() == 0
        assert retrace_events() == []
    finally:
        monkeypatch.delenv("CAKE_RETRACE_FATAL", raising=False)
        eng.stop()


def test_paged_decode_block_table_growth_never_retraces(monkeypatch):
    """Direct backend-level pin: growing a lane's block table between decode
    chunks (the _extend_pages protocol) changes only the VALUES of a traced
    operand — same compiled entry, zero traces, fatal-armed."""
    from cake_tpu.runtime.batch_backend import PagedLocalBackend

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    params = M.init_params(cfg, jax.random.PRNGKey(12), jnp.float32)
    backend = PagedLocalBackend(
        cfg, params, max_seq_len=128, cache_dtype=jnp.float32, page_size=16,
    )
    kv = backend.init_kv(2)
    alloc = backend.allocator
    for lane in range(2):
        alloc.map_range(lane, 0, 16)
    b = 2
    tok = jnp.zeros((b,), jnp.int32)
    pads = jnp.zeros((b,), jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(b)])
    ring = jnp.full((b, 0), -1, jnp.int32)
    ring_idx = jnp.zeros((b,), jnp.int32)
    s = GREEDY

    toks, kv, keys, ring, ring_idx = backend.decode(
        kv, tok, 12, pads, keys, ring, ring_idx, 4, s
    )  # warmup compile
    monkeypatch.setenv("CAKE_RETRACE_FATAL", "1")
    try:
        with jitwatch.expect_no_retrace():
            slot = 16
            for _ in range(3):
                for lane in range(2):
                    alloc.map_range(lane, slot, slot + 4)  # page growth
                toks, kv, keys, ring, ring_idx = backend.decode(
                    kv, toks[:, -1], slot, pads, keys, ring, ring_idx, 4, s
                )
                slot += 4
            alloc.release(1)  # release mid-epoch: table row -> UNMAPPED
            for lane in (0,):
                alloc.map_range(lane, slot, slot + 4)
            backend.decode(
                kv, toks[:, -1], slot, pads, keys, ring, ring_idx, 4, s
            )
        assert jitwatch.retrace_total() == 0
    finally:
        monkeypatch.delenv("CAKE_RETRACE_FATAL", raising=False)


def test_forced_shape_change_counts_retrace_with_event():
    """The watchdog's positive case: a genuinely new shape after warmup is
    counted and lands a flight-recorder event (non-fatal mode degrades to
    telemetry, never to a failed request)."""
    eng = setup_engine()
    try:
        h = eng.submit([Message.user("short")], 6, GREEDY)
        assert len([t for t in h.tokens()]) >= 1
        wait_epochs_closed(1)
        with jitwatch.expect_no_retrace():
            # 4x longer prompt: a different prefill bucket MUST trace.
            h2 = eng.submit(
                [Message.user("a much longer prompt " * 8)], 6, GREEDY
            )
            out = [t for t in h2.tokens()]
        assert len(out) >= 1  # stream completed despite the flagged trace
        assert jitwatch.retrace_total() >= 1
        events = retrace_events()
        assert events and all(e["reason"] == "armed" for e in events)
        assert (
            metrics.registry.counter("cake_jit_retraces_total").value(
                fn=events[0]["fn"]
            )
            >= 1
        )
    finally:
        eng.stop()


# ------------------------------------------------------------- compile tap


def test_compile_listener_accumulates():
    assert jitwatch.install_compile_listener()  # idempotent
    assert jitwatch.install_compile_listener()
    n0, s0 = jitwatch.compile_totals()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(8)).block_until_ready()
    n1, s1 = jitwatch.compile_totals()
    assert n1 > n0 and s1 > s0


# ----------------------------------------------------------- program names


def test_module_is_named_for_the_family_and_the_label_keeps_its_key():
    """The XLA module of a tracked function carries the label's family and
    no key (a device trace tells programs apart by it); the key stays in the
    label and so in the metrics; ``module=`` overrides the default."""
    from cake_tpu.obs.timeline import timeline

    def run(x):
        return x * 2

    fn = jitwatch.tracked_jit(run, name="test.family[w=64,t=0.0]")
    named = jitwatch.tracked_jit(
        run, name="test.family2[w=64]", module="prefill_join_test"
    )
    x = jnp.ones((4, 3))
    assert "module @jit_test_family " in fn._jitted.lower(x).as_text()
    assert "module @jit_prefill_join_test " in named._jitted.lower(x).as_text()
    timeline.clear()
    fn(jnp.ones((5, 3)))
    assert fn._watch_name == "test.family[w=64,t=0.0]"
    assert jitwatch.watch.trace_count("test.family[w=64,t=0.0]") >= 1
    assert jitwatch.family("test.family[w=64,t=0.0]") == "test.family"
    # the call that traced left a ``compile`` span: label and argument shapes
    (span,) = [e for e in timeline.snapshot() if e["name"] == "compile"]
    assert span["args"] == {
        "fn": "test.family[w=64,t=0.0]", "shapes": ["float32[5, 3]"],
    }
    fn(jnp.ones((5, 3)))  # a cache hit compiles nothing and records nothing
    assert len([e for e in timeline.snapshot() if e["name"] == "compile"]) == 1


def test_compile_stats_by_family_and_untracked_sum_to_the_count():
    assert jitwatch.install_compile_listener()
    before = jitwatch.compile_stats()
    a = jitwatch.tracked_jit(lambda x: x + 1, name="test.stats[w=1]")
    b = jitwatch.tracked_jit(lambda x: x + 2, name="test.stats[w=2]")
    a(jnp.ones(7)).block_until_ready()
    b(jnp.ones(7)).block_until_ready()
    jax.jit(lambda x: x * 5 - 1)(jnp.ones(9)).block_until_ready()  # untracked
    after = jitwatch.compile_stats()
    fam = after["by_family"]["test.stats"]
    assert fam["count"] == 2 and fam["seconds"] > 0
    assert after["untracked"] + sum(
        f["count"] for f in after["by_family"].values()
    ) == after["count"]
    assert after["untracked"] > before["untracked"]
    assert after["stall_seconds"] >= before["stall_seconds"] + fam["seconds"] - 1e-6
