"""Loadgen unit tests: arrival processes, workload shapes, the open-loop
runner/report, and capture->replay planning (cake_tpu/loadgen/*).

Everything here is stdlib-only and fast — no jax, no sockets: the
targets are fakes with the ``chat()`` interface. The live end-to-end
path (real --api master, real engine) is the ``make loadgen-smoke``
gate; the in-proc path is ``client.EngineTarget``.
"""

import random

import pytest

from cake_tpu.loadgen import replay as replay_mod
from cake_tpu.loadgen.arrivals import bursty, make_arrivals, poisson, take_until
from cake_tpu.loadgen.client import Result
from cake_tpu.loadgen.runner import Shot, build_report, run_shots
from cake_tpu.loadgen.workload import (
    PROMPT_UNIT,
    TenantSpec,
    make_dist,
    parse_tenants,
    pick_tenant,
    prompt_units,
    synth_prompt,
)


class TestArrivals:
    @pytest.mark.parametrize(
        "spec", ["poisson:20", "bursty:30,2,0.5,0.25", "ramp:5,40,2.0"]
    )
    def test_deterministic_and_monotonic(self, spec):
        a = take_until(make_arrivals(spec, random.Random(7)), 3.0)
        b = take_until(make_arrivals(spec, random.Random(7)), 3.0)
        assert a == b and a, f"{spec} must be seeded-reproducible"
        assert all(y > x for x, y in zip(a, a[1:])), "offsets must increase"
        assert all(0.0 <= t < 3.0 for t in a)

    def test_poisson_rate_is_roughly_right(self):
        n = len(take_until(poisson(50.0, random.Random(3)), 10.0))
        assert 350 < n < 650  # ~500 expected; wide seeded bounds

    def test_bursty_silent_off_phase_emits_nothing(self):
        # off_rate=0: every offset falls inside an ON phase. With mean
        # phases of 0.2s ON / 10s OFF over 3s, a leaked OFF arrival
        # would be near-certain to show as a huge count.
        train = take_until(bursty(100.0, 0.0, 0.2, 10.0, random.Random(5)), 3.0)
        assert 0 < len(train) < 100

    @pytest.mark.parametrize(
        "spec",
        ["poisson:", "poisson:1,2", "bursty:1,2,3", "drizzle:5",
         "poisson:abc"],
    )
    def test_bad_spec_shapes_raise_at_parse(self, spec):
        with pytest.raises(ValueError):
            make_arrivals(spec, random.Random(0))

    @pytest.mark.parametrize(
        "spec", ["poisson:0", "bursty:0,1,1,1", "ramp:0,0,1", "ramp:1,2,0"]
    )
    def test_bad_spec_values_raise_on_first_draw(self, spec):
        # The processes are lazy generators: value validation fires when
        # the train is first consumed, not at parse time.
        with pytest.raises(ValueError):
            take_until(make_arrivals(spec, random.Random(0)), 1.0)


class TestWorkload:
    def test_synth_prompt_roundtrip(self):
        for units in (1, 2, 7, 40):
            p = synth_prompt(units)
            assert p == PROMPT_UNIT * units
            assert prompt_units(p) == units
        assert synth_prompt(0) == PROMPT_UNIT  # floor at one unit

    def test_dists(self):
        rng = random.Random(11)
        assert make_dist("fixed:12", rng)() == 12
        uni = make_dist("uniform:3,9", rng)
        assert all(3 <= uni() <= 9 for _ in range(200))
        logn = make_dist("lognormal:2.0,0.8", rng)
        assert all(logn() >= 1 for _ in range(200))

    @pytest.mark.parametrize(
        "spec", ["fixed:", "uniform:9,3", "uniform:0,5", "zipf:2", "fixed:a"]
    )
    def test_bad_dists_raise(self, spec):
        with pytest.raises(ValueError):
            make_dist(spec, random.Random(0))

    def test_parse_tenants(self):
        assert parse_tenants("interactive:3@2,batch:1") == [
            TenantSpec("interactive", 3.0, 2),
            TenantSpec("batch", 1.0, None),
        ]

    @pytest.mark.parametrize(
        "spec", ["", "noweight", "t:0", "t:-1", "t:1@7", "t:x"]
    )
    def test_bad_tenants_raise(self, spec):
        with pytest.raises(ValueError):
            parse_tenants(spec)

    def test_pick_tenant_respects_weights(self):
        specs = parse_tenants("heavy:9,light:1")
        rng = random.Random(2)
        picks = [pick_tenant(specs, rng).name for _ in range(500)]
        assert 380 < picks.count("heavy") < 490


class _FakeTarget:
    """chat() that answers instantly from a scripted status map and an
    affine tokenizer (tokens = overhead + per_unit * units)."""

    def __init__(self, overhead=7, per_unit=3, status_for=None):
        self.overhead = overhead
        self.per_unit = per_unit
        self.status_for = status_for or {}
        self.calls: list = []

    def chat(self, prompt, max_tokens, tenant=None, priority=None,
             deadline_s=None, prompt_units=0):
        units = prompt_units or len(prompt) // len(PROMPT_UNIT)
        self.calls.append((units, max_tokens, tenant, priority))
        status = self.status_for.get(tenant, 200)
        res = Result(
            tenant=tenant or "default", status=status,
            prompt_units=units, max_tokens=max_tokens,
            deadline_s=deadline_s,
        )
        if status == 200:
            res.finish_reason = "length"
            res.prompt_tokens = self.overhead + self.per_unit * units
            res.completion_tokens = max_tokens
            res.ttft_s = 0.010 * units
            res.tpot_s = 0.002
        elif status == 429:
            res.finish_reason = "quota"
        elif status == 503:
            res.finish_reason = "shed"
        return res


class TestReplay:
    def test_calibrate_recovers_affine_map(self):
        overhead, per_unit = replay_mod.calibrate(_FakeTarget(7, 3))
        assert (overhead, per_unit) == (7.0, 3.0)
        for ptok in (10, 13, 40, 127):
            units = replay_mod.units_for_tokens(ptok, overhead, per_unit)
            assert 7 + 3 * units == ptok

    def test_calibrate_raises_on_failure_and_degeneracy(self):
        with pytest.raises(RuntimeError, match="probe"):
            replay_mod.calibrate(_FakeTarget(status_for={None: 503}))
        with pytest.raises(RuntimeError, match="degenerate"):
            replay_mod.calibrate(_FakeTarget(overhead=9, per_unit=0))

    def _trace(self):
        return [
            {"request_id": "a", "t_wall": 100.0, "tenant": "default",
             "prompt_tokens": 13, "max_tokens": 6, "finish_reason": "stop"},
            {"request_id": "b", "t_wall": 101.0, "tenant": "bob",
             "priority": 2, "prompt_tokens": 22, "max_tokens": 4,
             "deadline_s": 30.0, "finish_reason": "quota"},
            {"request_id": "c", "t_wall": 102.5, "tenant": "bob",
             "prompt_tokens": 16, "completion_tokens": 5,
             "finish_reason": "stop"},
        ]

    def test_plan_from_trace_preserves_everything(self):
        shots = replay_mod.plan_from_trace(
            self._trace(), speed=2.0, calibration=(7.0, 3.0)
        )
        # Gaps scaled by speed; t0 anchors at zero.
        assert [s.t_offset for s in shots] == [0.0, 0.5, 1.25]
        # prompt_tokens invert through the calibration: 13->2, 22->5, 16->3.
        assert [s.prompt_units for s in shots] == [2, 5, 3]
        assert [prompt_units(s.prompt) for s in shots] == [2, 5, 3]
        # "default" maps to no-tenant-field; identities otherwise kept —
        # the refused record ("b", a 429) is replayed too: a refusal is
        # part of the offered load.
        assert [s.tenant for s in shots] == [None, "bob", "bob"]
        assert [s.priority for s in shots] == [None, 2, None]
        assert [s.deadline_s for s in shots] == [None, 30.0, None]
        # max_tokens falls back to completion_tokens when unrecorded.
        assert [s.max_tokens for s in shots] == [6, 4, 5]

    def test_plan_without_calibration_uses_tokens_as_units(self):
        shots = replay_mod.plan_from_trace(self._trace())
        assert [s.prompt_units for s in shots] == [13, 22, 16]
        assert [s.t_offset for s in shots] == [0.0, 1.0, 2.5]

    def test_plan_validates_speed_and_empty(self):
        assert replay_mod.plan_from_trace([]) == []
        with pytest.raises(ValueError):
            replay_mod.plan_from_trace(self._trace(), speed=0.0)

    def test_trace_expectation(self):
        assert replay_mod.trace_expectation(self._trace()) == {
            "count": 3,
            "tenants": {"default": 1, "bob": 2},
            "prompt_tokens_total": 51,
        }


class TestRunnerAndReport:
    def test_run_shots_open_loop_results(self):
        target = _FakeTarget(status_for={"capped": 429})
        shots = [
            Shot(0.02, synth_prompt(2), 2, 4, tenant="capped"),
            Shot(0.0, synth_prompt(3), 3, 5, tenant="ok", deadline_s=9.0),
        ]
        results, duration, capped = run_shots(target, shots, max_inflight=4)
        assert capped == 0 and duration > 0
        # Results come back in schedule order (sorted by offset).
        assert [r.tenant for r in results] == ["ok", "capped"]
        assert [r.t_offset for r in results] == [0.0, 0.02]
        assert results[0].status == 200 and results[1].status == 429

    def test_run_shots_survives_a_raising_target(self):
        class _Boom:
            def chat(self, *a, **k):
                raise ConnectionError("nope")

        (res,), _, _ = run_shots(
            _Boom(), [Shot(0.0, synth_prompt(1), 1, 2)], max_inflight=2
        )
        assert res.status == 0 and res.finish_reason == "error"
        assert "ConnectionError" in res.error

    def test_build_report_shape(self):
        target = _FakeTarget(status_for={"abuser": 429, "shed": 503})
        shots = (
            [Shot(0.0, synth_prompt(2), 2, 4, tenant="good",
                  deadline_s=9.0)] * 2
            + [Shot(0.0, synth_prompt(2), 2, 4, tenant="abuser")]
            + [Shot(0.0, synth_prompt(2), 2, 4, tenant="shed")]
        )
        results, duration, capped = run_shots(target, shots, max_inflight=8)
        report = build_report(results, duration, inflight_capped=capped)
        assert report["n_requests"] == 4 and report["n_ok"] == 2
        assert report["n_quota_429"] == 1 and report["n_shed_503"] == 1
        assert report["refusal_429_frac"] == 0.25
        assert report["refusal_503_frac"] == 0.25
        assert report["n_errors"] == 0
        assert report["deadline_met_frac"] == 1.0
        assert report["ttft_p99_ms"] == 20.0    # 0.010 * 2 units
        assert report["tpot_mean_ms"] == 2.0
        assert report["prompt_tokens_total"] == 2 * (7 + 3 * 2)
        assert report["completion_tokens_total"] == 8
        assert report["inflight_capped"] == 0
        assert report["tenants"]["good"] == {
            "n": 2, "ok": 2, "quota_429": 0, "shed_503": 0,
            "prompt_tokens": 26, "completion_tokens": 8,
        }
        assert report["tenants"]["abuser"]["quota_429"] == 1

    def test_build_report_empty_run(self):
        report = build_report([], 0.0)
        assert report["n_requests"] == 0
        assert report["refusal_429_frac"] == 0.0
        assert report["goodput_tok_s"] == 0.0
        assert report["deadline_met_frac"] is None
        assert report["tpot_mean_ms"] is None
