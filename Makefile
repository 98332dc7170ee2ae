# Developer entry points (role of the reference's Makefile, minus its
# machine-specific rsync deploy helpers).

# verify needs bash for PIPESTATUS (the tier-1 command reports pytest's rc
# through the tee pipe).
SHELL := /bin/bash

PY ?= python

.PHONY: all native test test-fast verify lint lint-ci trace-smoke chaos-smoke obs-smoke loadgen-smoke clean

all: native

native:
	$(PY) -m cake_tpu.native.build

test: native
	$(PY) -m pytest tests/ -x -q

test-fast:
	$(PY) -m pytest tests/ -x -q -m "not slow"

# Static analysis: ruff (if installed) as an advisory general-Python layer,
# then cake-tpu lint (cake_tpu/analysis) as the gating JAX-aware layer — the
# rules that know about jit boundaries, donation, lock discipline, and the
# proto.py frame contract. Ruff findings print but do not gate: the [tool.ruff]
# baseline in pyproject.toml is maintained best-effort on machines that have it.
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check cake_tpu tests || echo "ruff: advisory findings above (not gating)"; \
	else \
		echo "ruff not installed; skipping the advisory layer"; \
	fi
	$(PY) -m cake_tpu.analysis cake_tpu tests

# CI variant: ::error/::warning workflow-command annotations that GitHub
# renders inline on the PR diff. Strict (warnings gate) — CI is where the
# warn-severity drift rules earn their keep. The full registry runs here,
# lockorder pack included (lock-order-cycle, blocking-call-under-lock,
# callback-under-lock, notify-outside-lock annotate PR diffs like any
# other rule), and the lock-graph cycle gate runs after it so an ABBA
# inversion fails CI even if its acquire sites are baselined/suppressed.
lint-ci:
	$(PY) -m cake_tpu.analysis cake_tpu tests --format sarif > cake-lint.sarif || true
	$(PY) -m cake_tpu.analysis cake_tpu tests --strict --format github
	$(PY) -m cake_tpu.cli locks cake_tpu --check
	$(PY) -m cake_tpu.cli resources cake_tpu --check

# The exact tier-1 command from ROADMAP.md: full suite, no -x (test/test-fast
# stop at the first failure, which hides the real pass count), collection
# errors tolerated, and a DOTS_PASSED count echoed from the teed log.
# The lint step GATES since PR 3 (the ROADMAP PR 2 convention: every
# subsystem invariant is a rule, and the tree stays rule-clean).
# Timeline-export smoke gate: a 2-stream local serve (tiny random weights,
# CPU) with --trace-jsonl streaming, then the export is rendered and pushed
# through the trace-event schema checker (cake_tpu/obs/timeline.py). Exits
# nonzero on malformed output — the Perfetto contract gates like a test.
trace-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke --paged-pallas
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke --fused-pallas

# Chaos gate: a seeded fault plan kills a REAL TCP worker mid-decode
# (runtime/chaos_smoke.py). Exits nonzero unless the co-batched survivor is
# bit-identical to a fault-free run, the victim finishes "error" cleanly,
# and the engine keeps serving — the failure semantics gate like a test.
# Also gates replica failover, the shared-prefix crash, and the
# overload-storm A/B (fair queue isolates a compliant tenant; the FIFO
# baseline demonstrably starves it; quotas 429; deadline-doomed requests
# never run; the pool drains).
chaos-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.runtime.chaos_smoke

# Cluster observability gate: a REAL 2-process (master + TCP worker) serve
# (cake_tpu/obs/cluster_smoke.py). Exits nonzero unless ONE merged /metrics
# carries both nodes' series under node labels, ONE merged Perfetto export
# passes validate_export with worker op spans nested inside the master's
# wire.<node> spans and cross-process flow arrows, /slo attributes a
# nonzero burn rate to the offending tenant only, GET /explain decomposes
# the long stream's latency into phases summing to its measured wall, a
# seeded stall@backend.decode yields exactly one blackbox bundle that
# `cake-tpu doctor` attributes to `stall`, and GET /efficiency accounts
# >= 95% of the device wall into goodput buckets with node-labelled
# cake_device_seconds_total in the federated view and `cake-tpu top
# --once` rendering against the live server.
obs-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.cluster_smoke

# Traffic-observatory gate: a REAL --api master (tiny model, CPU) with a
# --request-log sink, hit by the open-loop loadgen (cake_tpu/loadgen).
# Exits nonzero unless the client-measured p99 TTFT agrees with the
# server's request-log attribution within tolerance, replaying the run's
# own capture reproduces count / tenant mix / prompt-token totals
# exactly, and /requests + /timeseries + `top --once` sparklines +
# `cake-tpu requests` are all live (cake_tpu/loadgen/smoke.py).
loadgen-smoke:
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.loadgen.smoke

verify:
	$(PY) -m cake_tpu.analysis cake_tpu --strict --quiet
	$(PY) -m cake_tpu.cli locks cake_tpu --check
	$(PY) -m cake_tpu.cli resources cake_tpu --check
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke --paged-pallas
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.trace_smoke --fused-pallas
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.runtime.chaos_smoke
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.obs.cluster_smoke
	env JAX_PLATFORMS=cpu $(PY) -m cake_tpu.loadgen.smoke
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

clean:
	rm -f cake_tpu/native/libcakecodec.so cake_tpu/native/libcakeembed.so
	find . -name __pycache__ -type d -exec rm -rf {} +
