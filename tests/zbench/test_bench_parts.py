"""``bench/parts.py``: a served program's device time by PART, and the nine
per-layer metrics that read it.

Two recorded traces of a v5e hold the reader to its arithmetic. The first is
PR 34's (``v5e_scope_probe.xplane.pb``: a program whose products run under
the scope ``gated_delta_rule``): over the vocabulary ``("gated_delta_rule",)``
the walk here gives what that PR's own ``scoped()`` gives, list for list, so
the two readers cannot drift. The second (``v5e_parts_probe.xplane.pb``: a
tiny Olmo-Hybrid-like model of two delta-rule layers and one of attention,
its decode chunk of two steps three times and one join between them; the
device plane alone, cut to what the readers read: the lines ``XLA Modules``
and ``XLA Ops``, an event's metadata id, offset and duration, a metadata's id,
its name (an operation's cut to 64 characters) and its ``tf_op``; the cut
file reads list for list as the whole one did) pins each part's own time and
the nine readers' milliseconds a whole run. At that size the unscoped share
is the compiler's own copies and the loops' bookkeeping, a third of a chunk;
at a cell's size it is 0.3% (PERF.md section 6). The nine entries of ``BENCHMARK.json`` are
checked as data. Nothing here starts a server.
"""

from __future__ import annotations

import importlib.util
import json
import shutil

import pytest

from bench import parts, readers, xplane
from bench.manifest import Manifest

from conftest import REPO

SCOPE_PROBE = REPO / "bench/testdata/v5e_scope_probe.xplane.pb"
PARTS_PROBE = REPO / "bench/testdata/v5e_parts_probe.xplane.pb"
DECODE, JOIN = "^jit_decode_chunk", "^jit_prefill_join"
CELLS = ["jamba2-3b-chat-closed", "pangu-ultra-ep16-chat-closed", "olmo-hybrid-7b-chat-closed"]
# metric: (module, parts, unit, layer)
NINE = {
    "decode_projections_dev_ms": (DECODE, ["mixer_in", "mixer_out"], "ms", "backend and model step"),
    "decode_cache_write_dev_ms": (DECODE, ["cache_write"], "ms", "backend and model step"),
    "decode_mixer_dev_ms": (DECODE, ["mixer"], "ms", "kernels"),
    "decode_feed_forward_dev_ms": (DECODE, ["feed_forward"], "ms", "backend and model step"),
    "decode_head_dev_ms": (DECODE, ["head"], "ms", "backend and model step"),
    "decode_sample_dev_ms": (DECODE, ["sample"], "ms", "backend and model step"),
    "decode_unscoped_pct": (DECODE, None, "%", "backend and model step"),
    "join_cache_write_dev_ms": (JOIN, ["cache_write"], "ms", "backend and model step"),
    "join_mixer_dev_ms": (JOIN, ["mixer"], "ms", "kernels"),
}
# The recorded probe, microseconds: the one whole decode chunk (the window's
# edges cut the other two) and the one join, own time by part.
PROBE = {
    DECODE: {"runs": 1, "program": 61.725, "own": {
        "": 19.436, "embed": 0.521, "mixer_in": 8.17, "cache_write": 9.42, "mixer": 13.958,
        "mixer_out": 4.127, "feed_forward": 3.573, "head": 1.905, "sample": 0.237}},
    JOIN: {"runs": 1, "program": 111.234, "own": {
        "": 14.193, "embed": 0.302, "mixer_in": 6.465, "cache_write": 43.891, "mixer": 39.469,
        "mixer_out": 1.415, "feed_forward": 1.832, "head": 1.37}},
}


@pytest.fixture
def traces(tmp_path, monkeypatch):
    """An empty trace directory where the readers look; ``hold(file)`` puts a
    recorded trace there."""
    held = tmp_path / "plugins" / "profile" / "run"
    held.mkdir(parents=True)
    monkeypatch.setattr(parts, "TRACES", tmp_path)

    def hold(probe):
        shutil.copy(probe, held / "host.xplane.pb")
        return str(held / "host.xplane.pb")

    return hold


def _facts(traced=True):
    """What a reader here looks at of a run's facts: whether it was traced."""
    return {"trace": {"programs": {}} if traced else None}


def test_the_two_vocabularies_are_equal():
    from cake_tpu.obs.taxonomy import PROGRAM_PARTS

    assert parts.PARTS == PROGRAM_PARTS and len(set(parts.PARTS)) == 8


def test_the_walk_reads_what_pr_34s_reader_reads():
    path = REPO / "bench/layer_metrics/delta_rule_prefill_roofline_pct.py"
    spec = importlib.util.spec_from_file_location("scoped_reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    scope = "gated_delta_rule"
    mine = parts.labelled(str(SCOPE_PROBE), (scope,))
    assert mine == module.scoped(str(SCOPE_PROBE), scope)
    ops = mine["/device:TPU:0"]
    assert len(ops[xplane.MODULES]) == 3 and len(ops[xplane.OPS]) == 105
    inside = xplane.op_times(mine, {"op": f"^{scope}$", "module": "^jit_f"})
    every = xplane.op_times(mine, {"op": "", "module": "^jit_f"})
    assert (inside["count"], every["count"]) == (27, 35)
    # (PR 34's comment and ISSUE 37 say 25.089 of 26.202: the file says these)
    assert inside["seconds"] * 1e6 == pytest.approx(25.098, abs=1e-3)
    assert every["seconds"] * 1e6 == pytest.approx(26.210, abs=1e-3)
    # through this module's own arithmetic and vocabulary: the one whole run, no part
    got = parts.part_seconds(str(SCOPE_PROBE), "^jit_f")
    assert got["runs"] == 1 and set(got["own_s"]) == {""}
    assert got["own_s"][""] * 1e6 == pytest.approx(26.210, abs=1e-3)
    assert got["program_s"] * 1e6 == pytest.approx(26.231, abs=1e-3)


@pytest.mark.parametrize("module", [DECODE, JOIN])
def test_each_parts_own_time_in_the_recorded_probe(module):
    got = parts.part_seconds(str(PARTS_PROBE), module)
    want = PROBE[module]
    assert got["runs"] == want["runs"]
    assert got["program_s"] * 1e6 == pytest.approx(want["program"], abs=1e-3)
    assert {k: round(v * 1e6, 3) for k, v in got["own_s"].items()} == want["own"]
    assert sum(got["own_s"].values()) == pytest.approx(got["program_s"], rel=0.03)
    assert set(got["own_s"]) <= {"", *parts.PARTS}


@pytest.mark.parametrize("name", NINE)
def test_a_reader_gives_a_whole_runs_milliseconds(name, traces):
    module, which, _, _ = NINE[name]
    facts = _facts()
    assert readers.read_metric(REPO, name, facts) is None  # no trace file
    traces(PARTS_PROBE)
    want = PROBE[module]
    if which is None:
        expected = 100.0 * want["own"][""] / want["program"]
    else:
        expected = sum(want["own"].get(p, 0.0) for p in which) / want["runs"] / 1e3
    assert expected > 0
    assert readers.read_metric(REPO, name, facts) == pytest.approx(expected, rel=3e-3)
    assert readers.read_metric(REPO, name, _facts(traced=False)) is None  # an untraced run
    traces(SCOPE_PROBE)  # no whole run of the module, and a program without the parts
    assert readers.read_metric(REPO, name, facts) is None


def test_a_program_without_the_scopes_gives_nothing(traces):
    """The parent of the PR that brought the scopes: whole runs, no part."""
    traces(SCOPE_PROBE)
    spec = {"pattern": {"module": "^jit_f"}, "parts": ["mixer"]}
    assert parts.dispatch_ms(_facts(), spec) is None
    assert parts.unscoped_pct(_facts(), spec) is None


def test_nine_metrics_share_one_walk(traces):
    traces(PARTS_PROBE)
    parts._walk.cache_clear()
    parts._part_seconds.cache_clear()
    values = [readers.read_metric(REPO, name, _facts()) for name in NINE]
    assert all(v is not None for v in values)
    assert parts._walk.cache_info().misses == 1
    assert parts._part_seconds.cache_info().misses == 2  # the decode chunk's, the join's


def test_the_nine_entries_are_data():
    with open(REPO / "BENCHMARK.json") as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    # after PR 34's three, in the table's order; what a later PR appends is its own
    first = names.index("delta_rule_prefill_roofline_pct") + 1
    assert names[first:first + 9] == list(NINE)
    for entry in bench["per_layer"][first:first + 9]:
        module, which, unit, layer = NINE[entry["name"]]
        assert entry == {
            "name": entry["name"], "unit": unit, "better": "lower", "source": "device_trace",
            "layer": layer, "moves": "gap_p95_ms", "workloads": CELLS}
        spec = readers.load_spec(REPO, entry["name"])
        assert spec["kind"] == "python" and spec["pattern"] == {"module": module}
        assert spec.get("parts") == which
        assert (REPO / "bench/layer_metrics" / f"{entry['name']}.py").exists()
    manifest = Manifest(REPO)
    manifest.check()
    assert len(manifest.cell("mistral7b-chat-closed")["per_layer"]) == 16
    for cell in CELLS:
        assert set(NINE) <= {m["name"] for m in manifest.cell(cell)["per_layer"]}
