"""Perf ledger (obs/perf_ledger.py): the history-file trajectory and the
noise-aware `cake-tpu benchdiff` regression gate."""

import json
import os

import pytest

from cake_tpu.obs import perf_ledger as pl


def test_append_history_stamps_rev_and_ts(tmp_path):
    path = tmp_path / "BENCH_HISTORY.jsonl"
    line = pl.append_history({"tok_s": 100.0, "unit": "tok/s"}, str(path))
    assert line["ts"] > 0
    # Two runs -> two lines, parseable, newest last.
    pl.append_history({"tok_s": 101.0}, str(path))
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(rows) == 2
    assert rows[0]["record"]["tok_s"] == 100.0
    assert rows[1]["record"]["tok_s"] == 101.0
    # This repo IS a git checkout: the revision stamp must resolve.
    assert pl.git_rev(os.path.dirname(os.path.abspath(__file__))) is not None


def test_append_history_keeps_the_record_whole_and_never_raises(tmp_path):
    """An emitter's contract with the ledger: the record lands verbatim
    (nested sections included, with the caller's timestamp), and a path
    that cannot be written costs the line, never the run."""
    path = tmp_path / "hist.jsonl"
    rec = {"value": 42.0, "sections": {"batch8_tok_s": 800.0}, "ok": True}
    line = pl.append_history(rec, str(path), ts=123.4567)
    assert line["ts"] == 123.457 and line["record"] == rec
    (row,) = path.read_text().splitlines()
    assert json.loads(row) == line
    assert pl.load_record(str(path))["sections"]["batch8_tok_s"] == 800.0
    lost = pl.append_history(rec, str(tmp_path / "no" / "such" / "dir.jsonl"))
    assert lost["record"] == rec


def test_diff_flags_20pct_regression():
    old = {"tok_s": 100.0, "prefill_tok_s": 20000.0, "compile_s": 5.0}
    new = {"tok_s": 80.0, "prefill_tok_s": 20100.0, "compile_s": 5.0}
    diff = pl.diff_records(old, new, pct=0.10)
    keys = [e["key"] for e in diff["regressions"]]
    assert keys == ["tok_s"]
    assert diff["regressions"][0]["delta_pct"] == pytest.approx(-20.0)
    # The 0.5% prefill wobble stays inside noise.
    assert any(e["key"] == "prefill_tok_s" for e in diff["unchanged"])


def test_diff_directions_and_floors():
    # Lower-better: compile time growing 30% regresses.
    diff = pl.diff_records({"compile_s": 5.0}, {"compile_s": 6.5})
    assert [e["key"] for e in diff["regressions"]] == ["compile_s"]
    # Higher-better improvement is not a regression.
    diff = pl.diff_records({"tok_s": 100.0}, {"tok_s": 130.0})
    assert not diff["regressions"]
    assert [e["key"] for e in diff["improvements"]] == ["tok_s"]
    # Abs floor: a 50% swing on a 0.01s compile key is sub-noise.
    diff = pl.diff_records({"compile_s": 0.01}, {"compile_s": 0.015})
    assert not diff["regressions"]
    # Unknown-direction keys inform, never gate.
    diff = pl.diff_records({"seed": 1.0}, {"seed": 9.0})
    assert not diff["regressions"] and diff["info"]
    # Keys on one side only are reported, not gated.
    diff = pl.diff_records({"tok_s": 1.0}, {"tok_s": 1.0, "new_tok_s": 2.0})
    assert [e["key"] for e in diff["missing"]] == ["new_tok_s"]


def test_nested_records_flatten():
    flat = pl.flatten_numeric(
        {"a": 1, "b": {"c": 2.0, "d": {"e": 3}}, "s": "x", "f": True}
    )
    assert flat == {"a": 1.0, "b.c": 2.0, "b.d.e": 3.0}


def test_benchdiff_cli_exit_codes(tmp_path, capsys):
    from cake_tpu.cli import _benchdiff_main

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps({"tok_s": 100.0}))
    new.write_text(json.dumps({"tok_s": 80.0}))
    assert _benchdiff_main([str(old), str(new)]) == 1  # 20% regression
    out = capsys.readouterr().out
    assert "REGRESSIONS" in out and "tok_s" in out
    new.write_text(json.dumps({"tok_s": 99.0}))
    assert _benchdiff_main([str(old), str(new)]) == 0  # inside noise
    capsys.readouterr()
    assert _benchdiff_main([str(old), str(tmp_path / "nope.json")]) == 2
    # Ledger JSONL input: the last line's record is the comparand.
    hist = tmp_path / "hist.jsonl"
    pl.append_history({"tok_s": 100.0}, str(hist))
    pl.append_history({"tok_s": 50.0}, str(hist))
    assert _benchdiff_main([str(old), str(hist)]) == 1
    capsys.readouterr()


def test_load_record_shapes(tmp_path):
    j = tmp_path / "r.json"
    j.write_text(json.dumps({"tok_s": 5.0}))
    assert pl.load_record(str(j)) == {"tok_s": 5.0}
    hist = tmp_path / "h.jsonl"
    pl.append_history({"tok_s": 1.0}, str(hist))
    pl.append_history({"tok_s": 2.0}, str(hist))
    assert pl.load_record(str(hist)) == {"tok_s": 2.0}
