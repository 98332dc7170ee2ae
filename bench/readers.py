"""Per-layer metrics: one small reader each, found by the metric's name.

``bench/layer_metrics/<name>.json`` says how the metric is read: a
declarative ``kind`` served here, or ``"kind": "python"`` with a
``<name>.py`` beside it that defines ``read(facts, spec)``. A reader that
finds nothing to read returns None and the metric is left out of the line.
``facts`` is what the run gathered: see ``facts`` in ``run.measure``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from statistics import fmean

from bench.stats import percentile


def _dig(obj, path: str):
    for key in path.split("."):
        obj = obj[key]
    return obj


def stats_delta(facts: dict, spec: dict):
    """A counter of ``GET /stats`` after the window less before it."""
    return _dig(facts["stats_after"], spec["path"]) - _dig(facts["stats_before"], spec["path"])


def gauge_mean(facts: dict, spec: dict):
    """Mean of a gauge of ``GET /metrics`` sampled through the window."""
    samples = facts["gauges"].get(spec["gauge"])
    return fmean(samples) if samples else None


def requestlog_percentile(facts: dict, spec: dict):
    """A percentile of one field of the program's request log
    (``GET /requests``) over the window's requests."""
    values = [r[spec["field"]] for r in facts["requests"].values()
              if r.get(spec["field"]) is not None]
    p = percentile(values, spec["q"])
    return None if p is None else p * spec.get("scale", 1.0)


def program_mean_ms(facts: dict, spec: dict):
    """Mean device time of the runs of the programs ``pattern`` picks."""
    runs = (facts["trace"] or {}).get("programs", {}).get(facts["metric"])
    return fmean(runs) * 1e3 if runs else None


def op_mean_us(facts: dict, spec: dict):
    """Mean own device time, in microseconds a call, of the operations
    ``pattern`` picks by name (``{"op": regex}``, optionally inside
    ``module``): one kernel's time. A ``python`` reader divides its
    architecture's bytes or operations for the call by the same
    ``facts["trace"]["ops"][metric]`` for a roofline share."""
    got = (facts["trace"] or {}).get("ops", {}).get(facts["metric"])
    return got["seconds"] / got["count"] * 1e6 if got and got["count"] else None


KINDS = {f.__name__: f for f in
         (stats_delta, gauge_mean, requestlog_percentile, program_mean_ms, op_mean_us)}


def load_spec(root: Path, name: str) -> dict:
    with open(root / "bench" / "layer_metrics" / f"{name}.json") as f:
        return json.load(f)


def read_metric(root: Path, name: str, facts: dict):
    spec = load_spec(root, name)
    if spec["kind"] == "python":
        path = root / "bench" / "layer_metrics" / f"{name}.py"
        mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        reader = module.read
    else:
        reader = KINDS[spec["kind"]]
    return reader({**facts, "metric": name}, spec)
