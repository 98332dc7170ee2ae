"""BENCHMARK.json and the data files it names, loaded and cross-checked.

A cell, a configuration, a traffic mix, a per-layer metric or an
architecture is added by adding an entry to BENCHMARK.json and files under
``bench/``; nothing here or in ``run.py`` names one. What depends on a model's
architecture (tensors, plain reference, template, costs) is in
``bench/architectures/<model_type>.py``, found by the configuration's own
``model_type`` key. Stdlib only.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path


# A configuration file is the model's own config.json, key for key at the
# top level, and beside them the benchmark's.
BENCH_KEYS = ("source", "reduced", "assumed", "deployment", "served_dtype",
              "weights_seed", "server_flags", "judge")


def model_config(config: dict) -> dict:
    """The part of a configuration file that is written as ``config.json``."""
    return {k: v for k, v in config.items() if k not in BENCH_KEYS}


class ManifestError(ValueError):
    pass


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"{path}: {e}") from e
    except ValueError as e:
        raise ManifestError(f"{path}: not JSON: {e}") from e


def architecture(root: Path, config: dict):
    """The module of the configuration's ``model_type``, loaded from its
    file under ``root`` (what it gives: ``bench/architectures/__init__.py``)."""
    model_type = config.get("model_type")
    path = Path(root) / "bench" / "architectures" / f"{model_type}.py"
    if not path.is_file():
        raise ManifestError(
            f"no architecture file for model_type {model_type!r}: add {path.relative_to(root)} "
            "(bench/architectures/__init__.py says what it gives)"
        )
    spec = importlib.util.spec_from_file_location(f"bench_architecture_{model_type}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = _json(self.root / "BENCHMARK.json")
        self.cells = {w["name"]: w for w in self.bench["workloads"]}
        self.configs = {c["name"]: c for c in self.bench["configs"]}

    def cell(self, name: str) -> dict:
        """Everything one run needs: the cell's entry and file, its
        configuration's file and architecture, its mix, and the metrics it
        reports."""
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json (has: {sorted(self.cells)})"
            )
        entry = self.cells[name]
        cell_file = _json(self.root / "bench" / "workloads" / f"{name}.json")
        for key in ("config", "traffic"):
            if cell_file.get(key) != entry[key]:
                raise ManifestError(
                    f"bench/workloads/{name}.json says {key}={cell_file.get(key)!r}, "
                    f"BENCHMARK.json says {entry[key]!r}"
                )
        if entry["config"] not in self.configs:
            raise ManifestError(f"workload {name!r}: unknown config {entry['config']!r}")
        config = _json(self.root / self.configs[entry["config"]]["file"])
        arch = architecture(self.root, config)
        mix = _json(self.root / "bench" / "traffic" / f"{entry['traffic']}.json")
        if config["deployment"]["chips"] != entry["chips"]:
            raise ManifestError(
                f"workload {name!r} asks for {entry['chips']} chip(s), its "
                f"configuration is laid out on {config['deployment']['chips']}"
            )
        end_to_end = [m for m in self.bench["end_to_end"] if _in_cell(m, name)]
        per_layer = [m for m in self.bench["per_layer"] if _in_cell(m, name)]
        reported = {m["name"] for m in end_to_end}
        for m in per_layer:
            if m["moves"] not in reported:
                raise ManifestError(
                    f"per-layer metric {m['name']!r} moves {m['moves']!r}, "
                    f"which workload {name!r} does not report"
                )
        if "setup_s" not in reported or len(reported) < 2 or not per_layer:
            raise ManifestError(
                f"workload {name!r} must report setup_s, another end-to-end "
                "metric and a per-layer metric"
            )
        return {
            "name": name, "entry": entry, "file": cell_file, "config": config,
            "config_file": self.configs[entry["config"]]["file"],
            "config_name": entry["config"], "architecture": arch, "mix": mix,
            "end_to_end": end_to_end, "per_layer": per_layer,
        }

    def check(self) -> None:
        """Every cell loads (so its configuration's architecture file is
        there), and every configuration is used."""
        for name in self.cells:
            self.cell(name)
        unused = set(self.configs) - {w["config"] for w in self.cells.values()}
        if unused:
            raise ManifestError(f"configurations no cell uses: {sorted(unused)}")
        metrics = self.root / "bench" / "layer_metrics"
        for m in self.bench["per_layer"]:
            if not any((metrics / f"{m['name']}{ext}").exists() for ext in (".json", ".py")):
                raise ManifestError(f"per-layer metric {m['name']!r} has no reader")
