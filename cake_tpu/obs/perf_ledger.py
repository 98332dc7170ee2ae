"""Perf ledger: a durable bench trajectory + noise-aware regression diffs.

A benchmark emits one normalized JSON record per run. This module

  * appends a record to a history file (one line per run, stamped with the
    git revision and a wall timestamp — ``append_history``), and
  * compares two bench records with noise-aware thresholds
    (``diff_records`` behind ``cake-tpu benchdiff old.json new.json``):
    a key regresses only when it moves BOTH more than the relative
    threshold AND more than the key class's absolute floor — a 3% wobble
    on a 150 tok/s headline is noise; a 20% drop is a gate failure.

Direction is inferred from the key name (the bench's own conventions):
throughput/utilization keys (``*tok_s*``, ``*mfu*``, ``*util*``,
``*hit_rate*``, ``*goodput*``, ``vs_baseline``) are higher-better; latency/compile keys
(``*_s``, ``*_ms``, ``*seconds*``, ``*compile*``, ``*retrace*``,
``*ttft*``) are lower-better; anything else is reported informationally
and never gates. Stdlib-only.
"""

from __future__ import annotations

import json
import os
import subprocess
import time

# Absolute floors per key class: a change smaller than the floor never
# regresses regardless of its relative size (sub-noise keys like a 0.01s
# compile wobble would otherwise flap the gate).
DEFAULT_FLOORS = {
    "tok_s": 1.0,       # throughput keys (tok/s)
    "seconds": 0.02,    # latency / compile-time keys
    "count": 0.5,       # retrace / integer counters
    "ratio": 0.01,      # mfu / util / hit-rate fractions
    "default": 1e-9,
}

_HIGHER = ("tok_s", "tok/s", "mfu", "util", "hit_rate", "vs_baseline",
           "bandwidth", "gbps", "goodput")
_LOWER = ("_s", "_ms", "seconds", "compile", "retrace", "ttft", "latency")


def git_rev(repo_dir: str | None = None) -> str | None:
    """Short git revision of ``repo_dir`` (this file's repo by default);
    None when git or the repo is unavailable (the record still lands)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=repo_dir or os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, timeout=10, text=True,
        )
        rev = out.stdout.strip()
        return rev or None
    except (OSError, subprocess.SubprocessError):
        return None


def flatten_numeric(rec: dict, prefix: str = "") -> dict[str, float]:
    """Dotted numeric leaves of a (possibly nested) bench record — the
    comparable key set. Bools and strings never gate."""
    out: dict[str, float] = {}
    for k, v in rec.items():
        key = f"{prefix}{k}"
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[key] = float(v)
        elif isinstance(v, dict):
            out.update(flatten_numeric(v, prefix=f"{key}."))
    return out


def append_history(
    rec: dict, path: str, *, repo_dir: str | None = None,
    ts: float | None = None,
) -> dict:
    """Append one normalized ledger line for a bench emit; returns the line
    that was written. Failures never propagate into the bench (the stdout
    record is still the result)."""
    line = {
        "ts": round(time.time() if ts is None else ts, 3),
        "git_rev": git_rev(repo_dir),
        "record": rec,
    }
    try:
        with open(path, "a") as f:
            f.write(json.dumps(line, separators=(",", ":"), default=str))
            f.write("\n")
    except OSError:
        pass
    return line


def load_record(path: str) -> dict:
    """A bench record from a bench JSON file (single-line or pretty-
    printed) OR a ledger JSONL, whatever the extension says: the whole
    text is tried as one JSON document first, and a multi-line parse
    failure falls back to the LAST line (the ledger contract — the
    newest run wins)."""
    with open(path) as f:
        text = f.read().strip()
    try:
        rec = json.loads(text)
    except ValueError:
        rec = json.loads(text.splitlines()[-1])
    return rec.get("record", rec)


def _direction(key: str) -> str:
    low = key.lower()
    if any(t in low for t in _HIGHER):
        return "higher"
    if any(low.endswith(t) or t in low for t in _LOWER):
        return "lower"
    return "info"


def _floor(key: str, floors: dict) -> float:
    low = key.lower()
    if any(t in low for t in ("tok_s", "tok/s")):
        return floors.get("tok_s", DEFAULT_FLOORS["tok_s"])
    if any(t in low for t in ("mfu", "util", "hit_rate", "vs_baseline",
                              "goodput")):
        return floors.get("ratio", DEFAULT_FLOORS["ratio"])
    if any(t in low for t in ("retrace", "count")):
        return floors.get("count", DEFAULT_FLOORS["count"])
    if any(low.endswith(t) or t in low for t in ("_s", "_ms", "seconds",
                                                 "compile", "ttft")):
        return floors.get("seconds", DEFAULT_FLOORS["seconds"])
    return floors.get("default", DEFAULT_FLOORS["default"])


def diff_records(
    old: dict, new: dict, *, pct: float = 0.10, floors: dict | None = None,
) -> dict:
    """Compare two bench records key by key.

    Returns ``{regressions, improvements, unchanged, info, missing}`` —
    each entry ``{key, old, new, delta_pct, direction}``. A key regresses
    when it moves against its direction by more than ``pct`` relative AND
    more than its class's absolute floor.
    """
    floors = {**DEFAULT_FLOORS, **(floors or {})}
    a, b = flatten_numeric(old), flatten_numeric(new)
    out = {
        "regressions": [], "improvements": [], "unchanged": [],
        "info": [], "missing": [],
    }
    for key in sorted(set(a) | set(b)):
        if key not in a or key not in b:
            out["missing"].append({
                "key": key, "old": a.get(key), "new": b.get(key),
            })
            continue
        ov, nv = a[key], b[key]
        delta = nv - ov
        rel = abs(delta) / abs(ov) if ov else (0.0 if not delta else 1.0)
        direction = _direction(key)
        entry = {
            "key": key, "old": ov, "new": nv,
            "delta_pct": round(rel * 100.0 * (1 if delta >= 0 else -1), 2),
            "direction": direction,
        }
        if direction == "info":
            out["info"].append(entry)
            continue
        worse = delta < 0 if direction == "higher" else delta > 0
        significant = rel > pct and abs(delta) > _floor(key, floors)
        if not significant:
            out["unchanged"].append(entry)
        elif worse:
            out["regressions"].append(entry)
        else:
            out["improvements"].append(entry)
    return out


def render_diff(diff: dict, *, pct: float = 0.10) -> str:
    """Terminal rendering for ``cake-tpu benchdiff``."""
    lines = [
        f"benchdiff (threshold {pct * 100:.0f}% + per-class floors): "
        f"{len(diff['regressions'])} regression(s), "
        f"{len(diff['improvements'])} improvement(s), "
        f"{len(diff['unchanged'])} within noise, "
        f"{len(diff['missing'])} key(s) only on one side"
    ]

    def block(title, entries, mark):
        if not entries:
            return
        lines.append("")
        lines.append(title)
        for e in entries:
            lines.append(
                f"  {mark} {e['key']:44} {e['old']:>12.3f} -> "
                f"{e['new']:>12.3f}  ({e['delta_pct']:+.1f}%)"
            )

    block("REGRESSIONS", diff["regressions"], "!")
    block("improvements", diff["improvements"], "+")
    if diff["missing"]:
        lines.append("")
        lines.append("only on one side:")
        for e in diff["missing"][:20]:
            lines.append(f"  ? {e['key']} (old={e['old']}, new={e['new']})")
    return "\n".join(lines)
