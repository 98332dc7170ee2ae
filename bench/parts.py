"""A served program's device time by PART, from the trace's metadata.

The program enters one ``jax.named_scope`` a block part (``PARTS`` below;
the program's own tuple is ``cake_tpu/obs/taxonomy.PROGRAM_PARTS`` and a test
holds the two equal: the benchmark reads the program from outside and does
not import it). A device trace names an operation by its HLO instruction's
text, which holds no scope, and ``xplane.load`` keeps that name alone; the
scope is in the ``tf_op`` stat of the operation's METADATA in the
``.xplane.pb`` (``jit(decode_chunk_paged)/while/body/.../mixer_in/
dot_general:``), which JAX's reader does not hand out. So the trace file is
walked here in the protobuf's wire format, once a file (``labelled``,
memoised by path and size: nine metrics share one walk), each operation of
the device planes' ``XLA Ops`` line named by the FIRST name of the vocabulary
on its ``tf_op`` path and "" where none is. It is the walk of
``layer_metrics/delta_rule_prefill_roofline_pct.py scoped()`` over a
vocabulary (that file keeps its own copy; a test holds the two to one
reading of the recorded probe). An operation XLA fused across a part's edge
counts by its root instruction's scope.

The arithmetic is ``xplane.op_times``'s, for every label in one pass
(``part_seconds``): own time (an operation's interval less its children's)
inside the WHOLE runs of the programs ``module`` picks; a run the window's
edge cut is left out with its operations. A metric is a DISPATCH's
milliseconds: its parts' own seconds over the count of whole runs, so it
stands beside ``decode_dispatch_dev_ms`` or ``join_prefill_dev_ms``. No
trace file, no whole run of the module, or a program without the scopes
(the parent of the PR that brought them) gives nothing to read.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
from pathlib import Path

from bench import xplane

PARTS = (
    "embed", "mixer_in", "cache_write", "mixer", "mixer_out", "feed_forward",
    "head", "sample",
)
TRACES = Path(__file__).resolve().parents[1] / ".bench_work" / "trace"


def _fields(buf, lo, hi):
    """(number, value) of a message's fields: an integer for a varint, a
    (lo, hi) span of ``buf`` for a length-delimited one."""

    def varint():
        nonlocal lo
        value = shift = 0
        while True:
            byte = buf[lo]
            lo += 1
            value |= (byte & 0x7F) << shift
            shift += 7
            if byte < 0x80:
                return value

    while lo < hi:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
        elif wire == 2:
            size = varint()
            yield key >> 3, (lo, lo + size)
            lo += size
        else:  # fixed 64 or 32 bits: nothing this walk reads
            lo += 8 if wire == 1 else 4


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _values(buf, spans):
    """The spans of a map's values (field 2 of each entry)."""
    for span in spans:
        for number, value in _fields(buf, *span):
            if number == 2:
                yield value


def _label(tf_op: str, vocabulary: tuple) -> str:
    return next((p for p in tf_op.split("/") if p in vocabulary), "")


@functools.lru_cache(maxsize=2)
def _walk(path: str, size: int, vocabulary: tuple) -> dict:
    """XSpace.planes = 1; XPlane: name 2, lines 3, event_metadata 4,
    stat_metadata 5; XLine: name 2, timestamp_ns 3, events 4; XEvent:
    metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata: id 1, name 2,
    stats 5; XStat: metadata_id 1, str_value 5, ref_value 7; XStatMetadata:
    id 1, name 2."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, held = "", {3: [], 4: [], 5: []}
        for number, value in _fields(buf, *plane):
            if number == 2:
                name = _text(buf, value)
            elif number in held:
                held[number].append(value)
        if not xplane.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for span in _values(buf, held[5]):
            meta = dict(_fields(buf, *span))
            stat_names[meta.get(1)] = _text(buf, meta[2]) if 2 in meta else ""
        labels = {}  # metadata id -> (the event's name, its part)
        for span in _values(buf, held[4]):
            ident, text, part = None, "", ""
            for number, value in _fields(buf, *span):
                if number == 1:
                    ident = value
                elif number == 2:
                    text = _text(buf, value)
                elif number == 5:
                    stat = dict(_fields(buf, *value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        # a string, or a reference to a stat's name as one
                        op = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                        part = _label(op, vocabulary)
            labels[ident] = (text, part)
        lines = out.setdefault(name, {})
        for span in held[3]:
            line, start_ns, events = "", 0, []
            for number, value in _fields(buf, *span):
                if number == 2:
                    line = _text(buf, value)
                elif number == 3:
                    start_ns = value
                elif number == 4:
                    events.append(value)
            if line not in (xplane.OPS, xplane.MODULES):
                continue
            which = 1 if line == xplane.OPS else 0  # a module keeps its name
            for span in events:
                event = dict(_fields(buf, *span))
                a = start_ns * 1e-9 + event.get(2, 0) * 1e-12
                label = labels.get(event.get(1), ("", ""))[which]
                lines.setdefault(line, []).append((label, a, a + event.get(3, 0) * 1e-12))
    return out


def labelled(path: str, vocabulary: tuple = PARTS) -> dict:
    """``xplane.load``'s lists for the device planes' ``XLA Modules`` and
    ``XLA Ops`` lines of the trace file at ``path``, an operation named by
    its part (the first of ``vocabulary`` on its ``tf_op`` path, else "").
    One walk a file: a second call is handed the first one's lists."""
    return _walk(path, os.path.getsize(path), tuple(vocabulary))


@functools.lru_cache(maxsize=4)
def _part_seconds(path: str, size: int, module: str) -> dict:
    planes = labelled(path)
    chips = xplane.device_planes(planes)
    if not chips:
        return {"runs": 0, "program_s": 0.0, "own_s": {}}
    ops, runs = xplane._whole_runs(planes[chips[0]], module)
    runs.sort(key=lambda r: r[1])
    starts = [a for _, a, _ in runs]
    own_s: dict = {}
    for label, a, b, own in xplane.own_events(ops):
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and b <= runs[k][2]:
            own_s[label] = own_s.get(label, 0.0) + own
    return {"runs": len(runs), "program_s": sum(b - a for _, a, b in runs), "own_s": own_s}


def part_seconds(path: str, module: str) -> dict:
    """``{"runs", "program_s", "own_s": {part: seconds}}`` of the first
    chip: the whole runs of the programs whose module's name matches
    ``module``, their device seconds, and the own seconds of the operations
    inside them by part (``xplane.op_times``'s reading of each label)."""
    return _part_seconds(path, os.path.getsize(path), module)


def _read(spec: dict) -> dict | None:
    """The parts of the traced window's programs ``spec`` picks, or None
    where there is nothing to read."""
    files = sorted(glob.glob(f"{TRACES}/plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    got = part_seconds(files[0], spec["pattern"]["module"])
    if not got["runs"] or not any(got["own_s"]):
        return None
    return got


def dispatch_ms(facts: dict, spec: dict) -> float | None:
    """Own device milliseconds a whole run of ``spec``'s programs under
    ``spec["parts"]``."""
    got = _read(spec) if facts["trace"] else None
    if got is None:
        return None
    return 1e3 * sum(got["own_s"].get(p, 0.0) for p in spec["parts"]) / got["runs"]


def unscoped_pct(facts: dict, spec: dict) -> float | None:
    """Own device time under NO part over the programs' device time: the
    scopes' own health (the carries' copies and selects between the parts
    are meant to be all of it)."""
    got = _read(spec) if facts["trace"] else None
    if got is None:
        return None
    return 100.0 * got["own_s"].get("", 0.0) / got["program_s"]
