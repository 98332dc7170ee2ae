"""The chunkwise gated delta rule's share of ITS roofline in the prefill and
join programs: the least time the chip could take for the traced window's
calls, the larger of their operations over the peak bf16 rate and their bytes
over the peak HBM bandwidth, over the device time the rule took there.

The rule is PLAIN XLA (``ops/delta_rule.py``: products, one triangular solve
a chunk, a scan over chunks), not one operation: it runs under the
``jax.named_scope`` ``gated_delta_rule`` and this reader SUMS the scope's
operations. A device trace names an operation by its HLO instruction's text,
which holds no scope, and ``bench/xplane.py load()`` keeps that name alone;
the scope lives in the ``tf_op`` stat of the operation's METADATA in the
``.xplane.pb`` (``jit(prefill_join...)/.../gated_delta_rule/while/body/
dot_general:``), which JAX's reader does not hand out. So the trace file is
walked here, in the protobuf's wire format (``scoped``: the device planes'
two lines, each operation named by whether its metadata is in the scope),
and the time is ``xplane.op_times``'s as for any kernel: own time (a loop's
interval less its body's operations) inside whole runs of ``^jit_prefill``
programs. An operation XLA fused across the scope's edge counts by its root
instruction's scope.

Operations and bytes are the architecture's (``gated_delta_rule_cost``, one
call = one layer) for the window's mean call: the prompt tokens first served
in the traced window (``prefill_dev_tokens_per_s``'s count) over its whole
prefill and join runs, one row a run (an epoch's program holds two rows; a
row's state is an eighth of a mean call's bytes), times the configuration's
state layers. A floor over LIVE tokens: the programs also compute a window's
pads and dead tail. The operations are float32 products held against the bf16
peak, so three bfloat16 passes a product cap the share at a third. A program
without the scope (the parent of the PR that brought it), a trace without a
whole prefill run, or no trace file gives nothing to read."""

import glob
from pathlib import Path

from bench import xplane
from bench.costs import peaks

SCOPE = "gated_delta_rule"
TRACES = Path(__file__).resolve().parents[2] / ".bench_work" / "trace"


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


def scoped(path: str, scope: str) -> dict:
    """``xplane.load``'s lists for the device planes' ``XLA Modules`` and
    ``XLA Ops`` lines, an operation named ``scope`` where its metadata's
    ``tf_op`` holds ``/<scope>/`` and "" elsewhere. XSpace.planes = 1;
    XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5; XLine: name
    2, timestamp_ns 3, events 4; XEvent: metadata_id 1, offset_ps 2,
    duration_ps 3; XEventMetadata: id 1, name 2, stats 5; XStat: metadata_id
    1, str_value 5, ref_value 7; XStatMetadata: id 1, name 2."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, parts = "", {3: [], 4: [], 5: []}
        for number, value in _fields(buf, *plane):
            if number == 2:
                name = _text(buf, value)
            elif number in parts:
                parts[number].append(value)
        if not xplane.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for span in _values(buf, parts[5]):
            meta = dict(_fields(buf, *span))
            stat_names[meta.get(1)] = _text(buf, meta[2]) if 2 in meta else ""
        labels = {}  # metadata id -> (the event's name, in the scope)
        for span in _values(buf, parts[4]):
            ident, label, inside = None, "", False
            for number, value in _fields(buf, *span):
                if number == 1:
                    ident = value
                elif number == 2:
                    label = _text(buf, value)
                elif number == 5:
                    stat = dict(_fields(buf, *value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        # a string, or a reference to a stat's name as one
                        op = _text(buf, stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                        inside = f"/{scope}/" in op
            labels[ident] = (label, inside)
        lines = out.setdefault(name, {})
        for span in parts[3]:
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
            for span in events:
                event = dict(_fields(buf, *span))
                label, inside = labels.get(event.get(1), ("", False))
                a = start_ns * 1e-9 + event.get(2, 0) * 1e-12
                if line == xplane.OPS:
                    label = scope if inside else ""
                lines.setdefault(line, []).append((label, a, a + event.get(3, 0) * 1e-12))
    return out


def read(facts, spec):
    trace = facts["trace"] or {}
    runs = trace.get("programs", {}).get(facts["metric"])
    arch = facts["architecture"]
    files = glob.glob(f"{TRACES}/plugins/profile/*/*.xplane.pb")
    if not runs or not files or not hasattr(arch, "gated_delta_rule_cost"):
        return None
    got = xplane.op_times(
        scoped(files[0], SCOPE), {"op": f"^{SCOPE}$", "module": spec["pattern"]["module"]}
    )
    lo, hi = trace["t_start"], trace["t_stop"]
    tokens = sum(
        len(o.vocab.chat_ids(o.request.prompt_ids)) for o in facts["outcomes"]
        if o.arrivals and lo <= o.arrivals[0] < hi
    )
    if not got["seconds"] or not tokens:
        return None
    cfg = facts["config"]
    ops, moved = arch.gated_delta_rule_cost(cfg, len(runs), tokens / len(runs), cfg["served_dtype"])
    layers = sum(kind == arch.LINEAR for kind in cfg["layer_types"])
    peak = peaks(facts["device"]["device_kind"])
    floor_s = max(ops / (peak["bf16_tflops"] * 1e12), moved / (peak["hbm_gb_per_s"] * 1e9))
    return 100.0 * layers * floor_s / got["seconds"]
