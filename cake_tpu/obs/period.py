"""The scheduler loop's cumulative account: what a dispatch period is made of.

A *period* is one iteration of the engine's step loop (runtime/serving.py
``_run_epoch``) that dispatches a decode chunk or a speculative round, from
the end of the iteration before it to the end of its own: the time between
two bursts of tokens that a client sees as a gap. The engine brackets the
loop's phases with timeline spans; this module keeps, at the same boundaries
and from the same clock reads, CUMULATIVE counters, so that a difference of
two ``GET /stats`` reads means something (``/stats["timeline"]`` aggregates
a bounded ring and cannot be differenced).

Each span charges its SELF time (its interval less its children's) to one
phase, so a period's phases sum to its wall exactly; ``other`` is what of a
period no span covers. Writes come from the engine thread alone; a period's
numbers are committed under a lock in one step, and ``snapshot()`` copies
under it. Stdlib only.
"""

from __future__ import annotations

import math
import threading
import time

PHASES = (
    "sweep", "admit", "join", "pages", "dispatch", "readback", "emit", "other",
)

# Span name -> the phase its self time is charged to. ``step``'s own time is
# admission (budget, restore and join picking); a join's or a restore's own
# time is the host's work around its prefill; the rest of a decode chunk or
# a speculative round outside its dispatch and readback (the guard, the
# drafting) counts as dispatch.
SELF_PHASE = {
    "sweep": "sweep",
    "step": "admit",
    "join": "join",
    "prefix-fork": "join",
    "restore": "join",
    "page-extend": "pages",
    "decode-chunk": "dispatch",
    "spec-round": "dispatch",
    "dispatch": "dispatch",
    "readback": "readback",
    "emit": "emit",
}

# Why a dispatching period's decode chunk was NOT enqueued before the chunk
# in front of it was read (``ahead`` counts those that were): the segment's
# first chunk; a backend that cannot run ahead (it may have to redo a chunk
# from pre-chunk state, or dispatches through the watchdog's thread); a
# speculative round, whose drafts the host accepts; a restore or a pool too
# short for the chunk, which spill and restore from settled state; a join
# that lost its worker.
SERIAL_WHY = ("segment-start", "backend", "spec", "restore", "pages", "join")

# Histogram of the periods' durations: geometric buckets from 1 ms to 10 s
# whose ratio is at most 1.02, so a percentile read from it is within 1% of
# the sample's. counts[0] holds what fell below the first edge, counts[-1]
# what reached the last.
_LO_S, _HI_S, _RATIO = 1e-3, 10.0, 1.02
_N_BUCKETS = math.ceil(math.log(_HI_S / _LO_S) / math.log(_RATIO))
_LOG_STEP = math.log(_HI_S / _LO_S) / _N_BUCKETS
EDGES_S = tuple(
    round(_LO_S * math.exp(i * _LOG_STEP), 9) for i in range(_N_BUCKETS + 1)
)


def bucket_index(seconds: float) -> int:
    """Index into ``counts`` of a duration: computed, not searched."""
    if seconds < _LO_S:
        return 0
    if seconds >= _HI_S:
        return _N_BUCKETS + 1
    return 1 + min(_N_BUCKETS - 1, int(math.log(seconds / _LO_S) / _LOG_STEP))


class PeriodAccount:
    """Cumulative period and segment counters of one engine.

    ``lanes`` is the engine's lane budget (``--api-batch``): a period offers
    that many lane-seconds a second whatever the segment's own lane count.
    """

    def __init__(self, lanes: int):
        self.lanes = int(lanes)
        self._lock = threading.Lock()
        self._period = {
            "count": 0, "seconds": 0.0,
            # Sum over dispatching periods of the decode steps their chunk
            # or round was dispatched for (a segment's last chunk is a
            # program of fewer): over ``count`` the steps a dispatch made,
            # what a device time of a chunk is divided by for a step's.
            "steps": 0,
            "with_join": {"count": 0, "seconds": 0.0},
            "undispatched": {"count": 0, "seconds": 0.0},
            "phase_seconds": dict.fromkeys(PHASES, 0.0),
            "joins": 0, "join_seconds": 0.0, "join_readback_seconds": 0.0,
            # Of ``joins``, those that went several a program (the joiners
            # of one step as one join program of ``rows`` rows, dead rows
            # among them): ``joiners / joins`` is the share that went
            # grouped, ``joiners / rows`` a group's fill.
            "join_groups": {"programs": 0, "rows": 0, "joiners": 0},
            "lane_seconds": {"live": 0.0, "offered": 0.0, "idle_queued": 0.0},
            "ahead": 0, "serial": dict.fromkeys(SERIAL_WHY, 0),
            # Sum over dispatching periods of the tokens their live lanes
            # held in the cache (each lane's position at the dispatch):
            # over ``count`` it is the mean cached tokens a decode dispatch.
            "cached_tokens": 0,
        }
        self._hist = [0] * (_N_BUCKETS + 2)
        self._segment = {
            "count": 0, "seconds": 0.0, "prefill_seconds": 0.0,
            "between_seconds": 0.0,
        }
        # The open period (engine thread only; ``_t0`` and ``_open`` under the lock).
        self._t0 = self._mark = 0.0
        self._open = False
        self._queued = False
        self._stack: list[tuple[str, float]] = []
        self._self = dict.fromkeys(PHASES, 0.0)
        self._joins = 0
        self._join_s = self._join_readback_s = 0.0
        self._groups = [0, 0, 0]  # programs, rows, joiners
        # When the engine last had work and no segment (set by the loop).
        self._t_work: float | None = None

    # ------------------------------------------------------------ a period

    def begin(self, queued: bool) -> None:
        """The iteration starts; ``queued``: a request is waiting for a lane."""
        self._mark = now = time.perf_counter()
        with self._lock:  # a reader takes the pair (``open_seconds``)
            self._t0, self._open = now, True
        self._queued = queued
        self._stack.clear()
        self._self = dict.fromkeys(PHASES, 0.0)
        self._joins = 0
        self._join_s = self._join_readback_s = 0.0
        self._groups = [0, 0, 0]

    def push(self, span: str) -> None:
        """A span of the loop opens: the time since the last boundary was
        its parent's own. (Outside a period, as in a segment's prefill, the
        stack still runs so that ``pop`` gives durations; nothing of it is
        committed.)"""
        now = time.perf_counter()
        parent = self._stack[-1][0] if self._stack else "other"
        self._self[parent] += now - self._mark
        self._stack.append((SELF_PHASE[span], now))
        self._mark = now

    def pop(self) -> float:
        """The innermost span closes; returns its whole duration (the
        engine's chunk timer reads this clock, not one of its own)."""
        now = time.perf_counter()
        phase, t_in = self._stack.pop()
        self._self[phase] += now - self._mark
        self._mark = now
        return now - t_in

    def note_join(self, seconds: float, joiners: int = 1, rows: int = 0) -> None:
        """One join program's ``join`` span: the host's work to enqueue its
        prefill, its first sample and its lanes' writes, for its ``joiners``
        (several where a step's joiners went as one program of ``rows``
        rows). The wait for a first token is no part of it
        (``note_join_wait``)."""
        self._joins += joiners
        self._join_s += seconds
        if rows:
            for i, n in enumerate((1, rows, joiners)):
                self._groups[i] += n

    def note_join_wait(self, seconds: float) -> None:
        """The host's wait for a joiner's first token, read with its
        boundary's other values: what was still to run of the join when
        the host got there."""
        self._join_readback_s += seconds

    def end(
        self, live: int | None, order: str = "", cached: int = 0, steps: int = 0,
    ) -> None:
        """The iteration ends. ``live``: lanes that decoded in it; None when
        it dispatched nothing (the segment's last look for work, a chunk
        lost to a failover), which is no period. ``order``: ``"ahead"``
        when its chunk was enqueued before the one in front of it was
        read, else one of ``SERIAL_WHY``. ``steps``: the decode steps its
        chunk was dispatched for (a speculative round: the positions it
        verified)."""
        now = time.perf_counter()
        self._self["other"] += now - self._mark
        wall = now - self._t0
        with self._lock:
            self._open = False
            p = self._period
            if live is None:
                p["undispatched"]["count"] += 1
                p["undispatched"]["seconds"] += wall
                return
            p["count"] += 1
            p["seconds"] += wall
            p["cached_tokens"] += cached
            p["steps"] += steps
            if order == "ahead":
                p["ahead"] += 1
            else:
                p["serial"][order] += 1
            for phase, s in self._self.items():
                p["phase_seconds"][phase] += s
            if self._joins:
                p["with_join"]["count"] += 1
                p["with_join"]["seconds"] += wall
                p["joins"] += self._joins
                p["join_seconds"] += self._join_s
                p["join_readback_seconds"] += self._join_readback_s
                for key, n in zip(("programs", "rows", "joiners"), self._groups):
                    p["join_groups"][key] += n
            lanes = p["lane_seconds"]
            lanes["live"] += live * wall
            lanes["offered"] += self.lanes * wall
            if self._queued:
                lanes["idle_queued"] += max(0, self.lanes - live) * wall
            self._hist[bucket_index(wall)] += 1

    # ----------------------------------------------------------- a segment

    def work_seen(self) -> None:
        """The engine's loop holds work and runs no segment: the clock of
        ``between_seconds`` starts (idle waits are not counted)."""
        self._t_work = time.perf_counter()

    def first_dispatch(self) -> None:
        """A segment reaches its first decode dispatch: admission,
        ``init_kv`` and the segment's prefill lie behind it."""
        if self._t_work is not None:
            with self._lock:
                self._segment["between_seconds"] += (
                    time.perf_counter() - self._t_work
                )
            self._t_work = None

    def segment_done(self, seconds: float, prefill_seconds: float) -> None:
        with self._lock:
            self._segment["count"] += 1
            self._segment["seconds"] += seconds
            self._segment["prefill_seconds"] += prefill_seconds

    # ------------------------------------------------------------- reading

    def snapshot(self, now: float | None = None) -> dict:
        """{"period": ..., "segment": ...} as ``GET /stats`` carries them
        under ``engine``. ``period.open_seconds`` is the one number that is
        not cumulative: how long the iteration open at ``now`` (this read's
        ``time.perf_counter()``) has run, 0.0 between iterations. A period's
        wall is committed whole at its end, so ``seconds + open_seconds`` is
        the loop's time in periods UP TO the read, and its difference over
        two reads the time in periods BETWEEN them (an open iteration that
        then dispatches nothing, a segment's last look, is no period: the
        difference errs by that look)."""
        with self._lock:
            p = self._period
            period = {
                k: dict(v) if isinstance(v, dict) else v for k, v in p.items()
            }
            if now is None:
                now = time.perf_counter()
            period["open_seconds"] = max(0.0, now - self._t0) if self._open else 0.0
            period["hist"] = {
                "edges_s": list(EDGES_S), "counts": list(self._hist),
            }
            return {"period": period, "segment": dict(self._segment)}
