"""Pages of the windowed kinds' pools that went back to the free list
because the lanes' shared position passed them, a second of the window:
``freed_behind_window`` of every kind of ``GET /stats`` engine.cache.kinds
(a count that only grows), after less before, over the periods' seconds
(``engine.period.seconds``). Zero in a cell whose lanes never outlive the
window; in one whose lanes do, each live lane frees a page every
``page_size`` decode steps. A program without the counters gives nothing to
read."""

from bench.period_stats import PERIOD, delta, dig


def read(facts, spec):
    kinds = dig(facts["stats_after"], "engine.cache.kinds")
    seconds = delta(facts, f"{PERIOD}.seconds")
    if not kinds or not seconds:
        return None
    freed = [delta(facts, f"engine.cache.kinds.{k}.freed_behind_window") for k in kinds]
    if any(f is None for f in freed):
        return None
    return sum(freed) / seconds
