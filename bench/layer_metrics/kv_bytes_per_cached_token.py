"""Bytes of pool the lanes have MAPPED for every token they hold in the
cache, at the window's two ends: over the kinds of ``GET /stats``
engine.cache.kinds, ``pages_mapped`` times ``bytes_per_page``, summed, over
engine.cache.cached_tokens (the tokens the lanes hold storage for at that
instant, a chunk ahead of what they have written), the mean of the ends that
held a token. A model whose
attention layers are all of one kind pays every layer's K and V for every
token (36,864 B a token at Laguna-S-2.1's nine layers, were they one pool);
a pool a kind pays the full layers' alone and a window's pages a lane for
the others. Mapped pages round a lane up to whole pages and run one chunk
ahead of its tokens. A program without the counters (the parent commit, a
model of one kind) gives nothing to read."""

from bench.period_stats import dig


def _end(stats):
    cache = dig(stats, "engine.cache")
    if not cache or not cache.get("kinds") or not cache.get("cached_tokens"):
        return None
    mapped = sum(k["pages_mapped"] * k["bytes_per_page"] for k in cache["kinds"].values())
    return mapped / cache["cached_tokens"]


def read(facts, spec):
    ends = [e for e in (_end(facts["stats_before"]), _end(facts["stats_after"])) if e]
    return sum(ends) / len(ends) if ends else None
