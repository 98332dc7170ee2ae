"""The one traffic generator: a mix's data file and a seed -> requests.

A mix (``bench/traffic/<name>.json``) holds the loop (``open`` with an arrival
process, or ``closed`` with a number of clients and a lead-in), the two
length distributions in tokens, the sharing of prompt text, the seed of its
order and the warm-up. A later PR adds a mix by adding a file.

Every seed gets the same work in the same order; the seed draws the words.
The n lengths of a mix are its distribution's quantiles at (i + 0.5) / n,
continuous and with the tail kept, not n draws; an open loop's arrivals are
the exponential distribution's n quantile gaps (a stratified sample of a
Poisson train with a fixed count, not a Poisson process); the mix's own
``order_seed`` shuffles both. Draws of a few hundred lognormal lengths differ
by 5 to 7% in their sum, and the program under test compiles a program for
every shape an order of requests leads it through (PERF.md section 6): a
seed that changed the lengths or their order would change the work and what
compiles inside the window, and both move every metric more than a change of
the program would.

The arrival processes are copied from ``cake_tpu/loadgen/arrivals.py``, the
length distributions from ``cake_tpu/loadgen/workload.py``. Its prompts
(``"cake " * n``) are not: each would be a prefix of every longer one, so
with the prefix cache on the cache would serve them all. Here every request
has its own seeded words, unless the mix declares ``sharing``. ``vocab`` is the
cell's ``tokens.Vocabulary``: it draws the ordinary words. Stdlib only.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import random
from collections.abc import Iterator
from statistics import NormalDist

from bench.tokens import Vocabulary


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # offset from the window's start; 0 in a closed loop
    prompt_ids: tuple[int, ...]
    max_tokens: int


def _quantile(spec: dict, u: float) -> float:
    kind = spec["dist"]
    if kind == "fixed":
        return spec["value"]
    if kind == "uniform":
        return spec["min"] + u * (spec["max"] - spec["min"])
    if kind == "lognormal":
        return math.exp(spec["mu"] + spec["sigma"] * NormalDist().inv_cdf(u))
    raise ValueError(f"unknown length distribution {kind!r}")


def length_set(spec: dict, n: int) -> list[int]:
    """The n lengths of a mix, in order of size: the distribution's quantiles
    at (i + 0.5) / n, clipped to ``min`` and ``max``."""
    out = []
    for i in range(n):
        x = round(_quantile(spec, (i + 0.5) / n))
        out.append(max(spec.get("min", 1), min(spec.get("max", x), x)))
    return out


def _bursty(spec: dict, seconds: float) -> list[float]:
    """ON/OFF modulated Poisson with exponential phase lengths. The order of
    its gaps is the burst structure, so the train comes whole from the mix's
    own ``pattern_seed`` and is the same in every run."""
    rng = random.Random(spec["pattern_seed"])
    on_rate, off_rate = spec["on_rate_per_s"], spec["off_rate_per_s"]
    means = {True: spec["mean_on_s"], False: spec["mean_off_s"]}
    out, t, on = [], 0.0, True
    phase_end = rng.expovariate(1.0 / means[on])
    while t < seconds:
        rate = on_rate if on else off_rate
        gap = rng.expovariate(rate) if rate > 0 else math.inf
        if t + gap < phase_end:
            t += gap
            out.append(t)
        else:
            t, on = phase_end, not on
            phase_end = t + rng.expovariate(1.0 / means[on])
    return [x for x in out if x < seconds]


def arrival_offsets(spec: dict, seconds: float, rng: random.Random) -> list[float]:
    if spec["process"] == "bursty":
        return _bursty(spec, seconds)
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = spec["rate_per_s"]
    n = int(rate * seconds)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    scale = min(1.0, seconds * (n - 0.5) / n / sum(gaps)) if n else 1.0
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def _lengths_in_order(mix: dict, n: int, order: random.Random) -> tuple[list[int], list[int]]:
    prompts = length_set(mix["prompt_tokens"], n)
    outputs = length_set(mix["output_tokens"], n)
    order.shuffle(prompts)
    order.shuffle(outputs)
    return prompts, outputs


def _request_maker(mix: dict, seed: int, vocab: Vocabulary):
    """-> make(index, due_s, prompt_tokens, max_tokens), words from ``seed``."""
    rng = random.Random(seed)
    share = mix.get("sharing") or {}
    prefixes = [
        vocab.draw(rng, share["prefix_tokens"]) for _ in range(share.get("groups", 0))
    ]

    def make(index: int, due_s: float, n_prompt: int, n_new: int) -> Request:
        head = rng.choice(prefixes) if prefixes else []
        ids = head + vocab.draw(rng, max(1, n_prompt - len(head)))
        return Request(index, due_s, tuple(ids), n_new)

    return make


def open_requests(mix: dict, seed: int, seconds: float, vocab: Vocabulary) -> list[Request]:
    """An open loop's requests: one per arrival, due ``due_s`` after the
    window's start whatever happened to the ones before."""
    order = random.Random(mix["order_seed"])
    due = arrival_offsets(mix["arrivals"], seconds, order)
    prompts, outputs = _lengths_in_order(mix, len(due), order)
    make = _request_maker(mix, seed, vocab)
    return [make(i, due[i], prompts[i], outputs[i]) for i in range(len(due))]


def closed_requests(mix: dict, seed: int, vocab: Vocabulary) -> Iterator[Request]:
    """A closed loop's requests, without end: the callers take them in
    order. The mix's ``pool`` lengths come round again; the words never do,
    so a request is never the prefix cache's repeat of an earlier one."""
    n = mix["pool"]
    prompts, outputs = _lengths_in_order(mix, n, random.Random(mix["order_seed"]))
    make = _request_maker(mix, seed, vocab)
    for k in itertools.count():
        yield make(k, 0.0, prompts[k % n], outputs[k % n])


def warmup_requests(mix: dict, vocab: Vocabulary) -> list[Request]:
    """``warmup.alone_points`` requests whose prompts are that many quantiles
    of the mix's lengths, each with the mix's longest answer, because the
    program sizes an epoch's attention by prompt plus answer. Sent one at a
    time and cut after a few tokens, they meet the programs an idle engine
    starts with (an open loop below its knee often finds it idle)."""
    n = mix["warmup"].get("alone_points", 0)
    if not n:
        return []
    rng = random.Random(mix["order_seed"])
    longest = max(length_set(mix["output_tokens"], n))
    return [
        Request(i, 0.0, tuple(vocab.draw(rng, m)), longest)
        for i, m in enumerate(length_set(mix["prompt_tokens"], n))
    ]


def probe_requests(lengths: list[int], n_new: int, seed: int, vocab: Vocabulary) -> list[Request]:
    """The probes the reference judges: fixed lengths, words from the seed."""
    rng = random.Random(seed + 0x9E3779B9)
    return [Request(i, 0.0, tuple(vocab.draw(rng, m)), n_new)
            for i, m in enumerate(lengths)]
