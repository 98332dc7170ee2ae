"""Which programs a server compiles: the one home of that decision.

A served program's key is lanes x prompt width x capacity x one-row window x
decode tail. One frozen value type answers for every part; the backend owns
its instance (``backend.shapes``) and the engine reads it from there. Two
instances that differ in data, picked by ``for_model`` from the config alone:

  * OPEN (the defaults): prompt widths in 16s (``prompt_bucket``), one-row
    windows in 64s from slot 0, capacities in 256s. A server meets new
    programs for as long as it runs (PERF.md: 45% of Mistral's window is
    compile stall) and runs none ahead.
  * CLOSED, for a model whose cache is not plain K and V (state layers:
    programs that compile in 3 to 10 s each; a latent pool or a pool a kind
    of attention layer: its prefill computes a window's own K and V, so a
    window is a whole prompt): eleven window widths (six for pools a kind,
    and for state layers beside routed experts) and three capacities, shares of a lane's table. An epoch's prefill is right-padded to a width (a dead tail under
    ``ends``: the recurrence stands still there), a joiner's window is as
    wide as its prompt and ends at the shared slot, and the few dozen
    programs there are run once at start-up (``programs``).

Closing the set for every model is ROADMAP S2: a ``perf_opt`` that changes
the open instance's tables and is measured on both cells.
"""

from __future__ import annotations

import dataclasses

from cake_tpu.models.llama.batch import prompt_bucket
from cake_tpu.models.llama.config import (
    CACHE_KV, CACHE_KV_KINDS, CACHE_KV_STATE, CACHE_LATENT, CACHE_LATENT_INDEX,
    GATED_DELTA, SPARSE,
)

# The closed tables as shares of a lane's table: widths in 64ths of its
# slots, capacities in quarters of its pages.
_WIDTH_64THS = (1, 2, 4, 8, 12, 16, 24, 32, 40, 48, 64)
# Pools a kind: six widths, not eleven. A program of Laguna's holds a body a
# run of layers and a megabyte of code a large product in it: a join compiles
# to 19 to 33 MB (compiled for a described v5e, PERF.md section 4) and takes
# 17 s to compile, so the set can never sit in a 192 MiB compile cache and
# every start pays for every program it runs ahead.
# A latent pool beside an index's keys: six too, eighths of the table so that
# every width is whole 128s on a 168-page table (2,688, 5,376, 8,064, 13,440,
# 18,816 and 21,504 slots: a window's attention kernel tiles its keys by
# 128s, ops/pallas/masked_prefill.py), placed for prompts of 2,048 to 16,384
# tokens (18,816 holds the longest with its template; the cell's probes of
# 300, 3,000 and 8,000 tokens take the first, second and third). A join is
# 27 MB of code (compiled for a described v5e, PERF.md section 4).
_WIDTH_64THS_BY_KIND = {
    CACHE_KV_KINDS: (4, 8, 16, 32, 48, 64),
    CACHE_LATENT_INDEX: (8, 16, 24, 40, 56, 64),
}
# State layers beside routed experts (``kv+state`` with a sparse feed-forward:
# ``lfm2_moe``): PROGRAMS ARE DEAR AND DEAD LANES CHEAP, and one rule follows
# (``_dear_programs``). The body of every run of layers but the first holds
# the grouped experts' sort and three grouped products, so a program is code
# by the run: at nine runs, compiled for a described v5e at 64 lanes of 32
# pages, a decode chunk is 11.8 MB, a join 9.1 (64 slots) to 28.6 (4,096), an
# epoch's group 21.6 to 23.4 (PERF.md section 4): the kind's eleven joins,
# eleven groups and three chunks are 490 MB for a 192 MiB compile cache, and
# each is 8 to 20 s of compile that stalls every live stream when it is met
# inside a window (16 to 22 of them in 51 s: my chip call 2, PR 48). So:
#   * six widths (these shares), not the kind's eleven;
#   * an epoch's rows ONE a program, which is then the join's program of that
#     width (``one_row_prefill_is_join``): no group programs at all. The
#     experts somebody chose are read once a program either way, so 64 rows
#     of 400 tokens one at a time stream them 64 times: 0.8 s beside 0.4 s of
#     products at the chip's peak, once a segment;
#   * every epoch ``max_batch`` lanes wide (``whole_batch``), the chunks that
#     run into a capacity warmed too: a hybrid's lane state has a lane axis,
#     so its programs are compiled a lane count, and a dead lane here costs
#     next to nothing (it takes no expert's rows and the attention kernel
#     walks one slot of it). The closed set is then ALL the model runs.
_DEAR_WIDTH_64THS = (4, 8, 16, 32, 48, 64)


def _dear_programs(config) -> bool:
    return config.cache_kind == CACHE_KV_STATE and SPARSE in config.ff_kinds


_CAPACITY_QUARTERS = (1, 2, 4)
# Tokens a prefill program may hold, by cache kind. State layers: the mixer's
# float32 intermediates are [rows, width, d_inner] several times over, so an
# epoch of 32 lanes x 2080 slots would need 8.6 GB of temporaries beside 6.5
# GB of arguments (compiled for a described v5e); 16k tokens need 2.1 to 2.7.
# A latent pool: the expanded K and V of a window are [tokens, heads, 256]
# each and the grouped experts' rows [tokens * top_k, hidden]; a joiner's
# window is one row of up to a lane's table, and an epoch's groups are held
# to the same (PERF.md section 4 has the bytes, compiled for a described v5e).
# A delta-rule mixer's chunkwise form AS ITS XLA TWIN holds q, k, v, its two
# pseudo-value products and o in float32 a head ([tokens, H, 128 or 256] each)
# beside the chunks' triangles: 0.29 MB a token at Olmo-Hybrid's widths, 4.8
# GB at 16k tokens beside 11.6 GB of arguments (compiled for a described
# v5e), 2.4 at 8k. Since PR 35 a window at widths that tile is a kernel
# (ops/pallas/delta_rule.py) whose triangles never leave VMEM: the 0.29 MB a
# token is the twin's alone (an epoch's group of 2 x 2560 compiles to 0.89 GB
# of temporaries for the twin's 1.41), and 8,192 stands until a PR of its own
# re-measures it.
# Pools a kind (models/llama/kinds.py): a window attends over its own K and V
# ([tokens, heads, 128]: 72 heads at Laguna's widest kind, 18 KB a token for
# q, as much for the gated output, 4 KB for K and V, 6 KB for a residual
# copy or two), and its tail runs 2,048 tokens at a time whatever the width
# (``kinds._TAIL_TOKENS``), so the widest join's temporaries are its
# attention's: compiled for a v5e about 155 KB a token (2.9 GB at 18,432
# slots, 4.1 at 24,576), which is what an epoch's groups are held to as well.
# A latent pool beside an index's keys: a window goes through its layer a
# block of at most 2,048 tokens at a time whatever its width (a block's index
# scores and attention scores are [block, keys], and the grouped experts'
# combine rows x tokens), so rows share a program only where all of them fit
# one block; a wider row is a program of its own.
_PREFILL_TOKENS = {
    CACHE_KV_STATE: 16384, CACHE_LATENT: 4096, CACHE_KV_KINDS: 16384,
    CACHE_LATENT_INDEX: 2048,
}
_PREFILL_TOKENS_BY_MIXER = {GATED_DELTA: 8192}


def _prefill_tokens(config) -> int:
    # only a kv+state configuration names another mixer than the default
    return _PREFILL_TOKENS_BY_MIXER.get(config.state_mixer, _PREFILL_TOKENS[config.cache_kind])


def _ceil_to(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def _at_least(table: tuple[int, ...], x: int) -> int:
    """The least entry of an ascending table that holds ``x``; ``x`` itself
    when none does (an open table is empty)."""
    return next((t for t in table if t >= x), x)


@dataclasses.dataclass(frozen=True)
class ProgramShapes:
    window_multiple: int = 64
    capacity_multiple: int = 256
    # The closed sets, ascending, in slots; empty = open.
    widths: tuple[int, ...] = ()
    capacities: tuple[int, ...] = ()
    # None: an epoch's prefill is one program, whatever it holds.
    prefill_tokens: int | None = None
    # A one-row group of an epoch's prefill IS the join's program of that
    # width (the backend dispatches it so: ``_PagedBackend.prefill``), and
    # is not run ahead a second time.
    one_row_prefill_is_join: bool = False
    # Every epoch has ``max_batch`` lanes, whatever seeds it (``lanes``), and
    # the chunk that runs into an epoch's capacity is warmed with the others
    # (``programs``: "decode_tail").
    whole_batch: bool = False

    @classmethod
    def for_model(cls, config, page_size: int = 0, pages_per_seq: int = 0):
        """The closed instance for a model whose cache is not plain K and V
        (``config.cache_kind``), the open one otherwise (a dense backend has
        no table to pass). At 32 pages of 128
        the sets are 64, 128, 256, 512, 768, 1024, 1536, 2048, 2560, 3072,
        4096 slots and 8, 16, 32 pages (``jamba2-3b-chat-closed``'s and
        ``olmo-hybrid-7b-chat-closed``'s, and at 64 lanes
        ``pangu-ultra-ep16-chat-closed``'s); at any other geometry they are
        as closed."""
        if config.cache_kind == CACHE_KV:
            return cls()
        slots = page_size * pages_per_seq
        dear = _dear_programs(config)
        shares = _DEAR_WIDTH_64THS if dear else (
            _WIDTH_64THS_BY_KIND.get(config.cache_kind, _WIDTH_64THS))
        widths = {min(slots, -(-slots * f // (64 * 64)) * 64) for f in shares}
        pages = {max(1, -(-pages_per_seq * q // 4)) for q in _CAPACITY_QUARTERS}
        return cls(
            widths=tuple(sorted(widths)),
            capacities=tuple(p * page_size for p in sorted(pages)),
            prefill_tokens=1 if dear else _prefill_tokens(config),
            one_row_prefill_is_join=dear or config.cache_kind == CACHE_LATENT_INDEX,
            whole_batch=dear,
        )

    def lanes(self, n_seed: int, max_batch: int) -> int:
        """Lanes of an epoch seeded with ``n_seed`` rows: the next power of
        two, doubled once (joins need free lanes), capped (light load must
        not pay ``max_batch``-wide programs); all of them where the model's
        programs are dear and its dead lanes cheap (``whole_batch``)."""
        if self.whole_batch:
            return max_batch
        b = 1
        while b < n_seed:
            b *= 2
        return min(max(b * 2, 2), max_batch)

    def prompt_width(self, longest: int, max_seq_len: int) -> int:
        """The shared left-pad bucket, and so the shared slot, of a batch
        whose longest prompt is ``longest`` (``layout_prompts`` asks the same
        helper)."""
        return prompt_bucket(longest, max_seq_len)

    def program_width(self, bucket: int) -> int:
        """The width an epoch's prefill program is compiled for."""
        return _at_least(self.widths, bucket)

    def capacity(self, reach: int, max_seq_len: int) -> int:
        """An epoch's attention capacity in slots, for rows that reach slot
        ``reach``. (Closed: three, not one. The capacity also ends a segment,
        and one that began with one request has two lanes while it lives:
        with the whole table every time, Jamba's cell served 140 tokens/s,
        not 500.)"""
        slots = min(max_seq_len, _ceil_to(reach, self.capacity_multiple))
        return _at_least(self.capacities, slots)

    def window(
        self, fresh: int, slot: int, limit: int, *, reads_pool: bool = False
    ) -> tuple[int, int]:
        """(start, width) of the program that computes a row's slots
        [``fresh``, ``slot``) so that it ends at the shared slot. Closed: as
        wide as that span, ending at the slot; a span the slot cannot hold
        starts at 0 and leaves a dead tail. Open over the pool's prefix
        (``reads_pool``: a prefix cache's suffix programs): ending at the
        slot, never wider than it. Open otherwise: from slot 0, since the
        plain join takes no start, at most ``limit`` wide."""
        if self.widths:
            width = _at_least(self.widths, slot - fresh)
            return max(0, slot - width), width
        if reads_pool:
            width = min(_ceil_to(slot - fresh, self.window_multiple), slot)
            return slot - width, width
        return 0, min(_ceil_to(slot, self.window_multiple), limit)

    def decode_steps(self, chunk: int, cap: int, slot: int) -> int:
        """A chunk, or what is left under the epoch's slot ceiling."""
        return min(chunk, cap - 1 - slot)

    def prefill_group(self, rows: int, width: int) -> int:
        """Rows (a power of two) one prefill program takes of an epoch's."""
        group = rows
        while self.prefill_tokens and group > 1 and (
            group * width > self.prefill_tokens
        ):
            group //= 2
        return group

    def programs(self, lanes: int) -> tuple[tuple[str, int, int], ...]:
        """What a saturated server dispatches at ``lanes`` lanes, as
        (operation, rows, slots): an epoch's prefill and a join at each
        width, a decode chunk at each capacity. Empty when open: nothing can
        be run ahead of a set that has no end. "decode_tail": the chunk that
        runs into the capacity (``decode_steps``: what is left under the
        ceiling, a program of its own step count)."""
        out = []
        for width in self.widths:
            if not (self.one_row_prefill_is_join and self.prefill_group(lanes, width) == 1):
                out.append(("prefill", lanes, width))
            out.append(("join", 1, width))
        out += [("decode", lanes, c) for c in self.capacities]
        if self.whole_batch:
            out += [("decode_tail", lanes, c) for c in self.capacities]
        return tuple(out)
