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
# THE JOINERS OF ONE STEP AS ONE PROGRAM (``join_rows``, ``join_groups``). A
# join of this kind reads every expert its row touches, and at 200 tokens x 4
# of 32 that is all of them: 9.9 GB over 14 sparse layers, 12 ms at the HBM
# peak, of a one-row join's 18 to 23. A step's grant of join work is spent on
# two or three such rows, so they go as one program of ``_DEAR_JOIN_ROWS``
# rows at the widest row's width, rows the step did not fill dead, and the
# experts are read once. The chip's clock (``chip_smoke.py`` phase J: my chip
# call 1, PR 52; device ms a program at the cell's widths and geometry, rows
# x slots: live rows x tokens), a program alone | as many rows one a program:
#     1 x 256: 200 18.3      1 x 512: 300 23.1      1 x 1024: 600 34.4
#     2 x 256: 2x200 26.6 | 36.6     2 x 512: 2x300 38.6 | 46.2
#     3 x 256: 2x200 31.1 | 36.6, 3x200 33.4 | 54.9, 1x200 28.8
#     3 x 512: 2x300 48.5 | 46.2, 3x300 51.6 | 69.2, 3x200 48.3 | 54.9
#     4 x 256: 2x200 36.2 | 36.6, 3x200 38.6 | 54.9, 4x200 40.0 | 73.2
#     4 x 512: 3x300 62.5 | 69.2      3 x 1024: 2x600 90.7 | 68.9, 3x600 97.5 | 103.3
# A program is 10.7 ms whatever it holds (the experts' stream and the
# launch), a token 11.5 us, and a SLOT, live or dead, 20.5 us at 256 and 512
# slots and 24 at 1,024 (the attention kernel's grid steps over a row's whole
# table, the sorts, the projections and the combine's one-hot product, which
# is rows x tokens): dead slots are not free, so
#   * three rows: a step's grant (1,024 tokens in the cell) holds at most
#     three rows wider than 256 slots, the steps of three joiners are the
#     long periods that set the judged percentile, and a fourth row costs a
#     two-joiner step what it saves a four-joiner one (4 x 256 beside 3 x
#     256 above); two rows would leave the three-joiner steps at two programs;
#   * ONE group program, at 512 slots (``_DEAR_JOIN_64THS``; of 4,096): a
#     start-up program of this kind is 5 s of ``setup_s`` in the served
#     process even from the compile cache (trace, lower, fetch, load: 8 to 10
#     s under a probe, my chip call 5, PR 52; with programs at 256 AND 512
#     the cell's warm ``setup_s`` read 78.6 to 80.8 s for the parent's 68.2
#     to 68.5, my chip call 2), so the set grows by the one program most
#     steps can take: narrower rows go in it too (three rows of 200 tokens
#     48.3 | 54.9 above, where 3 x 256 would run 33.4), and at 1,024 slots
#     three rows run no faster than their rows one a program;
#   * a group is taken where the slots it adds to its rows' own windows (the
#     narrower rows' and the dead rows') are at most ``_DEAR_DEAD_SLOTS`` a
#     program saved: 10.7 ms / 20.5 us = 524. Two rows of 512 in three (512
#     added) run 48.5 for 46.2 on the device and save a launch and the host's
#     enqueue of a program: taken. A row of 512 and one of 256 (768 added)
#     would run 47 for 41, two of 256 (1,024 added) 46 for 37: one a program.
_DEAR_JOIN_ROWS = 3
_DEAR_JOIN_64THS = (8,)
_DEAR_DEAD_SLOTS = 512


def _dear_programs(config) -> bool:
    """State layers beside routed experts (above), or plain K and V under
    generation by diffusion over blocks (``sdar_moe``): a decode program of
    that kind holds two pass bodies (a denoising pass and the commit) of a
    layer scan with the grouped experts' sort and three grouped products, a
    join reads whole expert layers, and a dead lane takes no expert's rows:
    six widths, a row a program, every epoch ``max_batch`` wide, the tails
    warmed. Its joiners go one a program (no window of rows with a lane each
    is written for it)."""
    if getattr(config, "block_length", 0):
        return True
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
    # The joiners one step accepts go as one program of ``join_rows`` rows at
    # one of ``join_widths`` where ``join_groups`` says so (rows the step did
    # not fill are dead); 1: one a program, always.
    join_rows: int = 1
    join_widths: tuple[int, ...] = ()
    dead_slots: int = 0
    # A model that generates by diffusion over blocks: slots a block (every
    # width, capacity and decode dispatch is whole blocks); 0: one token a step.
    block: int = 0

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
        block = getattr(config, "block_length", 0)  # slots a block; 0: a token a step
        if config.cache_kind == CACHE_KV and not block:
            return cls()
        slots = page_size * pages_per_seq
        dear = _dear_programs(config)
        grouped = dear and not block  # a step's joiners as one program
        shares = _DEAR_WIDTH_64THS if dear else (
            _WIDTH_64THS_BY_KIND.get(config.cache_kind, _WIDTH_64THS))
        def in_slots(shares):
            return tuple(sorted({min(slots, -(-slots * f // (64 * 64)) * 64) for f in shares}))

        pages = {max(1, -(-pages_per_seq * q // 4)) for q in _CAPACITY_QUARTERS}
        return cls(
            widths=in_slots(shares),
            capacities=tuple(p * page_size for p in sorted(pages)),
            prefill_tokens=1 if dear else _prefill_tokens(config),
            one_row_prefill_is_join=dear or config.cache_kind == CACHE_LATENT_INDEX,
            whole_batch=dear,
            join_rows=_DEAR_JOIN_ROWS if grouped else 1,
            join_widths=in_slots(_DEAR_JOIN_64THS) if grouped else (),
            dead_slots=_DEAR_DEAD_SLOTS if grouped else 0,
            block=block,
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

    def join_width(self, widths: list[int]) -> int:
        """The width of the group program that holds rows whose own windows
        are ``widths`` wide: the least of ``join_widths`` that holds the
        widest (0: none does)."""
        return next((w for w in self.join_widths if w >= max(widths)), 0)

    def join_groups(self, widths: list[int]) -> list[list[int]]:
        """The programs one step's joiners go as, given each row's own window
        width (``window``), in the order they were accepted: lists of their
        indices, one a program. A list of two or more is one program of
        ``join_rows`` rows, ``join_width`` of its rows wide. Rows wider than
        the widest group program go alone; the others are taken ``join_rows``
        at a time, and such a run is one program where the slots that adds to
        the rows' own windows (the narrower rows' and the dead rows') are at
        most ``dead_slots`` a program saved, else one a program as before."""
        out: list[list[int]] = []
        run: list[int] = []

        def flush():
            if len(run) > 1:
                own = [widths[i] for i in run]
                added = self.join_rows * self.join_width(own) - sum(own)
                if added <= (len(run) - 1) * self.dead_slots:
                    out.append(list(run))
                    run.clear()
            out.extend([i] for i in run)
            run.clear()

        for i, width in enumerate(widths):
            if self.join_rows < 2 or not self.join_width([width]):
                out.append([i])
                continue
            run.append(i)
            if len(run) == self.join_rows:
                flush()
        flush()
        return sorted(out)  # by each program's first row

    def decode_steps(self, chunk: int, cap: int, slot: int) -> int:
        """A chunk, or what is left under the epoch's slot ceiling; whole
        blocks from ``slot`` on, the first unwritten slot, where the model
        generates by blocks (0 when no block is left: ``more``)."""
        if self.block:
            return min(chunk, cap - slot) // self.block * self.block
        return min(chunk, cap - 1 - slot)

    def more(self, cap: int, slot: int) -> bool:
        """Whether a lane at ``slot`` can take another step under ``cap``."""
        return slot + self.block <= cap if self.block else slot < cap - 1

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
        width, a step's joiners together at each of ``join_widths``, a decode
        chunk at each capacity. Empty when open: nothing can
        be run ahead of a set that has no end. "decode_tail": the chunk that
        runs into the capacity (``decode_steps``: what is left under the
        ceiling, a program of its own step count)."""
        out = []
        for width in self.widths:
            if not (self.one_row_prefill_is_join and self.prefill_group(lanes, width) == 1):
                out.append(("prefill", lanes, width))
            out.append(("join", 1, width))
        out += [("join", self.join_rows, width) for width in self.join_widths]
        out += [("decode", lanes, c) for c in self.capacities]
        if self.whole_batch:
            out += [("decode_tail", lanes, c) for c in self.capacities]
        return tuple(out)
