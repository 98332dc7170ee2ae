"""What a cache kind's served programs are: the one home of that.

Every paged server dispatches one shape of program set over a cache whose
kind the config names (``config.cache_kind``): a group of an epoch's
prefill, one joining row's window, a decode chunk of ``--decode-chunk``
steps. A kind is a frozen ``CacheKind`` in ``KINDS``: how its cache is made,
the window function and the one-token step that are really its own (its
model module's), which operands its programs take, what rides back beside
the tokens, and the strings the programs are known by. Beside the records:

  * the three makers, written once (``prefill_program``, ``join_program``,
    ``decode_program``): the ``lru_cache``, the fused sampled decode scan,
    the carried counts, the ``tracked_jit`` wrapper;
  * ``served_programs``: a kind's programs with abstract operands for a
    geometry, for whoever lowers or compiles one without a server
    (``pool_audit.audit_programs``, ``tests/test_program_parts.py``,
    ``tests/lowered_programs.py``).

``runtime/batch_backend._PagedBackend`` drives any kind through its record;
which programs a server compiles is ``runtime/shapes.py``'s. A new cache
kind is its model module and one record here.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Callable, Mapping
from typing import NamedTuple

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import batch as B
from cake_tpu.models.llama import diffusion as D
from cake_tpu.models.llama import hybrid as H
from cake_tpu.models.llama import kinds as K
from cake_tpu.models.llama import latent as L
from cake_tpu.models.llama import latent_index as LI
from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import (
    ATTENTION, BLOCK_DIFFUSION, CACHE_KV, CACHE_KV_KINDS, CACHE_KV_STATE, CACHE_LATENT,
    CACHE_LATENT_INDEX, SPARSE, LlamaConfig,
)
from cake_tpu.models.llama.fused import sampled_decode_scan
from cake_tpu.models.llama.paged_cache import init_paged_cache
from cake_tpu.obs.jitwatch import tracked_jit
from cake_tpu.ops.fuse import fuse_params, resolve_fusion

# Operands of the programs, by role and in the jitted functions' order. A
# record's flags and ``window_operands`` say which a kind's programs take.
_SUFFIX = ("params", "tokens", "cache", "pads", "write_starts", "tables",
           "config", "start")
# The forms every kind's ``engine.state`` reports (None without state layers).
_STATE_FORMS = (
    ("state", "window_form", lambda c, page, allow: H.window_form(c, allow)),
    ("state", "step_form", lambda c, page, allow: H.step_form(c, allow)),
)


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One cache kind's say on its served programs (module docstring)."""

    name: str  # ``config.cache_kind``
    # ``tracked_jit``'s strings: ``batch.<label>_decode[...]`` is the series
    # jitwatch counts, ``jit_decode_chunk_<module>`` / ``jit_prefill_join_
    # <module>`` / ``jit_prefill_<module>`` what a device trace, the
    # benchmark's readers and the persistent compile cache know a program by.
    label: str
    module: str
    init_params: Callable  # (config, key, dtype)
    # (config, lanes, n_pages, page_size, dtype); ``n_pages`` one number, or
    # one a pool where ``pools_by_kind``
    init_cache: Callable
    # Every prefill, an epoch's group and a joiner's: (params, tokens, cache,
    # pads, ends, tables, config, **window_operands, allow_pallas) ->
    # (logits, cache, *count vectors)
    window: Callable
    # (params, pads, tables, live, config, capacity, allow_pallas) -> a
    # decode step (tok, cache, slot) -> (logits, cache, *count vectors)
    forward_one: Callable
    token_bytes: Callable  # (config, dtype) -> (needed, stored) a cached token
    pools: Callable  # cache -> the arrays no program may slice, stack or copy
    # A join's scalar operands behind its table row, in order.
    window_operands: tuple[str, ...] = ("start",)
    # Whether the decode chunk takes ``valid`` [lanes]: a lane that holds no
    # pages keeps its state and takes no expert's rows.
    masks_lanes: bool = True
    # Whether the decode chunk closes over the epoch's capacity in slots
    # (position grids as wide as the table operand).
    closes_over_capacity: bool = False
    # Whether a window's table operand is cut to the epoch's capacity like a
    # decode chunk's (plain K and V reads the pool's prefix back through a
    # view that wide: ``_PagedBackend``'s one-capacity rule); a whole row
    # otherwise.
    capped_windows: bool = False
    # A pool, an allocator and a block table a kind of attention layer
    # (``paged_cache.PagePools``; a windowed kind's pool sized by the lanes):
    # the tables operand is a tuple.
    pools_by_kind: bool = False
    # cache -> the per-lane state beside the pools (None: the kind has none).
    lane_state: Callable | None = None
    # Whether a layer's write into the pool may be the kernel
    # (ops/pallas/paged_write.py); a latent's is one row a token, a scatter.
    pool_write_kernel: bool = True
    # What the programs return beside the tokens, one vector an account, in
    # order (``latent.ExpertAccount``, ``latent_index.IndexAccount``).
    accounts: tuple[type, ...] = ()
    # Which models of the kind return them (``accounts_of``): a kind whose
    # models may have no layer to count returns none for those.
    counts_where: Callable = lambda config: True
    # (``/stats`` section, key, predicate(config, page_size, allow_pallas)).
    forms: tuple[tuple[str, str, Callable], ...] = _STATE_FORMS
    # (config, allocator, page_size, dtype) -> what ``engine.cache`` adds.
    cache_facts: Callable = lambda config, allocator, page_size, dtype: {}
    # Programs beside the three, or in their place: name -> (the jitted
    # function, its operands by role).
    more_programs: Mapping[str, tuple[Callable, tuple[str, ...]]] = dataclasses.field(
        default_factory=dict, hash=False, compare=False
    )
    rows_window: Callable | None = None  # ``join_rows_program``'s: R joining rows, a lane each

    def accounts_of(self, config: LlamaConfig) -> tuple[type, ...]:
        """The accounts this model's programs return beside their tokens."""
        return self.accounts if self.counts_where(config) else ()


def _kv_token_bytes(layers: Callable) -> Callable:
    def token_bytes(config, dtype):
        per = 2 * config.num_key_value_heads * config.head_dim * len(layers(config))
        return 2 * (per * jnp.dtype(dtype).itemsize,)

    return token_bytes


def _kv_window(params, tokens, cache, pads, ends, tables, config, allow_pallas=True):
    return B.paged_prefill(
        params, tokens, cache, pads, tables, config,
        ends=ends, seq_len=ends[0], allow_pallas=allow_pallas,
    )


def _latent_index_bytes(config, dtype):
    per = LI.cache_bytes_per_token(config, dtype)
    return per["latent_needed"] + per["index"], per["latent"] + per["index"]


def _kinds_cache_facts(config, allocator, page_size, dtype) -> dict:
    """``engine.cache`` with the pools a kind: ``kinds.<kind>`` as
    ``PagePools.facts`` says, ``cached_tokens`` what the lanes hold storage
    for now, ``bytes`` all pools'."""
    kinds = allocator.facts(K.bytes_per_page(config, page_size, dtype))
    return {
        "bytes": sum(k["pages_total"] * k["bytes_per_page"] for k in kinds.values()),
        "kinds": kinds, "cached_tokens": allocator.cached_tokens(),
    }


_ALL = (
    CacheKind(
        name=CACHE_KV, label="paged", module="paged",
        init_params=M.init_params,
        init_cache=lambda c, lanes, pages, page, dt: init_paged_cache(
            c.num_hidden_layers, pages, c.num_key_value_heads, page, c.head_dim, dt),
        window=_kv_window,
        forward_one=lambda params, pads, tables, live, c, capacity, allow: (
            B.paged_forward_one(params, pads, tables, c, capacity, allow_pallas=allow)),
        token_bytes=_kv_token_bytes(lambda c: c.layers_of(ATTENTION)),
        pools=lambda cache: (cache.k,),  # V is of K's shape: one reading finds both
        window_operands=(), masks_lanes=False, closes_over_capacity=True,
        capped_windows=True,
        # An epoch's prefill is ONE open-shape program; with a prefix cache
        # every window is a suffix program (``PagedLocalBackend``).
        more_programs={
            "prefill": (B._paged_prefill_jit,
                        ("params", "tokens", "cache", "pads", "tables", "config")),
            "suffix_join": (B._paged_suffix_join_jit, _SUFFIX),
            "suffix_prefill": (B._paged_suffix_jit, _SUFFIX),
        },
    ),
    CacheKind(
        name=CACHE_KV_STATE, label="hybrid", module="paged_hybrid",
        init_params=H.init_params, init_cache=H.init_hybrid_cache,
        window=H.hybrid_prefill, forward_one=H.hybrid_forward_one, rows_window=H.hybrid_join_rows,
        token_bytes=_kv_token_bytes(lambda c: c.layers_of(ATTENTION)),
        pools=lambda cache: (cache.kv.k,),
        window_operands=("start", "lane"), closes_over_capacity=True,
        # ``ssm`` is None where the mixer's state is its window alone
        lane_state=lambda cache: tuple(
            a for a in (cache.ssm, cache.conv) if a is not None),
        # routed experts beside state layers (``lfm2_moe``): the account for a
        # model that has a sparse layer, nothing for one that has none
        accounts=(L.ExpertAccount,), counts_where=lambda c: SPARSE in c.ff_kinds,
    ),
    CacheKind(
        name=CACHE_LATENT, label="latent", module="paged_latent",
        init_params=L.init_params,
        init_cache=lambda c, lanes, pages, page, dt: L.init_cache(c, pages, page, dt),
        window=L.latent_prefill, forward_one=L.latent_forward_one,
        token_bytes=lambda c, dt: tuple(
            L.cache_bytes_per_token(c, dt)[k] for k in ("needed", "stored")),
        pools=tuple, pool_write_kernel=False, accounts=(L.ExpertAccount,),
    ),
    CacheKind(
        name=CACHE_LATENT_INDEX, label="latent_index", module="paged_latent_index",
        init_params=L.init_params,
        init_cache=lambda c, lanes, pages, page, dt: LI.init_cache(c, pages, page, dt),
        window=LI.latent_index_prefill, forward_one=LI.latent_index_forward_one,
        token_bytes=_latent_index_bytes, pools=tuple, pool_write_kernel=False,
        accounts=(L.ExpertAccount, LI.IndexAccount),
        forms=(
            *_STATE_FORMS,
            ("sparse", "scores_form", LI.scores_form),
            ("sparse", "select_form", LI.select_form),
        ),
        # ``bytes_per_token`` stays the sum; what of it is the latent and
        # what the index key
        cache_facts=lambda c, allocator, page, dt: {"bytes_per_token_by": {
            k: LI.cache_bytes_per_token(c, dt)[k] for k in ("latent", "index")}},
    ),
    CacheKind(
        name=CACHE_KV_KINDS, label="kinds", module="paged_kinds",
        init_params=K.init_params,
        init_cache=lambda c, lanes, pages, page, dt: K.init_cache(c, pages, page, dt),
        window=K.kinds_prefill, forward_one=K.kinds_forward_one,
        # the FIRST kind's layers keep a token for the lane's life; a windowed
        # kind's keep the window's tokens, a constant a lane
        token_bytes=_kv_token_bytes(lambda c: c.kind_layers(c.attention_kinds[0])),
        pools=lambda cache: tuple(a for pool in cache.pools for a in pool),
        pools_by_kind=True, accounts=(L.ExpertAccount,),
        cache_facts=_kinds_cache_facts,
    ),
)
KINDS: dict[str, CacheKind] = {kind.name: kind for kind in _ALL}
# Plain K and V under a model that generates by diffusion over blocks
# (``config.generation``; models/llama/diffusion.py): the same cache, pool,
# pages and strings, and the generation's own programs over them: every
# window under the block-causal mask from a start slot (the closed shapes'
# windows), a decode dispatch of whole blocks (``block_decode_program``) that
# masks dead lanes out of the experts' rows, and the two accounts riding back.
# No cache kind of its own: ``kind_of`` hands it out for ``CACHE_KV``.
_KV_BLOCKS = dataclasses.replace(
    KINDS[CACHE_KV], window=D.block_window, forward_one=None,
    window_operands=("start",), masks_lanes=True, capped_windows=False,
    accounts=(L.ExpertAccount, D.DiffusionAccount), more_programs={},
)


def kind_of(config: LlamaConfig) -> CacheKind:
    """The record a model's served programs are made by: its cache kind's,
    and for plain K and V also its generation kind's say."""
    if config.generation == BLOCK_DIFFUSION:
        assert config.cache_kind == CACHE_KV, config.cache_kind
        return _KV_BLOCKS
    return KINDS[config.cache_kind]


def pool_pages(config: LlamaConfig, max_pages: int, page_size: int, lanes: int):
    """Pages of each pool of a ``pools_by_kind`` model, in ``config.
    attention_kinds``' order: ``max_pages`` for a kind that stores every
    token (what admission is priced in), ``window // page_size + 2`` a lane
    for a windowed kind: the most a lane maps of it at a time (the window,
    the page its start lies in and the page the next chunk writes)."""
    windows = (config.kind_window(kind) for kind in config.attention_kinds)
    return tuple(
        max_pages if w is None else max(1, lanes) * (-(-w // page_size) + 2)
        for w in windows
    )


# ------------------------------------------------------------- the makers


@functools.lru_cache(maxsize=None)
def prefill_program(kind: CacheKind):
    """One group of an epoch's prefill: the kind's window function over the
    group's own lanes' table rows, the cache donated. One compile a (rows,
    width); ``config`` and ``allow_pallas`` are static."""
    return tracked_jit(
        kind.window, name=f"batch.{kind.label}_prefill",
        module=f"prefill_{kind.module}",
        static_argnames=("config", "allow_pallas"), donate_argnames=("cache",),
    )


@functools.lru_cache(maxsize=32)
def join_program(kind: CacheKind, config: LlamaConfig, width: int, allow_pallas: bool = True):
    """One joining (or restored) row's prefill through its lane's table row
    into the shared pool: the same arithmetic as the epoch's, one row, its
    own jit so that a join is a program of its own name. One compile per
    window width."""

    def run(params, cache, tokens, pads1, ends1, lane_table, *operands):
        return kind.window(
            params, tokens, cache, pads1, ends1, lane_table, config,
            **dict(zip(kind.window_operands, operands, strict=True)),
            allow_pallas=allow_pallas,
        )

    return tracked_jit(
        run, name=f"batch.{kind.label}_join[w={width}]",
        module=f"prefill_join_{kind.module}", donate_argnums=(1,),
    )


@functools.lru_cache(maxsize=16)
def decode_program(
    kind: CacheKind,
    config: LlamaConfig,
    capacity: int | None,  # in slots, where the kind closes over it
    n_steps: int,
    temperature: float,
    top_k,
    top_p,
    repeat_penalty: float,
    allow_pallas: bool = True,
):
    """Jit one fused batch-decode scan over the kind's one-token step: the
    whole cache its carried, donated state, the block table a traced operand
    (it changes at chunk boundaries, joins, page growth and releases, without
    retracing). Returns the scan's five values and, where the kind's
    programs count, the chunk's count vectors as one."""
    fusions, fimpl = resolve_fusion(config, allow_pallas)
    tail_impl = fimpl if "tail" in fusions else None
    accounts = kind.accounts_of(config)

    def run(params, cache, tok, slot, pads, block_tables, *rest):
        *valid, key, ring, ring_idx = rest
        step = kind.forward_one(
            params, pads, block_tables, valid[0][:, None] if valid else None,
            config, capacity, allow_pallas,
        )

        def forward_one(tok, carry, slot):
            cache, *totals = carry
            logits, cache, *counts = step(tok, cache, slot)
            return logits, (cache, *(
                a.add(total, c) for a, total, c in zip(accounts, totals, counts, strict=True)
            ))

        zeros = (jnp.zeros((len(a.names),), jnp.int32) for a in accounts)
        toks, (cache, *totals), key, ring, ring_idx = sampled_decode_scan(
            forward_one, (cache, *zeros), tok, slot, key, ring, ring_idx,
            n_steps=n_steps, temperature=temperature, top_k=top_k,
            top_p=top_p, repeat_penalty=repeat_penalty, tail_impl=tail_impl,
        )
        out = (toks, cache, key, ring, ring_idx)
        if not totals:
            return out
        return (*out, totals[0] if len(totals) == 1 else jnp.concatenate(totals))

    fu = f",fu={config.fusion_impl}" if fusions else ""
    return tracked_jit(
        run,
        name=(
            f"batch.{kind.label}_decode[n={n_steps},t={temperature},k={top_k},"
            f"p={top_p},rp={repeat_penalty}{fu}]"
        ),
        module=f"decode_chunk_{kind.module}",
        donate_argnums=(1,),
    )


# ------------------------------------------------- with abstract operands


class Served(NamedTuple):
    """``served_programs``' answer: the abstract weights and cache, and a
    thunk a program that traces it from them."""

    params: M.Params
    cache: object
    programs: dict[str, Callable]


def served_programs(
    config: LlamaConfig,
    *,
    n_pages: int | tuple[int, ...],
    page_size: int,
    lanes: int,
    table_pages: int,
    n_steps: int,
    width: int,
    prefill_rows: int = 2, join_rows: int = 1,
    dtype=jnp.bfloat16,
    allow_pallas: bool = True,
    sharding=None,
) -> Served:
    """The programs a paged server of this model's cache kind dispatches,
    from ``jax.ShapeDtypeStruct`` operands: no weights, no pool, nothing on
    a device. ``decode``: a greedy chunk of ``n_steps`` over ``lanes`` rows
    with a block table of ``table_pages`` pages a row; ``join``: one row's
    window of ``width`` slots; ``prefill``: one group of ``prefill_rows``
    rows of an epoch's, as wide; and what else the kind's record names.
    ``n_pages`` sizes the pool (one a pool, or the first with the others as
    ``pool_pages`` sizes them, where ``pools_by_kind``); ``sharding`` names
    the device to compile for (one that is described and not attached will
    do). ``programs[name]()`` is ``jit(...).trace(...)``'s result."""
    kind = kind_of(config)
    blocks = config.generation == BLOCK_DIFFUSION

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def abstract(build):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), jax.eval_shape(build))

    if kind.pools_by_kind and not isinstance(n_pages, tuple):
        n_pages = pool_pages(config, n_pages, page_size, lanes)
    params = abstract(lambda: fuse_params(
        kind.init_params(config, jax.random.PRNGKey(0), dtype)))
    cache = abstract(lambda: kind.init_cache(config, lanes, n_pages, page_size, dtype))

    def operands(roles, rows):
        table = spec((rows, table_pages))
        have = {
            "params": params, "cache": cache, "config": config,
            "tokens": spec((rows, width)), "slot": spec(()),
            "tok": spec((rows, config.block_length) if blocks else (rows,)),
            "pads": spec((rows,)), "ends": spec((rows,)),
            "write_starts": spec((rows,)), "start": spec(()), "lane": spec(()), "lanes": spec((rows,)),
            "tables": tuple(table for _ in n_pages) if kind.pools_by_kind else table,
            "valid": spec((rows,), jnp.bool_), "key": spec((rows, 2), jnp.uint32),
            "ring": spec((rows, 0)), "ring_idx": spec((rows,)),
        }
        return [have[role] for role in roles]

    window = ("pads", "ends", "tables")
    decode = block_decode_program(
        kind, config, n_steps, 0.0, None, None, allow_pallas
    ) if blocks else decode_program(
        kind, config, table_pages * page_size if kind.closes_over_capacity else None,
        n_steps, 0.0, None, None, 1.0, allow_pallas,
    )
    programs = {
        "decode": (decode, (
            "params", "cache", "tok", "slot", "pads", "tables",
            *(("valid",) if kind.masks_lanes else ()), "key",
            *(() if blocks else ("ring", "ring_idx")))),
        "join": (join_program(kind, config, width, allow_pallas),
                 ("params", "cache", "tokens", *window, *kind.window_operands)),
        "prefill": (prefill_program(kind),
                    ("params", "tokens", "cache", *window, "config", *kind.window_operands)),
        **kind.more_programs, **_rows_programs(kind, config, join_rows, width, allow_pallas),
    }

    def thunk(name, fn, roles):
        rows = {"decode": lanes, "join_rows": join_rows}.get(name, 1 if name.endswith("join") else prefill_rows)
        static = {"allow_pallas": allow_pallas} if "config" in roles else {}
        return lambda: fn._jitted.trace(*operands(roles, rows), **static)

    return Served(params, cache, {
        name: thunk(name, fn, roles) for name, (fn, roles) in programs.items()
    })


# ------------------------------------------------- the joiners of one step
# (below everything else: a Mosaic kernel's payload carries its callers' line
# numbers, so a line that moves above a served program's frame is a new
# compile-cache key for every cell: PERF.md section 7, row 17)


@functools.lru_cache(maxsize=8)
def join_rows_program(
    kind: CacheKind, config: LlamaConfig, rows: int, width: int, allow_pallas: bool = True
):
    """``join_program`` for the joiners one step accepted together, where the
    kind has a window of rows with a lane each (``rows_window``): ``rows``
    rows of ``width`` slots, one program, a join by its module's name. Its
    operands are the join's with ``lanes`` [rows] in the lane's place. One
    compile a (rows, width): which of them exist is ``shapes.join_widths``."""

    def run(params, cache, tokens, pads, ends, tables, start, lanes):
        return kind.rows_window(
            params, tokens, cache, pads, ends, tables, config,
            start=start, lanes=lanes, allow_pallas=allow_pallas,
        )

    return tracked_jit(
        run, name=f"batch.{kind.label}_join[r={rows},w={width}]",
        module=f"prefill_join_{kind.module}", donate_argnums=(1,),
    )


def _rows_programs(kind, config, rows, width, allow_pallas) -> dict:
    """``served_programs``' entry for the group of ``rows`` joining rows."""
    if rows < 2 or kind.rows_window is None:
        return {}
    return {"join_rows": (
        join_rows_program(kind, config, rows, width, allow_pallas),
        ("params", "cache", "tokens", "pads", "ends", "tables", "start", "lanes"),
    )}


# --------------------------------------------- a dispatch of whole blocks


@functools.lru_cache(maxsize=16)
def block_decode_program(
    kind: CacheKind,
    config: LlamaConfig,
    n_steps: int,  # slots a dispatch advances: whole blocks
    temperature: float,
    top_k,
    top_p,
    allow_pallas: bool = True,
):
    """``decode_program`` for a model that generates by diffusion over blocks
    (``diffusion.block_decode``): ``n_steps // block_length`` blocks of
    ``denoising_steps`` passes and a commit, the cache donated, the block
    table a traced operand. Operands: the first block's known tokens
    ``[lanes, B]`` in the last token's place, ``valid`` [lanes], the rows'
    keys; no penalty ring (``--repeat-penalty`` is refused). Returns (tokens
    [lanes, n_steps], cache, keys, the two accounts' counts as one vector)."""

    def run(params, cache, known, slot, pads, block_tables, valid, keys):
        return D.block_decode(
            params, cache, known, slot, pads, block_tables, valid, keys, config,
            n_steps=n_steps, temperature=temperature, top_k=top_k, top_p=top_p,
            allow_pallas=allow_pallas,
        )

    return tracked_jit(
        run,
        name=(
            f"batch.{kind.label}_decode[n={n_steps},t={temperature},k={top_k},"
            f"p={top_p},steps={config.denoising_steps},remask={config.remask}]"
        ),
        module=f"decode_chunk_{kind.module}",
        donate_argnums=(1,),
    )
