"""Does a served paged program move the KV pool? The check, in one place.

The paged pool ([n_layers, n_pages, n_kv, page_size, head_dim], K and V) is
most of what a server holds beside its weights, and a decode step reads a
few pages of it. A program that slices a layer out of it, stacks layers
back into it, or copies it pays one read and one write of gigabytes per
layer scan for nothing: on the v5e that was half of a decode step (PERF.md,
PR 26). The model's scan therefore carries the pool and writes it in place
(models/llama/batch.batched_blocks_forward, paged branch); this module is
how the tests (tests/test_paged_pool_carry.py, at a tiny size on the CPU)
and ``chip_smoke.py`` (phase P, at the benchmark cell's geometry on the
chip) find out whether that still holds:

  * ``audit_paged_programs`` builds the decode-chunk and suffix-join
    programs a ``PagedLocalBackend`` would dispatch, with abstract arguments
    — no weights, no pool, nothing on a device — compiles them and reports,
    for each, the two readings below with the compiler's
    ``temp_size_in_bytes`` beside one pool's bytes;
  * ``scans_moving_pool`` reads a jaxpr: a ``scan`` with a pool-shaped or
    layer-of-pool-shaped scanned input or stacked output is the defect;
  * ``pool_ops_in_hlo`` reads compiled HLO text: ``copy``, ``dynamic-slice``
    and ``dynamic-update-slice`` ops (fused or not) whose result is a pool
    or one layer of it.
"""

from __future__ import annotations

import math
import re
import time

import jax
import jax.numpy as jnp

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.config import LlamaConfig
from cake_tpu.models.llama.paged_cache import PagedKVCache

_HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
# A defining HLO line: `%name = type[dims]{layout} opcode(`; fusions carry
# the ops they hold in their names (`bitcast_dynamic-update-slice_fusion`).
_HLO_DEF = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^\s]*\s+([\w\-]+)\("
)
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice")
# The pool's write as a kernel (ops/pallas/paged_write.py) in compiled HLO.
_POOL_WRITE = re.compile(r"^\s*(?:ROOT\s+)?%?paged_pool_write[\w.\-]* = ", re.M)


def pool_shapes(kv_shape: tuple[int, ...]) -> tuple[tuple[int, ...], ...]:
    """The two shapes that must not be moved: the pool, and one layer."""
    return (tuple(kv_shape), tuple(kv_shape[1:]))


def audit_paged_programs(
    config: LlamaConfig,
    *,
    n_pages: int,
    page_size: int,
    lanes: int,
    table_pages: int,
    n_steps: int,
    join_width: int,
    dtype=jnp.bfloat16,
    allow_pallas: bool = True,
    sharding=None,
) -> dict[str, dict]:
    """{"decode": report, "suffix_join": report} for the two programs a
    saturated continuous-batching server runs (a decode chunk of ``n_steps``
    over ``lanes`` rows; one joining row's suffix prefill of ``join_width``
    slots), greedy, lowered from ``jax.ShapeDtypeStruct`` arguments with a
    block table of ``table_pages`` pages a row and compiled for the default
    backend, or for the device ``sharding`` names (one that is described and
    not attached will do). A report holds ``scans`` (``scans_moving_pool``),
    ``pool_ops`` (``pool_ops_in_hlo``), ``pool_writes`` (how many of its
    custom calls are the pool's write as a kernel: one a layer scan where
    the paged kernels run, none where the write is the scatter),
    ``temp_bytes`` (None where the backend gives no memory analysis),
    ``pool_bytes`` and ``seconds``."""
    from cake_tpu.models.llama.batch import (
        _paged_decode_fn,
        _paged_suffix_join_jit,
    )
    from cake_tpu.ops.fuse import fuse_params

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: fuse_params(
            M.init_params(config, jax.random.PRNGKey(0), dtype)
        )),
    )
    kv_shape = (
        config.num_hidden_layers, n_pages, config.num_key_value_heads,
        page_size, config.head_dim,
    )
    kv = PagedKVCache(k=spec(kv_shape, dtype), v=spec(kv_shape, dtype))
    decode = _paged_decode_fn(
        config, table_pages * page_size, n_steps, 0.0, None, None, 1.0,
        allow_pallas=allow_pallas,
    )
    programs = {
        "decode": (
            decode._jitted,
            (params, kv, spec((lanes,)), spec(()), spec((lanes,)),
             spec((lanes, table_pages)), spec((lanes, 2), jnp.uint32),
             spec((lanes, 0)), spec((lanes,))),
            {},
        ),
        "suffix_join": (
            _paged_suffix_join_jit._jitted,
            (params, spec((1, join_width)), kv, spec((1,)), spec((1,)),
             spec((1, table_pages)), config, spec(())),
            {"allow_pallas": allow_pallas},
        ),
    }
    reports = {}
    for name, (fn, args, kwargs) in programs.items():
        t0 = time.perf_counter()
        traced = fn.trace(*args, **kwargs)
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        reports[name] = {
            "scans": scans_moving_pool(traced.jaxpr, kv_shape),
            "pool_ops": pool_ops_in_hlo(hlo, kv_shape, dtype),
            "pool_writes": len(_POOL_WRITE.findall(hlo)),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "pool_bytes": math.prod(kv_shape) * jnp.dtype(dtype).itemsize,
            "seconds": round(time.perf_counter() - t0, 1),
        }
    return reports


def audit_hybrid_programs(
    config: LlamaConfig,
    *,
    n_pages: int,
    page_size: int,
    lanes: int,
    table_pages: int,
    n_steps: int,
    join_width: int,
    prefill_rows: int = 0,
    dtype=jnp.bfloat16,
    allow_pallas: bool = True,
    sharding=None,
) -> dict[str, dict]:
    """``audit_paged_programs`` for a model with state layers
    (models/llama/hybrid.py): {"decode": report, "join": report} for a decode
    chunk over ``lanes`` rows and one joining row's prefill. Beside the page
    pool's readings a report holds the lane state's: ``state_scans`` (a scan
    that takes ``ssm`` or ``conv``, or a layer of one, as a scanned input or
    gives one back stacked) and ``state_copies`` (a compiled ``copy`` of a
    whole state array). A layer reads and writes its own slice of the state
    in place, so slices and update-slices of ONE layer are the work itself
    and are not counted; ``temp_bytes`` beside ``state_bytes`` says whether
    a second state exists. With ``prefill_rows`` a third program, "prefill":
    one group of that many rows of an epoch's prefill, ``join_width`` wide,
    written at a lane offset."""
    from cake_tpu.models.llama import hybrid as H
    from cake_tpu.ops.fuse import fuse_params

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def abstract(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(lambda: fuse_params(
        H.init_params(config, jax.random.PRNGKey(0), dtype)
    )))
    cache = abstract(jax.eval_shape(
        lambda: H.init_hybrid_cache(config, lanes, n_pages, page_size, dtype)
    ))
    kv_shape = tuple(cache.kv.k.shape)
    weight_dims = {
        ",".join(map(str, a.shape)) for a in jax.tree.leaves(params["layers"])
    }
    state = {"ssm": (tuple(cache.ssm.shape), jnp.float32),
             "conv": (tuple(cache.conv.shape), dtype)}
    decode = H._hybrid_decode_fn(
        config, table_pages * page_size, n_steps, 0.0, None, None, 1.0,
        allow_pallas=allow_pallas,
    )
    join = H._hybrid_join_fn(config, join_width, allow_pallas)
    programs = {
        "decode": lambda: decode._jitted.trace(
            params, cache, spec((lanes,)), spec(()), spec((lanes,)),
            spec((lanes, table_pages)), spec((lanes,), jnp.bool_),
            spec((lanes, 2), jnp.uint32), spec((lanes, 0)), spec((lanes,)),
        ),
        "join": lambda: join._jitted.trace(
            params, cache, spec((1, join_width)), spec((1,)), spec((1,)),
            spec((1, table_pages)), spec(()), spec(()),
        ),
    }
    if prefill_rows:
        g = prefill_rows
        programs["prefill"] = lambda: H._hybrid_prefill_jit._jitted.trace(
            params, spec((g, join_width)), cache, spec((g,)), spec((g,)),
            spec((g, table_pages)), config, spec(()), spec(()),
            allow_pallas=allow_pallas,
        )
    reports = {}
    for name, trace in programs.items():
        t0 = time.perf_counter()
        traced = trace()
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        state_scans, state_copies = [], []
        for shape, dt in state.values():
            # A run's stacked weights are scanned inputs by design; at a
            # tiny size one may have the shape of a layer of the state.
            state_scans += [
                found for found in scans_moving_pool(traced.jaxpr, shape)
                if not any(f"[{dims}]" in found for dims in weight_dims)
            ]
            state_copies += [
                op for op in pool_ops_in_hlo(hlo, shape, dt)
                if op.startswith("copy") and f"[{','.join(map(str, shape))}]" in op
            ]
        reports[name] = {
            "scans": scans_moving_pool(traced.jaxpr, kv_shape),
            "pool_ops": pool_ops_in_hlo(hlo, kv_shape, dtype),
            "state_scans": state_scans,
            "state_copies": state_copies,
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            # the compiled program's own size: what 25 of them at start-up
            # must fit the compile cache with (PERF.md section 7, row 17)
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "pool_bytes": math.prod(kv_shape) * jnp.dtype(dtype).itemsize,
            "state_bytes": sum(
                math.prod(shape) * jnp.dtype(dt).itemsize
                for shape, dt in state.values()
            ),
            "kernels": hlo.count("tpu_custom_call"),
            "pool_writes": len(_POOL_WRITE.findall(hlo)),
            "seconds": round(time.perf_counter() - t0, 1),
        }
    return reports


def audit_latent_programs(
    config: LlamaConfig,
    *,
    n_pages: int,
    page_size: int,
    lanes: int,
    table_pages: int,
    n_steps: int,
    join_width: int,
    prefill_rows: int = 0,
    dtype=jnp.bfloat16,
    allow_pallas: bool = True,
    sharding=None,
    only: tuple[str, ...] = (),
) -> dict[str, dict]:
    """``audit_paged_programs`` for a model with latent attention
    (models/llama/latent.py): {"decode": report, "join": report} and, with
    ``prefill_rows``, "prefill" (one group of an epoch's); ``only`` names the
    programs wanted. The pool is the
    latent one, [n_layers, n_pages, page_size, latent_width] (and, for a model
    with a learned index, the pool of its keys); ``argument_bytes``
    and ``temp_bytes`` together are what the program needs on the device."""
    from cake_tpu.models.llama import latent as L
    from cake_tpu.ops.fuse import fuse_params

    if config.index_topk:
        # The index's keys ride behind the same table in a pool of their own
        # (models/llama/latent_index.py): neither pool may be copied.
        from cake_tpu.models.llama import latent_index as LI

        init_cache, prefill = LI.init_cache, LI._prefill_jit
        make_decode, make_join = LI._decode_fn, LI._join_fn
    else:
        init_cache, prefill = L.init_cache, L._latent_prefill_jit
        make_decode, make_join = L._latent_decode_fn, L._latent_join_fn

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def abstract(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(lambda: fuse_params(
        L.init_params(config, jax.random.PRNGKey(0), dtype)
    )))
    cache = abstract(jax.eval_shape(
        lambda: init_cache(config, n_pages, page_size, dtype)
    ))
    pool_shapes_ = [tuple(p.shape) for p in cache]
    decode = make_decode(
        config, n_steps, 0.0, None, None, 1.0, allow_pallas=allow_pallas
    )
    join = make_join(config, join_width, allow_pallas)
    programs = {
        "decode": lambda: decode._jitted.trace(
            params, cache, spec((lanes,)), spec(()), spec((lanes,)),
            spec((lanes, table_pages)), spec((lanes,), jnp.bool_),
            spec((lanes, 2), jnp.uint32), spec((lanes, 0)), spec((lanes,)),
        ),
        "join": lambda: join._jitted.trace(
            params, cache, spec((1, join_width)), spec((1,)), spec((1,)),
            spec((1, table_pages)), spec(()),
        ),
    }
    if prefill_rows:
        g = prefill_rows
        programs["prefill"] = lambda: prefill._jitted.trace(
            params, spec((g, join_width)), cache, spec((g,)), spec((g,)),
            spec((g, table_pages)), config, spec(()),
            allow_pallas=allow_pallas,
        )
    reports = {}
    for name, trace in programs.items():
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        traced = trace()
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        reports[name] = {
            "scans": [f for s in pool_shapes_ for f in scans_moving_pool(traced.jaxpr, s)],
            "pool_ops": [f for s in pool_shapes_ for f in pool_ops_in_hlo(hlo, s, dtype)],
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "pool_bytes": sum(
                math.prod(s) * jnp.dtype(dtype).itemsize for s in pool_shapes_
            ),
            "kernels": hlo.count("tpu_custom_call"),
            "seconds": round(time.perf_counter() - t0, 1),
        }
    return reports


def audit_kinds_programs(
    config: LlamaConfig,
    *,
    n_pages: tuple[int, ...],
    page_size: int,
    lanes: int,
    table_pages: int,
    n_steps: int,
    join_width: int,
    prefill_rows: int = 0,
    dtype=jnp.bfloat16,
    allow_pallas: bool = True,
    sharding=None,
) -> dict[str, dict]:
    """``audit_latent_programs`` for a model whose attention layers are of
    more than one kind (models/llama/kinds.py): the cache is a pool a kind
    (``n_pages[k]`` pages of kind k), NEITHER of which a program may slice,
    stack or copy, whole or a layer of it: ``scans`` and ``pool_ops`` list
    both kinds' findings, ``pool_bytes`` sums them."""
    from cake_tpu.models.llama import kinds as K
    from cake_tpu.ops.fuse import fuse_params

    def spec(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    def abstract(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(lambda: fuse_params(
        K.init_params(config, jax.random.PRNGKey(0), dtype)
    )))
    cache = abstract(jax.eval_shape(
        lambda: K.init_cache(config, n_pages, page_size, dtype)
    ))
    pool_shapes_ = [tuple(p.k.shape) for p in cache.pools]

    def tables(rows):
        return tuple(spec((rows, table_pages)) for _ in pool_shapes_)

    decode = K._kinds_decode_fn(
        config, n_steps, 0.0, None, None, 1.0, allow_pallas=allow_pallas
    )
    join = K._kinds_join_fn(config, join_width, allow_pallas)
    programs = {
        "decode": lambda: decode._jitted.trace(
            params, cache, spec((lanes,)), spec(()), spec((lanes,)),
            tables(lanes), spec((lanes,), jnp.bool_),
            spec((lanes, 2), jnp.uint32), spec((lanes, 0)), spec((lanes,)),
        ),
        "join": lambda: join._jitted.trace(
            params, cache, spec((1, join_width)), spec((1,)), spec((1,)),
            tables(1), spec(()),
        ),
    }
    if prefill_rows:
        g = prefill_rows
        programs["prefill"] = lambda: K._kinds_prefill_jit._jitted.trace(
            params, spec((g, join_width)), cache, spec((g,)), spec((g,)),
            tables(g), config, spec(()), allow_pallas=allow_pallas,
        )
    reports = {}
    for name, trace in programs.items():
        t0 = time.perf_counter()
        traced = trace()
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        reports[name] = {
            "scans": [f for s in pool_shapes_ for f in scans_moving_pool(traced.jaxpr, s)],
            "pool_ops": [f for s in pool_shapes_ for f in pool_ops_in_hlo(hlo, s, dtype)],
            "pool_writes": len(_POOL_WRITE.findall(hlo)),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "pool_bytes": sum(
                2 * math.prod(s) * jnp.dtype(dtype).itemsize for s in pool_shapes_
            ),
            "kernels": hlo.count("tpu_custom_call"),
            "seconds": round(time.perf_counter() - t0, 1),
        }
    return reports


def scans_moving_pool(jaxpr, kv_shape: tuple[int, ...]) -> list[str]:
    """Every ``scan`` in ``jaxpr`` (nested ones included) that takes the
    pool, or a layer of it, as a scanned input or gives one back as a
    stacked output. A scanned input of shape [n, ...] is layer-shaped inside
    the body and pool-shaped outside: both are named."""
    shapes = set(pool_shapes(kv_shape))
    found: list[str] = []

    def walk(jp, where):
        for eqn in jp.eqns:
            here = f"{where}/{eqn.primitive.name}"
            if eqn.primitive.name == "scan":
                n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
                n_carry_out = eqn.params["num_carry"]
                for kind, vs in (
                    ("scanned input", eqn.invars[n_fixed:]),
                    ("stacked output", eqn.outvars[n_carry_out:]),
                ):
                    for v in vs:
                        shape = tuple(v.aval.shape)
                        if shape in shapes or shape[1:] in shapes:
                            found.append(f"{here}: {kind} {v.aval.str_short()}")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr, "")
    return found


def pool_ops_in_hlo(hlo_text: str, kv_shape: tuple[int, ...], dtype) -> list[str]:
    """Names of the compiled ops that copy, slice or update-slice a pool or
    a layer of it: ``copy.110 bf16[16,256,8,128,128]``."""
    dt = _HLO_DTYPES[jnp.dtype(dtype).name]
    dims = {",".join(map(str, s)) for s in pool_shapes(kv_shape)}
    found = []
    for line in hlo_text.splitlines():
        m = _HLO_DEF.match(line)
        if not m:
            continue
        name, ty, shape, opcode = m.groups()
        if ty != dt or shape not in dims:
            continue
        if opcode in _MOVERS or (
            opcode == "fusion" and any(mv in name for mv in _MOVERS)
        ):
            found.append(f"{name} {ty}[{shape}]")
    return found
