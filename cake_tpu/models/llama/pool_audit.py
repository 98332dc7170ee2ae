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

  * ``audit_programs`` takes the programs a paged server of the model's
    cache kind would dispatch, with abstract arguments — no weights, no
    pool, nothing on a device (``programs.served_programs``) — compiles them
    and reports, for each, the two readings below over every pool the kind
    holds, with the compiler's ``temp_size_in_bytes`` beside the pools'
    bytes;
  * ``scans_moving_pool`` reads a jaxpr: a ``scan`` with a pool-shaped or
    layer-of-pool-shaped scanned input or stacked output is the defect;
  * ``pool_ops_in_hlo`` reads compiled HLO text: ``copy``, ``dynamic-slice``
    and ``dynamic-update-slice`` ops (fused or not) whose result is a pool
    or one layer of it;
  * ``weight_ops_in_hlo`` reads it for a run's stacked WEIGHTS: an operation
    that writes a stack, or a layer of one, out again (PR 56: a product whose
    result is reshaped into heads was rewritten into a convolution that wants
    its weight transposed, and the chip laid 75 MB out again a layer-step).
    A layer's weights are read where they lie: a ``dynamic-slice`` INSIDE the
    product's own fusion.
"""

from __future__ import annotations

import math
import re
import time

import jax
import jax.numpy as jnp

from cake_tpu.models.llama.config import LlamaConfig

_HLO_DTYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}
# A defining HLO line: `%name = type[dims]{layout} opcode(`; fusions carry
# the ops they hold in their names (`bitcast_dynamic-update-slice_fusion`).
_HLO_DEF = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^\s]*\s+([\w\-]+)\("
)
_MOVERS = ("copy", "dynamic-slice", "dynamic-update-slice")
# The pool's write as a kernel (ops/pallas/paged_write.py) in compiled HLO.
_POOL_WRITE = re.compile(r"^\s*(?:ROOT\s+)?%?paged_pool_write[\w.\-]* = ", re.M)
# A routed expert layer's grouped product (``megablox.gmm``, ops/moe._ragged).
_GROUPED_PRODUCT = re.compile(r"^\s*(?:ROOT\s+)?%?gmm[\w.\-]* = ", re.M)


# Lines that define no new array, and the compiler's own prefetch of an
# operand (asynchronous, beside the work: the stream itself).
_NOT_WRITTEN = frozenset((
    "parameter", "get-tuple-element", "tuple", "bitcast", "while", "conditional",
    "call", "constant", "copy-start", "copy-done",
))
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_HLO_FUSED = re.compile(r"kind=k\w+, calls=%?([\w.\-]+)")


def pool_shapes(kv_shape: tuple[int, ...]) -> tuple[tuple[int, ...], ...]:
    """The two shapes that must not be moved: the pool, and one layer."""
    return (tuple(kv_shape), tuple(kv_shape[1:]))


def audit_programs(
    config: LlamaConfig,
    *,
    only: tuple[str, ...] = ("decode", "join"),
    watch: tuple[str, ...] = ("wq_b", "wi_q"),
    **geometry,
) -> dict[str, dict]:
    """{program: report} for the programs ``only`` names of those a saturated
    continuous-batching server of this model's cache kind runs, greedy,
    lowered from ``jax.ShapeDtypeStruct`` arguments (``programs.
    served_programs`` and its ``geometry``: ``n_pages``, ``page_size``,
    ``lanes``, ``table_pages``, ``n_steps``, ``width``, ``prefill_rows``,
    ``dtype``, ``allow_pallas``, ``sharding``) and compiled for the default
    backend, or for the device ``sharding`` names. A report holds ``scans``
    (``scans_moving_pool``) and ``pool_ops`` (``pool_ops_in_hlo``) over every
    pool the kind's record names (the page pool; the latent pool and the pool
    of index keys; a pool a kind of attention layer: NONE may be sliced,
    stacked or copied, whole or a layer of it), ``pool_writes`` (how many of
    its custom calls are the pool's write as a kernel: one a layer scan where
    the paged kernels run, none where the write is the scatter),
    ``grouped_products`` (how many are a routed expert layer's grouped
    product: three a run of sparse layers on the grouped path, none where the
    dispatch takes the dense combine: ``ops/moe.dispatch_path``),
    ``weight_ops`` (``weight_ops_in_hlo`` over the stacks ``watch`` names of
    every run's tree, those the model has: the projections ``latent.
    into_heads`` splits into heads; none may be written out again), ``kernels``,
    ``pool_bytes`` (the named pools'), the compiler's ``temp_bytes``,
    ``argument_bytes`` (together what the program needs on the device) and
    ``code_bytes`` (the compiled program's own size: what a start-up's
    programs must fit the compile cache with; None where the backend gives no
    memory analysis) and ``seconds``.

    Where the kind keeps a state a lane, the report adds its readings:
    ``state_scans`` (a scan that takes a state array, or a layer of one, as a
    scanned input or gives one back stacked), ``state_copies`` (a compiled
    ``copy`` of a whole state array) and ``state_bytes``. A layer reads and
    writes its own slice of the state in place, so slices and update-slices
    of ONE layer are the work itself and are not counted."""
    from cake_tpu.models.llama.programs import KINDS, served_programs

    kind = KINDS[config.cache_kind]
    params, cache, programs = served_programs(config, **geometry)
    pools = kind.pools(cache)
    state = kind.lane_state(cache) if kind.lane_state is not None else ()
    # one reading a shape: K and V of a pool share theirs
    shapes = {tuple(p.shape): p.dtype for p in pools}
    weight_dims = {
        ",".join(map(str, a.shape)) for a in jax.tree.leaves(params["layers"])
    }
    # (a list of runs' trees, or one stack's tree: plain K and V's)
    runs = params["layers"] if isinstance(params["layers"], (list, tuple)) else [params["layers"]]
    watched = [run[key].shape for run in runs for key in watch if key in run]

    def nbytes(arrays):
        return sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize for a in arrays)

    reports = {}
    for name in only:
        t0 = time.perf_counter()
        traced = programs[name]()
        compiled = traced.lower().compile()
        hlo = compiled.as_text()
        mem = compiled.memory_analysis()
        report = {
            "scans": [f for s in shapes for f in scans_moving_pool(traced.jaxpr, s)],
            "pool_ops": [f for s, dt in shapes.items() for f in pool_ops_in_hlo(hlo, s, dt)],
            "pool_writes": len(_POOL_WRITE.findall(hlo)),
            "grouped_products": len(_GROUPED_PRODUCT.findall(hlo)),
            "weight_ops": weight_ops_in_hlo(hlo, watched),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            "pool_bytes": nbytes(pools),
            "kernels": hlo.count("tpu_custom_call"),
        }
        if state:
            # A run's stacked weights are scanned inputs by design; at a
            # tiny size one may have the shape of a layer of the state.
            report["state_scans"] = [
                found for a in state
                for found in scans_moving_pool(traced.jaxpr, tuple(a.shape))
                if not any(f"[{dims}]" in found for dims in weight_dims)
            ]
            report["state_copies"] = [
                op for a in state
                for op in pool_ops_in_hlo(hlo, tuple(a.shape), a.dtype)
                if op.startswith("copy") and f"[{','.join(map(str, a.shape))}]" in op
            ]
            report["state_bytes"] = nbytes(state)
        reports[name] = {**report, "seconds": round(time.perf_counter() - t0, 1)}
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


def weight_ops_in_hlo(hlo_text: str, stacks: list[tuple[int, ...]]) -> list[dict]:
    """The compiled operations that WRITE an array of the size and sides of
    one of ``stacks`` ([layers, in, out]) or of a layer of one, in any order
    of its sides (the compiler's copies are often transposes): {``op``:
    ``copy.103 bf16[4,1536,24576] copy``, ``text``: its line and, of a
    fusion, the computation it calls}. An operation inside a fusion writes
    nothing (a ``dynamic-slice`` fused into the product that reads it is how
    a layer's weights are meant to be read); a ``bitcast`` is a view; the
    compiler's asynchronous prefetch of an operand (``copy-start`` /
    ``copy-done``: a one-layer run's weights, fetched ahead beside the work)
    is the stream itself, not a second one."""
    def sides(shape):
        return tuple(sorted(d for d in shape if d != 1))

    want = {sides(s) for stack in stacks for s in (stack, stack[1:])}
    fused = set(_HLO_FUSED.findall(hlo_text))
    bodies: dict[str, list[str]] = {}
    found, here = [], None
    for line in hlo_text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            here = m.group(1)
            continue
        bodies.setdefault(here, []).append(line)
        m = None if here in fused else _HLO_DEF.match(line)
        if not m or m.group(4) in _NOT_WRITTEN:
            continue
        name, ty, shape, opcode = m.groups()
        if sides(int(d) for d in shape.split(",") if d) in want:
            found.append((f"{name} {ty}[{shape}] {opcode}", line))
    out = []
    for op, line in found:
        called = _HLO_FUSED.search(line)
        body = bodies.get(called.group(1), []) if called else []
        out.append({"op": op, "text": "\n".join(t[:400] for t in [line, *body[:24]])})
    return out
