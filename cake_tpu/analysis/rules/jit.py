"""JAX jit-discipline rules.

These encode the invariants the hot path depends on (ISSUE 2, and the
regression classes PAPERS.md attributes serving cliffs to): no host-device
sync inside a jitted step, no jit construction per call, hashable static
arguments, and donated buffers never read after the donating call.

Analysis is name-based: a "jit root" is any function a linted file
jit-compiles (decorator form or ``jax.jit(f, ...)`` call form). Since PR 3,
``host-sync-in-jit`` and ``donation-after-use`` are PROJECT-scoped: roots are
collected per file, but reachability follows the cross-module call graph
(analysis/callgraph.py) — plain calls, ``mod.f(...)`` through imports and
aliases, and ``self.m(...)`` bound methods — so a sync two modules away from
the jit site is still caught. Names that resolve outside the linted set
(jax, numpy, stdlib) end the walk; deliberate sites are suppressed inline
with ``# cake-lint: disable=<rule>``.
"""

from __future__ import annotations

import ast
from typing import Iterable

from cake_tpu.analysis import _util as u
from cake_tpu.analysis import callgraph as cg
from cake_tpu.analysis.engine import FileContext, Finding, Rule, register

# Call targets that force a device->host transfer (or a fresh host array)
# when executed under a jit trace.
_HOST_SYNC_CALLS = {
    "jax.device_get",
    "np.asarray",
    "np.array",
    "np.frombuffer",
    "numpy.asarray",
    "numpy.array",
    "numpy.frombuffer",
}
_HOST_SYNC_METHODS = {"item", "block_until_ready", "tolist", "__array__"}
_CAST_NAMES = {"int", "float", "bool", "complex"}


def collect_jit_roots(ctx: FileContext) -> dict[ast.AST, set[str]]:
    """Jit roots declared in one file: fn node -> static param names at its
    jit site(s). Shared by host-sync-in-jit (reachability roots) and
    rules/pallas.py (traced-block-dim needs to know which wrapper params are
    concrete Python values)."""
    defs = u.defs_by_name(ctx.tree)
    roots: dict[ast.AST, set[str]] = {}
    # Decorator form: @jax.jit / @functools.partial(jax.jit, ...)
    for fn in u.functions(ctx.tree):
        for deco in fn.decorator_list:
            statics: set[str] | None = None
            if u.is_jit_name(deco):
                statics = set()
            elif isinstance(deco, ast.Call) and u.is_jit_call(deco):
                names, nums = u.jit_statics(deco)
                params = u.param_names(fn)
                statics = names | {
                    params[i] for i in nums if 0 <= i < len(params)
                }
            if statics is not None:
                roots.setdefault(fn, set()).update(statics)
    # Call form: jax.jit(f, ...) / jax.jit(self._f, ...) with the wrapped
    # function (or method) defined in this file.
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and u.is_jit_name(node.func)):
            continue
        if not node.args:
            continue
        target = node.args[0]
        if isinstance(target, ast.Name):
            wrapped = target.id
        else:
            wrapped = u.self_attr(target)
            if wrapped is None:
                continue
        names, nums = u.jit_statics(node)
        for fn in defs.get(wrapped, ()):
            params = u.param_names(fn)
            if params and params[0] == "self":
                # Bound method: jit positions exclude self.
                params = params[1:]
            statics = names | {
                params[i] for i in nums if 0 <= i < len(params)
            }
            roots.setdefault(fn, set()).update(statics)
    return roots


def _enclosing_function(ctx: FileContext, node: ast.AST):
    for anc in ctx.ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return anc
    return None


@register
class HostSyncInJit(Rule):
    name = "host-sync-in-jit"
    severity = "error"
    scope = "project"
    description = (
        "Host-device sync (.item(), float()/int() casts on traced args, "
        "np.asarray, jax.device_get, .block_until_ready) reachable from a "
        "jitted function — including through cross-module helper calls: "
        "breaks tracing or forces a device round trip per step."
    )

    def check_project(self, ctxs: list[FileContext]) -> Iterable[Finding]:
        index = cg.project_index(ctxs)
        # Roots per file, reachability across the whole linted set. A root's
        # static params are exempt (concrete Python values, not tracers);
        # callees get no exemption — their params are traced at the root.
        statics_by_node: dict[int, set[str]] = {}
        roots: list[cg.FuncInfo] = []
        for mod in index.modules:
            for fn, statics in collect_jit_roots(mod.ctx).items():
                roots.append(cg.FuncInfo(mod, fn.name, fn))
                statics_by_node.setdefault(id(fn), set()).update(statics)
        for info in index.reachable(roots).values():
            statics = statics_by_node.get(id(info.node), set())
            traced = set(u.all_param_names(info.node)) - statics - {"self"}
            yield from self._scan(info.ctx, info.node, traced)

    def _scan(
        self, ctx: FileContext, fn: ast.AST, traced: set[str]
    ) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            # Stay inside THIS function: nested defs are scanned iff reachable.
            owner = _enclosing_function(ctx, node)
            if owner is not fn:
                continue
            target = u.dotted(node.func)
            if target in _HOST_SYNC_CALLS:
                yield ctx.finding(
                    self,
                    node,
                    f"`{target}(...)` inside jitted `{fn.name}` forces a "
                    "host round trip (or fails to trace); keep the step "
                    "device-side and convert outside the jit boundary",
                )
                continue
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _HOST_SYNC_METHODS
            ):
                yield ctx.finding(
                    self,
                    node,
                    f"`.{node.func.attr}()` inside jitted `{fn.name}` is a "
                    "blocking device->host sync; hoist it out of the jit",
                )
                continue
            if (
                isinstance(node.func, ast.Name)
                and node.func.id in _CAST_NAMES
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in traced
            ):
                yield ctx.finding(
                    self,
                    node,
                    f"`{node.func.id}({node.args[0].id})` casts a traced "
                    f"argument of jitted `{fn.name}` to a Python scalar — a "
                    "host sync on concrete values and a TracerError under "
                    "trace; use jnp casts or mark the arg static",
                )


# The fused decode family (ISSUE 13): entries whose sampling knobs are
# STATIC by contract (ops/sampling.py: knobs compile into the sampler; the
# fused tail kernel builds its grid/operand list from them). A jit that
# takes one of these knobs as a traced operand either fails to trace (the
# knob steers python-level branching) or silently compiles a sampler per
# value — the retrace class jitwatch exists to catch at runtime, caught
# here at review time.
_FUSED_FAMILY_CALLS = {
    "fused_sample_tail",
    "fused_norm_matmul",
    "sample_step",
    "sampled_decode_scan",
}
_SAMPLING_KNOBS = ("temperature", "top_k", "top_p", "repeat_penalty")


@register
class TracedSamplingKnob(Rule):
    name = "traced-sampling-knob"
    severity = "error"
    description = (
        "A jitted wrapper in the fused decode family (calls "
        "fused_sample_tail / sample_step / sampled_decode_scan or a fused "
        "kernel entry) takes temperature/top_k/top_p/repeat_penalty as "
        "TRACED parameters: the sampling knobs are static by contract "
        "(compiled into the sampler) — a traced knob fails to trace or "
        "recompiles per value; list it in static_argnums/static_argnames "
        "or close over it."
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for fn, statics in collect_jit_roots(ctx).items():
            called = {
                u.last_component(node.func)
                for node in ast.walk(fn)
                if isinstance(node, ast.Call)
            }
            if not (called & _FUSED_FAMILY_CALLS):
                continue
            for p in u.all_param_names(fn):
                if p in _SAMPLING_KNOBS and p not in statics:
                    yield ctx.finding(
                        self,
                        fn,
                        f"sampling knob `{p}` reaches jitted `{fn.name}` "
                        "as a traced operand but the fused decode family "
                        "requires it static — mark it in static_argnums/"
                        "static_argnames (or close over the value)",
                    )


@register
class JitInHotLoop(Rule):
    name = "jit-in-hot-loop"
    severity = "error"
    description = (
        "jax.jit / functools.partial(jax.jit, ...) constructed inside a "
        "loop: every iteration builds a fresh wrapper with an empty compile "
        "cache, so XLA recompiles each call."
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and u.is_jit_call(node)):
                continue
            loop = next(
                (
                    a
                    for a in ctx.ancestors(node)
                    if isinstance(a, (ast.For, ast.While, ast.AsyncFor))
                ),
                None,
            )
            if loop is not None:
                yield ctx.finding(
                    self,
                    node,
                    "jit wrapper constructed inside a loop recompiles every "
                    "iteration; hoist the jax.jit(...) out of the loop (or "
                    "cache it keyed on its static knobs)",
                )


def _resolve_wrapped(
    index_defs: dict[str, list], call: ast.Call
) -> tuple[ast.FunctionDef | None, bool]:
    """The function a ``jax.jit(f, ...)`` call wraps, if defined in-file.

    Returns (def, is_method): ``jax.jit(self._impl)`` wraps a BOUND method,
    so positional indices at the jit site exclude ``self``.
    """
    if not call.args:
        return None, False
    target = call.args[0]
    if isinstance(target, ast.Name):
        defs = index_defs.get(target.id, [])
        return (defs[0], False) if len(defs) == 1 else (None, False)
    attr = u.self_attr(target)
    if attr is not None:
        defs = index_defs.get(attr, [])
        return (defs[0], True) if len(defs) == 1 else (None, True)
    return None, False


_UNHASHABLE_ANNOTATIONS = {
    "list",
    "dict",
    "set",
    "bytearray",
    "List",
    "Dict",
    "Set",
    "np.ndarray",
    "numpy.ndarray",
    "jnp.ndarray",
    "jax.Array",
    "jax.numpy.ndarray",
}


def _annotation_name(node: ast.AST | None) -> str | None:
    if node is None:
        return None
    if isinstance(node, ast.Subscript):  # list[int], Dict[str, int]
        node = node.value
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        # String annotation: take the base name before any subscript.
        return node.value.split("[", 1)[0].strip()
    return u.dotted(node)


@register
class UnhashableStaticArg(Rule):
    name = "unhashable-static-arg"
    severity = "error"
    description = (
        "static_argnums/static_argnames pointing at list/dict/set/array "
        "parameters: jit hashes static args for its compile cache, so "
        "unhashable values raise (and arrays as statics recompile per "
        "value). Also flags static names that match no parameter."
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        defs = u.defs_by_name(ctx.tree)
        for node in ast.walk(ctx.tree):
            # Decorator form.
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if isinstance(deco, ast.Call) and u.is_jit_call(deco):
                        yield from self._check_site(
                            ctx, deco, node, is_method=False
                        )
                continue
            # Call form: jax.jit(f, static_...=...).
            if (
                isinstance(node, ast.Call)
                and u.is_jit_name(node.func)
                and node.args
            ):
                fn, is_method = _resolve_wrapped(defs, node)
                if fn is not None:
                    yield from self._check_site(ctx, node, fn, is_method)

    def _check_site(
        self,
        ctx: FileContext,
        site: ast.Call,
        fn: ast.FunctionDef | ast.AsyncFunctionDef,
        is_method: bool,
    ) -> Iterable[Finding]:
        names, nums = u.jit_statics(site)
        if not names and not nums:
            return
        a = fn.args
        params = list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        if is_method and params and params[0].arg == "self":
            params = params[1:]
        by_name = {p.arg: p for p in params}
        positional = list(a.posonlyargs) + list(a.args)
        if is_method and positional and positional[0].arg == "self":
            positional = positional[1:]

        checked: list[tuple[str, ast.arg]] = []
        for n in sorted(names):
            p = by_name.get(n)
            if p is None:
                if a.kwarg is None:
                    yield ctx.finding(
                        self,
                        site,
                        f"static_argnames {n!r} matches no parameter of "
                        f"`{fn.name}` — the jit raises at call time",
                    )
                continue
            checked.append((n, p))
        for i in sorted(nums):
            if 0 <= i < len(positional):
                checked.append((positional[i].arg, positional[i]))
            elif a.vararg is None:
                yield ctx.finding(
                    self,
                    site,
                    f"static_argnums {i} is out of range for `{fn.name}` "
                    f"({len(positional)} positional parameter(s))",
                )
        for name, p in checked:
            ann = _annotation_name(p.annotation)
            if ann in _UNHASHABLE_ANNOTATIONS:
                yield ctx.finding(
                    self,
                    site,
                    f"static arg {name!r} of `{fn.name}` is annotated "
                    f"`{ann}` — unhashable (or per-value recompiling) as a "
                    "jit cache key; pass it traced or as a hashable tuple",
                )
                continue
            default = self._default_for(fn, p)
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                kind = type(default).__name__.lower()
                yield ctx.finding(
                    self,
                    site,
                    f"static arg {name!r} of `{fn.name}` defaults to a "
                    f"{kind} literal — unhashable as a jit cache key",
                )

    @staticmethod
    def _default_for(fn, param: ast.arg) -> ast.AST | None:
        a = fn.args
        positional = list(a.posonlyargs) + list(a.args)
        if param in positional:
            i = positional.index(param) - (len(positional) - len(a.defaults))
            return a.defaults[i] if 0 <= i < len(a.defaults) else None
        if param in a.kwonlyargs:
            return a.kw_defaults[a.kwonlyargs.index(param)]
        return None


_TIME_ORIGINS = {
    "time.perf_counter", "perf_counter",
    "time.monotonic", "monotonic",
    "time.time",
}
# Calls that force the dispatched work to complete before the clock is read
# again — a timing window containing one of these measures compute, not
# dispatch.
_SYNC_CALLS = {
    "jax.block_until_ready",
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
}


@register
class UnblockedTiming(Rule):
    name = "unblocked-timing"
    severity = "warn"
    description = (
        "A perf_counter()/time.time() delta taken around a call into a jit "
        "wrapper without a block_until_ready (or np.asarray readback) on the "
        "result: jax dispatches asynchronously, so the delta measures "
        "dispatch overhead, not compute — the number looks impossibly good "
        "and poisons dashboards."
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        wrappers = self._jit_wrapper_names(ctx)
        if not wrappers:
            return
        for fn in u.functions(ctx.tree):
            yield from self._scan_function(ctx, fn, wrappers)

    # -- which local names hold (or produce) jit-compiled callables ---------

    def _jit_wrapper_names(self, ctx: FileContext) -> set[str]:
        factories = {
            fn.name
            for fn in u.functions(ctx.tree)
            if any(
                isinstance(n, ast.Return)
                and n.value is not None
                and u.is_jit_call(n.value)
                for n in ast.walk(fn)
            )
        }
        out: set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            is_wrapper = u.is_jit_call(v) or (
                isinstance(v, ast.Call)
                and isinstance(v.func, ast.Name)
                and v.func.id in factories
            )
            if not is_wrapper:
                continue
            for target in node.targets:
                if isinstance(target, ast.Name):
                    out.add(target.id)
                else:
                    attr = u.self_attr(target)
                    if attr is not None:
                        out.add(f"self.{attr}")
        return out

    # -- the t0 = perf_counter() ... jit(...) ... x - t0 window -------------

    def _scan_function(
        self, ctx: FileContext, fn, wrappers: set[str]
    ) -> Iterable[Finding]:
        # t-var -> EVERY assignment line: the same timer name is commonly
        # reused for consecutive windows, and each delta must be checked
        # against the binding live at that point, not just the last one.
        origins: dict[str, list[int]] = {}
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and u.dotted(node.value.func) in _TIME_ORIGINS
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
            ):
                origins.setdefault(node.targets[0].id, []).append(node.lineno)
        if not origins:
            return
        calls: list[tuple[int, bool]] = []  # (line, is_sync)
        deltas: list[tuple[str, ast.BinOp]] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                target = u.dotted(node.func)
                is_sync = target in _SYNC_CALLS or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"
                )
                name = u.call_name(node)
                if is_sync:
                    calls.append((node.lineno, True))
                elif name in wrappers:
                    calls.append((node.lineno, False))
            elif (
                isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.Sub)
                and isinstance(node.right, ast.Name)
                and node.right.id in origins
            ):
                deltas.append((node.right.id, node))
        for tvar, delta in deltas:
            d_line = delta.lineno
            live = [ln for ln in origins[tvar] if ln < d_line]
            if not live:
                continue
            t_line = max(live)  # the binding live at the delta
            window = [c for c in calls if t_line < c[0] <= d_line]
            jit_lines = [ln for ln, sync in window if not sync]
            if not jit_lines:
                continue
            # A sync anywhere after the LAST jit call closes the window: the
            # delta then covers completed compute.
            if any(sync and ln >= jit_lines[-1] for ln, sync in window):
                continue
            yield ctx.finding(
                self,
                delta,
                f"timing delta `... - {tvar}` covers a jit-wrapper call "
                f"(line {jit_lines[-1]}) with no block_until_ready/readback "
                "before the clock is read — this measures async dispatch, "
                "not compute; block on the result (or suppress if dispatch "
                "time is the point)",
            )


@register
class DonationAfterUse(Rule):
    name = "donation-after-use"
    severity = "error"
    scope = "project"
    description = (
        "A buffer passed at a donated position (donate_argnums/argnames) is "
        "read again after the donating call — the donating jit wrapper may "
        "live in another module: XLA may have reused its memory, so the "
        "read returns garbage (or raises on deletion-checking backends)."
    )

    def check_project(self, ctxs: list[FileContext]) -> Iterable[Finding]:
        index = cg.project_index(ctxs)
        # Donating wrappers per module, by the LOCAL name they bind. Plain
        # Name bindings are also importable from other modules.
        local_maps: dict[int, dict[str, set[int]]] = {}
        exported: dict[tuple[int, str], set[int]] = {}
        for mod in index.modules:
            local = self._donated_callables(mod.ctx)
            local_maps[id(mod)] = local
            # Only MODULE-LEVEL bindings are importable; a wrapper built
            # inside a function stays file-local.
            top_names = {
                t.id
                for stmt in mod.ctx.tree.body
                if isinstance(stmt, ast.Assign)
                for t in stmt.targets
                if isinstance(t, ast.Name)
            }
            for name, positions in local.items():
                if name in top_names:
                    exported[(id(mod), name)] = positions
        for mod in index.modules:
            donated = dict(local_maps[id(mod)])
            # Imported donors: `from runtime.backend import step` (possibly
            # re-exported through __init__.py, possibly aliased).
            for local_name, _target in mod.imports.items():
                origin = index.resolve_origin(mod, (local_name,))
                if origin is None:
                    continue
                owner, symbol = origin
                if len(symbol) != 1:
                    continue
                positions = exported.get((id(owner), symbol[0]))
                if positions is not None and owner is not mod:
                    donated.setdefault(local_name, positions)
            if not donated:
                continue
            for fn in u.functions(mod.ctx.tree):
                yield from self._scan_function(mod.ctx, fn, donated)

    # -- index: which names hold donating jits, and which positions donate --

    def _donated_callables(self, ctx: FileContext) -> dict[str, set[int]]:
        """"f" / "self._f" -> set of donated POSITIONAL indices at call time."""
        defs = u.defs_by_name(ctx.tree)
        out: dict[str, set[int]] = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            call = node.value
            if not (isinstance(call, ast.Call) and u.is_jit_name(call.func)):
                continue
            names, nums = u.jit_donations(call)
            if not names and not nums:
                continue
            positions = set(nums)
            if names:
                fn, is_method = _resolve_wrapped(defs, call)
                if fn is not None:
                    params = u.param_names(fn)
                    if is_method and params and params[0] == "self":
                        params = params[1:]
                    positions |= {
                        params.index(n) for n in names if n in params
                    }
            if not positions:
                continue
            for target in node.targets:
                if isinstance(target, ast.Name):
                    out[target.id] = positions
                else:
                    attr = u.self_attr(target)
                    if attr is not None:
                        out[f"self.{attr}"] = positions
        return out

    # -- scan: donated arg vars read after the call without a rebind --------

    def _scan_function(
        self, ctx: FileContext, fn, donated: dict[str, set[int]]
    ) -> Iterable[Finding]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = u.call_name(node)
            if callee not in donated:
                continue
            for i in donated[callee]:
                if i >= len(node.args):
                    continue
                var = self._var_of(node.args[i])
                if var is None:
                    continue
                if self._rebinds(ctx, node, var):
                    continue  # `x, kv = f(kv)` — the donation IS the rebind
                use = self._use_after(ctx, fn, node, var)
                if use is not None:
                    yield ctx.finding(
                        self,
                        use,
                        f"`{var}` was donated to `{callee}` (line "
                        f"{node.lineno}) and is read here afterwards — the "
                        "buffer may already be reused; rebind the result or "
                        "pass a copy",
                    )

    @staticmethod
    def _var_of(node: ast.AST) -> str | None:
        if isinstance(node, ast.Name):
            return node.id
        attr = u.self_attr(node)
        return f"self.{attr}" if attr is not None else None

    def _rebinds(self, ctx: FileContext, call: ast.Call, var: str) -> bool:
        """Is the donating call's result assigned back over ``var``?"""
        stmt = self._stmt_of(ctx, call)
        if not isinstance(stmt, ast.Assign):
            return False
        for target in stmt.targets:
            elts = target.elts if isinstance(target, ast.Tuple) else [target]
            for e in elts:
                if self._var_of(e) == var or (
                    isinstance(e, ast.Starred)
                    and self._var_of(e.value) == var
                ):
                    return True
        return False

    @staticmethod
    def _stmt_of(ctx: FileContext, node: ast.AST) -> ast.AST | None:
        cur: ast.AST | None = node
        while cur is not None and not isinstance(cur, ast.stmt):
            cur = ctx.parents.get(cur)
        return cur

    def _use_after(self, ctx, fn, call: ast.Call, var: str) -> ast.AST | None:
        """First read of ``var`` that executes after the donating call and
        before any rebind. Line-ordered within the enclosing function; a
        surrounding loop re-executes reads ABOVE the call too."""
        call_line = getattr(call, "end_lineno", call.lineno)
        loop = next(
            (
                a
                for a in ctx.ancestors(call)
                if isinstance(a, (ast.For, ast.While, ast.AsyncFor))
            ),
            None,
        )
        reads: list[ast.AST] = []
        rebind_lines: list[int] = []
        for node in ast.walk(fn):
            v = self._var_of(node)
            if v != var:
                continue
            in_call_args = any(a is call for a in ctx.ancestors(node)) or (
                node in getattr(call, "args", ())
            )
            isctx = getattr(node, "ctx", None)
            if isinstance(isctx, ast.Store):
                rebind_lines.append(node.lineno)
            elif isinstance(isctx, ast.Load) and not in_call_args:
                reads.append(node)
        next_rebind = min(
            (ln for ln in rebind_lines if ln > call_line), default=None
        )
        for r in sorted(reads, key=lambda n: n.lineno):
            if r.lineno > call_line and (
                next_rebind is None or r.lineno <= next_rebind
            ):
                return r
            if (
                loop is not None
                and r.lineno < call.lineno
                and r.lineno >= loop.lineno
                and not any(ln <= r.lineno for ln in rebind_lines)
            ):
                # Read earlier in the same loop body: it re-executes after
                # the donation on the next iteration, unrebound.
                return r
        return None
