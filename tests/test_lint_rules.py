"""Per-rule regression tests for cake_tpu/analysis.

Every shipped rule gets at least one TRUE-POSITIVE snippet (the test fails if
the rule is deleted or stops firing) and negative snippets pinning the
false-positive boundaries the real tree depends on (static-arg casts, rebind
donation, guarded mutations, narrowed excepts).

The analysis package is stdlib-only; none of these tests need jax.
"""

from __future__ import annotations

from cake_tpu.analysis import engine, lint_source


def rules_of(findings):
    return [f.rule for f in findings]


def lint_rule(src: str, rule: str, path: str = "snippet.py"):
    """Run ONE rule over a snippet (select= raises if the rule was deleted,
    so deleting a rule fails every test that names it)."""
    return lint_source(src, path=path, select=[rule])


# ------------------------------------------------------------ host-sync-in-jit


class TestHostSyncInJit:
    RULE = "host-sync-in-jit"

    def test_item_in_decorated_jit(self):
        fs = lint_rule(
            """
import jax

@jax.jit
def step(x):
    return x.item()
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert ".item()" in fs[0].message

    def test_np_asarray_in_reachable_helper(self):
        # The sync hides one call deep: step -> helper -> np.asarray.
        fs = lint_rule(
            """
import jax
import numpy as np

def helper(y):
    return np.asarray(y)

def step(x):
    return helper(x) + 1

run = jax.jit(step)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_cast_of_traced_param(self):
        fs = lint_rule(
            """
import jax

def step(x, n):
    return x * int(n)

run = jax.jit(step)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_static_arg_cast_is_exempt(self):
        # int(n) on a static arg is concrete Python — the idiom every Pallas
        # kernel wrapper in ops/pallas/ uses.
        fs = lint_rule(
            """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("n",))
def step(x, n):
    return x * int(n)
""",
            self.RULE,
        )
        assert fs == []

    def test_jitted_bound_method(self):
        fs = lint_rule(
            """
import jax

class Backend:
    def __init__(self):
        self._step = jax.jit(self._impl)

    def _impl(self, x):
        return float(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_sync_outside_jit_is_fine(self):
        fs = lint_rule(
            """
import numpy as np

def host_side(x):
    return np.asarray(x).item()
""",
            self.RULE,
        )
        assert fs == []


# ------------------------------------------------------------- jit-in-hot-loop


class TestJitInHotLoop:
    RULE = "jit-in-hot-loop"

    def test_jit_constructed_in_loop(self):
        fs = lint_rule(
            """
import jax

def drive(f, steps):
    for s in steps:
        y = jax.jit(f)(s)
    return y
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_partial_jit_in_while(self):
        fs = lint_rule(
            """
import functools
import jax

def drive(f, xs):
    while xs:
        g = functools.partial(jax.jit, static_argnums=(1,))(f)
        xs = g(xs, 1)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_jit_hoisted_before_loop_is_fine(self):
        fs = lint_rule(
            """
import jax

def drive(f, steps):
    g = jax.jit(f)
    for s in steps:
        y = g(s)
    return y
""",
            self.RULE,
        )
        assert fs == []


# ------------------------------------------------------- unhashable-static-arg


class TestUnhashableStaticArg:
    RULE = "unhashable-static-arg"

    def test_list_annotated_static_argnum(self):
        fs = lint_rule(
            """
import jax

def step(x, shape: list):
    return x

run = jax.jit(step, static_argnums=(1,))
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_dict_default_static_argname(self):
        fs = lint_rule(
            """
import jax

def step(x, opts={"a": 1}):
    return x

run = jax.jit(step, static_argnames=("opts",))
""",
            self.RULE,
            # The snippet also trips mutable-default-arg; selecting one rule
            # keeps the assertion precise.
        )
        assert rules_of(fs) == [self.RULE]

    def test_static_name_matching_no_param(self):
        fs = lint_rule(
            """
import jax

def step(x):
    return x

run = jax.jit(step, static_argnames=("block_q",))
""",
            self.RULE,
        )
        assert "matches no parameter" in fs[0].message

    def test_hashable_static_is_fine(self):
        fs = lint_rule(
            """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def kernel(x, block_q: int = 128, interpret: bool = False):
    return x
""",
            self.RULE,
        )
        assert fs == []


# ---------------------------------------------------------- donation-after-use


class TestDonationAfterUse:
    RULE = "donation-after-use"

    def test_read_after_donating_call(self):
        fs = lint_rule(
            """
import jax

def impl(params, kv):
    return kv

step = jax.jit(impl, donate_argnums=(1,))

def drive(params, kv):
    out = step(params, kv)
    return out, kv.sum()
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "donated" in fs[0].message

    def test_donate_argnames_resolved_through_signature(self):
        fs = lint_rule(
            """
import jax

def impl(params, kv):
    return kv

step = jax.jit(impl, donate_argnames=("kv",))

def drive(params, kv):
    out = step(params, kv)
    log(kv)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_loop_reuse_without_rebind(self):
        # The donated buffer is read at the TOP of the next iteration.
        fs = lint_rule(
            """
import jax

def impl(kv):
    return kv

step = jax.jit(impl, donate_argnums=(0,))

def drive(kv, n):
    for _ in range(n):
        check(kv)
        out = step(kv)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_rebind_is_the_blessed_pattern(self):
        # `logits, kv = step(kv)` — what the whole tree does.
        fs = lint_rule(
            """
import jax

def impl(params, kv):
    return kv, kv

step = jax.jit(impl, donate_argnums=(1,))

def drive(params, kv):
    for _ in range(8):
        logits, kv = step(params, kv)
    return logits
""",
            self.RULE,
        )
        assert fs == []

    def test_read_before_call_is_fine(self):
        fs = lint_rule(
            """
import jax

def impl(kv):
    return kv

step = jax.jit(impl, donate_argnums=(0,))

def drive(kv):
    check(kv)
    return step(kv)
""",
            self.RULE,
        )
        assert fs == []


# ----------------------------------------------------- unlocked-shared-mutation


class TestUnlockedSharedMutation:
    RULE = "unlocked-shared-mutation"

    POSITIVE = """
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def clear(self):
        self._items = []
"""

    def test_unlocked_mutation_of_guarded_attr(self):
        fs = lint_rule(self.POSITIVE, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "_items" in fs[0].message

    def test_condition_counts_as_lock(self):
        fs = lint_rule(
            """
import threading

class Queue:
    def __init__(self):
        self._cv = threading.Condition()
        self._q = []

    def put(self, x):
        with self._cv:
            self._q.append(x)
            self._cv.notify()

    def drop_all(self):
        self._q.clear()
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_all_mutations_guarded_is_fine(self):
        fs = lint_rule(
            """
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._items = []

    def add(self, x):
        with self._lock:
            self._items.append(x)

    def clear(self):
        with self._lock:
            self._items = []
""",
            self.RULE,
        )
        assert fs == []

    def test_init_and_unguarded_attrs_exempt(self):
        # _threads is never lock-guarded anywhere -> single-owner state, not
        # flagged (the worker accept-loop pattern).
        fs = lint_rule(
            """
import threading

class Server:
    def __init__(self):
        self._lock = threading.Lock()
        self._conns = set()
        self._threads = []

    def accept(self, c, t):
        with self._lock:
            self._conns.add(c)
        self._threads.append(t)
""",
            self.RULE,
        )
        assert fs == []


# ------------------------------------------------------------ frame-field-drift


class TestFrameFieldDrift:
    RULE = "frame-field-drift"

    PROTO = """
def forward_frame(x, ranges, pos):
    header = {"ranges": ranges, "pos": pos}
    header["ghost"] = 1
    return Frame(3, header, payload=x)


def error_frame(msg):
    return Frame(6, {"error": msg})
"""

    CLIENT = """
def unpack(frame):
    if "error" in frame.header:
        raise RuntimeError(frame.header["error"])
    h = frame.header
    return h["ranges"], h.get("pos"), h.get("phantom")
"""

    def _run(self, srcs):
        return engine.run_lint(
            list(srcs), select=[self.RULE], reader=lambda p: srcs[str(p)]
        )

    def test_pack_only_and_read_only_fields_flagged(self):
        res = self._run({"proto.py": self.PROTO, "client.py": self.CLIENT})
        flagged = {f.message.split("'")[1] for f in res.findings}
        assert flagged == {"ghost", "phantom"}

    def test_symmetric_contract_is_clean(self):
        res = self._run(
            {
                "proto.py": """
def forward_frame(x, pos):
    return Frame(3, {"pos": pos}, payload=x)
""",
                "client.py": """
def unpack(frame):
    return frame.header["pos"]
""",
            }
        )
        assert res.findings == []

    def test_rule_needs_a_proto_file(self):
        res = self._run({"client.py": self.CLIENT})
        assert res.findings == []

    def test_real_tree_contract_is_symmetric(self):
        repo = __import__("pathlib").Path(__file__).resolve().parent.parent
        res = engine.run_lint([repo / "cake_tpu"], select=[self.RULE])
        assert res.findings == [], [f.render() for f in res.findings]


# ---------------------------------------------------------- mutable-default-arg


class TestMutableDefaultArg:
    RULE = "mutable-default-arg"

    def test_list_default(self):
        fs = lint_rule("def f(x, acc=[]):\n    return acc\n", self.RULE)
        assert rules_of(fs) == [self.RULE]

    def test_dict_call_kwonly_default(self):
        fs = lint_rule(
            "def f(x, *, opts=dict()):\n    return opts\n", self.RULE
        )
        assert rules_of(fs) == [self.RULE]

    def test_none_default_is_fine(self):
        fs = lint_rule(
            """
def f(x, acc=None):
    acc = [] if acc is None else acc
    return acc
""",
            self.RULE,
        )
        assert fs == []

    def test_call_with_list_arg_is_not_a_default(self):
        # BatchResult(text="", token_ids=[]) at a CALL site is fine.
        fs = lint_rule("r = Result(text='', token_ids=[])\n", self.RULE)
        assert fs == []


# ---------------------------------------------------------- bare-except-swallow


class TestBareExceptSwallow:
    RULE = "bare-except-swallow"

    def test_except_exception_pass(self):
        fs = lint_rule(
            """
try:
    probe()
except Exception:
    pass
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_bare_except_continue(self):
        fs = lint_rule(
            """
while True:
    try:
        step()
    except:
        continue
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_narrow_except_pass_is_fine(self):
        # `except OSError: pass` around socket close is the tree's idiom.
        fs = lint_rule(
            """
try:
    sock.close()
except OSError:
    pass
""",
            self.RULE,
        )
        assert fs == []

    def test_logged_broad_except_is_fine(self):
        fs = lint_rule(
            """
try:
    step()
except Exception as e:
    log.debug("step failed: %s", e)
""",
            self.RULE,
        )
        assert fs == []


# ----------------------------------------------------- MsgType drift (PR 3)


class TestMsgTypeDrift:
    RULE = "frame-field-drift"

    PROTO = """
from enum import IntEnum

class MsgType(IntEnum):
    HELLO = 1
    ORPHAN = 2
    UNREAD = 3

def hello_frame():
    return Frame(MsgType.HELLO, {})

def unread_frame():
    return Frame(MsgType.UNREAD, {})
"""

    WORKER = """
import proto

def serve(frame):
    if frame.type == proto.MsgType.HELLO:
        return "hi"
"""

    def _run(self, srcs):
        return engine.run_lint(
            list(srcs), select=[self.RULE], reader=lambda p: srcs[str(p)]
        )

    def test_member_without_producer_and_without_consumer(self):
        res = self._run({"proto.py": self.PROTO, "worker.py": self.WORKER})
        msgs = sorted(f.message for f in res.findings)
        assert len(msgs) == 2
        assert "MsgType.ORPHAN has no producer" in msgs[0]
        assert "MsgType.UNREAD is produced but never consumed" in msgs[1]

    def test_match_case_and_dispatch_dict_count_as_consumers(self):
        worker = """
import proto

HANDLERS = {proto.MsgType.UNREAD: print}

def serve(frame):
    match frame.type:
        case proto.MsgType.HELLO:
            return "hi"
"""
        proto_src = self.PROTO.replace("    ORPHAN = 2\n", "")
        res = self._run({"proto.py": proto_src, "worker.py": worker})
        assert res.findings == []

    def test_lone_proto_does_not_flag_unconsumed(self):
        # Without the consumer files in the run, "never consumed" cannot be
        # judged; "no producer" still can (builders live in proto.py).
        res = self._run({"proto.py": self.PROTO})
        assert [
            f.message.split(" ")[0] for f in res.findings
        ] == ["MsgType.ORPHAN"]


# ------------------------------------------------------------- sharding pack


class TestUnknownMeshAxis:
    RULE = "unknown-mesh-axis"

    def test_typod_axis_flagged(self):
        fs = lint_rule(
            """
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

TP_AXIS = "tp"
mesh = Mesh(np.array([0]), (TP_AXIS,))
spec = P(None, "tpp")
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "'tpp'" in fs[0].message

    def test_axis_constant_resolved_through_import(self):
        srcs = {
            "pkg/tensor.py": (
                "import numpy as np\n"
                "from jax.sharding import Mesh\n"
                'TP_AXIS = "tp"\n'
                "mesh = Mesh(np.array([0]), (TP_AXIS,))\n"
            ),
            "pkg/user.py": (
                "from jax.sharding import PartitionSpec as P\n"
                "from pkg.tensor import TP_AXIS\n"
                "good = P(None, TP_AXIS)\n"
                'bad = P("stage")\n'
            ),
        }
        res = engine.run_lint(
            list(srcs), select=[self.RULE], reader=lambda p: srcs[str(p)]
        )
        assert len(res.findings) == 1
        assert "'stage'" in res.findings[0].message
        assert res.findings[0].path == "pkg/user.py"

    def test_no_mesh_in_run_is_silent(self):
        fs = lint_rule(
            """
from jax.sharding import PartitionSpec as P

spec = P("anything")
""",
            self.RULE,
        )
        assert fs == []

    def test_unresolvable_axis_name_is_skipped(self):
        fs = lint_rule(
            """
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

mesh = Mesh(np.array([0]), ("tp",))

def spec_for(axis_name):
    return P(None, axis_name)
""",
            self.RULE,
        )
        assert fs == []


class TestSpecArityMismatch:
    RULE = "spec-arity-mismatch"

    def test_in_specs_count_vs_params(self):
        fs = lint_rule(
            """
def outer(mesh, P, shard_map):
    def body(a, b):
        return a
    return shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                     out_specs=P())
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "3 spec(s)" in fs[0].message and "2 positional" in fs[0].message

    def test_out_specs_tuple_vs_return_arity(self):
        fs = lint_rule(
            """
def outer(mesh, P, checked_shard_map):
    def body(a, b):
        return a, b
    return checked_shard_map(body, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(),))
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "returns a 2-tuple" in fs[0].message

    def test_matching_site_is_clean_and_nested_returns_ignored(self):
        fs = lint_rule(
            """
def outer(mesh, P, shard_map):
    def body(a, b):
        def inner(c):
            return c, c, c
        return a, inner(b)
    return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                     out_specs=(P(), P()))
""",
            self.RULE,
        )
        assert fs == []

    def test_defaulted_trailing_params_are_optional(self):
        # shard_map(body) with fewer operands than params is valid when the
        # tail params have defaults — the specs match what is passed.
        fs = lint_rule(
            """
def outer(mesh, P, shard_map):
    def body(a, b, scale=1.0):
        return a
    return shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P())
""",
            self.RULE,
        )
        assert fs == []

    def test_specs_above_param_count_still_flagged(self):
        fs = lint_rule(
            """
def outer(mesh, P, shard_map):
    def body(a, b, scale=1.0):
        return a
    return shard_map(body, mesh=mesh, in_specs=(P(), P(), P(), P()),
                     out_specs=P())
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "2-3 positional" in fs[0].message

    def test_forwarding_wrapper_site_is_checked(self):
        # The sequence.py _shard_specs idiom: any call forwarding both
        # in_specs= and out_specs= with a resolvable body.
        fs = lint_rule(
            """
class Runner:
    def build(self):
        def body(a):
            return a
        return self._shard_specs(body, in_specs=(P(), P()), out_specs=P())
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_pallas_call_in_specs_exempt(self):
        # pallas_call's in_specs obey the KERNEL contract (refs include
        # outputs + scratch) — rules/pallas.py owns that surface.
        fs = lint_rule(
            """
def kern(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(pl, x):
    return pl.pallas_call(kern, grid=(1,), in_specs=[pl.BlockSpec()],
                          out_specs=pl.BlockSpec())(x)
""",
            self.RULE,
        )
        assert fs == []


# --------------------------------------------------------------- pallas pack


class TestBlockSpecIndexMapArity:
    RULE = "blockspec-indexmap-arity"

    def test_lambda_arity_vs_grid_rank(self):
        fs = lint_rule(
            """
def run(pl, x):
    return pl.pallas_call(
        kern,
        grid=(4, 4),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
    )(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "takes 1 argument(s)" in fs[0].message

    def test_prefetch_grid_spec_adds_leading_args(self):
        # num_scalar_prefetch=2 + rank-2 grid: maps take 4 args; the named
        # 3-arg map (resolved through the local grid_spec binding) fails.
        fs = lint_rule(
            """
def idx3(i, j, s):
    return (i, j)

def run(pl, pltpu, x):
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((1, 8), idx3)],
        out_specs=pl.BlockSpec((1, 8), lambda i, j, s, t: (i, j)),
    )
    return pl.pallas_call(kern, grid_spec=gs)(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "2 scalar-prefetch" in fs[0].message

    def test_grid_through_local_name_and_matching_arity_clean(self):
        fs = lint_rule(
            """
def run(pl, x):
    grid = (4, 4, 2)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 128), lambda i, j, k: (i, k))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j, k: (i, j)),
    )(x)
""",
            self.RULE,
        )
        assert fs == []

    def test_nested_def_binding_does_not_shadow_grid(self):
        # A nested helper's own `grid` lives in a different namespace; the
        # pallas_call's grid= must resolve to the ENCLOSING scope's tuple.
        fs = lint_rule(
            """
def run(pl, x):
    grid = (4, 4)
    def helper():
        grid = (8,)
        return grid
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
    )(x)
""",
            self.RULE,
        )
        assert fs == []


class TestGridBlockRankMismatch:
    RULE = "grid-block-rank-mismatch"

    def test_block_rank_vs_index_tuple(self):
        fs = lint_rule(
            """
def run(pl, x):
    return pl.pallas_call(
        kern,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
    )(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "rank 2" in fs[0].message and "3-tuple" in fs[0].message

    def test_named_index_map_checked(self):
        fs = lint_rule(
            """
def kv_index(i, j):
    return (i, j, 0)

def run(pl, x):
    return pl.pallas_call(
        kern,
        grid=(4, 2),
        in_specs=[pl.BlockSpec((1, 8, 128), kv_index)],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
    )(x)
""",
            self.RULE,
        )
        assert fs == []


class TestTracedBlockDim:
    RULE = "traced-block-dim"

    def test_traced_param_in_block_shape(self):
        fs = lint_rule(
            """
import jax

@jax.jit
def run(x, bq):
    return pl.pallas_call(
        kern, grid=(4,),
        in_specs=[pl.BlockSpec((bq, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
    )(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "`bq`" in fs[0].message

    def test_static_param_is_exempt(self):
        # The block_q/block_k static-knob idiom of every ops/pallas wrapper.
        fs = lint_rule(
            """
import functools
import jax

@functools.partial(jax.jit, static_argnames=("bq",))
def run(x, bq):
    bq = min(bq, 128)
    return pl.pallas_call(
        kern, grid=(4,),
        in_specs=[pl.BlockSpec((bq, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
    )(x)
""",
            self.RULE,
        )
        assert fs == []

    def test_traced_param_in_grid(self):
        fs = lint_rule(
            """
import jax

@jax.jit
def run(x, n):
    return pl.pallas_call(
        kern, grid=(n, 4),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
    )(x)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "grid entry" in fs[0].message

    def test_unjitted_wrapper_is_not_flagged(self):
        fs = lint_rule(
            """
def run(pl, x, bq):
    return pl.pallas_call(
        kern, grid=(4,),
        in_specs=[pl.BlockSpec((bq, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
    )(x)
""",
            self.RULE,
        )
        assert fs == []

    # The ops/pallas/paged_prefill.py family shape (ISSUE 9 convention:
    # new kernel family => rule engagement pinned positive AND negative):
    # a jitted wrapper whose block geometry derives page_size from a pool
    # operand's SHAPE (static at trace time — clean), vs one that takes
    # page_size as a traced parameter (flagged).
    PAGED_SHAPE = """
import functools
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

@functools.partial(jax.jit, static_argnames=("block_q",))
def paged_chunk(q, k_pages, qs, tables, block_q=128):
    page_size = {PAGE_EXPR}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(4, 2),
        in_specs=[
            pl.BlockSpec((1, block_q), lambda b, i, qs, tables: (b, i)),
            pl.BlockSpec(
                (1, page_size), lambda b, i, qs, tables: (tables[b, i], 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, block_q), lambda b, i, qs, tables: (b, i)),
    )
    return pl.pallas_call(
        functools.partial(kern, block_q=block_q), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(qs, tables, q, k_pages)
"""

    def test_paged_family_shape_derived_page_size_is_clean(self):
        src = self.PAGED_SHAPE.replace("{PAGE_EXPR}", "k_pages.shape[2]")
        assert lint_rule(src, self.RULE) == []

    def test_paged_family_traced_page_size_is_flagged(self):
        src = self.PAGED_SHAPE.replace(
            "def paged_chunk(q, k_pages, qs, tables, block_q=128):",
            "def paged_chunk(q, k_pages, qs, tables, page_size, block_q=128):",
        ).replace("    page_size = {PAGE_EXPR}\n", "")
        fs = lint_rule(src, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "`page_size`" in fs[0].message


# ------------------------------------------------------- prefetch-ref-unused


class TestPrefetchRefUnused:
    RULE = "prefetch-ref-unused"

    # The ISSUE's motivating bug: a block table passed as scalar prefetch but
    # read by NOTHING — every sequence silently reads page 0.
    SNIPPET = """
import functools
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kern(tables_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...]

def run(x, tables):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), lambda i, tables: (i, 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i, tables: (i, 0)),
    )
    return pl.pallas_call(_kern, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(tables, x)
"""

    def test_ignored_block_table_is_flagged(self):
        fs = lint_rule(self.SNIPPET, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "`tables_ref`" in fs[0].message

    def test_index_map_read_counts_as_used(self):
        src = self.SNIPPET.replace(
            "in_specs=[pl.BlockSpec((1, 128), lambda i, tables: (i, 0))],",
            "in_specs=[pl.BlockSpec((1, 128),"
            " lambda i, tables: (tables[i], 0))],",
        )
        assert lint_rule(src, self.RULE) == []

    def test_kernel_body_read_counts_as_used(self):
        src = self.SNIPPET.replace(
            "o_ref[...] = x_ref[...]",
            "o_ref[...] = x_ref[...] * tables_ref[0]",
        )
        assert lint_rule(src, self.RULE) == []

    def test_partial_wrapped_kernel_resolves(self):
        # The ops/pallas idiom: the kernel rides functools.partial with
        # keyword-only static knobs; the body ignores the prefetch ref.
        src = self.SNIPPET.replace(
            "pl.pallas_call(_kern, grid_spec=grid_spec,",
            "pl.pallas_call(functools.partial(_kern, ), grid_spec=grid_spec,",
        )
        fs = lint_rule(src, self.RULE)
        assert rules_of(fs) == [self.RULE]

    def test_unresolvable_index_map_stays_silent(self):
        # An index map whose arity cannot line up with the prefetch args
        # might read anything — no finding, by design.
        src = self.SNIPPET.replace(
            "lambda i, tables: (i, 0))],\n", "make_imap())],\n", 1
        )
        assert lint_rule(src, self.RULE) == []

    def test_second_of_two_refs_flagged(self):
        src = """
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kern(lens_ref, starts_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...] * lens_ref[0]

def run(x, lens, starts):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), lambda i, lens, starts: (lens[i], 0))],
        out_specs=pl.BlockSpec((1, 128), lambda i, lens, starts: (i, 0)),
    )
    return pl.pallas_call(_kern, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(lens, starts, x)
"""
        fs = lint_rule(src, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "#1" in fs[0].message and "`starts_ref`" in fs[0].message

    # The ops/pallas/paged_prefill.py family shape (ISSUE 9 convention): a
    # 4-D grid with FIVE scalar-prefetch operands and a NAMED page-resolving
    # index map shared by K and V. Negative: the real pattern — the block
    # table is read inside `_kv_index`, everything else inside the kernel.
    # Positive: an index map that clamps the logical page but never consults
    # the table — every sequence silently streams page `ki` as physical.
    PAGED_SHAPE = """
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kern(qs_ref, lens_ref, ks_ref, tables_ref, flag_ref, q_ref, k_ref, o_ref):
    o_ref[...] = q_ref[...] * qs_ref[0] * lens_ref[0] * ks_ref[0] * flag_ref[0]

def _kv_index(bi, hi, qi, ki, qs, lens, ks, tables, fl):
    last = jnp.maximum(lens[bi] // 128 - 1, 0)
    phys = tables[bi, jnp.clip(ki, 0, last)]
    return (jnp.maximum(phys, 0), hi, 0, 0)

def run(q, k_pages, qs, lens, ks, tables, flag):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(2, 2, 2, 4),
        in_specs=[
            pl.BlockSpec(
                (1, 1, 128, 64),
                lambda bi, hi, qi, ki, qs, lens, ks, tables, fl: (bi, hi, qi, 0),
            ),
            pl.BlockSpec((1, 1, 128, 64), _kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, 128, 64),
            lambda bi, hi, qi, ki, qs, lens, ks, tables, fl: (bi, hi, qi, 0),
        ),
    )
    return pl.pallas_call(
        functools.partial(_kern), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(qs, lens, ks, tables, flag, q, k_pages)
"""

    def test_paged_chunk_family_shape_is_clean(self):
        assert lint_rule(self.PAGED_SHAPE, self.RULE) == []

    def test_paged_chunk_index_map_ignoring_table_is_flagged(self):
        src = self.PAGED_SHAPE.replace(
            "    phys = tables[bi, jnp.clip(ki, 0, last)]\n"
            "    return (jnp.maximum(phys, 0), hi, 0, 0)",
            "    return (jnp.clip(ki, 0, last), hi, 0, 0)",
        )
        fs = lint_rule(src, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "#3" in fs[0].message and "`tables_ref`" in fs[0].message


# ------------------------------------------------------------ unblocked-timing


class TestUnblockedTiming:
    RULE = "unblocked-timing"

    def test_delta_around_jit_call_without_block(self):
        fs = lint_rule(
            """
import time
import jax

step = jax.jit(lambda x: x + 1)

def measure(x):
    t0 = time.perf_counter()
    y = step(x)
    return time.perf_counter() - t0
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "async dispatch" in fs[0].message

    def test_block_until_ready_closes_the_window(self):
        fs = lint_rule(
            """
import time
import jax

step = jax.jit(lambda x: x + 1)

def measure(x):
    t0 = time.perf_counter()
    y = step(x)
    jax.block_until_ready(y)
    return time.perf_counter() - t0
""",
            self.RULE,
        )
        assert fs == []

    def test_np_asarray_readback_closes_the_window(self):
        fs = lint_rule(
            """
import time
import jax
import numpy as np

step = jax.jit(lambda x: x + 1)

def measure(x):
    t0 = time.perf_counter()
    y = step(x)
    out = np.asarray(y)
    return time.perf_counter() - t0
""",
            self.RULE,
        )
        assert fs == []

    def test_wrapper_from_local_jit_factory(self):
        # The lru-cached builder idiom: fn = _decode_fn(...); fn(...) —
        # the factory's return jax.jit(...) marks its products as wrappers.
        fs = lint_rule(
            """
import time
import jax

def _build(n):
    def run(x):
        return x * n
    return jax.jit(run)

def measure(x):
    g = _build(2)
    t0 = time.perf_counter()
    y = g(x)
    dt = time.perf_counter() - t0
    return dt
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_tracked_jit_counts_as_a_jit_wrapper(self):
        fs = lint_rule(
            """
import time
from cake_tpu.obs.jitwatch import tracked_jit

step = tracked_jit(lambda x: x + 1, name="s")

def measure(x):
    t0 = time.perf_counter()
    y = step(x)
    return time.perf_counter() - t0
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_timed_non_jit_call_is_fine(self):
        fs = lint_rule(
            """
import time

def measure(sock):
    t0 = time.perf_counter()
    sock.send(b"x")
    return time.perf_counter() - t0
""",
            self.RULE,
        )
        assert fs == []

    def test_timer_reuse_checks_each_window_against_its_own_binding(self):
        # The same t0 name reused for a second (blocked) window must not
        # mask the FIRST window's missing sync.
        fs = lint_rule(
            """
import time
import jax

step = jax.jit(lambda x: x + 1)

def measure(x):
    t0 = time.perf_counter()
    y = step(x)
    bad = time.perf_counter() - t0
    t0 = time.perf_counter()
    z = step(x)
    jax.block_until_ready(z)
    good = time.perf_counter() - t0
    return bad, good
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert fs[0].line == 10  # the FIRST delta, not the blocked second

    def test_delta_before_the_jit_call_is_fine(self):
        # The window is positional: a call AFTER the clock is read again
        # is not inside the measurement.
        fs = lint_rule(
            """
import time
import jax

step = jax.jit(lambda x: x + 1)

def measure(x):
    t0 = time.perf_counter()
    dt = time.perf_counter() - t0
    y = step(x)
    return dt
""",
            self.RULE,
        )
        assert fs == []


# ------------------------------------------------------------ unbounded-socket-op


class TestUnboundedSocketOp:
    RULE = "unbounded-socket-op"
    PATH = "cake_tpu/runtime/snippet.py"

    def test_recv_with_no_timeout_in_scope(self):
        fs = lint_rule(
            """
def pump(sock):
    return sock.recv(4096)
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]
        assert "sock.recv" in fs[0].message

    def test_sendall_on_untimed_created_socket(self):
        fs = lint_rule(
            """
import socket

def push(data):
    s = socket.create_connection(("h", 1))
    s.sendall(data)
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_settimeout_in_scope_is_fine(self):
        fs = lint_rule(
            """
def pump(sock):
    sock.settimeout(5.0)
    return sock.recv(4096)
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_settimeout_none_does_not_count(self):
        fs = lint_rule(
            """
def pump(sock):
    sock.settimeout(None)
    return sock.recv(4096)
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_create_connection_timeout_kwarg_is_fine(self):
        fs = lint_rule(
            """
import socket

def push(data):
    s = socket.create_connection(("h", 1), timeout=3.0)
    s.sendall(data)
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_class_scope_covers_handed_around_connections(self):
        # The accept loop configures the conn; another method uses it —
        # the whole class is the configuring scope for parameters/self attrs.
        fs = lint_rule(
            """
class Server:
    def accept_loop(self, conn):
        conn.settimeout(30.0)
        self._serve(conn)

    def _serve(self, conn):
        conn.sendall(b"hi")
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_self_sock_untimed_across_methods(self):
        fs = lint_rule(
            """
import socket

class Client:
    def __init__(self):
        self._sock = socket.create_connection(("h", 1))

    def push(self, data):
        self._sock.sendall(data)
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_non_socket_connect_is_ignored(self):
        fs = lint_rule(
            """
def run(db):
    db.connect()
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_outside_runtime_is_ignored(self):
        fs = lint_rule(
            """
def pump(sock):
    return sock.recv(4096)
""",
            self.RULE,
            path="cake_tpu/utils/snippet.py",
        )
        assert fs == []


# ------------------------------------------------------------------- the tree


def test_every_shipped_rule_is_registered():
    names = {r["name"] for r in engine.rule_table()}
    assert names == {
        "host-sync-in-jit",
        "jit-in-hot-loop",
        "unhashable-static-arg",
        "unblocked-timing",
        "donation-after-use",
        "unlocked-shared-mutation",
        "frame-field-drift",
        "unknown-mesh-axis",
        "spec-arity-mismatch",
        "blockspec-indexmap-arity",
        "grid-block-rank-mismatch",
        "traced-block-dim",
        "traced-sampling-knob",
        "prefetch-ref-unused",
        "mutable-default-arg",
        "bare-except-swallow",
        "unbounded-socket-op",
        "naked-retry-loop",
        "stale-block-table",
        "unbounded-wait",
        "unbounded-metric-label",
        "span-leak",
        "step-state-unlocked",
        "taxonomy-drift",
        "requestlog-field-drift",
        "lock-order-cycle",
        "blocking-call-under-lock",
        "callback-under-lock",
        "notify-outside-lock",
        "leak-on-error-path",
        "double-release",
        "release-outside-choke-point",
        "refund-missing-on-shed",
    }


def test_readme_documents_every_rule():
    """The README rule catalog is pinned against the registry: adding a
    rule without a README row (or renaming one) fails here, so the docs
    cannot drift from the code."""
    repo = __import__("pathlib").Path(__file__).resolve().parent.parent
    readme = (repo / "README.md").read_text()
    missing = [
        r["name"]
        for r in engine.rule_table()
        if f"`{r['name']}`" not in readme
    ]
    assert missing == [], f"rules missing from README.md: {missing}"


# ------------------------------------------------------------ naked-retry-loop


class TestNakedRetryLoop:
    RULE = "naked-retry-loop"
    PATH = "cake_tpu/runtime/snippet.py"

    def test_unbounded_retry_without_backoff(self):
        fs = lint_rule(
            """
def pump(sock):
    while True:
        try:
            return sock.recv(4096)
        except ConnectionError:
            continue
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]
        assert "while True" in fs[0].message

    def test_hop_call_retry_flagged(self):
        fs = lint_rule(
            """
def round_trip(client, frame):
    while True:
        try:
            return client.forward(frame)
        except (TimeoutError, OSError):
            client.reconnect()
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_bounded_for_loop_is_fine(self):
        fs = lint_rule(
            """
def pump(sock):
    for attempt in range(3):
        try:
            return sock.recv(4096)
        except ConnectionError:
            continue
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_backoff_in_scope_is_fine(self):
        fs = lint_rule(
            """
import time

def pump(sock):
    while True:
        try:
            return sock.recv(4096)
        except ConnectionError:
            time.sleep(0.5)
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_event_wait_counts_as_backoff(self):
        fs = lint_rule(
            """
def probe(self, sock):
    while True:
        try:
            sock.sendall(b"ping")
        except ConnectionError:
            pass
        self._stop.wait(1.0)
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_handler_that_raises_is_fine(self):
        fs = lint_rule(
            """
def pump(sock):
    while True:
        try:
            return sock.recv(4096)
        except ConnectionError:
            raise
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_stop_flag_loop_is_fine(self):
        fs = lint_rule(
            """
def serve(self, conn):
    while not self._stop.is_set():
        try:
            conn.recv(1)
        except ConnectionError:
            continue
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_non_connection_except_is_fine(self):
        fs = lint_rule(
            """
def pump(sock):
    while True:
        try:
            return sock.recv(4096)
        except ValueError:
            continue
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_outside_runtime_is_fine(self):
        fs = lint_rule(
            """
def pump(sock):
    while True:
        try:
            return sock.recv(4096)
        except ConnectionError:
            continue
""",
            self.RULE,
            path="cake_tpu/ops/snippet.py",
        )
        assert fs == []


# ----------------------------------------------------------- stale-block-table


class TestStaleBlockTable:
    RULE = "stale-block-table"

    def test_row_used_after_make_private(self):
        # The detached-row bug class: the captured row still names the
        # SHARED page after the CoW split remapped the lane.
        fs = lint_rule(
            """
def write(self, lane, lp):
    row = self.allocator.block_tables[lane]
    self.allocator.make_private(lane, lp)
    return row[lp]
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "`row`" in fs[0].message

    def test_table_snapshot_used_after_fork_chain(self):
        # Whole-table snapshots (the jnp.asarray operand idiom) go stale
        # the same way — copies are snapshots of the same dead mapping.
        fs = lint_rule(
            """
def dispatch(self, lane, pages):
    tables = jnp.asarray(self.allocator.block_tables)
    self.allocator.fork_chain(lane, pages, 0)
    return run(tables)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_generic_mutator_needs_allocatorish_receiver(self):
        # `lease.release()` is not an allocator mutation; `alloc.release`
        # and `self._prefix.fork` are.
        fs = lint_rule(
            """
def ok(self, lane, lease):
    row = self.allocator.block_tables[lane]
    lease.release()
    return row[0]

def bad(self, lane, alloc):
    row = alloc.block_tables[lane]
    alloc.release(lane)
    return row[0]

def bad2(self, lane, ids, pad):
    row = self.allocator.block_tables[lane]
    self._prefix.fork(lane, ids, pad)
    return row[0]
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE, self.RULE]
        assert [f.line for f in fs] == [10, 15]

    def test_reread_after_mutation_is_fine(self):
        # Rebinding from a fresh read AFTER the mutation is the fix.
        fs = lint_rule(
            """
def write(self, lane, lp):
    row = self.allocator.block_tables[lane]
    use(row)
    self.allocator.make_private(lane, lp)
    row = self.allocator.block_tables[lane]
    return row[lp]
""",
            self.RULE,
        )
        assert fs == []

    def test_inline_read_at_use_site_is_fine(self):
        fs = lint_rule(
            """
def write(self, lane, lp):
    self.allocator.make_private(lane, lp)
    return self.allocator.block_tables[lane][lp]
""",
            self.RULE,
        )
        assert fs == []

    def test_refcount_only_ops_do_not_invalidate(self):
        # retain/release_pages touch refcounts, never lane rows: the
        # prefix cache's insert path captures a lane's page and swaps
        # cache references around it legitimately.
        fs = lint_rule(
            """
def insert(self, lane, logical):
    phys = int(self.allocator.block_tables[lane][logical])
    self.allocator.retain_pages([phys])
    self.allocator.release_pages([phys])
    return phys
""",
            self.RULE,
        )
        assert fs == []

    def test_use_before_mutation_is_fine(self):
        fs = lint_rule(
            """
def release(self, lane):
    row = self.allocator.block_tables[lane]
    flush(row)
    self.allocator.release(lane)
""",
            self.RULE,
        )
        assert fs == []


# --------------------------------------------------------------- unbounded-wait


class TestUnboundedWait:
    RULE = "unbounded-wait"
    PATH = "cake_tpu/runtime/snippet.py"

    def test_condition_wait_without_timeout(self):
        fs = lint_rule(
            """
import threading

class Engine:
    def __init__(self):
        self._cv = threading.Condition()

    def run(self):
        with self._cv:
            self._cv.wait()
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]
        assert "self._cv.wait()" in fs[0].message

    def test_event_wait_without_timeout_as_parameter(self):
        # Name heuristic: a handed-around `*event` parameter counts.
        fs = lint_rule(
            """
def block(done_event):
    done_event.wait()
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_thread_join_without_timeout(self):
        fs = lint_rule(
            """
import threading

class Guard:
    def __init__(self):
        self._worker = threading.Thread(target=print)

    def stop(self):
        self._worker.join()
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]
        assert ".join()" in fs[0].message

    def test_bounded_waits_and_joins_are_fine(self):
        fs = lint_rule(
            """
import threading

class Engine:
    def __init__(self):
        self._cv = threading.Condition()
        self._worker = threading.Thread(target=print)

    def run(self):
        with self._cv:
            self._cv.wait(timeout=1.0)

    def stop(self):
        self._worker.join(5.0)
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []

    def test_timeout_none_is_still_unbounded(self):
        fs = lint_rule(
            """
import threading

class Engine:
    def __init__(self):
        self._cv = threading.Condition()

    def run(self):
        self._cv.wait(timeout=None)
""",
            self.RULE,
            path=self.PATH,
        )
        assert rules_of(fs) == [self.RULE]

    def test_obs_and_utils_are_in_scope(self):
        # ISSUE 17 widened the gate beyond runtime/: the telemetry locks
        # and flusher threads in obs/ and utils/ play by the same rules.
        src = """
import threading

class Engine:
    def __init__(self):
        self._cv = threading.Condition()

    def run(self):
        self._cv.wait()
"""
        for path in (
            "cake_tpu/obs/snippet.py",
            "cake_tpu/utils/snippet.py",
        ):
            fs = lint_rule(src, self.RULE, path=path)
            assert rules_of(fs) == [self.RULE], path

    def test_jit_side_trees_are_out_of_scope(self):
        # ops/ and models/ stay out: no thread coordination there, and a
        # `wait` is somebody's math helper.
        fs = lint_rule(
            """
import threading

class Engine:
    def __init__(self):
        self._cv = threading.Condition()

    def run(self):
        self._cv.wait()
""",
            self.RULE,
            path="cake_tpu/models/snippet.py",
        )
        assert fs == []

    def test_unrelated_wait_receivers_not_flagged(self):
        # A `.wait()` on something that is neither factory-assigned nor
        # name-matched (a subprocess handle, a future) is out of scope.
        fs = lint_rule(
            """
def reap(proc):
    proc.wait()
""",
            self.RULE,
            path=self.PATH,
        )
        assert fs == []


# ---------------------------------------------------- unbounded-metric-label


class TestUnboundedMetricLabel:
    RULE = "unbounded-metric-label"

    def test_request_id_label_flagged(self):
        fs = lint_rule(
            """
from cake_tpu.utils import metrics

def record(rid):
    metrics.registry.counter("cake_ops_total", "ops").inc(rid=rid)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "rid" in fs[0].message

    def test_raw_header_label_flagged(self):
        fs = lint_rule(
            """
from cake_tpu.utils import metrics

def record(handler):
    metrics.registry.gauge("cake_client_info", "x").set(
        1, client=handler.headers.get("User-Agent")
    )
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_fresh_uuid_and_prompt_flagged_on_local_metric(self):
        fs = lint_rule(
            """
import uuid
from cake_tpu.utils import metrics

def record(prompt):
    h = metrics.registry.histogram("cake_x_seconds", "x")
    h.observe(0.5, req=str(uuid.uuid4()))
    h.observe(0.5, text=prompt)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE, self.RULE]

    def test_bounded_labels_not_flagged(self):
        # The real tree's conventions: node names, capped tenant ids, enum
        # kinds, directions — all bounded sets, none flagged.
        fs = lint_rule(
            """
from cake_tpu.utils import metrics

def record(node, tenant, kind):
    metrics.registry.counter("cake_ops_total", "ops").inc(
        node=node, tenant=tenant, kind=kind, direction="rx"
    )
    metrics.registry.gauge("cake_level", "x").set(3.0, node=node)
""",
            self.RULE,
        )
        assert fs == []

    def test_value_kwargs_and_non_metric_calls_out_of_scope(self):
        # n=/v= are sample values, not labels; flight.record and arbitrary
        # .set() receivers are not metric record calls.
        fs = lint_rule(
            """
from cake_tpu.utils import metrics

def record(rid, cost):
    metrics.registry.counter("cake_tokens_total", "t").inc(n=cost)
    metrics.flight.record("submitted", rid, request_id=rid)
    some_dict = {}
    some_dict.setdefault("x", 1)

class Config:
    def set(self, **kw): ...

def configure(cfg, request_id):
    cfg.set(request_id=request_id)
""",
            self.RULE,
        )
        assert fs == []

    def test_inline_suppression_respected(self):
        fs = lint_rule(
            """
from cake_tpu.utils import metrics

def record(rid):
    metrics.registry.counter("cake_debug_total", "d").inc(
        rid=rid  # cake-lint: disable=unbounded-metric-label
    )
""",
            self.RULE,
        )
        assert fs == []


# ---------------------------------------------------- traced-sampling-knob


class TestTracedSamplingKnob:
    RULE = "traced-sampling-knob"

    # The fused decode family contract (ISSUE 13): sampling knobs are
    # static; a jitted wrapper that takes one traced either fails to trace
    # or recompiles per value.
    SNIPPET = """
import jax
from cake_tpu.ops.pallas.fused_sample_tail import fused_sample_tail

@jax.jit
def tail(logits, ring, noise, temperature):
    return fused_sample_tail(
        logits, ring, noise, temperature=temperature, top_k=None,
        top_p=None, repeat_penalty=1.0, impl="xla",
    )
"""

    def test_traced_temperature_is_flagged(self):
        fs = lint_rule(self.SNIPPET, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "`temperature`" in fs[0].message

    def test_static_argnames_knob_is_clean(self):
        src = self.SNIPPET.replace(
            "@jax.jit",
            '@functools.partial(jax.jit, static_argnames=("temperature",))',
        ).replace("import jax", "import functools\nimport jax")
        assert lint_rule(src, self.RULE) == []

    def test_closure_knobs_are_clean(self):
        # The repo idiom: knobs close over the jitted fn, never ride it.
        src = """
import jax
from cake_tpu.models.llama.fused import sampled_decode_scan

def build(temperature, top_k):
    def run(kv, tok, slot, keys, ring, ring_idx):
        return sampled_decode_scan(
            lambda t, kv, p: (t, kv), kv, tok, slot, keys, ring, ring_idx,
            n_steps=4, temperature=temperature, top_k=top_k, top_p=None,
            repeat_penalty=1.0,
        )
    return jax.jit(run, donate_argnums=(0,))
"""
        assert lint_rule(src, self.RULE) == []

    def test_non_fused_family_jit_with_knob_param_is_clean(self):
        # A jit that never calls into the fused family may do what it
        # likes with a parameter that happens to be named temperature.
        src = """
import jax

@jax.jit
def scale(x, temperature):
    return x / temperature
"""
        assert lint_rule(src, self.RULE) == []

    def test_call_form_jit_traced_knob_is_flagged(self):
        src = """
import jax
from cake_tpu.models.llama.fused import sample_step

def one(logits, keys, ring, ring_idx, top_k):
    return sample_step(
        logits, keys, ring, ring_idx, temperature=0.7, top_k=top_k,
        top_p=None, repeat_penalty=1.0,
    )

sampler = jax.jit(one)
"""
        fs = lint_rule(src, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "`top_k`" in fs[0].message


class TestFusedFamilyKernelShapes:
    """ISSUE 13 convention (mirrors the ISSUE 9 pins): the new fused-kernel
    family shapes keep traced-block-dim and prefetch-ref-unused ENGAGED —
    positive and negative for each, on snippets shaped like the real
    kernels (ops/pallas/fused_sample_tail.py and a slot-DMA cache write)."""

    # The fused sampling tail's shape: ring as ONE scalar-prefetch operand,
    # a (b, n_v) grid over vocab tiles, block_v as a static knob.
    TAIL_SHAPE = """
import functools
import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kern(ring_ref, logits_ref, o_ref, scr):
    o_ref[0, 0] = ring_ref[0, 0] + logits_ref[0, 0].astype('int32')

def _tile(bi, vi, ring):
    return (bi, vi)

def _out(bi, vi, ring):
    return (bi, 0)

@functools.partial(jax.jit, static_argnames=("block_v",))
def tail(logits, ring, block_v=128):
    vocab = logits.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 2),
        in_specs=[pl.BlockSpec((1, block_v), _tile)],
        out_specs=pl.BlockSpec((1, 1), _out),
        scratch_shapes=[pltpu.VMEM((1, 256), 'float32')],
    )
    return pl.pallas_call(
        functools.partial(_kern), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, 1), 'int32'),
    )(ring, logits)
"""

    def test_tail_shape_static_block_v_is_clean(self):
        assert lint_rule(self.TAIL_SHAPE, "traced-block-dim") == []

    def test_tail_shape_traced_block_v_is_flagged(self):
        src = self.TAIL_SHAPE.replace(
            '@functools.partial(jax.jit, static_argnames=("block_v",))',
            "@jax.jit",
        )
        fs = lint_rule(src, "traced-block-dim")
        assert rules_of(fs) == ["traced-block-dim"]
        assert "`block_v`" in fs[0].message

    def test_tail_shape_ring_read_in_kernel_is_clean(self):
        assert lint_rule(self.TAIL_SHAPE, "prefetch-ref-unused") == []

    def test_tail_shape_ignored_ring_is_flagged(self):
        # A penalty ring that is plumbed but never read: the fusion would
        # silently sample unpenalized logits.
        src = self.TAIL_SHAPE.replace(
            "o_ref[0, 0] = ring_ref[0, 0] + logits_ref[0, 0].astype('int32')",
            "o_ref[0, 0] = logits_ref[0, 0].astype('int32')",
        )
        fs = lint_rule(src, "prefetch-ref-unused")
        assert rules_of(fs) == ["prefetch-ref-unused"]
        assert "`ring_ref`" in fs[0].message

    # The paged ingest's shape: slot + block table as scalar prefetch, the
    # write resolved through the table inside the kernel body.
    INGEST_SHAPE = """
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kern(slot_ref, tab_ref, qkv_ref, q_ref):
    bi = pl.program_id(0)
    phys = tab_ref[bi, jnp.minimum(slot_ref[0] // 8, tab_ref.shape[1] - 1)]
    q_ref[...] = qkv_ref[...] * (phys >= 0) * slot_ref[0]

def _row(bi, slot, tab):
    return (bi, 0)

def ingest(qkv, slot, tables):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(4,),
        in_specs=[pl.BlockSpec((1, 128), _row)],
        out_specs=pl.BlockSpec((1, 128), _row),
    )
    return pl.pallas_call(
        functools.partial(_kern), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
    )(slot, tables, qkv)
"""

    def test_ingest_shape_table_read_in_body_is_clean(self):
        assert lint_rule(self.INGEST_SHAPE, "prefetch-ref-unused") == []

    def test_ingest_shape_ignored_table_is_flagged(self):
        # The paging bug class: a block table passed but ignored — every
        # lane writes wherever the clamp lands instead of its own pages.
        src = self.INGEST_SHAPE.replace(
            "    phys = tab_ref[bi, jnp.minimum(slot_ref[0] // 8, "
            "tab_ref.shape[1] - 1)]\n"
            "    q_ref[...] = qkv_ref[...] * (phys >= 0) * slot_ref[0]",
            "    q_ref[...] = qkv_ref[...] * slot_ref[0]",
        )
        fs = lint_rule(src, "prefetch-ref-unused")
        assert rules_of(fs) == ["prefetch-ref-unused"]
        assert "`tab_ref`" in fs[0].message


# --------------------------------------------------------------- span-leak


class TestSpanLeak:
    RULE = "span-leak"

    def test_begin_without_end_is_flagged(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(req):
    sid = timeline.begin("request", track="lane0")
    do_work(req)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "never" in fs[0].message

    def test_end_only_under_if_is_flagged(self):
        # The non-raising else path leaks the span.
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(req, ok):
    sid = timeline.begin("request")
    if ok:
        timeline.end(sid)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "some paths" in fs[0].message

    def test_end_only_in_except_is_flagged(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(req):
    sid = timeline.begin("request")
    try:
        work(req)
    except ValueError:
        timeline.end(sid)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_end_in_finally_is_clean(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(req):
    sid = timeline.begin("request")
    try:
        work(req)
    finally:
        timeline.end(sid)
""",
            self.RULE,
        )
        assert fs == []

    def test_straight_line_end_is_clean(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(req):
    sid = timeline.begin("request")
    work(req)
    timeline.end(sid, args={"n": 1})
""",
            self.RULE,
        )
        assert fs == []

    def test_handed_off_id_is_clean(self):
        # Stored on self / returned / passed on: the lifecycle is the
        # holder's (exactly the serving.py _RowState shape).
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

class Row:
    def open_span(self):
        self._span = timeline.begin("request")

def open_and_return():
    sid = timeline.begin("request")
    return sid

def open_and_register(reg):
    sid = timeline.begin("request")
    reg.track(sid)
""",
            self.RULE,
        )
        assert fs == []

    def test_request_scoped_track_name_is_flagged(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(rid):
    with timeline.span("request", track=f"req-{rid}"):
        pass
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "track" in fs[0].message

    def test_bounded_track_names_are_clean(self):
        fs = lint_rule(
            """
from cake_tpu.obs.timeline import timeline

def serve(lane, rid):
    sid = timeline.begin("request", rid=rid, track=f"lane{lane}")
    timeline.instant("first-token", rid=rid, track="engine")
    timeline.end(sid)
""",
            self.RULE,
        )
        assert fs == []


# ----------------------------------------------------------- step-state-unlocked


class TestStepStateUnlocked:
    RULE = "step-state-unlocked"

    POSITIVE = """
import threading

class Engine:
    _STEP_STATE = ("_spilled", "_lane_map")

    def __init__(self):
        self._cv = threading.Condition()
        self._spilled = {}
        self._lane_map = {}

    def preempt(self, rid, rec):
        self._spilled[rid] = rec
"""

    NEGATIVE = """
import threading

class Engine:
    _STEP_STATE = ("_spilled",)

    def __init__(self):
        self._cv = threading.Condition()
        self._spilled = {}

    def preempt(self, rid, rec):
        with self._cv:
            self._spilled[rid] = rec

    def depth(self):
        return len(self._spilled)  # reads stay lock-free

    def other_state(self):
        self._scratch = 1  # undeclared attrs are not step state
"""

    def test_declared_attr_mutated_without_cv(self):
        fs = lint_rule(self.POSITIVE, self.RULE)
        assert rules_of(fs) == [self.RULE]
        assert "_spilled" in fs[0].message

    def test_first_ever_mutation_is_flagged(self):
        # The differentiator vs unlocked-shared-mutation: no guarded
        # sibling site exists anywhere, yet the declaration still fires.
        fs = lint_rule(self.POSITIVE, "unlocked-shared-mutation")
        assert fs == []  # the inference-based rule is blind here
        fs = lint_rule(self.POSITIVE, self.RULE)
        assert len(fs) == 1

    def test_guarded_mutations_and_reads_are_clean(self):
        assert lint_rule(self.NEGATIVE, self.RULE) == []

    def test_init_is_exempt_and_undeclared_classes_skipped(self):
        assert lint_rule(
            """
import threading

class Plain:
    def __init__(self):
        self._lock = threading.Lock()
        self._spilled = {}

    def mutate(self):
        self._spilled = {}
""",
            self.RULE,
        ) == []

    def test_pop_and_clear_count_as_mutations(self):
        fs = lint_rule(
            """
import threading

class Engine:
    _STEP_STATE = ("_spilled",)

    def __init__(self):
        self._cv = threading.Condition()
        self._spilled = {}

    def drain(self):
        self._spilled.clear()

    def drop(self, rid):
        self._spilled.pop(rid, None)
""",
            self.RULE,
        )
        assert len(fs) == 2


# ---------------------------------------------------------- taxonomy-drift


class TestTaxonomyDrift:
    RULE = "taxonomy-drift"

    def test_store_into_phase_accumulator_outside_registry(self):
        fs = lint_rule(
            """
class Row:
    def account(self, dt):
        self.phase["warmup"] += dt
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "'warmup'" in fs[0].message
        assert "PHASES" in fs[0].message

    def test_store_into_buckets_outside_registry(self):
        fs = lint_rule(
            """
class Ledger:
    def add(self, dt):
        self.buckets["padx"] = dt
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "BUCKETS" in fs[0].message

    def test_phase_kwarg_literal_outside_registry(self):
        fs = lint_rule(
            """
def observe(hist, v):
    hist.observe(v, phase="warmup")
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_phase_observe_positional_literal(self):
        fs = lint_rule(
            """
class Engine:
    def note(self, s):
        self._phase_observe("cooldown", s)
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]

    def test_decision_vocabulary_pinned(self):
        fs = lint_rule(
            """
def verdict(audit, rid):
    audit.record("admit", "because_reasons", rid=rid)
    audit.record("evaporate", "fair_order", rid=rid)
""",
            self.RULE,
        )
        assert len(fs) == 2
        assert any("DECISION_CAUSES" in f.message for f in fs)
        assert any("DECISION_ACTIONS" in f.message for f in fs)

    def test_registered_names_and_dynamic_values_pass(self):
        # Registry members, dynamic (non-literal) names, stats-dict READ
        # navigation, and unrelated receivers are all out of scope.
        fs = lint_rule(
            """
class Row:
    def account(self, dt, phase):
        self.phase["decode"] += dt
        self.phase[phase] += dt

def add(ledger, dt):
    ledger.buckets["host_gap"] += dt

def render(stats, hist, v):
    total = stats["phases"]["phases"]
    hist.observe(v, phase="prefill")
    other = {}
    other["warmup"] = 1.0

def verdict(audit, rid):
    audit.record("defer", "page_pressure", rid=rid)
""",
            self.RULE,
        )
        assert fs == []


# -------------------------------------------------- requestlog-field-drift


class TestRequestLogFieldDrift:
    RULE = "requestlog-field-drift"

    def test_unregistered_field_on_record(self):
        fs = lint_rule(
            """
def finish(engine, rid):
    engine.requestlog.record(
        request_id=rid, tenant="t", finish_reason="stop",
        latency_bucket="fast",
    )
""",
            self.RULE,
        )
        assert rules_of(fs) == [self.RULE]
        assert "'latency_bucket'" in fs[0].message
        assert "REQUEST_LOG_FIELDS" in fs[0].message

    def test_receiver_stem_variants_and_literal_vocabularies(self):
        # request_log / reqlog receivers are in scope; literal
        # finish_reason/slo values are pinned to their registries.
        fs = lint_rule(
            """
def a(request_log, rid):
    request_log.record(
        request_id=rid, tenant="t", finish_reason="evaporated",
    )

def b(reqlog, rid):
    reqlog.record(
        request_id=rid, tenant="t", finish_reason="stop", slo="fine",
    )
""",
            self.RULE,
        )
        assert len(fs) == 2
        assert any("REQUEST_OUTCOMES" in f.message for f in fs)
        assert any("REQUEST_SLO_VERDICTS" in f.message for f in fs)

    def test_registered_fields_and_other_receivers_pass(self):
        # Registered fields with dynamic values pass; record() on audit/
        # flight/metric receivers is someone else's vocabulary; **fields
        # fan-ins are the runtime check's job.
        fs = lint_rule(
            """
def finish(engine, rid, finish, verdict, fields):
    engine.requestlog.record(
        request_id=rid, tenant="t", priority=1, prompt_tokens=4,
        completion_tokens=2, ttft_s=0.1, finish_reason=finish,
        slo=verdict, phases={}, decisions=[], node="local",
    )
    engine.requestlog.record(**fields)
    engine.audit.record("admit", "fair_order", rid=rid)
    flight.record("submitted", rid, path="serialized")
""",
            self.RULE,
        )
        assert fs == []
