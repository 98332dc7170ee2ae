"""Every kernel in this package against its XLA twin, at one geometry.

``chip_smoke.py`` runs this on the chip at a model's real widths (phase C);
its CPU rehearsal runs it at a tiny geometry through the interpreter. The
unit tests pin the kernels at toy shapes in interpret mode only, and Mosaic
accepts or refuses a kernel by its block shapes, so the shapes here are the
ones the server dispatches: decode at batch 1 and 8, a prefill chunk and a
whole-prompt prefill, one page size, the model's vocabulary.

Each case runs the kernel with its defaults (what the server gets), then the
twin, and compares on the host. ``recorded_interpret`` notes the
``interpret`` argument every ``pallas_call`` was traced with, so a caller on
the chip can refuse a run in which a kernel went through the interpreter.

Tolerances. Kernel and twin do the same arithmetic in a different order, so
they agree to rounding, not to the bit (bit-identity between them is a
property of the CPU interpreter at f32, where both sides lower to the same
XLA ops). The error of a case is ``max|kernel - twin| / max|twin|``:

  * bf16 outputs: 2**-6. Both sides accumulate in f32 and round the result
    to bf16 once (half an ulp, 2**-9 relative, each); the attention kernels
    also round the softmax probabilities to bf16 before the value matmul
    where the twin rounds them after normalising (another 2**-9 each side).
    2**-6 is four bf16 ulps of the largest value.
  * f32 outputs (the rehearsal): 1e-4, reassociated f32 sums over at most a
    few thousand terms.
  * int4 matmul: the kernel scales the weights into the activation dtype
    before the dot, the twin scales the f32 group partials after it; with
    bf16 activations that is one more bf16 rounding per weight, averaged
    over the contraction: 2**-5.
  * token ids (the sampling tail): exact.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

_BIG = np.int32(2**30)  # a key position no query reaches (batch.PAD_SENTINEL)


@dataclasses.dataclass(frozen=True)
class Geometry:
    hidden: int
    intermediate: int
    n_q: int
    n_kv: int
    head_dim: int
    vocab: int
    window: int | None
    page_size: int
    max_seq: int
    chunk: int
    int4_group: int
    dtype: str  # "bf16" | "f32"
    batches: tuple[int, ...] = (1, 8)
    # KV-head counts beside ``n_kv`` at which the pool's write is tried: a
    # slab is all the KV heads of a row, so its block follows them
    write_kv: tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class HybridGeometry:
    """A model with state layers (models/llama/hybrid.py): its attention
    layers' head layout for the paged kernels, its mixer's sizes."""
    hidden: int
    n_q: int
    n_kv: int
    head_dim: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    page_size: int
    max_seq: int
    chunk: int
    prompt: int  # a mixer prefill's length; not a multiple of the scan's chunk
    dtype: str
    batches: tuple[int, ...] = (1, 8)


@contextlib.contextmanager
def recorded_interpret():
    """Yields a list that receives ``bool(interpret)`` for every
    ``pallas_call`` traced inside the block."""
    from jax.experimental import pallas as pl

    seen: list[bool] = []
    real = pl.pallas_call

    def recording(*args, **kwargs):
        seen.append(bool(kwargs.get("interpret", False)))
        return real(*args, **kwargs)

    pl.pallas_call = recording
    try:
        yield seen
    finally:
        pl.pallas_call = real


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    denom = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(got - want))) / denom


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


class _Cases:
    """Runs cases one by one; a case that raises is a failed case with the
    compiler's message, not the end of the run."""

    def __init__(self, geom: Geometry):
        self.g = geom
        self.dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[geom.dtype]
        self.tol = 2.0**-6 if geom.dtype == "bf16" else 1e-4
        self.results: list[dict] = []
        self._key = jax.random.PRNGKey(0)

    def key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def normal(self, shape, scale=1.0, dtype=None):
        x = jax.random.normal(self.key(), shape, jnp.float32) * scale
        return x.astype(dtype or self.dtype)

    def run(self, kernel: str, case: str, fn, tol: float | None = None):
        """``fn() -> (got, want, first_call_seconds)``; arrays or tuples of
        arrays. ``tol`` 0 demands equality."""
        tol = self.tol if tol is None else tol
        rec = {"kernel": kernel, "case": case, "tol": tol}
        try:
            got, want, first_s = fn()
            gots = got if isinstance(got, tuple) else (got,)
            wants = want if isinstance(want, tuple) else (want,)
            if tol == 0:
                err = 0.0 if all(
                    np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(gots, wants)
                ) else float("inf")
            else:
                err = max(_rel_err(a, b) for a, b in zip(gots, wants))
            rec.update(
                ok=err <= tol, max_err=err, first_call_s=round(first_s, 3)
            )
        except Exception as e:  # noqa: BLE001 — the message IS the finding
            rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        self.results.append(rec)


def _live_k_positions(n_slots, starts, lengths):
    slots = jnp.arange(n_slots, dtype=jnp.int32)[None, :]
    live = (slots >= starts[:, None]) & (slots < lengths[:, None])
    return jnp.where(live, slots, _BIG)


def _row_bounds(c: _Cases, b: int, n_slots: int, q_len: int):
    """Per-row (starts, lengths): left pads of differing size, live prefixes
    ending at differing slots, room for ``q_len`` queries at the end."""
    rng = np.random.default_rng(b * 1000 + n_slots + q_len)
    lengths = rng.integers(max(q_len, n_slots // 2), n_slots + 1, size=b)
    lengths[0] = n_slots  # one row at full length
    starts = rng.integers(0, np.maximum(1, (lengths - q_len) // 2 + 1))
    starts[0] = 0
    return (
        jnp.asarray(starts, jnp.int32),
        jnp.asarray(lengths, jnp.int32),
    )


def _attention_cases(c: _Cases) -> None:
    from cake_tpu.ops.attention import gqa_attention, gqa_attention_hm
    from cake_tpu.ops.pallas.chunk_prefill import chunk_prefill_attention
    from cake_tpu.ops.pallas.decode_attention import decode_attention
    from cake_tpu.ops.pallas.flash_attention import flash_attention
    from cake_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_xla,
    )
    from cake_tpu.ops.pallas.paged_prefill import (
        paged_chunk_attention,
        paged_chunk_attention_xla,
    )

    g = c.g
    n_p = g.max_seq // g.page_size
    # The model's own window (at these lengths it may never cut a key) and
    # one that does, so the mask and the front pruning both run.
    windows = tuple(dict.fromkeys((g.window, g.max_seq // 4)))

    for b in g.batches:
        k_cache = c.normal((b, g.n_kv, g.max_seq, g.head_dim))
        v_cache = c.normal((b, g.n_kv, g.max_seq, g.head_dim))
        # The same bytes as a page pool with every row's pages scattered.
        perm = np.random.default_rng(b).permutation(b * n_p)
        tables = jnp.asarray(perm.reshape(b, n_p), jnp.int32)

        def to_pool(cache):
            pages = cache.reshape(b, g.n_kv, n_p, g.page_size, g.head_dim)
            pages = jnp.moveaxis(pages, 2, 1).reshape(
                b * n_p, g.n_kv, g.page_size, g.head_dim
            )
            return jnp.zeros_like(pages).at[tables.reshape(-1)].set(pages)

        k_pool, v_pool = to_pool(k_cache), to_pool(v_cache)

        for window in windows:
            # ---- decode: one query per row at slot length-1
            starts, lengths = _row_bounds(c, b, g.max_seq, 1)
            q = c.normal((b, 1, g.n_q, g.head_dim))
            q_pos = (lengths - 1)[:, None]
            k_pos = _live_k_positions(g.max_seq, starts, lengths)

            def dense_decode():
                got, s = _timed(
                    lambda: decode_attention(
                        q, k_cache, v_cache, lengths, starts, window=window
                    )
                )
                want = gqa_attention_hm(
                    q, k_cache, v_cache, q_pos, k_pos, window=window
                )
                return got, want, s

            def paged_decode():
                got, s = _timed(
                    lambda: paged_decode_attention(
                        q, k_pool, v_pool, lengths, tables, starts,
                        window=window,
                    )
                )
                want = paged_decode_attention_xla(
                    q, k_pool, v_pool, q_pos, k_pos, tables, window=window
                )
                return got, want, s

            c.run("decode_attention", f"b={b} window={window}", dense_decode)
            c.run(
                "paged_attention", f"b={b} window={window}", paged_decode
            )

            # ---- a chunk of queries at the end of each row's live prefix
            # and, for one row, a whole-prompt prefill (chunk = max_seq from
            # slot 0; the twin's f32 score tensor at batch 8 would not fit)
            for chunk in (g.chunk, g.max_seq)[: 2 if b == 1 else 1]:
                if chunk == g.max_seq:
                    starts_c = jnp.zeros((b,), jnp.int32)
                    lengths_c = jnp.full((b,), g.max_seq, jnp.int32)
                else:
                    starts_c, lengths_c = _row_bounds(c, b, g.max_seq, chunk)
                q_starts = lengths_c - chunk
                qc = c.normal((b, chunk, g.n_q, g.head_dim))
                q_pos_c = q_starts[:, None] + jnp.arange(
                    chunk, dtype=jnp.int32
                )[None, :]
                k_pos_c = _live_k_positions(g.max_seq, starts_c, lengths_c)

                def dense_chunk():
                    got, s = _timed(
                        lambda: chunk_prefill_attention(
                            qc, k_cache, v_cache, q_starts, lengths_c, None,
                            starts_c, window=window,
                        )
                    )
                    want = gqa_attention_hm(
                        qc, k_cache, v_cache, q_pos_c, k_pos_c, window=window
                    )
                    return got, want, s

                def paged_chunk():
                    got, s = _timed(
                        lambda: paged_chunk_attention(
                            qc, k_pool, v_pool, q_starts, lengths_c,
                            starts_c, tables, window=window,
                        )
                    )
                    want = paged_chunk_attention_xla(
                        qc, k_pool, v_pool, q_pos_c, k_pos_c, tables,
                        window=window,
                    )
                    return got, want, s

                label = f"b={b} chunk={chunk} window={window}"
                c.run("chunk_prefill", label, dense_chunk)
                c.run("paged_prefill", label, paged_chunk)

            # ---- fresh prefill of a whole prompt from seq-major K/V
            if b > 1:
                continue
            s_len = g.max_seq
            qf = c.normal((b, s_len, g.n_q, g.head_dim))
            kf = c.normal((b, s_len, g.n_kv, g.head_dim))
            vf = c.normal((b, s_len, g.n_kv, g.head_dim))
            pos_f = jnp.broadcast_to(
                jnp.arange(s_len, dtype=jnp.int32)[None, :], (b, s_len)
            )

            def flash():
                got, s = _timed(
                    lambda: flash_attention(qf, kf, vf, window=window)
                )
                want = gqa_attention(qf, kf, vf, pos_f, pos_f, window=window)
                return got, want, s

            c.run(
                "flash_attention", f"b={b} seq={s_len} window={window}", flash
            )


def _fused_cases(c: _Cases) -> None:
    from cake_tpu.models.llama.fused import sample_step
    from cake_tpu.ops.pallas.fused_norm_matmul import fused_norm_matmul

    g = c.g
    qkv_dim = (g.n_q + 2 * g.n_kv) * g.head_dim
    # The three decode sites that pair a norm with a projection.
    sites = {
        "wqkv": qkv_dim, "w_gu": 2 * g.intermediate, "lm_head": g.vocab,
    }
    weights = {
        name: c.normal((g.hidden, out), g.hidden**-0.5)
        for name, out in sites.items()
    }
    norm_w = c.normal((g.hidden,), 0.1) + 1.0
    # Both sides run under jit, as they do inside the decode step.
    norm_run = jax.jit(
        lambda x, nw, w, impl: fused_norm_matmul(
            x, nw, w, eps=1e-5, impl=impl
        ),
        static_argnames="impl",
    )

    def tail_run(knobs):
        return jax.jit(
            lambda lg, key, ring, impl: sample_step(
                lg, key, ring, jnp.zeros((lg.shape[0],), jnp.int32),
                top_p=None, tail_impl=impl, **knobs,
            )[0],
            static_argnames="impl",
        )

    tail_runs = {
        "greedy": tail_run(
            dict(temperature=0.0, top_k=None, repeat_penalty=1.0)),
        "greedy+penalty": tail_run(
            dict(temperature=0.0, top_k=None, repeat_penalty=1.1)),
        "topk+penalty": tail_run(
            dict(temperature=0.7, top_k=40, repeat_penalty=1.1)),
    }

    for b in g.batches:
        x = c.normal((b, 1, g.hidden))
        for name, w in weights.items():
            def norm_matmul(w=w):
                got, s = _timed(norm_run, x, norm_w, w, "pallas")
                return got, norm_run(x, norm_w, w, "xla"), s

            c.run("fused_norm_matmul", f"b={b} site={name}", norm_matmul)

        # ---- sampling tail: ids must be the twin's exactly
        logits = c.normal((b, g.vocab), 4.0, jnp.float32)
        ring = jax.random.randint(c.key(), (b, 64), -1, g.vocab, jnp.int32)
        keys = jax.random.split(c.key(), b)
        for label, run in tail_runs.items():
            def tail(run=run):
                got, s = _timed(run, logits, keys, ring, "pallas")
                return got, run(logits, keys, ring, "xla"), s

            c.run("fused_sample_tail", f"b={b} {label}", tail, tol=0)


def _int4_cases(c: _Cases) -> None:
    from cake_tpu.ops.pallas.int4_matmul import int4_matmul
    from cake_tpu.ops.quant import Quant4Weight, _qmat4, quantize4_weight

    g = c.g
    tol = 2.0**-5 if g.dtype == "bf16" else 1e-4
    twin = jax.jit(_qmat4)
    for name, (in_dim, out) in {
        "w_gu": (g.hidden, 2 * g.intermediate),
        "w_down": (g.intermediate, g.hidden),
    }.items():
        qw = quantize4_weight(
            c.normal((in_dim, out), in_dim**-0.5, jnp.float32),
            group_size=g.int4_group,
        )
        for rows in (*g.batches, g.chunk):
            x = c.normal((rows, in_dim))

            def int4(x=x, qw=qw):
                got, s = _timed(lambda: int4_matmul(x, qw.w, qw.scale))
                want = twin(x, Quant4Weight(qw.w, qw.scale))
                return got, want, s

            c.run("int4_matmul", f"rows={rows} site={name}", int4, tol=tol)


def _pool_write_cases(c: _Cases, kv_heads: tuple[int, ...]) -> None:
    """The pool's write as a kernel (ops/pallas/paged_write.py) against its
    scatter, every byte of a stale pool equal: a decode step at each batch
    (one row with no page where there are several) and a chunk whose window
    starts and ends inside pages, its first row's first slots not written."""
    from cake_tpu.models.llama.paged_cache import UNMAPPED, paged_write_pool

    g = c.g
    n_p = g.max_seq // g.page_size
    for n_kv, b, width in itertools.product(
        dict.fromkeys(kv_heads), g.batches, (1, g.chunk)
    ):
        perm = np.random.default_rng(b).permutation(b * n_p).reshape(b, n_p)
        if b > 1:
            perm[1] = UNMAPPED
        tables = jnp.asarray(perm, jnp.int32)
        pool = (2, b * n_p, n_kv, g.page_size, g.head_dim)
        k_pool, v_pool = c.normal(pool), c.normal(pool)
        k_new = c.normal((b, width, n_kv, g.head_dim))
        v_new = c.normal((b, width, n_kv, g.head_dim))
        pos = jnp.int32(g.max_seq - width - 3)
        starts = jnp.zeros((b,), jnp.int32).at[0].set(pos + width // 3)

        def write():
            args = (k_pool, v_pool, jnp.int32(1), k_new, v_new, pos, tables)
            got, s = _timed(lambda: paged_write_pool(
                *args, starts=starts, kernel=True))
            return got, paged_write_pool(*args, starts=starts), s

        c.run("paged_pool_write", f"b={b} width={width} kv_heads={n_kv}",
              write, tol=0)


def _paged_head_cases(c: _Cases, n_q: int, n_kv: int) -> None:
    """The paged decode and chunk kernels, and the pool's write, at another
    head layout than the geometry's (one KV head under a group of 20: a
    block of 20 query rows)."""
    from cake_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
        paged_decode_attention_xla,
    )
    from cake_tpu.ops.pallas.paged_prefill import (
        paged_chunk_attention,
        paged_chunk_attention_xla,
    )

    g = c.g
    n_p = g.max_seq // g.page_size
    _pool_write_cases(c, (n_kv,))
    for b in g.batches:
        perm = np.random.default_rng(b).permutation(b * n_p)
        tables = jnp.asarray(perm.reshape(b, n_p), jnp.int32)
        k_pool = c.normal((b * n_p, n_kv, g.page_size, g.head_dim))
        v_pool = c.normal((b * n_p, n_kv, g.page_size, g.head_dim))
        starts, lengths = _row_bounds(c, b, g.max_seq, 1)
        q = c.normal((b, 1, n_q, g.head_dim))
        q_pos = (lengths - 1)[:, None]
        k_pos = _live_k_positions(g.max_seq, starts, lengths)

        def paged_decode():
            got, s = _timed(lambda: paged_decode_attention(
                q, k_pool, v_pool, lengths, tables, starts))
            want = paged_decode_attention_xla(
                q, k_pool, v_pool, q_pos, k_pos, tables)
            return got, want, s

        c.run("paged_attention", f"b={b} heads={n_q}/{n_kv}", paged_decode)
        starts_c, lengths_c = _row_bounds(c, b, g.max_seq, g.chunk)
        q_starts = lengths_c - g.chunk
        qc = c.normal((b, g.chunk, n_q, g.head_dim))
        q_pos_c = q_starts[:, None] + jnp.arange(g.chunk, dtype=jnp.int32)[None]
        k_pos_c = _live_k_positions(g.max_seq, starts_c, lengths_c)

        def paged_chunk():
            got, s = _timed(lambda: paged_chunk_attention(
                qc, k_pool, v_pool, q_starts, lengths_c, starts_c, tables))
            want = paged_chunk_attention_xla(
                qc, k_pool, v_pool, q_pos_c, k_pos_c, tables)
            return got, want, s

        c.run("paged_prefill", f"b={b} chunk={g.chunk} heads={n_q}/{n_kv}",
              paged_chunk)


def _mixer_reference(lp, h, s0, window, eps):
    """The mixer as published, float32 at matmul precision ``highest``, one
    step of the recurrence a step of ``lax.scan`` (what ops/ssm.py's chunked
    scan and one-token update are held against). Returns (y * silu(z), s
    after the last token, the convolution's last inputs)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    lp = {k: f32(v) for k, v in lp.items()}
    h, s0, window = f32(h), f32(s0), f32(window)
    rms = lambda x, w: x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
    with jax.default_matmul_precision("highest"):
        uz = h @ lp["in_proj"]
        d = uz.shape[-1] // 2
        u_in, z = uz[..., :d], uz[..., d:]
        k = lp["conv_w"].shape[0]
        padded = jnp.concatenate([jnp.moveaxis(window, 0, 1), u_in], axis=1)
        length = h.shape[1]
        u = lp["conv_b"] + sum(
            lp["conv_w"][j] * padded[:, j:j + length] for j in range(k))
        u = jax.nn.silu(u)
        dbc = u @ lp["x_proj"]
        n = s0.shape[-2]
        r = dbc.shape[-1] - 2 * n
        dt = jax.nn.softplus(
            rms(dbc[..., :r], lp["dt_ln"]) @ lp["dt_proj"] + lp["dt_bias"])
        b_in = rms(dbc[..., r:r + n], lp["b_ln"])
        c_out = rms(dbc[..., r + n:], lp["c_ln"])
        a = -jnp.exp(lp["A_log"])  # [n, d]

        def step(s, xs):
            dt_t, u_t, b_t, c_t = xs  # [b, d], [b, d], [b, n], [b, n]
            s = jnp.exp(dt_t[:, None, :] * a) * s + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
            return s, jnp.einsum("bnd,bn->bd", s, c_t)

        t_major = lambda x: jnp.moveaxis(x, 1, 0)
        s, y = jax.lax.scan(
            step, s0, (t_major(dt), t_major(u), t_major(b_in), t_major(c_out)))
        y = t_major(y) + lp["D"] * u
        return y * jax.nn.silu(z), s, jnp.moveaxis(padded[:, -(k - 1):], 1, 0)


def _mixer_cases(c: _Cases, g: HybridGeometry) -> None:
    from cake_tpu.models.llama.config import LlamaConfig
    from cake_tpu.models.llama.hybrid import run_shapes
    from cake_tpu.ops import ssm

    config = LlamaConfig(
        model_type="jamba", hidden_size=g.hidden, mamba_d_state=g.d_state,
        mamba_d_conv=g.d_conv, mamba_expand=g.d_inner // g.hidden,
        mamba_dt_rank=g.dt_rank, attn_layer_period=2, attn_layer_offset=1,
        num_attention_heads=g.n_q, num_key_value_heads=g.n_kv,
        head_dim_override=g.head_dim,
    )
    eps = 1e-6
    shapes = run_shapes(config, "state")
    ones = ("D", "dt_ln", "b_ln", "c_ln")
    lp = {
        name: jnp.ones(shape, c.dtype) if name in ones
        else c.normal(shape, 0.0 if name == "dt_bias" else 0.02)
        for name, shape in shapes.items()
        if name not in ("wo", "w_gate", "w_up", "w_down", "ln_attn", "ln_mlp")
    }
    # arrays as arguments: a closure would bake 100 MB of weights into the
    # program (a 3-minute compile at 8 rows on the chip)
    prefill_fn = jax.jit(lambda lp, h, s0, w0, live, ends: ssm.mixer_forward(
        lp, h, s0, w0, live, ends, eps))
    # the decode step as the model takes it: the stack whole, in place
    stacked = ssm.steps_in_place(jnp.zeros((1, 1, g.d_state, g.d_inner)))
    if stacked:
        step_fn = jax.jit(lambda lp, h1, stack, w1, alive: ssm.mixer_step_stacked(
            lp, h1, stack, jnp.int32(1), w1, alive[:, None], eps))
    else:
        def step_fn(lp, h1, stack, w1, alive):
            gated, s, w = ssm.mixer_forward(
                lp, h1, stack[1], w1, alive[:, None], None, eps)
            return gated, stack.at[1].set(s), w
        step_fn = jax.jit(step_fn)
    for b in g.batches:
        # ---- prefill: rows left-padded by differing amounts, from zero
        length = g.prompt
        pads = jnp.asarray(
            np.random.default_rng(b).integers(0, length // 3, size=b), jnp.int32)
        h = c.normal((b, length, g.hidden))
        grid = jnp.arange(length, dtype=jnp.int32)[None, :]
        live = grid >= pads[:, None]
        s0 = jnp.zeros((b, g.d_state, g.d_inner), jnp.float32)
        w0 = jnp.zeros((g.d_conv - 1, b, g.d_inner), c.dtype)
        ends = jnp.full((b,), length, jnp.int32)

        def prefill():
            (gated, s, w), first = _timed(prefill_fn, lp, h, s0, w0, live, ends)
            # the reference sees each row without its pads
            wants = [
                _mixer_reference(lp, h[r:r + 1, int(pads[r]):], s0[r:r + 1],
                                 w0[:, r:r + 1], eps)
                for r in range(b)
            ]
            got = (
                jnp.concatenate([gated[r, int(pads[r]):] for r in range(b)]),
                s, w,
            )
            want = (
                jnp.concatenate([x[0][0] for x in wants]),
                jnp.concatenate([x[1] for x in wants]),
                jnp.concatenate([x[2] for x in wants], axis=1),
            )
            return got, want, first

        c.run("ssm_mixer", f"prefill b={b} L={length}", prefill)

        # ---- one token from a state that is there, one lane not live
        stack = jnp.abs(c.normal((3, b, g.d_state, g.d_inner), 0.5, jnp.float32))
        w1 = c.normal((g.d_conv - 1, b, g.d_inner), 0.5)
        h1 = c.normal((b, 1, g.hidden))
        alive = jnp.arange(b) != b - 1 if b > 1 else jnp.ones((1,), bool)

        def step():
            # the stack is not donated here: the kernel's alias copies it
            (gated, out, w), first = _timed(step_fn, lp, h1, stack, w1, alive)
            want_g, want_s, want_w = _mixer_reference(lp, h1, stack[1], w1, eps)
            keep = alive[:, None, None]
            want_s = jnp.where(keep, want_s, stack[1])
            want_w = jnp.where(alive[None, :, None], want_w, w1.astype(jnp.float32))
            want_stack = stack.at[1].set(want_s)
            return (gated[alive], out, w), (want_g[alive], want_stack, want_w), first

        c.run("ssm_mixer", f"step b={b} {'kernel' if stacked else 'xla'}", step)


def run_hybrid_checks(geom: HybridGeometry) -> dict:
    """``run_checks`` for a model with state layers: the paged kernels at
    its attention layers' head layout, and the state-space mixer's prefill
    (chunked scan) and one-token step (the Pallas kernel where the widths
    tile) against the stepwise float32 scan."""
    c = _Cases(Geometry(
        hidden=geom.hidden, intermediate=0, n_q=geom.n_q, n_kv=geom.n_kv,
        head_dim=geom.head_dim, vocab=0, window=None,
        page_size=geom.page_size, max_seq=geom.max_seq, chunk=geom.chunk,
        int4_group=0, dtype=geom.dtype, batches=geom.batches,
    ))
    with recorded_interpret() as seen:
        _paged_head_cases(c, geom.n_q, geom.n_kv)
        _mixer_cases(c, geom)
    return {"results": c.results, "interpret": seen}


def run_checks(geom: Geometry) -> dict:
    """Run every case; returns ``{"results": [...], "interpret": [...]}``
    where ``interpret`` lists what each traced ``pallas_call`` was given."""
    c = _Cases(geom)
    with recorded_interpret() as seen:
        _attention_cases(c)
        _pool_write_cases(c, (geom.n_kv, *geom.write_kv))
        _fused_cases(c)
        _int4_cases(c)
    return {"results": c.results, "interpret": seen}


def _write_chain(kernel: bool, calls: int, layers: int, page_size: int):
    """``calls`` dependent writes as one program: the pools are the loop's
    carry, the layer and the slot change from call to call."""
    from cake_tpu.models.llama.paged_cache import paged_write_pool

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def chain(k_pool, v_pool, new, tables):
        return jax.lax.fori_loop(
            0, calls,
            lambda i, kv: paged_write_pool(
                *kv, i % layers, new[0], new[1],
                (7 * i) % (2 * page_size - 1), tables, kernel=kernel,
            ),
            (k_pool, v_pool),
        )

    return chain


def timed_pool_write(
    shapes: tuple[tuple[int, int, int], ...],
    head_dim: int,
    page_size: int,
    layers: int = 4,
    dtype: str = "bf16",
    calls: int = 256,
    repeats: int = 3,
) -> list[dict]:
    """The pool's write alone, as a layer calls it, kernel and scatter side
    by side: for each ``(rows, kv_heads, width)`` the microseconds a call of
    ``paged_write_pool`` with and without ``kernel`` (``kernel_us``,
    ``twin_us``) and the ``[head_dim]`` rows a call moves. ``calls``
    dependent calls make one program (``_write_chain``), timed on the host
    clock around ``block_until_ready``; the fastest of ``repeats``."""
    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    rows = []
    for b, n_kv, width in shapes:
        n_p = -(-width // page_size) + 2
        pool = (layers, b * n_p, n_kv, page_size, head_dim)
        tables = jnp.asarray(
            np.random.default_rng(0).permutation(b * n_p).reshape(b, n_p),
            jnp.int32,
        )
        new = jax.random.normal(
            jax.random.PRNGKey(6), (2, b, width, n_kv, head_dim), dt)
        rec = {"rows": b, "kv_heads": n_kv, "width": width,
               "head_rows": 2 * b * width * n_kv}
        for name, kernel in (("kernel_us", True), ("twin_us", False)):
            chain = _write_chain(kernel, calls, layers, page_size)
            kv = _timed(chain, jnp.zeros(pool, dt), jnp.zeros(pool, dt),
                        new, tables)[0]  # compile + warm
            fastest = float("inf")
            for _ in range(repeats):
                kv, s = _timed(chain, *kv, new, tables)
                fastest = min(fastest, s)
            rec[name] = round(fastest / calls * 1e6, 1)
            del kv
        rows.append(rec)
    return rows


def timed_paged_decode(
    n_q: int,
    n_kv: int,
    head_dim: int,
    page_size: int,
    lanes: int,
    layers: int,
    dtype: str = "bf16",
    table_pages: tuple[int, ...] = (4, 8, 16, 32),
    live_tokens: int = 450,
    calls: int = 256,
    repeats: int = 3,
) -> list[dict]:
    """``paged_decode_attention`` alone, as a decode step calls it: ``lanes``
    rows against a 5-D pool of ``layers`` layers (the layer changes from call
    to call), at each table width once with every page of the table live
    (``full_us``) and once with ``live_tokens`` live slots a row
    (``live_us``), in microseconds a call. ``calls`` dependent calls make one
    program (each call's output is the next one's query), timed on the host
    clock around ``block_until_ready``; the fastest of ``repeats``. A kernel
    that does a row's live pages reads flat in ``table_pages`` at
    ``live_us``."""
    from cake_tpu.ops.pallas.paged_attention import paged_decode_attention

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    n_pages = lanes * max(table_pages)
    pool = (layers, n_pages, n_kv, page_size, head_dim)
    k_pool = jax.random.normal(jax.random.PRNGKey(3), pool, dt)
    v_pool = jax.random.normal(jax.random.PRNGKey(4), pool, dt)
    q0 = jax.random.normal(jax.random.PRNGKey(5), (lanes, 1, n_q, head_dim), dt)
    perm = np.random.default_rng(0).permutation(n_pages)

    @jax.jit
    def chain(q, k_pool, v_pool, lengths, tables):
        return jax.lax.fori_loop(
            0, calls,
            lambda i, q: paged_decode_attention(
                q, k_pool, v_pool, lengths, tables, layer=i % layers
            ),
            q,
        )

    def us_a_call(tables, length):
        args = (q0, k_pool, v_pool, jnp.full((lanes,), length, jnp.int32), tables)
        _timed(chain, *args)  # compile + warm
        fastest = min(_timed(chain, *args)[1] for _ in range(repeats))
        return round(fastest / calls * 1e6, 1)

    rows = []
    for n_p in table_pages:
        tables = jnp.asarray(
            perm[: lanes * n_p].reshape(lanes, n_p), jnp.int32
        )
        slots = n_p * page_size
        live = min(live_tokens, slots)
        rows.append({
            "table_pages": n_p, "live_tokens": live,
            "full_us": us_a_call(tables, slots),
            "live_us": us_a_call(tables, live),
        })
    return rows


def timed_selective_scan(
    d_inner: int,
    d_state: int,
    windows: tuple[tuple[int, int], ...] = ((1, 512), (1, 2048), (4, 512)),
    calls: int = 26,
    repeats: int = 3,
) -> list[dict]:
    """The selective scan of a prefill window alone, the Pallas kernel and its
    XLA twin (``ops/ssm.selective_scan``) on the same inputs: for each
    (rows, length) of ``windows`` the kernel's error against the twin (``y``
    and the last state, over the largest value of the twin's) and the
    microseconds a call of both. ``calls`` dependent calls make one program
    (a model's state layers: each call's state and output feed the next),
    timed on the host clock around ``block_until_ready``, the fastest of
    ``repeats``. The first eighth of a window is not live (a join's left pad):
    neither form walks it."""
    from cake_tpu.ops import ssm
    from cake_tpu.ops.pallas.selective_scan import selective_scan as kernel

    twin = lambda u, dt, a, b, c, s0, span: ssm.selective_scan(
        u, dt, a, b, c, s0, span=span
    )

    def chain(scan):
        @jax.jit
        def run(u, dt, a, b_in, c_out, s0, lo, hi):
            def one(_, carry):
                u, s = carry
                y, s = scan(u, dt, a, b_in, c_out, s, (lo, hi))
                return u + 1e-3 * y, s

            return jax.lax.fori_loop(0, calls, one, (u, s0))

        return run

    once = {"kernel": jax.jit(kernel), "twin": jax.jit(twin)}
    chains = {"kernel": chain(kernel), "twin": chain(twin)}
    rows = []
    for b, length in windows:
        keys = jax.random.split(jax.random.PRNGKey(b * length), 6)
        lo = length // 8
        live = (jnp.arange(length) >= lo)[None, :, None]
        u = jax.random.normal(keys[0], (b, length, d_inner), jnp.float32)
        dt = jnp.where(live, jax.nn.softplus(
            jax.random.normal(keys[1], (b, length, d_inner), jnp.float32) - 3.0
        ), 0.0)
        a = -jnp.exp(jax.random.normal(keys[2], (d_state, d_inner)) * 0.5)
        b_in = jax.random.normal(keys[3], (b, length, d_state), jnp.float32)
        c_out = jax.random.normal(keys[4], (b, length, d_state), jnp.float32)
        s0 = jax.random.normal(keys[5], (b, d_state, d_inner), jnp.float32)
        args = (u, dt, a, b_in, c_out, s0, jnp.int32(lo), jnp.int32(length))
        y_k, s_k = once["kernel"](*args[:6], args[6:])
        y_t, s_t = once["twin"](*args[:6], args[6:])
        rec = {
            "rows": b, "length": length, "live": length - lo,
            # before the span the twin's first chunk holds s0 . C, the
            # kernel's first group zeros: nobody reads either
            "err_y": _rel_err(y_k[:, lo:], y_t[:, lo:]),
            "err_s": _rel_err(s_k, s_t),
        }
        for name, run in chains.items():
            _timed(run, *args)  # compile + warm
            fastest = min(_timed(run, *args)[1] for _ in range(repeats))
            rec[f"{name}_us"] = round(fastest / calls * 1e6, 1)
        rows.append(rec)
    return rows


def timed_selective_step(
    d_inner: int,
    d_state: int,
    lanes: int = 32,
    layers: int = 26,
    passes: int = 20,
    repeats: int = 3,
) -> list[dict]:
    """The selective mixer's one-token update alone at ``lanes`` rows: the
    Pallas kernel on a stack's state in place (``ops/pallas/selective_step.py``)
    beside its XLA twin (``ops/ssm.mixer_forward``'s decode branch over a
    layer of the stack, written back). One program steps each of the donated
    stack's ``layers`` layers ``passes`` times, a call's ``y`` fed to the
    next call's ``u``: 520 calls under one dispatch, because the host's part
    of a dispatch (a millisecond on the clock) is as long as 26 calls. The
    host clock around ``block_until_ready`` with the inputs ready before it
    starts, the fastest of ``repeats``. Microseconds a call beside
    ``floor_us`` (a layer's state read once and written once at 819 GB/s),
    the kernel's errors against the twin over the twin's largest value."""
    from cake_tpu.ops.pallas import selective_step

    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    u = n(keys[0], lanes, d_inner)
    dt = jax.nn.softplus(n(keys[1], lanes, d_inner) - 3.0)
    a = -jnp.exp(n(keys[2], d_state, d_inner) * 0.5)
    b_in, c_out = n(keys[3], lanes, d_state), n(keys[4], lanes, d_state)
    stack = n(keys[5], layers, lanes, d_state, d_inner)

    def twin_step(stack, i, u):
        s = jnp.exp(dt[:, None, :] * a[None]) * stack[i] + (
            (dt * u)[:, None, :] * b_in[:, :, None])
        return jnp.einsum("bnd,bn->bd", s, c_out), stack.at[i].set(s)

    def kernel_step(stack, i, u):
        return selective_step.selective_step(stack, i, u, dt, a, b_in, c_out)

    def chain(step):
        def steps(stack, u):
            def one(i, carry):
                stack, u = carry
                y, stack = step(stack, jax.lax.rem(i, layers), u)
                return stack, u + 1e-3 * y

            return jax.lax.fori_loop(0, layers * passes, one, (stack, u))

        return jax.jit(steps, donate_argnums=0)

    rec = {"op": "selective_step", "rows": lanes, "length": 1,
           "calls": layers * passes,
           "floor_us": round(2 * lanes * d_state * d_inner * 4 / 819e9 * 1e6, 1)}
    chains = {"twin": chain(twin_step)}
    if selective_step.tiles(d_inner, d_state):
        chains["kernel"] = chain(kernel_step)
    fresh = lambda: jax.block_until_ready(stack + 0.0)  # the chain donates it
    outs = {}
    for name, stepped in chains.items():
        outs[name] = jax.tree.map(np.asarray, stepped(fresh(), u))
        fastest = min(_timed(stepped, fresh(), u)[1] for _ in range(repeats))
        rec[f"{name}_us"] = round(fastest / (layers * passes) * 1e6, 1)
    if "kernel" in outs:
        rec["err_s"] = _rel_err(outs["kernel"][0], outs["twin"][0])
        rec["err_y"] = _rel_err(outs["kernel"][1], outs["twin"][1])
    return [rec]


@dataclasses.dataclass(frozen=True)
class DeltaGeometry:
    """A model whose state layers run the gated delta rule
    (``ops/delta_rule.py``): its attention layers' head layout for the paged
    kernels, its mixer's sizes."""
    hidden: int
    n_q: int
    n_kv: int
    head_dim: int
    heads: int
    dk: int
    dv: int
    taps: int
    page_size: int
    max_seq: int
    chunk: int
    prompt: int  # a mixer prefill's length; not a multiple of the rule's chunk
    dtype: str
    batches: tuple[int, ...] = (1, 8)


def _delta_stepwise(q, k, v, log_alpha, beta, s0):
    """The gated delta rule one position at a time (``lax.scan``), float32 at
    matmul precision ``highest``: what the chunkwise form and the step kernel
    are held against. s is S^T a head, [b, H, dk, dv]."""
    with jax.default_matmul_precision("highest"):
        def step(s, xs):
            q_t, k_t, v_t, a_t, b_t = xs
            s = jnp.exp(a_t)[..., None, None] * s
            err = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
            s = s + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * err)
            return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

        t_major = lambda x: jnp.moveaxis(x, 1, 0)
        s, o = jax.lax.scan(
            step, s0, tuple(map(t_major, (q, k, v, log_alpha, beta))))
        return t_major(o), s


def _delta_mixer_reference(lp, h, s0, window, eps, g: DeltaGeometry):
    """The delta-rule mixer as ``bench/architectures/olmo_hybrid.py`` states
    it, float32 at ``highest``, the rule stepwise. (rms(o) * silu(z), the
    state after the last token in the cache's layout, the convolution's last
    inputs.)"""
    from cake_tpu.ops import delta_rule as D

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    lp = {k: f32(v) for k, v in lp.items()}
    h, s0, window = f32(h), f32(s0), f32(window)
    b, length = h.shape[:2]
    n_k, n_v = g.heads * g.dk, g.heads * g.dv
    with jax.default_matmul_precision("highest"):
        qkvz = h @ lp["in_proj"]
        u_in, z = qkvz[..., :2 * n_k + n_v], qkvz[..., 2 * n_k + n_v:]
        padded = jnp.concatenate([jnp.moveaxis(window, 0, 1), u_in], axis=1)
        u = jax.nn.silu(sum(
            lp["conv_w"][j] * padded[:, j:j + length] for j in range(g.taps)))
        unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        q = unit(u[..., :n_k].reshape(b, length, g.heads, g.dk)) * g.dk ** -0.5
        k = unit(u[..., n_k:2 * n_k].reshape(b, length, g.heads, g.dk))
        v = u[..., 2 * n_k:].reshape(b, length, g.heads, g.dv)
        ab = h @ lp["ab_proj"]
        beta = 2.0 * jax.nn.sigmoid(ab[..., g.heads:])
        log_alpha = -jnp.exp(lp["A_log"]) * jax.nn.softplus(
            ab[..., :g.heads] + lp["dt_bias"])
    o, s = _delta_stepwise(q, k, v, log_alpha, beta, D.to_heads(s0, g.heads))
    y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * lp["o_norm"]
    y = y * jax.nn.silu(z).reshape(b, length, g.heads, g.dv)
    return (y.reshape(b, length, n_v), D.from_heads(s),
            jnp.moveaxis(padded[:, -(g.taps - 1):], 1, 0))


def _delta_mixer_cases(c: _Cases, g: DeltaGeometry) -> None:
    from cake_tpu.ops import delta_rule as D

    eps = 1e-6
    n_k, n_v = g.heads * g.dk, g.heads * g.dv
    channels = 2 * n_k + n_v
    lp = {
        "in_proj": c.normal((g.hidden, channels + n_v), 0.02),
        # gates over their range at inputs of unit size (beta on both sides of 1)
        "ab_proj": c.normal((g.hidden, 2 * g.heads), g.hidden ** -0.5),
        "conv_w": c.normal((g.taps, channels), 0.5),
        "A_log": c.normal((g.heads,), 0.5), "dt_bias": c.normal((g.heads,), 0.5),
        "o_norm": jnp.ones((g.dv,), c.dtype),
    }
    prefill_fn = jax.jit(lambda lp, h, s0, w0, live, ends: D.mixer_forward(
        lp, h, s0, w0, live, ends, eps))
    # the decode step as the model takes it: the stack whole, in place
    stacked = D.steps_in_place(jnp.zeros((1, 1, g.dk, n_v)), g.heads)
    if stacked:
        step_fn = jax.jit(lambda lp, h1, stack, w1, alive: D.mixer_step_stacked(
            lp, h1, stack, jnp.int32(1), w1, alive[:, None], eps))
    else:
        def step_fn(lp, h1, stack, w1, alive):
            gated, s, w = D.mixer_forward(
                lp, h1, stack[1], w1, alive[:, None], None, eps)
            return gated, stack.at[1].set(s), w
        step_fn = jax.jit(step_fn)
    for b in g.batches:
        length = g.prompt
        pads = jnp.asarray(
            np.random.default_rng(b).integers(0, length // 3, size=b), jnp.int32)
        h = c.normal((b, length, g.hidden))
        live = jnp.arange(length, dtype=jnp.int32)[None, :] >= pads[:, None]
        s0 = jnp.zeros((b, g.dk, n_v), jnp.float32)
        w0 = jnp.zeros((g.taps - 1, b, channels), c.dtype)
        ends = jnp.full((b,), length, jnp.int32)

        def prefill():
            (gated, s, w), first = _timed(prefill_fn, lp, h, s0, w0, live, ends)
            wants = [
                _delta_mixer_reference(lp, h[r:r + 1, int(pads[r]):], s0[r:r + 1],
                                       w0[:, r:r + 1], eps, g)
                for r in range(b)
            ]
            got = (jnp.concatenate([gated[r, int(pads[r]):] for r in range(b)]), s, w)
            want = (
                jnp.concatenate([x[0][0] for x in wants]),
                jnp.concatenate([x[1] for x in wants]),
                jnp.concatenate([x[2] for x in wants], axis=1),
            )
            return got, want, first

        c.run("delta_mixer", f"prefill b={b} L={length}", prefill)

        stack = c.normal((3, b, g.dk, n_v), 0.5, jnp.float32)
        w1 = c.normal((g.taps - 1, b, channels), 0.5)
        h1 = c.normal((b, 1, g.hidden))
        alive = jnp.arange(b) != b - 1 if b > 1 else jnp.ones((1,), bool)

        def step():
            # the stack is not donated here: the kernel's alias copies it
            (gated, out, w), first = _timed(step_fn, lp, h1, stack, w1, alive)
            want_g, want_s, want_w = _delta_mixer_reference(
                lp, h1, stack[1], w1, eps, g)
            want_s = jnp.where(alive[:, None, None], want_s, stack[1])
            want_w = jnp.where(alive[None, :, None], want_w, w1.astype(jnp.float32))
            want_stack = stack.at[1].set(want_s)
            return (gated[alive], out, w), (want_g[alive], want_stack, want_w), first

        c.run("delta_mixer", f"step b={b} {'kernel' if stacked else 'xla'}", step)


def run_delta_checks(geom: DeltaGeometry) -> dict:
    """``run_hybrid_checks`` for a model whose state layers run the gated
    delta rule: the paged kernels at its attention layers' head layout, and
    the mixer's prefill (the chunkwise form) and one-token step (the Pallas
    kernel where the widths tile) against the stepwise float32 rule."""
    c = _Cases(Geometry(
        hidden=geom.hidden, intermediate=0, n_q=geom.n_q, n_kv=geom.n_kv,
        head_dim=geom.head_dim, vocab=0, window=None,
        page_size=geom.page_size, max_seq=geom.max_seq, chunk=geom.chunk,
        int4_group=0, dtype=geom.dtype, batches=geom.batches,
    ))
    with recorded_interpret() as seen:
        _paged_head_cases(c, geom.n_q, geom.n_kv)
        _delta_mixer_cases(c, geom)
    return {"results": c.results, "interpret": seen}


def timed_delta_rule(
    heads: int,
    dk: int,
    dv: int,
    windows: tuple[tuple[int, ...], ...] = (
        (1, 512), (1, 2048), (4, 512), (2, 2560, 1100, 1500)),
    lanes: int = 32,
    calls: int = 12,
    repeats: int = 3,
    step_live: tuple[int, ...] = (20, 11),
) -> list[dict]:
    """The gated delta rule alone, ``calls`` dependent calls a program (a
    model's state layers: each call's state and output feed the next), timed
    on the host clock around ``block_until_ready``, the fastest of
    ``repeats``. For each (rows, length[, lo, hi]) of ``windows`` (live from
    ``lo`` to ``hi``; without them the first eighth of a window is not live;
    the last default is an epoch's program, 400 live positions a row of
    2560): the window's kernel (``ops/pallas/delta_rule.py``, where the
    widths tile, with the state in the cache's layout and the span as the
    mixer hands them) and its XLA twin (``ops/delta_rule.gated_delta_rule``)
    against the rule one position at a time, errors over the largest value
    of the stepwise form's, and microseconds a call (the stepwise form's up
    to 512 positions only: at 2048 it is most of the phase's minutes).
    Then the one-token update at ``lanes`` rows with all and with
    ``step_live`` of them live (``timed_delta_step``)."""
    from cake_tpu.ops import delta_rule as D
    from cake_tpu.ops.pallas import delta_rule, delta_step

    def draw(key, b, length, lo=None, hi=None):
        lo, hi = length // 8 if lo is None else lo, length if hi is None else hi
        keys = jax.random.split(key, 6)
        n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
        at = jnp.arange(length)
        live = ((at >= lo) & (at < hi))[None, :, None]
        q = D._unit(n(keys[0], b, length, heads, dk)) * dk ** -0.5
        k = D._unit(n(keys[1], b, length, heads, dk) + n(keys[1], b, 1, heads, dk))
        log_alpha = jnp.where(live, -jax.nn.softplus(n(keys[3], b, length, heads)), 0.0)
        beta = jnp.where(live, 2.0 * jax.nn.sigmoid(n(keys[4], b, length, heads)), 0.0)
        return (q, k, n(keys[2], b, length, heads, dv), log_alpha, beta,
                n(keys[5], b, heads, dk, dv))

    def chain(rule):
        @jax.jit
        def run(q, k, v, log_alpha, beta, s0):
            def one(_, carry):
                v, s = carry
                o, s = rule(q, k, v, log_alpha, beta, s)
                return v + 1e-3 * o, s

            return jax.lax.fori_loop(0, calls, one, (v, s0))

        return run

    def once(rule, *args):  # the kernel's rule holds its window's span
        return jax.jit(rule)(*args)

    rows = []
    for b, length, *live in windows:
        q, k, v, log_alpha, beta, s0 = draw(jax.random.PRNGKey(b * length), b, length, *live)
        lo, hi = live or (length // 8, length)
        spans = jnp.tile(jnp.asarray([[lo, hi]], jnp.int32), (b, 1))
        # name -> (the rule, its state as it takes it, that state by heads)
        forms = {
            "chunkwise": (D.gated_delta_rule, s0, lambda s: s),
            "stepwise": (_delta_stepwise, s0, lambda s: s),
        }
        if delta_rule.tiles(dk, heads * dv, dv):
            forms["kernel"] = (
                lambda *a: delta_rule.gated_delta_rule(*a, spans),
                D.from_heads(s0), lambda s: D.to_heads(s, heads))
        outs = {}
        for name, (rule, state, by_heads) in forms.items():
            o, s = once(rule, q, k, v, log_alpha, beta, state)
            outs[name] = (o, by_heads(s))
        o_s, s_s = outs["stepwise"]
        rec = {"op": "gated_delta_rule", "rows": b, "length": length, "live": hi - lo,
               "err_o": _rel_err(outs["chunkwise"][0], o_s),
               "err_s": _rel_err(outs["chunkwise"][1], s_s)}
        if "kernel" in outs:
            # o outside the span's chunks is nobody's: the kernel's is zero
            first, last = lo // delta_rule.CHUNK * delta_rule.CHUNK, hi
            rec["kernel_err_o"] = _rel_err(
                outs["kernel"][0][:, first:last], o_s[:, first:last])
            rec["kernel_err_s"] = _rel_err(outs["kernel"][1], s_s)
        for name, (rule, state, _) in forms.items():
            if name == "stepwise" and length > 512:
                continue
            run = chain(rule)
            _timed(run, q, k, v, log_alpha, beta, state)  # compile + warm
            fastest = min(
                _timed(run, q, k, v, log_alpha, beta, state)[1]
                for _ in range(repeats))
            rec[f"{name}_us"] = round(fastest / calls * 1e6, 1)
        rows.append(rec)

    rows += timed_delta_step(heads, dk, dv, lanes, step_live, calls, repeats)
    return rows


def timed_delta_step(
    heads: int,
    dk: int,
    dv: int,
    lanes: int = 32,
    live: tuple[int, ...] = (),
    calls: int = 12,
    repeats: int = 3,
    steps: int = 8,
) -> list[dict]:
    """The gated delta rule's one-token update alone at ``lanes`` rows:
    ``calls`` layers of one stack, each stepped ``steps`` times a program (a
    decode chunk's state layers), timed as ``timed_delta_rule``'s, once with every
    row live and once a count of ``live`` (those rows drawn from a seed;
    below ``lanes`` the first and the last row are dead). The Pallas kernel
    on the stack's state in place (``ops/pallas/delta_step.py``, where the
    widths tile: it walks the live rows alone, so its time follows the
    count) beside its XLA twin (every row, through gates that make a dead
    one the identity): errors over the twin's largest value ON THE LIVE
    ROWS, ``dead_rows_moved`` (dead rows of any layer whose state is not the
    input's bit for bit after the kernel's chain, or whose ``o`` is not
    zero: must be 0), and microseconds a call, the fresh stack ready before
    the clock starts."""
    from cake_tpu.ops import delta_rule as D
    from cake_tpu.ops.pallas import delta_step

    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    q = D._unit(n(keys[0], lanes, heads, dk)) * dk ** -0.5
    k = D._unit(n(keys[1], lanes, heads, dk))
    v = n(keys[2], lanes, heads, dv)
    log_alpha = -jax.nn.softplus(n(keys[3], lanes, heads))
    beta = 2.0 * jax.nn.sigmoid(n(keys[4], lanes, heads))
    stack = n(keys[5], calls, lanes, dk, heads * dv)

    def twin_chain(stack, q, k, v, log_alpha, beta, mask):
        log_alpha = jnp.where(mask[:, None], log_alpha, 0.0)
        beta = jnp.where(mask[:, None], beta, 0.0)

        def one(i, carry):
            stack, v = carry
            o, s = D.gated_delta_step(
                q, k, v, log_alpha, beta, D.to_heads(stack[i % calls], heads))
            return stack.at[i % calls].set(D.from_heads(s)), v + 1e-3 * o

        return jax.lax.fori_loop(0, calls * steps, one, (stack, v))

    def kernel_chain(stack, q, k, v, log_alpha, beta, mask):
        rows = delta_step.live_rows(mask)  # once a program, as a decode chunk's

        def one(i, carry):
            stack, v, dead_o = carry
            o, stack = delta_step.gated_delta_step(
                stack, i % calls, q, k, v, log_alpha, beta, mask, rows=rows)
            dead_o = dead_o | jnp.any((o != 0) & ~mask[:, None, None])
            return stack, v + 1e-3 * o, dead_o

        return jax.lax.fori_loop(0, calls * steps, one, (stack, v, jnp.bool_(False)))

    chains = {"twin": jax.jit(twin_chain, donate_argnums=0)}
    if delta_step.tiles(dk, heads * dv, dv):
        chains["kernel"] = jax.jit(kernel_chain, donate_argnums=0)
    recs = []
    for count in dict.fromkeys((lanes, *(c for c in live if 0 < c <= lanes - 2))):
        mask = np.zeros((lanes,), bool)
        inner = np.arange(lanes) if count == lanes else 1 + np.random.default_rng(
            count).permutation(lanes - 2)[:count]
        mask[inner] = True
        at = jnp.asarray(mask)
        rec = {"op": "gated_delta_step", "rows": lanes, "length": 1, "live": count}
        outs = {}
        fresh = lambda: jax.block_until_ready(stack + 0.0)  # the chain donates it
        for name, stepped in chains.items():
            outs[name] = jax.tree.map(
                np.asarray, stepped(fresh(), q, k, v, log_alpha, beta, at))
            fastest = min(
                _timed(stepped, fresh(), q, k, v, log_alpha, beta, at)[1]
                for _ in range(repeats))
            rec[f"{name}_us"] = round(fastest / (calls * steps) * 1e6, 1)
        if "kernel" in outs:
            (s_k, o_k, dead_o), (s_t, o_t) = outs["kernel"], outs["twin"]
            rec["err_s"] = _rel_err(s_k[:, mask], s_t[:, mask])
            rec["err_o"] = _rel_err(o_k[mask], o_t[mask])
            was = np.asarray(stack)[:, ~mask]
            moved = int((s_k[:, ~mask] != was).any(axis=(2, 3)).sum())
            rec["dead_rows_moved"] = moved + int(dead_o)
        recs.append(rec)
    return recs


def timed_matmul_chain(n: int, steps: int, repeats: int = 3) -> dict:
    """A chain of ``steps`` dependent [n, n] bf16 matmuls, timed on the host
    clock around ``block_until_ready``. Returns the FLOPs and the fastest
    elapsed time; the caller holds it against the device's peak: less than
    FLOPs / peak means the clock (or the wait) cannot be trusted."""
    w = (
        jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32) * n**-0.5
    ).astype(jnp.bfloat16)
    x0 = jax.random.normal(jax.random.PRNGKey(2), (n, n), jnp.bfloat16)

    @jax.jit
    def chain(x, w):
        return jax.lax.fori_loop(
            0, steps,
            lambda _, x: jnp.dot(
                x, w, preferred_element_type=jnp.float32
            ).astype(jnp.bfloat16),
            x,
        )

    jax.block_until_ready(chain(x0, w))  # compile + warm
    elapsed = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = jax.block_until_ready(chain(x0, w))
        elapsed.append(time.perf_counter() - t0)
    return {
        "flops": 2.0 * n**3 * steps,
        "elapsed_s": min(elapsed),
        "finite": bool(jnp.isfinite(out.astype(jnp.float32)).all()),
    }


# ------------------------------------------------- latent attention, experts


def run_latent_checks(
    n_heads: int, rank: int, rope: int, page_size: int, lanes: int,
    dtype: str = "bf16", table_pages: int = 8,
) -> dict:
    """``latent_decode_attention`` against its XLA twin at a model's widths:
    every page live, rows that start past slot 0 (left pads) and end inside
    a page, one row with a single live token. ``{"results", "interpret"}``
    as ``run_checks`` gives them."""
    from cake_tpu.ops.pallas.latent_attention import (
        latent_decode_attention, latent_decode_attention_xla,
    )

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    tol = 2.0**-6 if dtype == "bf16" else 1e-4
    width = -(-(rank + rope) // 128) * 128
    layers, n_pages = 2, lanes * table_pages
    pool = jax.random.normal(
        jax.random.PRNGKey(3), (layers, n_pages, page_size, width), dt
    )
    q = jax.random.normal(jax.random.PRNGKey(5), (lanes, n_heads, width), dt)
    tables = jnp.asarray(
        np.random.default_rng(0).permutation(n_pages).reshape(lanes, table_pages),
        jnp.int32,
    )
    slots = table_pages * page_size
    rows = np.arange(lanes)
    cases = {
        "every page live": (np.zeros(lanes, np.int32), np.full(lanes, slots, np.int32)),
        "left pads, ragged ends": (
            (rows * 37) % (slots // 2), slots // 2 + 1 + (rows * 53) % (slots // 2)),
        "one live token": (np.full(lanes, slots - 1, np.int32), np.full(lanes, slots, np.int32)),
    }
    results = []
    with recorded_interpret() as seen:
        for name, (starts, lengths) in cases.items():
            rec = {"kernel": "latent_decode_attention", "case": name, "tol": tol}
            try:
                args = (q, pool, jnp.asarray(lengths, jnp.int32), tables,
                        jnp.asarray(starts, jnp.int32))
                kw = dict(layer=jnp.int32(1), rank=rank, scale=(rank // 4 + rope) ** -0.5)
                got, first = _timed(lambda: latent_decode_attention(*args, **kw))
                want = latent_decode_attention_xla(*args, **kw)
                rec.update(max_err=_rel_err(got, want), first_call_s=round(first, 2))
                rec["ok"] = rec["max_err"] <= tol
            except Exception as e:  # noqa: BLE001 - the compiler's message is the result
                rec.update(ok=False, error=f"{type(e).__name__}: {str(e)[:300]}")
            results.append(rec)
    return {"results": results, "interpret": seen}


def timed_latent_decode(
    n_heads: int, rank: int, rope: int, page_size: int, lanes: int, layers: int,
    dtype: str = "bf16", table_pages: tuple[int, ...] = (8, 16, 32),
    live_tokens: int = 450, calls: int = 128, repeats: int = 3,
) -> list[dict]:
    """``latent_decode_attention`` alone, as ``timed_paged_decode`` times its
    kernel: microseconds a call with every page of the table live and with
    ``live_tokens`` live a row."""
    from cake_tpu.ops.pallas.latent_attention import latent_decode_attention

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    width = -(-(rank + rope) // 128) * 128
    n_pages = lanes * max(table_pages)
    pool = jax.random.normal(
        jax.random.PRNGKey(3), (layers, n_pages, page_size, width), dt
    ) * 0.1
    q0 = jax.random.normal(jax.random.PRNGKey(5), (lanes, n_heads, width), dt)
    perm = np.random.default_rng(0).permutation(n_pages)
    pad = jnp.zeros((lanes, n_heads, width - rank), dt)

    @jax.jit
    def chain(q, pool, lengths, tables):
        def call(i, q):
            c = latent_decode_attention(
                q, pool, lengths, tables, layer=i % layers, rank=rank,
                scale=(rank // 4 + rope) ** -0.5,
            )
            return jnp.concatenate([c, pad], axis=-1)  # the next call's query

        return jax.lax.fori_loop(0, calls, call, q)

    def us_a_call(tables, length):
        args = (q0, pool, jnp.full((lanes,), length, jnp.int32), tables)
        _timed(chain, *args)
        fastest = min(_timed(chain, *args)[1] for _ in range(repeats))
        return round(fastest / calls * 1e6, 1)

    rows = []
    for n_p in table_pages:
        tables = jnp.asarray(perm[: lanes * n_p].reshape(lanes, n_p), jnp.int32)
        slots = n_p * page_size
        live = min(live_tokens, slots)
        rows.append({
            "table_pages": n_p, "live_tokens": live,
            "full_us": us_a_call(tables, slots),
            "live_us": us_a_call(tables, live),
        })
    return rows


def timed_expert_layer(
    hidden: int, inter: int, held: int, ranked: int, top_k: int,
    tokens: tuple[int, ...], dtype: str = "bf16", layers: int = 4,
    calls: int = 16, repeats: int = 3, dead_every: int = 0,
) -> list[dict]:
    """One sparse layer's routed experts at a model's widths, ``held`` of
    ``ranked`` experts here (ops/moe.moe_swiglu, sigmoid routing): at each
    number of tokens in a dispatch the DENSE COMBINE against the GROUPED
    path, milliseconds a call, the largest difference of the two results
    of ONE call (in units of the result's spread), and which of the two
    ``dispatch="auto"`` takes at that shape (``rule``: ``moe.dispatch_path``).
    ``calls`` dependent calls over ``layers`` different layers' weights make
    one program, so a call streams its weights from HBM as a decode step
    does. ``dead_every`` k > 0: every k-th row is no token (``valid``), as a
    dispatch's dead lanes are; the difference is then over the live rows."""
    from cake_tpu.ops import moe

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    std = 0.02
    router = jax.random.normal(keys[0], (layers, hidden, ranked), dt) * std
    w_gate = jax.random.normal(keys[1], (layers, held, hidden, inter), dt) * std
    w_up = jax.random.normal(keys[2], (layers, held, hidden, inter), dt) * std
    w_down = jax.random.normal(keys[3], (layers, held, inter, hidden), dt) * std
    kw = dict(top_k=top_k, scoring="sigmoid", scale=2.5, norm_topk=True)

    def chain(dispatch):
        @jax.jit
        def run(x, valid, router, w_gate, w_up, w_down):
            def call(i, x):
                li = i % layers
                y = moe.moe_swiglu(  # the run's stacks and the layer, as the model passes them
                    x, router[li], w_gate, w_up, w_down, layer=li,
                    dispatch=dispatch, valid=valid, **kw,
                )
                x = (x + y).astype(jnp.float32)  # the next call's input
                return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))).astype(dt)

            return jax.lax.fori_loop(0, calls, call, x)

        return run

    def once(dispatch):
        return jax.jit(lambda x, v, r, g, u, d: moe.moe_swiglu(
            x, r[0], g, u, d, layer=jnp.int32(0), dispatch=dispatch, valid=v, **kw
        ))

    rows = []
    for n in tokens:
        x = jax.random.normal(keys[4], (n, 1, hidden), dt)
        live = np.ones((n, 1), bool)
        if dead_every:
            live[dead_every - 1::dead_every] = False
        args = (x, jnp.asarray(live), router, w_gate, w_up, w_down)
        rec = {"tokens": n, "live": int(live.sum()),
               "rule": moe.dispatch_path(n, 1, top_k, ranked)}
        outs = {}
        for dispatch in ("dense", "grouped"):
            if dispatch == "dense" and n * held * inter * 4 > 2**31:
                continue  # the dense combine's [tokens, held, inter] is too large
            fn = chain(dispatch)
            _timed(fn, *args)  # compile + warm
            fastest = min(_timed(fn, *args)[1] for _ in range(repeats))
            outs[dispatch] = np.asarray(once(dispatch)(*args), np.float32)[live[:, 0]]
            rec[f"{dispatch}_ms"] = round(fastest / calls * 1e3, 3)
        if len(outs) == 2:
            # ONE call's results (a chain re-routes on its own rounding)
            rec["max_diff_in_stds"] = float(
                np.abs(outs["dense"] - outs["grouped"]).max() / max(outs["dense"].std(), 1e-9)
            )
        rows.append(rec)
    return rows


# ------------------------------------------- a learned index, sparse attention


def _sparse_index_operands(
    n_heads, rank, rope, index_heads, index_dim, page_size, lanes, table_pages,
    layers, dt,
):
    """Seeded pools, queries and a table whose pages are scattered over the
    pool, at one model's widths (ops/sparse_index.py's operands)."""
    width = -(-(rank + rope) // 128) * 128
    n_pages = lanes * table_pages
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    latent = jnp.pad(
        jax.random.normal(ks[0], (layers, n_pages, page_size, rank + rope), dt) * 0.3,
        ((0, 0),) * 3 + ((0, width - rank - rope),),
    )
    keys = jax.random.normal(ks[1], (layers, n_pages, page_size, index_dim), dt)
    q = jnp.pad(
        jax.random.normal(ks[2], (lanes, n_heads, rank + rope), dt),
        ((0, 0), (0, 0), (0, width - rank - rope)),
    )
    q_i = jax.random.normal(ks[3], (lanes, index_heads, index_dim), dt)
    w = jax.random.normal(ks[4], (lanes, index_heads), jnp.float32) * (
        index_heads * index_dim
    ) ** -0.5
    perm = np.random.default_rng(0).permutation(n_pages)
    tables = jnp.asarray(perm.reshape(lanes, table_pages), jnp.int32)
    return latent, keys, q, q_i, w, tables


def run_sparse_index_checks(
    n_heads: int, rank: int, rope: int, index_heads: int, index_dim: int,
    topk: int, page_size: int, lanes: int, table_pages: int, dtype: str = "bf16",
) -> list[dict]:
    """One layer's index scores, choice and sparse attention (ops/
    sparse_index.py, as a decode step runs them) against the same arithmetic
    in float32 at the highest matmul precision over the same pools, rows of
    lengths spread from ``topk`` to the whole table, every other one behind
    pads: the error of ``I`` (in units of a row's spread of scores), the
    share of the reference's chosen set the program chose, and the error of
    the attention's output where both are given the REFERENCE's set (a
    rounding of ``I`` moves the set's edge; the output over one set tells the
    attention's own arithmetic). The scores are the Pallas kernel's
    (ops/pallas/index_scores.py) where the widths tile, as a served decode
    step's are (``form`` says which)."""
    from cake_tpu.models.llama.paged_cache import gather_latent
    from cake_tpu.ops import sparse_index as SI
    from cake_tpu.ops.pallas.index_scores import paged_index_scores_supported
    from cake_tpu.ops.pallas.kth_largest import kth_largest_supported

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    kernel = paged_index_scores_supported(page_size, index_dim, index_heads)
    latent, keys, q, q_i, w, tables = _sparse_index_operands(
        n_heads, rank, rope, index_heads, index_dim, page_size, lanes,
        table_pages, 1, dt,
    )
    slots = table_pages * page_size
    lengths = jnp.asarray(
        np.linspace(min(topk, slots), slots, lanes).astype(np.int32)
    )
    starts = jnp.asarray(
        np.where(np.arange(lanes) % 2, min(page_size + 37, slots // 4), 0).astype(np.int32)
    )
    scale = (rank // 4 + rope) ** -0.5
    layer = jnp.int32(0)

    @jax.jit
    def program(q, q_i, w, latent, keys):
        scores = SI.index_scores(
            q_i, w, keys, tables, starts, lengths, layer=layer, kernel=kernel
        )
        picked, chosen = SI.select_topk(
            scores, topk, tables, page_size, kernel=kth_largest_supported(page_size)
        )
        return scores, picked, chosen

    table_rows = SI.pool_rows(tables, page_size)

    @jax.jit
    def attend(q, latent, order, chosen):
        rows = jnp.take_along_axis(table_rows, order, axis=1)
        return SI.sparse_latent_attention(
            q, latent, rows, chosen, layer=layer, rank=rank, scale=scale
        )

    @jax.jit
    def reference(q, q_i, w, latent, keys):
        with jax.default_matmul_precision("highest"):
            f32 = jnp.float32
            k = gather_latent(keys, tables, layer).astype(f32)
            s = jnp.einsum("bhd,bsd->bhs", q_i.astype(f32), k)
            scores = jnp.einsum("bh,bhs->bs", w, jax.nn.relu(s))
            slot = jnp.arange(slots)[None, :]
            live = (slot >= starts[:, None]) & (slot < lengths[:, None])
            scores = jnp.where(live, scores, -jnp.inf)
            order = jnp.argsort(-scores, axis=-1, stable=True)[:, :topk]
            chosen = jnp.take_along_axis(scores, order, axis=-1) > -jnp.inf
            rows = jnp.take_along_axis(
                gather_latent(latent, tables, layer).astype(f32), order[..., None], axis=1
            )
            a = jnp.einsum("bhw,bkw->bhk", q.astype(f32), rows) * scale
            p = jax.nn.softmax(jnp.where(chosen[:, None, :], a, -jnp.inf), axis=-1)
            return scores, order, chosen, jnp.einsum("bhk,bkr->bhr", p, rows[..., :rank])

    tol = 2.0**-6 if dtype == "bf16" else 1e-4
    rec = {"kernel": "sparse_index", "case": f"lanes={lanes} slots={slots} topk={topk}",
           "tol": tol, "form": "pallas" if kernel else "xla"}
    try:
        (scores, picked, chosen), first = _timed(program, q, q_i, w, latent, keys)
        want_scores, order, want_chosen, want_out = reference(q, q_i, w, latent, keys)
        got_out = attend(q, latent, order.astype(jnp.int32), want_chosen)
        scores, want_scores = np.asarray(scores), np.asarray(want_scores)
        live = np.isfinite(want_scores)
        if not np.array_equal(live, scores > -np.inf):
            raise AssertionError("the scores are -inf at other slots than the reference's")
        spread = np.asarray([want_scores[b][live[b]].std() for b in range(lanes)])
        err_i = float(np.max(
            np.abs(np.where(live, scores, 0.0) - np.where(live, want_scores, 0.0))
            / spread[:, None]
        ))
        overlap = []
        for b in range(lanes):  # as pool rows, which is what the choice carries
            got = set(np.asarray(picked[b])[np.asarray(chosen[b])].tolist())
            want = np.asarray(order[b])[np.asarray(want_chosen[b])]
            want = set(np.asarray(table_rows[b])[want].tolist())
            overlap.append(len(got & want) / len(want))
        got_out, want_out = np.asarray(got_out, np.float32), np.asarray(want_out)
        err_out = float(np.max(np.abs(got_out - want_out)) / np.max(np.abs(want_out)))
        rec.update(
            ok=bool(err_out <= tol and err_i <= 0.05 and min(overlap) >= 0.95),
            max_err=err_out, score_err_in_spreads=err_i,
            chosen_overlap_min=min(overlap), chosen_overlap_mean=float(np.mean(overlap)),
            first_call_s=round(first, 2),
        )
    except Exception as e:  # noqa: BLE001 - a refused shape is a failed case
        rec.update(ok=False, error=f"{type(e).__name__}: {str(e)[:300]}")
    return [rec]


def _select_set_err(scores, picked, chosen, carried, topk) -> int:
    """Rows whose chosen set (what ``select_topk`` says its choice carries) is
    not what the first ``topk`` of a stable argsort of ``-scores`` carry."""
    scores, picked, chosen = (np.asarray(a) for a in (scores, picked, chosen))
    bad = 0
    for r in range(scores.shape[0]):
        order = np.argsort(-scores[r], kind="stable")[:topk]
        want = carried[r][order[scores[r][order] > -np.inf]]
        got = picked[r][chosen[r]]
        bad += not (len(got) == len(want) and set(got.tolist()) == set(want.tolist()))
    return bad


def timed_sparse_index(
    n_heads: int, rank: int, rope: int, index_heads: int, index_dim: int,
    topk: int, page_size: int, lanes: int, table_pages: int, layers: int,
    dtype: str = "bf16", lengths: tuple[int, ...] = (2048, 8192, 21504),
    calls: int = 20, repeats: int = 3,
) -> list[dict]:
    """A decode step's three pieces alone on the clock, microseconds a call a
    layer with every row at ``lengths`` cached tokens, then with the rows as
    the benchmark's cell holds them (``cached_tokens`` "mixed": 9 of 16 live
    at about 8k tokens, the rest dead lanes of one slot): the index's scores
    (``scores_us``: the Pallas kernel where the widths tile, and then its
    time follows what the rows hold), the choice (``select_us``, and
    ``select_set_err``: the rows whose chosen set is not a stable argsort's),
    and the attention over the chosen (``sparse_us``: flat in the cached
    length where its bytes follow the tokens chosen). A row also says what
    its floor is of: ``live_rows``, ``scanned_tokens``, ``chosen_tokens``."""
    from cake_tpu.ops import sparse_index as SI
    from cake_tpu.ops.pallas.index_scores import paged_index_scores_supported
    from cake_tpu.ops.pallas.kth_largest import kth_largest_supported

    dt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    kernel = paged_index_scores_supported(page_size, index_dim, index_heads)
    latent, keys, q, q_i, w, tables = _sparse_index_operands(
        n_heads, rank, rope, index_heads, index_dim, page_size, lanes,
        table_pages, layers, dt,
    )
    scale = (rank // 4 + rope) ** -0.5
    slots = table_pages * page_size

    def chained(fn):
        @jax.jit
        def run(*args):
            def body(i, acc):
                return acc + jnp.sum(fn(i % layers, *args).astype(jnp.float32)) * 0
            return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
        return run

    def us(fn, *args):
        _timed(fn, *args)
        return round(min(_timed(fn, *args)[1] for _ in range(repeats)) / calls * 1e6, 1)

    score = chained(lambda li, q_i, w, keys, starts, lens: SI.index_scores(
        q_i, w, keys, tables, starts, lens, layer=li, kernel=kernel))
    table_rows = np.asarray(SI.pool_rows(tables, page_size))
    select_kernel = kth_largest_supported(page_size)
    select = chained(lambda li, scores: SI.select_topk(
        scores + li, topk, tables, page_size, kernel=select_kernel)[0])
    attend = chained(lambda li, q, latent, picked, chosen: SI.sparse_latent_attention(
        q, latent, picked, chosen, layer=li, rank=rank, scale=scale))
    sets = [(min(n, slots), np.full((lanes,), min(n, slots), np.int32)) for n in lengths]
    # The cell's rows: 9 of 16 live at 3/8 of the table on average (8k of
    # 21,504), a dead lane one slot (``starts = ends - 1``).
    n_live = max(1, lanes * 9 // 16)
    held = np.ones((lanes,), np.int32)
    held[:n_live] = np.linspace(slots * 9 // 32, slots * 15 // 32, n_live)
    sets.append(("mixed", held))
    starts = jnp.zeros((lanes,), jnp.int32)
    rows = []
    for label, held in sets:
        lens = jnp.asarray(held)
        scores = SI.index_scores(q_i, w, keys, tables, starts, lens, layer=jnp.int32(0))
        picked, chosen = SI.select_topk(scores, topk, tables, page_size, kernel=select_kernel)
        live = held > 1  # a dead lane holds its one slot
        rows.append({
            "cached_tokens": label,
            "live_rows": int(live.sum()),
            "scanned_tokens": int(held[live].sum()),
            "chosen_tokens": int(np.minimum(held, topk)[live].sum()),
            "scores_form": "pallas" if kernel else "xla",
            "scores_us": us(score, q_i, w, keys, starts, lens),
            "select_form": "pallas" if select_kernel else "xla",
            "select_us": us(select, scores),
            "select_set_err": _select_set_err(scores, picked, chosen, table_rows, topk),
            "sparse_us": us(attend, q, latent, picked, chosen),
        })
    return rows
