"""Autoregressive generation loop.

Covers the reference's ``Generator`` trait and its ``LLama`` implementation
(cake-core/src/models/mod.rs:36-55, models/llama3/llama.rs:271-335): chat history,
prefill-then-decode with position bookkeeping, seeded sampling with repeat penalty,
incremental detokenization, EOS detection.

The pluggable seam is ``ForwardStep`` — the analogue of the reference's ``Forwarder``
trait (cake/mod.rs:104-146): the generator only needs `(tokens, pos, seq_len) ->
logits`; whether that runs locally, as a shard_map pipeline over a TPU mesh, or
through TCP workers is the step implementation's business. Tests script it.

TPU-first details:
  * Prefill pads the prompt to a power-of-two bucket so each bucket compiles once;
    decode is a single compiled shape (chunk=1) with traced ``pos``.
  * The KV cache is preallocated and donated back to the step, so decode is
    allocation-free.
  * The repeat-penalty window is a fixed-size ring (pad -1), keeping sampling jitted.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from pathlib import Path
from typing import Callable, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from cake_tpu.models.llama import model as M
from cake_tpu.models.llama.cache import init_cache
from cake_tpu.models.llama.chat import Message, encode_dialog
from cake_tpu.models.llama.config import CACHE_KV, LlamaConfig
from cake_tpu.models.llama.tokenizer import Tokenizer, load_tokenizer
from cake_tpu.ops.sampling import DEFAULT_SEED, apply_repeat_penalty, sample
from cake_tpu.utils import metrics

MODEL_NAME = "llama3"


@dataclasses.dataclass
class Token:
    """One generated token (models/mod.rs:11-18)."""

    id: int
    text: str
    is_end_of_stream: bool


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Sampling knobs, defaults matching the reference CLI (lib.rs:40-66)."""

    temperature: float = 1.0
    top_k: int | None = None
    top_p: float | None = None
    repeat_penalty: float = 1.1
    repeat_last_n: int = 128
    seed: int = DEFAULT_SEED

    def trace_knobs(self) -> tuple:
        """The fields compiled into a fused-decode trace (models/llama/fused.py).

        THE one definition of trace compatibility: configs sharing this tuple
        may share a compiled fused scan — and a lockstep serving batch
        (runtime/serving.py groups requests by it). The seed is excluded: PRNG
        keys are runtime arguments.
        """
        return (
            self.temperature,
            self.top_k,
            self.top_p,
            self.repeat_penalty,
            self.repeat_last_n,
        )


def decode_delta(
    tokenizer: Tokenizer, ids: list[int], decoded_len: int
) -> tuple[str, int]:
    """Incremental detokenization: (newly stabilized text, new stable length).

    Holds back a trailing replacement char — it may be a partial UTF-8
    sequence the next token completes. Shared by the generator and the
    batched serving rows so the hold-back rule exists once.
    """
    full = tokenizer.decode(ids)
    stable = len(full)
    if full.endswith("�"):
        stable -= 1
    return full[decoded_len:stable], stable


class StepConnectionError(RuntimeError):
    """A step's backing connection failed mid-call and was re-established.

    Raised by distributed ForwardStep implementations (runtime/master.py)
    AFTER reconnecting: the step's KV state is inconsistent/lost, and the
    generator recovers by resetting the step and replaying its token history
    (the reference has no recovery — errors tear the run down, SURVEY.md §5).
    """

    def __init__(self, node: str):
        super().__init__(f"connection to worker {node!r} was reset")
        self.node = node


class ForwardStep(Protocol):
    """One model step over a token chunk. Implementations own their KV state."""

    def __call__(
        self, tokens: np.ndarray, pos: int, seq_len: int
    ) -> np.ndarray:  # [batch, vocab] f32 logits at the last valid position
        ...

    def reset(self) -> None:
        """Drop cached sequence state (new dialog)."""
        ...

    @property
    def max_seq_len(self) -> int: ...


from cake_tpu.models.llama.fused import FusedDecodeCapability


class LocalForwardStep(FusedDecodeCapability):
    """Single-process step: full params resident, jitted prefill/decode.

    Fused multi-token decode comes from FusedDecodeCapability (decode_chunk)."""

    def __init__(
        self,
        config: LlamaConfig,
        params: M.Params,
        *,
        max_seq_len: int | None = None,
        batch_size: int = 1,
        cache_dtype: jnp.dtype = jnp.bfloat16,
        rolling_budget: int | None = None,
    ):
        from cake_tpu.ops.fuse import fuse_params
        from cake_tpu.ops.quant import apply_runtime_int4_repr

        self.config = config
        # Prep-time QKV / gate|up fusion (ops/fuse.py): fewer HBM-bound ops
        # per scanned layer; column-identical numerics, idempotent. The
        # optional native-s4 int4 conversion (CAKE_INT4_REPR=s4) happens
        # here too — the single-chip runtime prep site.
        self.params = apply_runtime_int4_repr(fuse_params(params))
        self._max_seq = int(max_seq_len or config.max_position_embeddings)
        self._batch = batch_size
        self._cache_dtype = cache_dtype
        # Rolling window cache (cache.py): for sliding-window models, bound
        # KV memory by window + largest chunk instead of max_seq_len.
        # ``rolling_budget`` is the caller's promise about the largest chunk
        # it will ever feed (its --prefill-chunk); enabled only when it
        # actually shrinks the allocation.
        self.rolling = False
        self._cache_len = self._max_seq
        win = config.sliding_window
        if config.alt_sliding_window or config.sliding_pattern is not None:
            # gemma2 alternating / gemma3 5:1 patterns: their full-attention
            # layers need EVERY key — a window-bounded ring would evict
            # history those layers must still attend.
            win = None
        if rolling_budget is not None and win is not None:
            from cake_tpu.models.llama.cache import SEQ_MULTIPLE

            budget = max(int(rolling_budget), 1)
            s_roll = -(-(win + budget) // SEQ_MULTIPLE) * SEQ_MULTIPLE
            s_dense = -(-self._max_seq // SEQ_MULTIPLE) * SEQ_MULTIPLE
            if s_roll < s_dense:
                self.rolling = True
                self._cache_len = s_roll
        from cake_tpu.obs.jitwatch import tracked_jit

        self._fwd = tracked_jit(
            M.forward,
            name="generator.forward",
            static_argnames=("config", "cached_prefill", "rolling", "rope_len"),
            donate_argnames=("kv",),
        )
        self.reset()

    @property
    def max_seq_len(self) -> int:
        return self._max_seq

    def reset(self) -> None:
        if self.config.cache_kind != CACHE_KV or self.config.block_length:
            # The weights' holder for the batch engine only: this step's
            # dense cache and M.forward know K and V a KV head alone, one
            # token a step.
            self._kv = None
            return
        self._kv = init_cache(
            self.config.num_hidden_layers,
            self._batch,
            self._cache_len,
            self.config.num_key_value_heads,
            self.config.head_dim,
            self._cache_dtype,
        )

    def __call__(self, tokens: np.ndarray, pos: int, seq_len: int) -> np.ndarray:
        if self._kv is None:
            from cake_tpu.models.llama.capability import refuse_unsupported

            refuse_unsupported(self.config, single_stream=True)
        if self.rolling:
            room = self._kv.max_seq_len - self.config.sliding_window
            if tokens.shape[1] > room:
                raise ValueError(
                    f"chunk of {tokens.shape[1]} tokens exceeds the rolling "
                    f"cache budget {room}; lower --prefill-chunk or raise "
                    "rolling_budget"
                )
        logits, self._kv = self._fwd(
            self.params,
            jnp.asarray(tokens, jnp.int32),
            self._kv,
            jnp.int32(pos),
            jnp.int32(seq_len),
            self.config,
            cached_prefill=M.is_cached_prefill(pos, tokens.shape[1]),
            rolling=self.rolling,
            rope_len=self._max_seq if self.rolling else None,
        )
        return np.asarray(logits)

    def _fused_forward_one(self):
        params, config = self.params, self.config
        rolling, rope_len = self.rolling, self._max_seq if self.rolling else None

        def forward_one(tok, kv, pos):
            return M.forward(
                params, tok, kv, pos, jnp.int32(1), config,
                rolling=rolling, rope_len=rope_len,
            )

        return forward_one

    def verify_chunk(self, tokens: np.ndarray, pos: int) -> np.ndarray:
        """Speculative-verify: GREEDY ids at EVERY position of the fed chunk
        (models/llama/speculative.py), argmax'd on device. KV for the whole
        chunk is written at [pos, pos + width); rejected tail slots are dead
        until overwritten."""
        if self.rolling:
            raise RuntimeError(
                "speculative verify is not supported on a rolling cache; "
                "construct the step without rolling_budget"
            )
        from cake_tpu.models.llama.speculative import _verify_fn

        fn = _verify_fn(self.config, tokens.shape[1])
        ids, self._kv = fn(
            self.params, jnp.asarray(tokens, jnp.int32), self._kv, jnp.int32(pos)
        )
        return np.asarray(ids)

    def verify_chunk_sampled(
        self,
        tokens: np.ndarray,
        pos: int,
        draft: np.ndarray,
        n_draft: int,
        key: jax.Array,
        sampling,
    ) -> tuple[int, int, jax.Array]:
        """Sampled speculative verify: forward + rejection acceptance +
        residual/bonus sample entirely on device (speculative.sampled_accept);
        only (n_accepted, next_token) scalars come back."""
        if self.rolling:
            raise RuntimeError(
                "speculative verify is not supported on a rolling cache; "
                "construct the step without rolling_budget"
            )
        from cake_tpu.models.llama.speculative import _sampled_verify_fn

        fn = _sampled_verify_fn(
            self.config, tokens.shape[1],
            sampling.temperature, sampling.top_k, sampling.top_p,
        )
        n_acc, nxt, self._kv, key = fn(
            self.params, jnp.asarray(tokens, jnp.int32), self._kv,
            jnp.int32(pos), jnp.asarray(draft, jnp.int32),
            jnp.int32(n_draft), key,
        )
        return int(n_acc), int(nxt), key


def prefill_bucket(n: int, max_seq_len: int, minimum: int = 16) -> int:
    """Power-of-two padding bucket: one compile per bucket, not per prompt length."""
    b = minimum
    while b < n:
        b *= 2
    return min(b, max_seq_len)


class LlamaGenerator:
    """Chat-aware token generator (the reference's Generator contract)."""

    def __init__(
        self,
        config: LlamaConfig,
        step: ForwardStep,
        tokenizer: Tokenizer,
        sampling: SamplingConfig = SamplingConfig(),
        decode_chunk_size: int = 1,
        prefill_chunk: int | None = None,
        speculative_k: int = 0,
        prefix_cache: bool = False,
        proposer=None,
    ):
        self.config = config
        self.step = step
        self.tokenizer = tokenizer
        self.sampling = sampling
        # The drafting seam (models/llama/speculative.py): anything with
        # ``propose(tokens, k) -> list[int]``. None = prompt lookup (free);
        # a DraftModelProposer plugs a small model in for free-generation
        # text. Correctness never depends on the proposal — the verify
        # forward re-derives the exact stream/distribution either way.
        self.proposer = proposer
        # Reuse the KV prefix across reset() boundaries: a new dialog whose
        # token stream shares a prefix with the previous one (multi-turn chat
        # through the per-request-reset API, api/mod.rs:78) prefills only the
        # new suffix, at its offset, via the cached-prefix attention path.
        # Token streams are unchanged — the shared prefix's KV is identical to
        # what a fresh prefill would write (causal attention: a token's KV
        # depends only on tokens before it).
        self.prefix_cache = prefix_cache
        # > 0 enables prompt-lookup speculative decoding
        # (models/llama/speculative.py): K drafted tokens verified in one
        # chunked forward. Greedy streams stay byte-identical; temperature>0
        # streams keep the exact plain-decode distribution via rejection
        # sampling. Draft quality affects speed only. Needs
        # repeat_penalty == 1.0 (see _speculative_applicable).
        self.speculative_k = speculative_k
        # Long prompts prefill in chunks of at most this many tokens (None =
        # one shot): bounds compiled shapes and attention-score memory to
        # [prefill_chunk, max_seq] instead of [prompt, prompt].
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # > 1 enables fused multi-token decode when the step supports it
        # (models/llama/fused.py): N tokens per device dispatch instead of a
        # host round trip per token. Streaming then emits in bursts of N.
        self.decode_chunk_size = decode_chunk_size
        # Fused decode compiles the FULL model scan per distinct sampling-knob
        # tuple — only the construction-time config may use it. Requests that
        # override sampling (the API path) fall back to per-step decode, whose
        # recompile unit is just the tiny sampler, so untrusted per-request
        # knobs can never trigger a whole-model recompile under the server lock.
        self._fused_knobs = sampling.trace_knobs()
        # One compiled sampler per distinct (temperature, top_k, top_p,
        # repeat_penalty): those are STATIC in the sampler (python branches), so
        # changing self.sampling (e.g. per-API-request overrides) must select a
        # different trace — a plain jit would silently reuse the first config's
        # constants. The seed is NOT part of the key: the PRNG key is a runtime
        # argument, and keying on seed would leak one compiled entry per seed.
        # Bounded LRU: untrusted per-request knobs (the API) must not grow the
        # compile cache without limit.
        self._sampler_cache: "collections.OrderedDict[tuple, Callable]" = (
            collections.OrderedDict()
        )
        self.last_finish_reason: str = "stop"
        self.reset()

    @classmethod
    def load(
        cls,
        model_dir: str | Path,
        *,
        dtype: jnp.dtype = jnp.bfloat16,
        max_seq_len: int | None = None,
        sampling: SamplingConfig = SamplingConfig(),
        step_factory: Callable[[LlamaConfig, M.Params], ForwardStep] | None = None,
        attention_impl: str | None = None,
        decode_chunk_size: int = 1,
        prefill_chunk: int | None = None,
        speculative_k: int = 0,
        quantize: str | None = None,
        draft_model_dir: str | Path | None = None,
        draft_quantize: str | None = None,
    ) -> "LlamaGenerator":
        """Load config + weights + tokenizer from a checkpoint dir (llama.rs:176-252).

        ``attention_impl`` overrides the kernel choice ("auto"/"pallas"/"xla",
        see LlamaConfig.attention_impl).
        """
        from cake_tpu.io.safetensors_io import load_params

        config = LlamaConfig.from_model_dir(model_dir, attention_impl=attention_impl)
        params = load_params(model_dir, config, dtype)
        if quantize is not None:
            if quantize not in ("int8", "int4"):
                raise ValueError(f"unknown quantize mode {quantize!r}")
            from cake_tpu.ops.quant import quantize_params

            params = quantize_params(params, quantize)
        if step_factory is None:
            step = LocalForwardStep(
                config, params, max_seq_len=max_seq_len, cache_dtype=dtype
            )
        else:
            step = step_factory(config, params)
        proposer = None
        if draft_model_dir is not None:
            from cake_tpu.models.llama.speculative import DraftModelProposer

            proposer = DraftModelProposer.load(
                draft_model_dir,
                dtype=dtype,
                max_seq_len=step.max_seq_len,
                quantize=draft_quantize,
            )
        return cls(
            config,
            step,
            load_tokenizer(model_dir),
            sampling,
            decode_chunk_size=decode_chunk_size,
            prefill_chunk=prefill_chunk,
            speculative_k=speculative_k,
            proposer=proposer,
        )

    # ------------------------------------------------------------- chat state

    def reset(self) -> None:
        """Clear dialog, KV cache, counters (llama.rs:261-268).

        With ``prefix_cache`` on, the step's KV survives the reset as a
        snapshot of the tokens it is valid for; the next dialog prefills only
        past the longest common prefix. The snapshot is bounded both by the
        last sampled token (never fed back, so its KV slot is unwritten; the
        same index bounds speculative decoding's rejected draft slots) and by
        ``_kv_high`` — the high-water mark of SUCCESSFUL step calls — so a
        prefill that failed partway (connection loss, OOM) can never poison
        the next request's reuse with slots that were never written.
        """
        if (
            self.prefix_cache
            and getattr(self, "_started", False)
            # A rolling cache cannot offer prefix reuse: truncating to a
            # common prefix would leave stale slots whose reconstructed
            # positions lie about data written past the prefix.
            and not getattr(self.step, "rolling", False)
        ):
            bound = min(self._kv_high, max(0, len(self._tokens) - 1))
            self._reusable = self._tokens[:bound]
        else:
            self._reusable = []
            if getattr(self, "step", None) is not None:
                self.step.reset()
        self._kv_high = 0
        self.messages: list[Message] = []
        self._tokens: list[int] = []  # full sequence: prompt + generated
        self._n_prompt = 0
        self._decoded_len = 0
        self._started = False
        self._prompt_cache: tuple[str, list[int]] | None = None
        self._key = jax.random.PRNGKey(self.sampling.seed)
        self.last_prefill_tokens = 0  # prefilled (non-reused) tokens, for tests/stats

    def add_message(self, message: Message) -> None:
        self.messages.append(message)

    @property
    def generated_count(self) -> int:
        return len(self._tokens) - self._n_prompt if self._started else 0

    @property
    def generated_token_ids(self) -> list[int]:
        return self._tokens[self._n_prompt :]

    def prompt_token_count(self) -> int:
        """Token count of the current dialog's rendered prompt (pre-generation).

        Lets servers reject over-length prompts with a client error before
        entering the decode path (which raises ValueError at next_token)."""
        return len(self._encode_prompt())

    def _encode_prompt(self) -> list[int]:
        """Encode the dialog, memoized on the rendered prompt string so the
        server's pre-validation and the first next_token share one tokenizer
        pass (rendering is cheap; BPE over a long prompt is not)."""
        prompt = encode_dialog(self.messages, self.config.dialog_template)
        if self._prompt_cache is None or self._prompt_cache[0] != prompt:
            self._prompt_cache = (prompt, self.tokenizer.encode(prompt))
        return self._prompt_cache[1]

    # ------------------------------------------------------------- sampling

    _SAMPLER_CACHE_MAX = 16

    def _sampler(self) -> Callable:
        s = self.sampling
        cache_key = (s.temperature, s.top_k, s.top_p, s.repeat_penalty)
        if cache_key in self._sampler_cache:
            self._sampler_cache.move_to_end(cache_key)
        else:

            def _impl(logits, key, window):
                out = apply_repeat_penalty(logits, s.repeat_penalty, window)
                return sample(
                    out, key, temperature=s.temperature, top_k=s.top_k, top_p=s.top_p
                )

            self._sampler_cache[cache_key] = jax.jit(_impl)
            while len(self._sampler_cache) > self._SAMPLER_CACHE_MAX:
                self._sampler_cache.popitem(last=False)
        return self._sampler_cache[cache_key]

    def _penalty_window(self) -> np.ndarray:
        n = self.sampling.repeat_last_n
        w = np.full((1, n), -1, np.int32)
        if n > 0 and self._tokens:
            recent = self._tokens[-n:]
            w[0, : len(recent)] = recent
        return w

    # ------------------------------------------------------------- decoding

    def _prefill(
        self, ids: list[int], cap: int | None = None, start: int = 0
    ) -> np.ndarray:
        """Run ``ids`` (which sit at positions [start, start+len)) through the
        step; returns logits at the last token.

        With a chunk cap set, a long prompt runs as full chunks of exactly
        that size (one compiled shape, cache-prefix attention) followed by one
        power-of-two-bucketed tail chunk; otherwise one shot at a power-of-two
        bucket (the reference prefills in one shot too, llama.rs:280-292).
        ``start`` > 0 is a continuation over an existing cache prefix (prefix
        reuse) and flows through the same cache-prefix attention path.

        Timing lands in the ``cake_prefill_seconds`` histogram — prefill and
        decode have opposite cost shapes (compute-bound vs HBM-bound), so
        serving telemetry keeps them separate distributions.
        """
        t0 = time.perf_counter()
        try:
            return self._prefill_inner(ids, cap, start)
        finally:
            metrics.registry.histogram(
                "cake_prefill_seconds",
                "Prompt prefill wall time per request (all chunks).",
            ).observe(time.perf_counter() - t0)

    def _prefill_inner(
        self, ids: list[int], cap: int | None = None, start: int = 0
    ) -> np.ndarray:
        if cap is None:
            cap = self.prefill_chunk
        off = start
        end = start + len(ids)
        if cap is not None and end - off > cap:
            n_full = (end - off - 1) // cap  # the tail chunk always remains
            if n_full >= 2 and hasattr(self.step, "prefill_chunks"):
                # Microbatched pipeline prefill: all full chunks in ONE
                # dispatch, overlapped across the mesh's stages
                # (parallel/pipeline.py prefill_chunks) — instead of walking
                # them serially with S-1 stages idle per chunk.
                span = np.asarray(
                    [ids[off - start : off - start + n_full * cap]], np.int32
                )
                self.step.prefill_chunks(span, off, cap)
                off += n_full * cap
                self._kv_high = max(self._kv_high, off)
            while end - off > cap:
                chunk = np.asarray([ids[off - start : off - start + cap]], np.int32)
                self.step(chunk, off, cap)  # logits discarded mid-prompt
                off += cap
                self._kv_high = max(self._kv_high, off)
        rem = ids[off - start :]
        bucket = prefill_bucket(len(rem), self.step.max_seq_len if cap is None else cap)
        # Clamp to the cache bounds: a pow2 bucket at offset `off` must not
        # write past max_seq_len — dynamic_update_slice would CLAMP the start
        # index and silently overwrite the tail of the prompt's KV prefix.
        bucket = min(bucket, self.step.max_seq_len - off)
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, : len(rem)] = rem
        logits = self.step(chunk, off, len(rem))
        self._kv_high = max(self._kv_high, off + len(rem))
        return logits

    def next_token(self) -> Token:
        """Generate one token (llama.rs:271-335)."""
        if not self._started:
            ids = self._encode_prompt()
            if len(ids) >= self.step.max_seq_len:
                raise ValueError(
                    f"prompt length {len(ids)} exceeds max_seq_len "
                    f"{self.step.max_seq_len}"
                )
            self._tokens = list(ids)
            self._n_prompt = len(ids)
            self._started = True
            # Prefix reuse: skip the tokens whose KV the step already holds.
            # At least the final prompt token is always fed — its logits are
            # needed — so lcp is capped at len(ids) - 1.
            lcp = 0
            if self._reusable:
                cap_lcp = min(len(ids) - 1, len(self._reusable))
                while lcp < cap_lcp and ids[lcp] == self._reusable[lcp]:
                    lcp += 1
                self._reusable = []
            self.last_prefill_tokens = len(ids) - lcp
            logits = self._prefill(ids[lcp:], start=lcp)
        else:
            pos = len(self._tokens) - 1
            if pos >= self.step.max_seq_len:
                # Without this, dynamic_update_slice would clamp the write index
                # and silently corrupt the tail of the cache.
                raise ValueError(
                    f"sequence length {pos + 1} exceeds max_seq_len "
                    f"{self.step.max_seq_len}"
                )
            chunk = np.array([[self._tokens[-1]]], np.int32)
            t0 = time.perf_counter()
            logits = self.step(chunk, pos, 1)
            metrics.registry.histogram(
                "cake_decode_step_seconds",
                "Decode dispatch wall time (mode: per-token step, fused "
                "chunk, or speculative verify).",
            ).observe(time.perf_counter() - t0, mode="step")
            self._kv_high = max(self._kv_high, pos + 1)

        self._key, sub = jax.random.split(self._key)
        next_id = int(
            self._sampler()(
                jnp.asarray(logits), sub, jnp.asarray(self._penalty_window())
            )[0]
        )
        return self._materialize(next_id)

    def _decode_delta(self) -> str:
        """Incremental detokenization: emit only the newly stabilized text."""
        delta, self._decoded_len = decode_delta(
            self.tokenizer, self.generated_token_ids, self._decoded_len
        )
        return delta

    def _materialize(self, tid: int) -> Token:
        """Append one accepted id and produce its Token — the ONE place the
        append/EOS/incremental-detokenize sequence lives (per-step, fused, and
        speculative paths all emit through here)."""
        self._tokens.append(tid)
        is_eos = tid in self.config.eos_token_ids
        text = "" if is_eos else self._decode_delta()
        return Token(id=tid, text=text, is_end_of_stream=is_eos)

    def _next_tokens_fused(self, n_steps: int) -> list[Token]:
        """Decode ``n_steps`` tokens in one fused device dispatch.

        Requires prefill to have run (self._started) and the step to expose
        ``decode_chunk``. The penalty ring is reseeded from the host-side token
        history each call, so chunks compose exactly with per-step decoding.
        Truncates at EOS (the scanned tail past EOS is discarded; its stale KV
        writes sit beyond the live length, masked and later overwritten).
        """
        window = self.sampling.repeat_last_n
        ring = self._penalty_window()
        ring_idx = min(len(self._tokens), window) % window if window > 0 else 0
        last = np.asarray([self._tokens[-1]], np.int32)
        pos = len(self._tokens) - 1
        t0 = time.perf_counter()
        toks, self._key = self.step.decode_chunk(  # type: ignore[attr-defined]
            last, pos, n_steps, self.sampling, self._key, ring, ring_idx
        )
        metrics.registry.histogram(
            "cake_decode_step_seconds",
            "Decode dispatch wall time (mode: per-token step, fused "
            "chunk, or speculative verify).",
        ).observe(time.perf_counter() - t0, mode="fused")
        # All n_steps fed positions were written; reset()'s len-1 clamp drops
        # any slots whose tokens an EOS truncation below discards.
        self._kv_high = max(self._kv_high, pos + n_steps)
        result: list[Token] = []
        for tid in toks[0].tolist():
            tok = self._materialize(int(tid))
            result.append(tok)
            if tok.is_end_of_stream:
                break
        return result

    def _next_tokens_speculative(
        self, draft: list[int], width: int, budget: int
    ) -> list[Token]:
        """Verify ``draft`` (padded to ``width``) in one chunked forward; emit
        the accepted prefix plus the corrected/bonus token, capped to budget.

        Pad drafts use token 0 — if 0 happens to BE the greedy continuation the
        "accepted pad" is still exactly the greedy token, so correctness never
        depends on the proposer.
        """
        from cake_tpu.models.llama.speculative import greedy_accept

        padded = list(draft) + [0] * (width - len(draft))
        chunk = np.asarray([[self._tokens[-1], *padded]], np.int32)
        pos = len(self._tokens) - 1
        s = self.sampling
        t0 = time.perf_counter()
        if s.temperature is not None and s.temperature > 0.0:
            # Sampled acceptance: the emitted marginal at every position is
            # exactly the plain-decode distribution (speculative.py); pads
            # never accept, so candidates past n_acc are just [nxt].
            n_acc, nxt, self._key = self.step.verify_chunk_sampled(  # type: ignore[attr-defined]
                chunk, pos, np.asarray(padded, np.int32), len(draft),
                self._key, s,
            )
        else:
            argm = self.step.verify_chunk(chunk, pos)[0]  # type: ignore[attr-defined]
            n_acc, nxt = greedy_accept(np.asarray(padded), argm)
        metrics.registry.histogram(
            "cake_decode_step_seconds",
            "Decode dispatch wall time (mode: per-token step, fused "
            "chunk, or speculative verify).",
        ).observe(time.perf_counter() - t0, mode="speculative")
        # Valid KV: the fed last token + accepted drafts; rejected-tail slots
        # beyond pos + n_acc hold wrong-token KV and stay unclaimed.
        self._kv_high = max(self._kv_high, pos + 1 + n_acc)
        candidates = padded[:n_acc] + [nxt]
        result: list[Token] = []
        for tid in candidates[:budget]:
            tok = self._materialize(int(tid))
            result.append(tok)
            if tok.is_end_of_stream:
                break
        return result

    def _speculative_applicable(self, budget: int) -> bool:
        s = self.sampling
        sampled = s.temperature is not None and s.temperature > 0.0
        return (
            self.speculative_k > 0
            and self._started
            # repeat_penalty would make the in-chunk target distribution
            # history-dependent; both acceptance modes gate on it.
            and s.repeat_penalty == 1.0
            and hasattr(
                self.step, "verify_chunk_sampled" if sampled else "verify_chunk"
            )
            and budget >= 2
            # Verify writes KV at slots [len-1, len-1+width]; stay in bounds.
            and len(self._tokens) + self.speculative_k <= self.step.max_seq_len
        )

    def _replay_history(self) -> None:
        """Elastic recovery: rebuild ALL step-side KV from the token history.

        After a StepConnectionError every cache (local and remote) is suspect;
        reset the step, then re-feed everything except the pending last token
        as a chunked prefill. The pending token is consumed by the next
        regular step, which resumes the stream exactly where it broke.
        """
        self.step.reset()
        self._kv_high = 0  # everything below re-earns its mark via _prefill
        ids = self._tokens[:-1]
        if not ids:
            return
        # Bound replay compiles even when normal prefill is one-shot.
        self._prefill(ids, cap=self.prefill_chunk or 256)

    def generate(
        self,
        max_new_tokens: int,
        on_token: Callable[[Token], None] | None = None,
        chunk_size: int | None = None,
    ) -> str:
        """Run the decode loop, streaming via callback (master.rs:54-97).

        Sets ``last_finish_reason``: "stop" if EOS ended the stream, "length" if
        the token budget or the context window did. ``chunk_size`` (default:
        self.decode_chunk_size) > 1 selects fused multi-token decode when the
        step supports it; the first token always goes through ``next_token``
        (prefill + host sample), and short tails fall back to per-step decode
        rather than compiling one fused variant per tail length.
        """
        chunk = self.decode_chunk_size if chunk_size is None else chunk_size
        out: list[str] = []
        self.last_finish_reason = "length"
        produced = 0

        def emit(tok: Token) -> bool:
            nonlocal produced
            produced += 1
            if on_token is not None:
                on_token(tok)
            if tok.is_end_of_stream:
                self.last_finish_reason = "stop"
                return False
            out.append(tok.text)
            return True

        recoveries = 0
        needs_replay = False
        produced_at_last_failure = 0
        while produced < max_new_tokens:
            # The budget bounds failures per INCIDENT, not per call: any tokens
            # emitted since the last failure prove the reconnect worked, so a
            # later, unrelated blip gets a fresh allowance. (Checked at the top
            # of the loop — every successful iteration path, including the
            # per-step and speculative branches, exits the try via continue,
            # which would skip a try/else clause.)
            if recoveries and produced > produced_at_last_failure:
                recoveries = 0
            if len(self._tokens) >= self.step.max_seq_len:
                break
            budget = min(
                max_new_tokens - produced,
                self.step.max_seq_len - len(self._tokens),
            )
            try:
                if needs_replay:
                    # Inside the try: a blip DURING replay consumes the same
                    # bounded recovery budget instead of escaping generate().
                    self._replay_history()
                    needs_replay = False
                if self._speculative_applicable(budget):
                    from cake_tpu.models.llama.speculative import propose_lookup

                    draft = (
                        self.proposer.propose(self._tokens, self.speculative_k)
                        if self.proposer is not None
                        else propose_lookup(self._tokens, self.speculative_k)
                    )
                    if draft:
                        stop = False
                        for tok in self._next_tokens_speculative(
                            draft, self.speculative_k, budget
                        ):
                            if not emit(tok):
                                stop = True
                                break
                        if stop:
                            return "".join(out)
                        continue
                if (
                    chunk < 2
                    or budget < chunk  # tail: per-step, single chunk size
                    or not self._started
                    or not hasattr(self.step, "decode_chunk")
                    or self.sampling.trace_knobs() != self._fused_knobs
                ):
                    if not emit(self.next_token()):
                        return "".join(out)
                    continue
                for tok in self._next_tokens_fused(chunk):
                    if not emit(tok):
                        return "".join(out)
            except StepConnectionError as e:
                # Elastic recovery (beyond the reference, which tears down,
                # SURVEY.md §5): the step reconnected; rebuild KV from the
                # token history and retry this iteration. Steps raise BEFORE
                # any token of the iteration materializes, so no emission is
                # lost or duplicated.
                recoveries += 1
                if recoveries > 2:
                    raise
                produced_at_last_failure = produced
                import logging

                logging.getLogger("cake_tpu.generator").warning(
                    "recovering from %s (replaying %d tokens)", e, len(self._tokens)
                )
                needs_replay = True
        return "".join(out)
