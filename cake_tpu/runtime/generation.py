"""How a lane's tokens come out of a step: the seam between the engine's step
loop (``runtime/serving.BatchEngine``) and a model's generation kind
(``config.generation``).

``OneToken`` is every autoregressive model and is today's behaviour: a prefill
yields a first token, a step one token a lane, the ``tok`` operand is the last
token sampled. ``ByBlocks`` is a model that generates by diffusion over blocks
(``models/llama/diffusion.py``): a row's first ``P0 = (P // B) * B`` prompt
tokens are prefilled, the last ``P mod B`` ride into its first block unmasked
(``_RowState.known``), no prefill yields a token, a dispatch is whole blocks
and the ``tok`` operand is the next block's known tokens [lanes, B]. ``slot``
is then the first slot not yet written. The engine asks its one object
(``BatchEngine._gen``) wherever the two differ; where the loop's own shape
differs (an epoch's and a joiner's seating) it branches on ``_gen.block``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class OneToken:
    """One token a lane a step."""

    block = 0  # slots a block; 0: none

    def __init__(self, config):
        self.config = config

    def check(self, sampling) -> None:
        """Refuse a request's sampling that the generation cannot serve."""

    def chunk(self, n: int) -> int:
        """The slots a decode dispatch advances, from ``--decode-chunk``."""
        return n

    def prefilled(self, req) -> list[int]:
        """The prompt tokens a prefill or a join computes K and V for."""
        return req.prompt_ids

    def slots_for(self, req) -> int:
        """Slots behind its prefill that a request's whole answer takes."""
        return req.max_tokens

    def dead_row(self) -> list[int]:
        """What an epoch's prefill lays into a lane that holds no request."""
        return [self.config.bos_token_id]

    def phase_args(self, n: int) -> dict:
        """More arguments of the phase around a dispatch of ``n`` slots."""
        return {}

    def first_token(self, host, entry) -> int | None:
        """A joiner's first token among its boundary's values (None: none)."""
        return int(host[entry.row])

    def note(self, backend, consumed: dict, known: dict) -> None:
        """A read dispatch's tokens, as the engine pushed them."""

    def spill_ctx(self, *ctx):
        """What ``_extend_pages`` may park a lane with (None: it truncates)."""
        return ctx

    def cached_more(self, chunk) -> int:
        """Cached tokens of a chunk's rows beyond history and tokens in flight."""
        return 0

    def next_operand(self, toks, lanes: int):
        """The next dispatch's ``tok`` operand, from this one's tokens."""
        return toks[:, -1]

    def keeps_lane(self, req) -> bool:
        """Whether a joiner's lane is the row's after its join (a budget of
        one token ends with the join's own token in flight)."""
        return req.max_tokens > 1


class ByBlocks(OneToken):
    """Blocks of ``config.block_length`` slots, denoised and committed."""

    def __init__(self, config):
        super().__init__(config)
        self.block = config.block_length
        self._blank: dict[int, jax.Array] = {}

    def check(self, sampling) -> None:
        if sampling.repeat_penalty != 1.0:
            # (the CLI refuses the flag: ``capability.REFUSED_BY_GENERATION``)
            raise ValueError(
                "repeat_penalty other than 1.0 is not supported for a model "
                "that generates by diffusion over blocks"
            )

    def chunk(self, n: int) -> int:
        return max(self.block, n // self.block * self.block)

    def tail(self, req) -> int:
        """Prompt tokens a row's first block carries unmasked."""
        return len(req.prompt_ids) % self.block

    def prefilled(self, req) -> list[int]:
        ids = req.prompt_ids
        return ids[: len(ids) - self.tail(req)]

    def slots_for(self, req) -> int:
        # the whole blocks that hold the prompt's tail and the budget
        return -(-(self.tail(req) + req.max_tokens) // self.block) * self.block

    def dead_row(self) -> list[int]:
        return []

    def phase_args(self, n: int) -> dict:
        blocks = n // self.block
        return {"blocks": blocks, "passes": blocks * (self.config.denoising_steps + 1)}

    def first_token(self, host, entry) -> None:
        return None  # a block's tokens come from its own passes

    def note(self, backend, consumed: dict, known: dict) -> None:
        backend.diffusion.note(emitted=sum(consumed.values()), known=sum(known.values()))

    def spill_ctx(self, *ctx) -> None:
        return None  # a block step has no restore: pool pressure truncates

    def cached_more(self, chunk) -> int:
        """``slot`` is a row's first UNWRITTEN slot (one more a row than an
        autoregressive row's), less the known tokens of the rows whose FIRST
        block ``chunk`` is, which move onto the chunk (``_Unread.known``)."""
        for lane, row in chunk.rows:
            if row.known:
                chunk.known[lane], row.known = row.known, 0
        return len(chunk.rows) - sum(chunk.known.values())

    def next_operand(self, toks, lanes: int):
        """[lanes, B] of the mask id: the blocks after a lane's first."""
        blank = self._blank.get(lanes)
        if blank is None:
            blank = self._blank[lanes] = jnp.full(
                (lanes, self.block), self.config.mask_token_id, jnp.int32
            )
        return blank

    def keeps_lane(self, req) -> bool:
        return True

    def known_block(self, req) -> np.ndarray:
        """A row's first block: its prompt's tail, the mask id behind it."""
        block = np.full((self.block,), self.config.mask_token_id, np.int32)
        tail = self.tail(req)
        block[:tail] = req.prompt_ids[len(req.prompt_ids) - tail:]
        return block

    def seat(self, reqs: list, rows: list):
        """An epoch's start: every lane's first block and its own key."""
        known = np.full((len(reqs), self.block), self.config.mask_token_id, np.int32)
        for lane, r in enumerate(reqs):
            if r is not None:
                known[lane] = self.known_block(r)
                rows[lane].known = self.tail(r)
        keys = jnp.stack([
            jax.random.PRNGKey(r.sampling.seed if r is not None else 0) for r in reqs
        ])
        return known, keys


def of(config) -> OneToken:
    return ByBlocks(config) if config.block_length else OneToken(config)
