"""The benchmark's vocabulary: one word a token, every token visible.

The server streams text, not token ids, and without a ``tokenizer.json`` its
byte tokenizer decodes every id above 260 to the empty string, for which no
SSE chunk is written: with random weights over a vocabulary of 32000 a client
would see almost no token arrive. So the benchmark's checkpoint carries a
word-level ``tokenizer.json`` (the file a user's checkpoint has) in which id
``i`` is the word ``w<i>``. Every generated token then streams as one chunk,
a prompt of n words is n tokens, and the client reads the served ids back out
of the text for the reference. The special words, their ids and the chat
template are the architecture's (``bench/architectures``), as the program
renders that ``model_type``. Stdlib and ``tokenizers`` only: no JAX.
"""

from __future__ import annotations

import random
from pathlib import Path


class Vocabulary:
    """The words of one configuration: its architecture's special words at
    their ids, ``w<i>`` at every other."""

    def __init__(self, arch, cfg: dict):
        self._arch, self._cfg = arch, cfg
        self.size = cfg["vocab_size"]
        self.specials: dict[int, str] = dict(arch.special_words(cfg))
        # Traffic never draws these ids, and the head's rows of them are zero.
        self.special_ids = sorted(self.specials)
        self._ids = {w: i for i, w in self.specials.items()}

    def word(self, token_id: int) -> str:
        return self.specials.get(token_id) or f"w{token_id}"

    def draw(self, rng: random.Random, n: int) -> list[int]:
        """``n`` ids of ordinary words: uniform over the ids that are no
        special's, one ``randrange`` each."""
        out = []
        for _ in range(n):
            k = rng.randrange(self.size - len(self.special_ids))
            for s in self.special_ids:  # the k-th id that is no special's
                if s <= k:
                    k += 1
            out.append(k)
        return out

    def prompt_text(self, ids: list[int]) -> str:
        return " ".join(self.word(i) for i in ids)

    def chat_text(self, user: str) -> str:
        return self._arch.chat_text(user)

    def chat_ids(self, prompt_ids: list[int]) -> list[int]:
        """The ids the server's tokenizer makes of
        ``chat_text(prompt_text(.))``: the context the judge is given."""
        return self._arch.chat_ids(self._cfg, list(prompt_ids))

    def ids_from_text(self, text: str) -> list[int]:
        """Served ids from streamed text; raises on a word not of the vocabulary."""
        out = []
        for w in text.split():
            if w in self._ids:
                out.append(self._ids[w])
            elif w[0] == "w" and w[1:].isdigit():
                out.append(int(w[1:]))
            else:
                raise ValueError(f"streamed word {w!r} is not in the vocabulary")
        return out

    def write_tokenizer(self, path: Path) -> None:
        from tokenizers import AddedToken, Tokenizer, models, pre_tokenizers

        unknown = self._arch.UNKNOWN_WORD  # None where the vocabulary has none
        vocab = {self.word(i): i for i in range(self.size)}
        tok = Tokenizer(models.WordLevel(vocab, **({"unk_token": unknown} if unknown else {})))
        tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
        # Found in the raw text before it is split, so a template needs no
        # space between a special word and its neighbour. Not marked special:
        # decoding keeps them, so even these ids stream as text.
        tok.add_tokens([AddedToken(self.specials[i], normalized=False)
                        for i in self.special_ids if self.specials[i] != unknown])
        probe = self.draw(random.Random(0), 2)
        got = tok.encode(self.chat_text(self.prompt_text(probe)), add_special_tokens=False)
        if got.ids != self.chat_ids(probe):
            raise RuntimeError(
                f"tokenizer encodes the chat template as {got.ids}, the "
                f"reference expects {self.chat_ids(probe)}"
            )
        tok.save(str(path))
