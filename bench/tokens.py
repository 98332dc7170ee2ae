"""The benchmark's vocabulary: one word a token, every token visible.

The server streams text, not token ids, and without a ``tokenizer.json`` its
byte tokenizer decodes every id above 260 to the empty string, for which no
SSE chunk is written: with random weights over a vocabulary of 32000 a client
would see almost no token arrive. So the benchmark's checkpoint carries a
word-level ``tokenizer.json`` (the file a user's checkpoint has) in which id
``i`` is the word ``w<i>``. Every generated token then streams as one chunk,
a prompt of n words is n tokens, and the client reads the served ids back out
of the text for the reference. Stdlib and ``tokenizers`` only: no JAX.
"""

from __future__ import annotations

from pathlib import Path

# Mistral's special ids as its config.json gives them (bos 1, eos 2); the
# instruction markers are ordinary words of the vocabulary here.
SPECIALS = ("<unk>", "<s>", "</s>", "[INST]", "[/INST]")
FIRST_WORD_ID = len(SPECIALS)


def word(token_id: int) -> str:
    return SPECIALS[token_id] if token_id < FIRST_WORD_ID else f"w{token_id}"


def write_tokenizer(path: Path, vocab_size: int) -> None:
    from tokenizers import AddedToken, Tokenizer, models, pre_tokenizers

    vocab = {word(i): i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab, unk_token=SPECIALS[0]))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    # Found in the raw text before it is split, so "<s>[INST] w7" needs no
    # space after "<s>". Not marked special: decoding keeps them, so even
    # these ids stream as text.
    tok.add_tokens([AddedToken(t, normalized=False) for t in SPECIALS[1:]])
    probe = [7, vocab_size - 1]
    got = tok.encode(chat_text(prompt_text(probe)), add_special_tokens=False)
    if got.ids != chat_ids(probe):
        raise RuntimeError(
            f"tokenizer encodes the chat template as {got.ids}, the "
            f"reference expects {chat_ids(probe)}"
        )
    tok.save(str(path))


def prompt_text(ids: list[int]) -> str:
    return " ".join(word(i) for i in ids)


def chat_text(user: str) -> str:
    """Mistral's instruction template for one user turn, as published (and
    as ``cake_tpu/models/llama/chat.py`` renders it)."""
    return f"<s>[INST] {user} [/INST]"


def chat_ids(prompt_ids: list[int]) -> list[int]:
    """The ids the server's tokenizer makes of ``chat_text(prompt_text(.))``."""
    return [1, 3, *prompt_ids, 4]


TEMPLATE_TOKENS = len(chat_ids([]))


def ids_from_text(text: str) -> list[int]:
    """Served ids from streamed text; raises on a word not of the vocabulary."""
    lookup = {s: i for i, s in enumerate(SPECIALS)}
    out = []
    for w in text.split():
        if w in lookup:
            out.append(lookup[w])
        elif w[0] == "w" and w[1:].isdigit():
            out.append(int(w[1:]))
        else:
            raise ValueError(f"streamed word {w!r} is not in the vocabulary")
    return out
