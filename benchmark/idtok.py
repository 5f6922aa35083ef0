"""A tokenizer that hides nothing: text is the ids, written out.

``"17 4052 9 "`` is three tokens.  The benchmark hands it to the server so
that (a) a prompt's length in tokens is exactly what the traffic file says,
which is what decides the admission bucket, and (b) the client reads back,
through the served HTTP stream itself, every token id the timed path
produced — which the plain reference needs (the vendored vocabulary decodes
about 4% of a random-weight model's ids).  Standard library only; the load
generator's child writes and reads the same format without importing this.
"""

from __future__ import annotations

from typing import List, Sequence

BOS_ID = 1
EOS_ID = 2


class IdTokenizer:
    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size
        self.bos_id = BOS_ID
        self.eos_id = EOS_ID

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = parse_ids(text)
        if any(i < 0 or i >= self.vocab_size for i in ids):
            raise ValueError("token id outside the vocabulary")
        return ([self.bos_id] + ids) if add_bos else ids

    def decode(self, ids: Sequence[int]) -> str:
        return render_ids(ids)


def parse_ids(text: str) -> List[int]:
    return [int(p) for p in text.split()]


def render_ids(ids: Sequence[int]) -> str:
    return "".join(f"{int(i)} " for i in ids)
