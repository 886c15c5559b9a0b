"""A tokenizer that carries token ids through the OpenAI text surface.

`build_openai_app(tokenizer=...)` takes any object with encode/decode. The
default byte tokenizer reaches 256 ids of the model's 32768 and folds
generated ids into bytes that may not decode, so a frame carries 0..n tokens.
This one writes a token as its decimal id and a space: a prompt of n chosen
ids is n tokens, and every generated token is one text delta, so the client
counts tokens exactly."""

from __future__ import annotations


class IdTokenizer:
    def encode(self, text: str) -> list[int]:
        return [int(t) for t in text.split()]

    def decode(self, ids: list[int]) -> str:
        return "".join(f"{int(i)} " for i in ids)
