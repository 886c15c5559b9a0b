"""Reaches the engine that the serving app builds, from outside.

The OpenAI replica constructs its engine itself, with weights from seed 0
made eagerly. The benchmark needs weights from `--seed`, made in one jitted
call, and the engine object to wrap. Both come from one wrapper around the
engine class's `__init__` (the family module names the class), put on from
here and taken off again; the engine is not edited. The check of every run
wraps two of the engine's bound callables, `_prefill` and `_decode`, to keep
the logits the engine samples from. Those two names and `__init__`'s
`(config, params)` are the seams the yardstick still stands on: where a
later PR renames one the command fails and names it, because a yardstick
that went missing is not a result. Spans need no wrapper: the engine records
its own admissions and decode steps (`harness/engine_records.py`), so the
loop's methods are free to be renamed, split or merged. Sampling on the
device (ROADMAP S4) will take the logits away from the host; the PR that does
it has to bring a seam of the program's own, such as log-probabilities
through the API, for the check to stand on.
"""

from __future__ import annotations

import contextlib


class EngineTap:
    def __init__(self, make_params):
        self.make_params = make_params
        self.engine = None
        self.captured: list = []     # ("prefill"|"decode", inputs, logits)
        self._undo: list = []

    @contextlib.contextmanager
    def constructing(self, cls):
        """While this is open, an engine of class `cls` built without `params`
        gets the seeded ones, and the engine built is kept in `self.engine`."""
        orig, tap = cls.__init__, self

        def init(engine, config=None, params=None, *args, **kwargs):
            if params is None:
                params = tap.make_params(config.model_config)
            orig(engine, config, params, *args, **kwargs)
            tap.engine = engine

        cls.__init__ = init
        try:
            yield self
        finally:
            cls.__init__ = orig

    def _wrap(self, name: str, make) -> None:
        orig = getattr(self.engine, name, None)
        if not callable(orig):
            raise SystemExit(
                f"benchmark: {type(self.engine).__name__} has no callable {name!r}; "
                f"the check wraps it (benchmarks/harness/engine_tap.py)")
        setattr(self.engine, name, make(orig))
        self._undo.append((name, orig))

    def unwrap(self) -> None:
        while self._undo:
            name, orig = self._undo.pop()
            setattr(self.engine, name, orig)

    def capture_logits(self) -> None:
        """Keeps every prefill's and decode step's logits on the host, for
        the comparison with the reference (a few requests, outside the window)."""
        import numpy as np

        def prefill(orig):
            def f(params, pool, tokens, table, start_len):
                logits, pool = orig(params, pool, tokens, table, start_len)
                self.captured.append(("prefill", np.asarray(tokens)[0],
                                      np.asarray(logits)))
                return logits, pool
            return f

        def decode(orig):
            def f(params, pool, last_tokens, lengths, tables):
                logits, pool = orig(params, pool, last_tokens, lengths, tables)
                self.captured.append(("decode", np.flatnonzero(self.engine.active),
                                      np.asarray(logits)))
                return logits, pool
            return f

        self._wrap("_prefill", prefill)
        self._wrap("_decode", decode)
