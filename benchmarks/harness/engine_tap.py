"""Reaches the engine that the serving app builds, from outside.

The OpenAI replica constructs its engine itself, with weights from seed 0
made eagerly. The benchmark needs weights from `--seed`, made in one jitted
call, and the engine object to wrap. Both come from one wrapper around the
engine class's `__init__` (the family module names the class), put on from
here and taken off again; the engine is not edited. Spans and captures wrap
the engine's own bound callables: `_prefill` and `_decode` for the check of
every run, `_admit_one` and `_step_decode` for the spans of a traced one.
Those four names, and the layout of `_pending` (serve_cell._stop_engine),
are the seams the yardstick stands on. Where a later PR renames one the
command fails and names it: a yardstick that went missing is not a result.
"""

from __future__ import annotations

import contextlib
import time


class EngineTap:
    def __init__(self, make_params):
        self.make_params = make_params
        self.engine = None
        self.spans: list = []        # ("admit"|"decode", t0, t1, info dict)
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
                f"the check and the spans wrap it (benchmarks/harness/engine_tap.py)")
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

    def record_spans(self, annotate: bool) -> None:
        """Clocks each admission (allocator, batch-1 prefill, the host copy of
        its logits, sampling) and each decode step (device step, host copy,
        sampling) on `time.monotonic()`, and writes the same spans into the
        profiler's trace so that idle gaps can be named."""
        import jax

        engine, spans = self.engine, self.spans
        note = jax.profiler.TraceAnnotation if annotate else (
            lambda name: contextlib.nullcontext())

        def admit(orig):
            def f(prompt, max_new, fut, t_enq, tq, slot):
                t0 = time.monotonic()
                with note("bench:admit"):
                    out = orig(prompt, max_new, fut, t_enq, tq, slot)
                spans.append(("admit", t0, time.monotonic(),
                              {"id": int(prompt[0]), "prompt_len": len(prompt),
                               "t_enq": t_enq, "admitted": bool(out)}))
                return out
            return f

        def decode(orig):
            def f():
                live = engine.active.copy()
                info = {"context_tokens": int(engine.lengths[live].sum()),
                        "live": int(live.sum())}
                t0 = time.monotonic()
                with note("bench:decode"):
                    out = orig()
                if out:
                    spans.append(("decode", t0, time.monotonic(), info))
                return out
            return f

        self._wrap("_admit_one", admit)
        self._wrap("_step_decode", decode)
