"""Spans around the calls into each layer, recorded from the benchmark's
own files.  Each span is a ``jax.profiler.TraceAnnotation`` too, so that in
a traced run it sits on the device trace's clock, and a host-clock record
that the per-layer readers use.

Wrapped calls (installed for one server, removed by ``uninstall``):

- ``bench.step``: ``SolServer.step``, the scheduler tick;
- ``bench.stage``: ``runtime.packed.stage_inputs`` / ``stage_batch``;
- ``bench.forward``: each call of a bucket program (``SolModel.forward``);
- ``bench.sample``: ``launch.serve.sample_token``, inside ``step``.

With ``sync`` (traced runs only) a bucket program's call waits for its
outputs inside its span, so that the span holds the program's device time
and the rest of a step is host time.  Runs that report end-to-end metrics
never wait there.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, List, Optional, Tuple

Span = Tuple[str, float, float, Optional[Any]]


class Recorder:
    def __init__(self, sync: bool = False):
        self.sync = sync
        self.spans: List[Span] = []
        self.on = False                 # record only inside the window
        self._undo: List[Callable[[], None]] = []

    @contextlib.contextmanager
    def span(self, name: str, meta: Any = None):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if self.on:
                    self.spans.append((name, t0, time.perf_counter(), meta))

    def _wrap(self, name: str, fn: Callable, meta_fn=None) -> Callable:
        def wrapped(*a, **k):
            with self.span(name, meta_fn(*a, **k) if meta_fn else None):
                return fn(*a, **k)
        return wrapped

    def _patch(self, obj, attr: str, new) -> None:
        old = getattr(obj, attr)
        own = attr in vars(obj)
        setattr(obj, attr, new)
        self._undo.append(lambda: setattr(obj, attr, old) if own
                          else delattr(obj, attr))

    def install(self, server) -> None:
        from repro.frontends.optimize import SolModel
        from repro.launch import serve as serve_mod
        from repro.runtime import packed
        self._patch(server, "step", self._wrap("bench.step", server.step))
        for fn in ("stage_inputs", "stage_batch"):
            self._patch(packed, fn, self._wrap("bench.stage",
                                               getattr(packed, fn)))
        self._patch(serve_mod, "sample_token",
                    self._wrap("bench.sample", serve_mod.sample_token))
        self._patch(SolModel, "forward", self._program(SolModel.forward))

    def _program(self, forward) -> Callable:
        import jax

        def call(model, *xs):
            with self.span("bench.forward"):
                out = forward(model, *xs)
                if self.sync:
                    jax.block_until_ready(out)
            return out
        return call

    def uninstall(self) -> None:
        while self._undo:
            self._undo.pop()()

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s[0] == name]
