"""One cell: set-up, the traffic, the measured window, the check.

``Cell.setup`` builds the framework model that the configuration's model
module (``bench/models/<lm.model>.py``) describes and the server, draws
the weights from the seed, loads (or on the first run measures and saves)
the autotune cache, and runs every bucket program the mix can open once
through the server itself.  ``Cell.serve`` drives the traffic: a lead-in, then the
window of ``seconds`` in which nothing compiles.  Everything a request saw
is kept per request on the host clock.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import model as model_mod
from . import traffic as traffic_mod
from . import weights
from .spans import Recorder
from .spec import Spec, lm_widths
from .trace import WINDOW_SPAN


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- compilations, counted by JAX's own monitoring events ---------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
# JAX's listeners are process-wide and cannot be taken back, so the count is
# too; a window reads the difference over itself
_compiles = {"n": 0, "listening": False}


def _listen_compiles() -> None:
    if _compiles["listening"]:
        return
    import jax

    def on_event(event, _duration, **_kw):
        if event in _COMPILE_EVENTS:
            _compiles["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    _compiles["listening"] = True


@dataclasses.dataclass
class Record:
    """One request as the benchmark saw it (host clock, seconds)."""
    req: traffic_mod.Request
    srv: object                       # the server's Request
    due: float
    tokens: List[float] = dataclasses.field(default_factory=list)
    logits: List = dataclasses.field(default_factory=list)  # per token
    first_step_start: Optional[float] = None   # start of its prefill step


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Record]
    steps: List[tuple]                # (start, end) of every step in window
    counters: Dict[str, float]        # deltas over the window
    compiles: int
    lead_in_s: float
    lead_in_tokens: int
    late_s: float                     # how late the generator ran, worst


class Cell:
    def __init__(self, spec: Spec, name: str, *, backend: str = "pallas_tpu"):
        self.spec = spec
        self.name = name
        self.cell = spec.cell(name)
        self.config = spec.config(self.cell["config"])
        self.mix = spec.traffic(self.cell["traffic"])
        self.lm = lm_widths(self.config)
        self.arch = spec.model(self.lm["model"])
        self.backend = backend
        self.server = None
        self.model = None

    # -- set-up -----------------------------------------------------------------

    def autotune_path(self, device_kind: str) -> Path:
        """The cell's autotune cache: one file per cell and device kind, so
        that a cell never finds another cell's buckets there."""
        kind = re.sub(r"[^A-Za-z0-9_.-]", "_", device_kind)
        return (self.spec.bench / ".cache" / "autotune"
                / f"{self.name}__{kind}.json")

    def tuned(self) -> bool:
        """Whether the checkout holds the cell's autotune cache yet."""
        return any((self.spec.bench / ".cache" / "autotune").glob(
            f"{self.name}__*.json"))

    def _build(self, seed: int) -> None:
        self.model = model_mod.build_seeded(self.arch, self.lm, seed)
        self.server = model_mod.server(self.arch, self.lm, self.mix["server"],
                                       self.model, self.embedding(seed),
                                       self.backend)

    def params(self, seed: int) -> Dict:
        """The seeded weights, drawn anew (for the reference)."""
        return weights.make_params(self.arch.weights(self.lm), seed)

    def embedding(self, seed: int):
        return model_mod.embedding(self.arch, self.lm, seed)

    def tune(self, seed: int, device_kind: str) -> None:
        """Measure and save the autotune cache, and nothing else.  A
        checkout's first run does this in a process of its own
        (``bench/tune.py``): a process that measured compiles other bucket
        programs than one that loads the saved cache, so the next run would
        compile them all again."""
        from repro.core import autotune as AT
        self._build(seed)
        path = self.autotune_path(device_kind)
        AT.set_cache(AT.AutotuneCache.load(str(path)))
        if not path.exists():
            self._autotune(path)
        self.free()

    def setup(self, seed: int, device_kind: str) -> Dict[str, float]:
        """Build, load weights, warm every bucket; returns phase times."""
        from repro.core import autotune as AT
        _listen_compiles()
        t = {}
        t0 = time.perf_counter()
        self._build(seed)
        t["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        path = self.autotune_path(device_kind)
        AT.set_cache(AT.AutotuneCache.load(str(path)))
        if not path.exists():
            self._autotune(path)
        self._warm(seed)
        t["programs_s"] = time.perf_counter() - t0
        return t

    def bucket_plan(self) -> List[tuple]:
        """``(batch, seq)`` of every bucket the mix can open, as a prefill
        bucket (its prompts) or a decode bucket (its caches: the prompt, up
        to the answer's last token but one).  Warming opens each as both."""
        from repro.core.autotune import ceil_pow2
        from repro.launch.serve import MIN_SEQ_BUCKET
        lim = self.mix["server"]
        lo = max(MIN_SEQ_BUCKET, ceil_pow2(traffic_mod.min_prompt(self.mix)))
        p_hi = ceil_pow2(self.mix["prompt_len"]["max"])
        d_hi = min(lim["max_seq"],
                   ceil_pow2(traffic_mod.max_total(self.mix) - 2))
        plan = []
        b = 1
        while b <= ceil_pow2(lim["max_batch"]):
            s = lo
            while s <= max(p_hi, d_hi):
                plan.append((b, s))
                s *= 2
            b *= 2
        return plan

    def _warm(self, seed: int) -> None:
        """Open every bucket once through the server: ``b`` requests of
        ``s - 2`` prompt tokens prefill at bucket ``(b, s)``, then decode
        one token at cache bucket ``s``."""
        rng = np.random.default_rng([seed & 0xFFFFFFFF, 7])
        srv = self.server
        for b, s in self.bucket_plan():
            for _ in range(b):
                srv.submit(rng.integers(0, self.lm["vocab"], s - 2,
                                        dtype=np.int32), 2)
            while srv.depth:
                srv.step()

    def _autotune(self, cache_path: Path) -> None:
        """Measure, on a checkout's first run and before any bucket is
        warmed, every served-kind node of the bucket programs this mix opens
        and no others, through the server's own measurement
        (``launch.serve._measure_node``, the loop of
        ``SolServer.warm_autotune``).  ``warm_autotune(max_len)`` would
        also measure every bucket from 8 rows up, which the mix never opens:
        for ``chat-poisson`` 90 nodes where the mix's buckets hold 50, and a
        first run cannot afford the difference at about 10 s a node on a
        v5e.  This call goes once ``warm_autotune`` takes a list of
        buckets."""
        from repro.core import autotune as AT
        from repro.core import passes
        from repro.frontends.extract import extract_decode, extract_prefill
        from repro.launch.serve import SERVED_KINDS, _measure_node
        t0 = time.perf_counter()
        srv, d = self.server, self.lm["d_model"]
        cache = AT.get_cache()
        nodes = impls = 0
        for b, s in self.bucket_plan():
            for g in (extract_prefill(self.model, (b, s, d)),
                      extract_decode(self.model, b, s, d)):
                g = passes.run_pipeline(g, srv.backend)
                for node in g.topo():
                    if node.op not in SERVED_KINDS or cache.has_bucket(
                            node.op.value, AT.node_shape(node),
                            node.spec.dtype, srv.backend.cache_name):
                        continue
                    nodes += 1
                    impls += _measure_node(node, srv.backend, cache,
                                           warmup=1, iters=3)
        cache.save(str(cache_path))
        log(f"[setup] autotune measured {impls} impls over {nodes} nodes in "
            f"{time.perf_counter() - t0:.1f}s; saved {cache_path}")

    # -- the traffic ------------------------------------------------------------

    def serve(self, seed: int, seconds: float, rec: Recorder,
              trace_dir: Optional[Path] = None) -> Window:
        from repro.runtime import packed
        srv = self.server
        records: Dict[int, Record] = {}
        steps: List[tuple] = []
        state = {"late": 0.0}
        snap: Dict[str, float] = {}

        def counters():
            out = {"h2d_bytes": packed.TRANSFER_STATS["bytes"],
                   "compiles": _compiles["n"]}
            if "d2h_bytes" in srv.stats:
                out["d2h_bytes"] = srv.stats["d2h_bytes"]
            return out

        def submit(r: traffic_mod.Request, due: float, now: float):
            state["late"] = max(state["late"], now - due)
            s = srv.submit(r.prompt, r.max_new)
            records[s.rid] = Record(r, s, due)

        def step():
            t_start = time.perf_counter()
            rids = srv.step()
            t_end = time.perf_counter()
            if rec.on:
                steps.append((t_start, t_end))
            for rid in rids:
                x = records.get(rid)
                if x is None:
                    continue
                if not x.tokens:
                    x.first_step_start = t_start
                x.tokens.append(t_end)
                # the row the server sampled this token from (a fresh
                # array per step: kept, not copied)
                x.logits.append(x.srv.last_logits)
            return rids

        def open_window():
            import jax
            snap.update(counters())
            rec.on = True
            if trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
            state["window"] = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            state["window"].__enter__()

        t_lead = time.perf_counter()
        if self.mix["loop"] == "open":
            reqs = traffic_mod.open_loop(self.mix, seed, seconds,
                                         self.lm["vocab"])
            lead = float(self.mix.get("lead_in_s", 0.0))
            w0 = t_lead + lead
            t1 = None
            i = 0
            while True:
                now = time.perf_counter()
                if t1 is None and now >= w0:
                    open_window()
                    t1 = w0 + seconds
                if t1 is not None and now >= t1:
                    break
                while i < len(reqs) and t_lead + reqs[i].due <= now:
                    submit(reqs[i], t_lead + reqs[i].due, now)
                    i += 1
                if srv.depth:
                    step()
                else:
                    nxt = t_lead + reqs[i].due if i < len(reqs) else np.inf
                    edge = w0 if t1 is None else t1
                    time.sleep(max(0.0, min(nxt, edge) - now))
            t0 = w0
        else:
            clients = traffic_mod.closed_loop(self.mix, seed,
                                              self.lm["vocab"])
            nxt_req = [0] * len(clients)
            send_at = [np.inf] * len(clients)
            # lead-in: each client's first request, prefilled one at a time
            for c, q in enumerate(clients):
                now = time.perf_counter()
                submit(q[0], now, now)
                nxt_req[c] = 1
                step()
            open_window()
            t0 = time.perf_counter()
            t1 = t0 + seconds
            seen_done = set()
            while True:
                now = time.perf_counter()
                if now >= t1:
                    break
                for c in range(len(clients)):
                    if send_at[c] <= now:
                        q = clients[c][nxt_req[c]]
                        submit(q, send_at[c], now)
                        nxt_req[c] += 1
                        send_at[c] = np.inf
                if srv.depth:
                    step()
                    for x in records.values():
                        if x.srv.done and x.srv.rid not in seen_done:
                            seen_done.add(x.srv.rid)
                            send_at[x.req.client] = (x.tokens[-1]
                                                     + x.req.think_s)
                else:
                    time.sleep(max(0.0, min(min(send_at), t1) - now))
        state["window"].__exit__(None, None, None)
        rec.on = False
        if trace_dir is not None:
            import jax
            jax.profiler.stop_trace()
        end = counters()
        lead_tokens = sum(1 for x in records.values() for t in x.tokens
                          if t < t0)
        return Window(t0=t0, t1=t1, records=list(records.values()),
                      steps=steps,
                      counters={k: end[k] - snap[k] for k in end},
                      compiles=end["compiles"] - snap["compiles"],
                      lead_in_s=t0 - t_lead, lead_in_tokens=lead_tokens,
                      late_s=state["late"])

    # -- after the window ---------------------------------------------------------

    def finished(self, w: Window) -> List[Record]:
        return [x for x in w.records if x.srv.done]

    def free(self) -> None:
        """Drop the program's state: server, compiled buckets, weights."""
        if self.server is not None:
            self.server.close()
        self.server = None
        self.model = None
        gc.collect()
