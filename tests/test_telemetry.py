"""The program's own spans, counters and scopes: the ``sol.*`` span tree of
``SolServer.step`` with its request ids, the padding, transfer and compile
counters in ``SolServer.stats``, the bounded recorder, the annotations in a
profiler session, and the ``op:impl`` scope of every graph node."""
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro.core import autotune as AT
from repro.core.executor import _impl_for
from repro.core.ir import OpKind
from repro.frontends.offload import device
from repro.frontends.optimize import SolModel, optimize
from repro.launch.serve import ServeConfig, SolServer, build_lm
from repro.runtime import packed, telemetry

CHILDREN = {
    None: {"sol.step"},
    "sol.step": {"sol.admit", "sol.arena.sync", "sol.prefill",
                 "sol.decode", "sol.sample"},
    "sol.prefill": {"sol.gather", "sol.stage", "sol.forward", "sol.fetch",
                    "sol.kv_write"},
    "sol.stage": {"sol.stage.pack", "sol.stage.put"},
    "sol.forward": {"sol.compile"},
}
# prefill gathers its prompt rows on the host and stages them; decode
# stages its slot ids first and gathers its caches on the device from them
PROGRAM = {"sol.prefill": ["sol.gather", "sol.stage", "sol.forward",
                           "sol.fetch", "sol.kv_write"],
           "sol.decode": ["sol.stage", "sol.gather", "sol.forward",
                          "sol.fetch", "sol.kv_write"]}
CHILDREN["sol.decode"] = CHILDREN["sol.prefill"]


@pytest.fixture(autouse=True)
def _native_mode_and_local_cache():
    device.set("cpu", 0, mode="native")
    prev = AT.get_cache()
    AT.set_cache(AT.AutotuneCache())
    yield
    AT.set_cache(prev)


def _server(**kw):
    base = dict(d_model=32, n_heads=2, n_layers=1, vocab=64, max_seq=32,
                max_batch=2, slots=3, backend="xla")
    base.update(kw)
    return SolServer(ServeConfig(**base))


def _serve(server, prompts, max_new=3):
    """Serve to the end; the spans recorded meanwhile and each step's
    returned rids."""
    t0 = time.perf_counter()
    reqs = [server.submit(p, max_new) for p in prompts]
    served = []
    while server.depth:
        served.append(server.step())
    recs = telemetry.spans()
    first = next(i for i, r in enumerate(recs) if r[1] >= t0)
    return reqs, served, recs, first


@pytest.fixture(scope="module")
def served():
    device.set("cpu", 0, mode="native")
    prev = AT.get_cache()
    AT.set_cache(AT.AutotuneCache())
    server = _server()
    forward = SolModel.forward
    outs = []

    def counting(model, *xs):
        y = forward(model, *xs)
        outs.append(int((y[0] if isinstance(y, tuple) else y).nbytes))
        return y
    SolModel.forward = counting
    try:
        prompts = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10, 11, 12, 13, 14, 15, 16],
                   [3, 3, 3]]
        reqs, steps, recs, first = _serve(server, prompts)
    finally:
        SolModel.forward = forward
    yield server, reqs, steps, recs, first, outs
    server.close()
    AT.set_cache(prev)


def test_span_tree_and_parents(served):
    _, _, steps, recs, first, _ = served
    mine = range(first, len(recs))
    for i in mine:
        name, t0, t1, parent, _ = recs[i]
        assert t1 is not None and t1 >= t0
        pname = recs[parent][0] if parent is not None else None
        assert name in CHILDREN[pname], (name, pname)
        if parent is not None:
            assert recs[parent][1] <= t0 and t1 <= recs[parent][2]
    kids = {}
    for i in mine:
        kids.setdefault(recs[i][3], []).append(recs[i][0])
    step_ix = [i for i in mine if recs[i][0] == "sol.step"]
    assert len(step_ix) == len(steps)
    for i in step_ix:
        names = kids[i]
        assert names[-1] == "sol.sample" and "sol.arena.sync" in names
        for j in (j for j in mine if recs[j][3] == i
                  and recs[j][0] in ("sol.prefill", "sol.decode")):
            want = PROGRAM[recs[j][0]]
            assert [n for n in kids[j] if n in want] == want


def test_span_rids_are_the_served_rids(served):
    _, _, steps, recs, first, _ = served
    step_ix = [i for i in range(first, len(recs))
               if recs[i][0] == "sol.step"]
    for i, rids in zip(step_ix, steps):
        want = " ".join(map(str, rids))
        assert recs[i][4]["rids"] == want
        assert recs[i][4]["step"] == step_ix.index(i) + 1
        kids = [r for r in recs[first:] if r[3] == i]
        prog = [r[4]["rids"] for r in kids
                if r[0] in ("sol.prefill", "sol.decode")]
        sample = [r[4]["rids"] for r in kids if r[0] == "sol.sample"]
        assert sorted(" ".join(prog).split(), key=int) == \
            sorted(want.split(), key=int)
        assert sorted(sample[0].split(), key=int) == \
            sorted(want.split(), key=int)


def test_padding_and_counters_by_hand(served):
    server, reqs, _, recs, first, outs = served
    plen = {r.rid: len(r.prompt) for r in reqs}
    pos = real = rows = residents = 0
    for name, _, _, _, a in recs[first:]:
        if name not in ("sol.prefill", "sol.decode"):
            continue
        b, s = (int(v) for v in a["bucket"].split("x"))
        rids = [int(x) for x in str(a["rids"]).split()]
        if name == "sol.prefill":
            assert a["real"] == sum(plen[r] for r in rids)
            assert a["padded"] == b * s - a["real"]
            pos, real = pos + b * s, real + a["real"]
        else:
            assert a["real"] == len(rids)
            assert a["padded"] == b - len(rids)
            rows, residents = rows + b, residents + len(rids)
    st = server.stats
    assert (st["prefill_positions"], st["prefill_real"]) == (pos, real)
    assert (st["decode_rows"], st["decode_real"]) == (rows, residents)
    assert st["prefill_real"] == sum(plen.values())
    assert st["prefill_positions"] > st["prefill_real"]      # pow2 padding
    # every program's logits came to the host, and were counted once; the
    # cache outputs stayed on the device
    assert st["d2h_bytes"] == sum(outs) > 0
    assert st["d2h_bytes"] == sum(r[4]["bytes"] for r in recs[first:]
                                  if r[0] == "sol.fetch")
    # one compile per bucket opened, timed by its span
    compiles = [r for r in recs[first:] if r[0] == "sol.compile"]
    assert st["compiles"] == len(server._models) == len(compiles)
    assert st["compile_s"] == pytest.approx(sum(r[2] - r[1]
                                                for r in compiles))
    assert {(r[4]["program"], r[4]["bucket"]) for r in compiles} == \
        {(k[0], f"{k[1]}x{k[2]}") for k in server._models}
    for r in reqs:
        assert r.submitted <= r.admitted_time <= r.first_token_time


def test_recorder_is_bounded_and_keeps_parents():
    with telemetry.span("outer"):
        with telemetry.span("inner", n=1) as sp:
            sp.attrs["late"] = "record only"
    recs = telemetry.spans()
    assert recs[-1][0] == "inner" and recs[-1][3] == len(recs) - 2
    assert recs[-1][4] == {"n": 1, "late": "record only"}
    assert recs[-2][0] == "outer" and recs[-2][3] is None
    with telemetry.span("top"):
        for _ in range(telemetry.MAX_SPANS + 5):
            with telemetry.span("leaf"):
                pass
    recs = telemetry.spans()
    assert len(recs) == telemetry.MAX_SPANS
    # the parent left the deque: no index, never a wrong one
    assert recs[-1][0] == "leaf" and recs[-1][3] is None


def test_spans_are_annotations_in_a_profiler_session(tmp_path):
    server = _server()
    server.submit([1, 2, 3], 3)
    server.step()                   # both buckets compile outside the trace
    server.step()
    with jax.profiler.trace(str(tmp_path)):
        rids = server.step()
    server.close()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path[-1])
    steps = [dict(e.stats) for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events if e.name == "sol.step"]
    assert len(steps) == 1
    assert str(steps[0]["rids"]) == " ".join(map(str, rids))
    names = {e.name for p in pd.planes if p.name == "/host:CPU"
             for line in p.lines for e in line.events}
    assert {"sol.arena.sync", "sol.decode", "sol.stage", "sol.forward",
            "sol.fetch", "sol.sample"} <= names


def test_every_node_carries_its_op_impl_scope():
    cfg = ServeConfig(d_model=32, n_heads=2, n_layers=2, vocab=64)
    model = optimize(build_lm(cfg), (1, 8, 32), backend="xla")
    text = model._fn.lower(model._params_for_call(),
                           np.zeros((1, 8, 32), np.float32)
                           ).as_text(debug_info=True)
    found = set(re.findall(r'loc\("jit\(fn\)/([^/"]+)/', text))
    want = {f"{n.op.value}:{_impl_for(n, model.backend).name}"
            for n in model.graph.topo()
            if n.op not in (OpKind.INPUT, OpKind.PARAM, OpKind.CONST,
                            OpKind.OUTPUT)}
    assert want and want <= found
    layout = ((((4, 8), "float32", 0), ((4,), "int32", 128)))
    unpack = packed._unpack_jit(layout).lower(
        np.zeros(256, np.uint8)).as_text(debug_info=True)
    assert "sol.unpack" in unpack
