"""Sharded-mesh serving: elected graphs under shard_map.

Three layers of coverage:

* **Parity** (subprocess, 8 forced host devices): the prefill, decode and
  plain-forward programs compiled on a 2×2 (data, model) mesh must match
  the single-device compile on shared weights at 1e-5 — TP column/row
  sharding, the psum at every row-parallel matmul, head-local attention
  and the KV-sharded decode caches all have to agree bit-for-bit-ish.
* **Per-shard autotune keys** (single device, hypothesis property): a
  measurement recorded under a mesh-tagged backend key
  (``Backend.cache_name`` = ``name@tag``) must NEVER be visible to a
  global-shape lookup under the plain backend name, and vice versa — a
  per-shard local shape divided out of a pow2 global shape lands in some
  other global bucket, so without the tag the nearest-bucket fallback
  would happily serve a flat-backend timing to a mesh election.
* **Provenance on the mesh** (subprocess): a strict-provenance SolServer
  on the mesh warms per-shard shapes, serves, and reports every
  served-kind election as 'measured' with zero exact-bucket violations.

The test process itself keeps 1 device (conftest pins JAX_PLATFORMS=cpu);
only the child processes force more, mirroring tests/test_moe_spmd.py.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

from _hypo import hypothesis, st

_ENV_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD_PARITY = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from repro.frontends.optimize import compile_graph, optimize
from repro.frontends.extract import extract_prefill, extract_decode
from repro.launch.serve import ServeConfig, build_lm
from repro.launch.mesh import make_debug_mesh

cfg = ServeConfig(d_model=32, n_heads=2, n_layers=1, vocab=64, max_seq=32,
                  max_batch=4, slots=4)
m = build_lm(cfg)
rng = np.random.default_rng(0)
mesh = make_debug_mesh(data=2, model=2)

# plain forward
x = rng.standard_normal((2, 8, 32)).astype("float32")
ref = optimize(m, (2, 8, 32))(x)
shr = optimize(m, (2, 8, 32), mesh=mesh)(x)
d = float(np.max(np.abs(np.asarray(ref) - np.asarray(shr))))
assert d < 1e-5, f"forward diverged: {d}"
print("FORWARD PARITY OK", d)

# prefill: each entry's last-position logits AND the kv rows that seed the
# cache slots; the positions are data-sharded with the batch
last = np.array([7, 3], np.int32)
ref = compile_graph(m, extract_prefill(m, (2, 8, 32)), "xla")(x, last)
shr = compile_graph(m, extract_prefill(m, (2, 8, 32)), "xla",
                    mesh=mesh)(x, last)
for i, (a, b) in enumerate(zip(ref, shr)):
    d = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
    assert d < 1e-5, f"prefill out[{i}] diverged: {d}"
print("PREFILL PARITY OK")

# decode: ragged lens, KV-sharded caches
gd = extract_decode(m, 2, 16, 32)
kv_shapes = [tuple(n.spec.shape) for n in gd.inputs[2:]]
xd = rng.standard_normal((2, 1, 32)).astype("float32")
lens = np.array([5, 9], np.int32)
caches = [rng.standard_normal(s).astype("float32") * 0.5 for s in kv_shapes]
ref = compile_graph(m, extract_decode(m, 2, 16, 32), "xla")(xd, lens, *caches)
shr = compile_graph(m, extract_decode(m, 2, 16, 32), "xla",
                    mesh=mesh)(xd, lens, *caches)
for i, (a, b) in enumerate(zip(ref, shr)):
    d = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
    assert d < 1e-5, f"decode out[{i}] diverged: {d}"
print("DECODE PARITY OK")
"""

_CHILD_SERVE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from repro.core import autotune as AT
from repro.core.ir import OpKind
from repro.launch.serve import (SERVED_KINDS, ServeConfig, SolServer,
                                _smoke_workload)

AT.set_cache(AT.AutotuneCache())
cfg = ServeConfig(d_model=32, n_heads=2, n_layers=1, vocab=64, max_seq=32,
                  max_batch=4, slots=4, mesh=(2, 2))
server = SolServer(cfg, strict_provenance=True)
for p, g in _smoke_workload(cfg, 4, 4):
    server.submit(p, g)
counts = server.warm_autotune()
assert counts["nodes"] > 0 and counts["impls"] > 0, counts
s = server.run()
assert s["tokens"] > 0 and s["mesh"] == [2, 2], s

served = {k.value for k in SERVED_KINDS}
for key, sol in server._models.items():
    # the autotune keys this model elected from carry the mesh tag
    assert sol.backend.cache_name == "xla@data2model2", sol.backend.cache_name
    prov = sol.impl_report(provenance=True)
    for kind, impls in sol.impl_report(by_kind=True).items():
        if kind not in served:
            continue
        for name in impls:
            srcs = prov[name]["sources"]
            assert srcs and set(srcs) <= {"measured", "pinned"}, (
                key, kind, name, srcs)
    assert not server._exact_bucket_violations(sol), key

# elections keyed on PER-SHARD shapes: the decode q/k/v projections are
# head-local (H*hd/model = 32/2 = 16 features), not global
dk = next(k for k in server._models if k[0] == "decode")
g = server._models[dk].graph
mm_out = [n.spec.shape[-1] for n in g.topo() if n.op is OpKind.MATMUL]
assert 16 in mm_out, mm_out
# ...and the batch dim is data-split: bucket batch / 2 locally
assert all(n.spec.shape[0] == dk[1] // 2 for n in g.topo()
           if n.op is OpKind.DECODE_ATTENTION), dk
server.close()
print("MESH SERVE PROVENANCE OK")
"""


def _run_child(src: str) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _ENV_SRC
    r = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, (f"stdout:\n{r.stdout}\n"
                               f"stderr:\n{r.stderr[-2000:]}")
    return r.stdout


@pytest.mark.slow
def test_mesh_parity_prefill_decode():
    out = _run_child(_CHILD_PARITY)
    assert "FORWARD PARITY OK" in out
    assert "PREFILL PARITY OK" in out
    assert "DECODE PARITY OK" in out


@pytest.mark.slow
def test_mesh_serving_strict_provenance():
    out = _run_child(_CHILD_SERVE)
    assert "MESH SERVE PROVENANCE OK" in out


# ---------------------------------------------------------------------------
# single-device: mesh validation + per-shard cache keys
# ---------------------------------------------------------------------------

def test_make_debug_mesh_validates_device_count():
    """A short device slice must raise with the XLA_FLAGS hint, never build
    a silently smaller mesh (satellite fix)."""
    import jax

    from repro.launch.mesh import make_debug_mesh
    have = len(jax.devices())
    with pytest.raises(RuntimeError, match="XLA_FLAGS"):
        make_debug_mesh(data=have + 1, model=1)


def test_mesh_backend_tags_cache_key():
    import jax

    from repro.backends import get_backend
    from repro.distributed.sharding import mesh_backend
    from repro.launch.mesh import make_debug_mesh
    bk = get_backend("xla")
    assert bk.cache_name == bk.name            # single device: unchanged
    mesh = make_debug_mesh(data=1, model=1)
    mk = mesh_backend(bk, mesh)
    assert mk.name == bk.name                  # dispatch matching unchanged
    assert mk.cache_name == "xla@data1model1"  # cache keys qualified


def test_per_shard_keys_never_hit_global_entries():
    """The collision the tag exists to prevent, concretely: a (64,) local
    shape divided out of a (128,) global shape IS the (64,) global bucket;
    with the tag, neither direction of lookup crosses over — not even via
    the nearest-bucket fallback."""
    from repro.backends import get_backend
    from repro.core import autotune as AT
    bk = get_backend("xla")
    mk = dataclasses.replace(bk, shard_tag="data2model2")
    cache = AT.AutotuneCache()
    cache.record("linear", (8, 64, 64), "float32", mk.cache_name,
                 "pallas.matmul", 5.0)
    cache.record("linear", (8, 64, 64), "float32", bk.cache_name,
                 "xla.linear", 9.0)
    shard_hits = cache.lookup("linear", (8, 64, 64), "float32",
                              mk.cache_name)
    global_hits = cache.lookup("linear", (8, 64, 64), "float32",
                               bk.cache_name)
    assert set(shard_hits) == {"pallas.matmul"}
    assert set(global_hits) == {"xla.linear"}
    # nearest-bucket fallback also stays within the tagged keyspace
    assert set(cache.lookup("linear", (4, 64, 64), "float32",
                            mk.cache_name)) == {"pallas.matmul"}
    assert not cache.lookup("attention", (8, 64, 64), "float32",
                            bk.cache_name)


@hypothesis.given(
    op=st.sampled_from(["linear", "matmul", "attention",
                        "decode_attention"]),
    shape=st.lists(st.integers(1, 1024), min_size=1, max_size=4),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    data=st.integers(1, 16),
    model=st.integers(1, 16),
)
@hypothesis.settings(max_examples=80, deadline=None)
def test_hypothesis_per_shard_and_global_keys_disjoint(op, shape, dtype,
                                                       data, model):
    """Property: for ANY op/shape/dtype and ANY mesh factorization, an
    entry recorded under the mesh-tagged backend key is invisible to the
    untagged lookup and vice versa — per-shard bucket keys cannot collide
    with global-shape keys by construction (distinct backend component),
    independent of how local shapes alias global pow2 buckets."""
    if (data, model) == (1, 1):
        return                                  # no tag — nothing to test
    from repro.backends import get_backend
    from repro.core import autotune as AT
    bk = get_backend("xla")
    mk = dataclasses.replace(bk, shard_tag=f"data{data}model{model}")
    assert mk.cache_name != bk.cache_name
    shape = tuple(shape)
    cache = AT.AutotuneCache()
    cache.record(op, shape, dtype, mk.cache_name, "impl.shard", 1.0)
    assert not cache.lookup(op, shape, dtype, bk.cache_name)
    assert not cache.has_bucket(op, shape, dtype, bk.cache_name)
    # the mirror direction: global entries stay invisible to shard lookups
    cache2 = AT.AutotuneCache()
    cache2.record(op, shape, dtype, bk.cache_name, "impl.global", 1.0)
    assert not cache2.lookup(op, shape, dtype, mk.cache_name)
    assert not cache2.has_bucket(op, shape, dtype, mk.cache_name)
