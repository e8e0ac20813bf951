"""Fixtures of the benchmark's own tests."""
import pytest

from benchkit import Checkout


@pytest.fixture
def fast_autotune(monkeypatch):
    """Record a timing for every admissible impl of a node without running
    it: the harness's warm-up path, minus the chip's timings."""
    from repro.backends import registry as R
    from repro.core import autotune as AT
    from repro.launch import serve

    def record(node, backend, cache, *, warmup, iters):
        impls = R.candidates(backend, node)
        for i, impl in enumerate(impls):
            cache.record(node.op.value, AT.node_shape(node), node.spec.dtype,
                         backend.cache_name, impl.name, 1.0 + i)
        return len(impls)
    monkeypatch.setattr(serve, "_measure_node", record)
    saved = AT.get_cache()
    yield
    AT.set_cache(saved)


@pytest.fixture
def checkout(tmp_path):
    return Checkout(tmp_path / "checkout")
