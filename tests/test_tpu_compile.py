"""Compile the kernels of the served path for a described TPU v5e.

Nothing runs: each case lowers and compiles for one chip of a ``v5e:2x2``
topology that the TPU compiler describes without a chip attached, at the
widths of the served qwen2-1.5b-wide LM (d_model 1536, 12 heads of 128,
MLP 6144, vocab 151,936), and the routed experts at Mellum2's (d_model
2304, 16 held experts 896 wide, top 8 of 64).  A case passes when the TPU compiler accepts the
program and a Pallas kernel (``tpu_custom_call``) is in it — what interpret
mode on the CPU cannot show: block shapes off the (8, 128) tiling and
kernels over their VMEM limit are refused here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backends import registry as R
from repro.core.ir import Node, OpKind, TensorSpec, input_node
from repro.frontends import nn
from repro.frontends.optimize import optimize
from repro.kernels.decode_attention.kernel import decode_attention_call
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.kernel import flash_attention_call
from repro.kernels.matmul.kernel import matmul_call
from repro.kernels.rglru_scan.kernel import rglru_scan_call
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_call

D, H, HD, MLP, VOCAB = 1536, 12, 128, 6144, 151936


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        s) for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("m,k,n", [(2048, D, MLP), (2048, D, VOCAB)])
def test_matmul_compiles(one_chip, m, k, n):
    _compile(lambda x, w: matmul_call(x, w, block=(512, 256, 512)),
             one_chip, _s((m, k)), _s((k, n)))


@pytest.mark.parametrize("rows", [8, 4096], ids=["decode8", "prefill8x512"])
def test_moe_gmm_compiles(one_chip, rows):
    """Every row tile the election may pin at the cell's largest decode
    and prefill buckets, routing included, and the XLA candidate."""
    from repro.kernels.moe.ops import moe, moe_tune_space
    node = Node(OpKind.MOE, [input_node((rows, 2304)),
                             input_node((2304, 64)),
                             input_node((16, 2304, 896)),
                             input_node((16, 2304, 896)),
                             input_node((16, 896, 2304))],
                TensorSpec((rows, 2304)), attrs={"top_k": 8})
    from repro.kernels.moe.ref import moe_ref
    shapes = (_s((rows, 2304)), _s((2304, 64)), _s((16, 2304, 896)),
              _s((16, 2304, 896)), _s((16, 896, 2304)))
    for (tm,) in moe_tune_space(node, None):
        _compile(lambda x, r, g, u, dn: moe(x, r, g, u, dn, top_k=8, tm=tm),
                 one_chip, *shapes)
    # the XLA candidate: ragged_dot lowers to the TPU's own grouped matmul
    _compile(lambda *a: moe_ref(*a, top_k=8), one_chip, *shapes)


def test_flash_attention_compiles(one_chip):
    _compile(flash_attention_call, one_chip,
             _s((4, H, 512, HD)), _s((4, H, 512, HD)), _s((4, H, 512, HD)))


def _attention_node(s):
    q = input_node((1, s, 1, HD))
    return Node(OpKind.ATTENTION, [q, q, q], TensorSpec((1, s, 1, HD)))


def test_flash_attention_compiles_at_largest_admitted_seq(one_chip):
    """The longest power-of-two sequence the ``supports`` predicate admits
    compiles under the kernels' VMEM limit; the next one elects the
    reference instead."""
    s = 512
    while flash_ops._supports(_attention_node(2 * s)):
        s *= 2
    assert s >= 8192
    _compile(flash_attention_call, one_chip,
             _s((1, 1, s, HD)), _s((1, 1, s, HD)), _s((1, 1, s, HD)))


def test_decode_attention_compiles(one_chip):
    b, s = 4, 512
    _compile(decode_attention_call, one_chip,
             _s((b, H, HD)), _s((b, H, s, HD)), _s((b, H, s, HD)),
             _s((b, H, HD)), _s((b, H, HD)), _s((b,), jnp.int32))


def test_rglru_scan_compiles(one_chip):
    _compile(rglru_scan_call, one_chip,
             _s((2, 512, D)), _s((2, 512, D)), _s((2, D)))


def test_rwkv6_scan_compiles(one_chip):
    h, hd = D // 64, 64
    seq = _s((1, 512, h, hd))
    _compile(lambda *a: rwkv6_scan_call(*a, bt=128), one_chip,
             seq, seq, seq, seq, _s((h, hd)), _s((1, h, hd, hd)))


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "grad"])
def test_transformer_block_graph_compiles(topo, one_chip, grad):
    """The elected graph ``optimize`` builds for the chip, forward and
    ``jax.grad`` through the elected backward kernels."""
    model = nn.transformer_block(D, H)
    backend = R.tpu_backend(topo.devices[0].device_kind)
    sol = optimize(model, (2, 512, D), backend=backend, training=grad)
    sd = model.state_dict()
    params = {k: _s(sd[k].shape, sd[k].dtype) for k in sol.graph.params}
    fn = sol._fn
    if grad:
        fn = jax.grad(lambda p, x: jnp.sum(sol._fn(p, x) ** 2))
    _compile(fn, one_chip, params, _s((2, 512, D)))


@pytest.mark.parametrize("b,s,d", [(4, 512, D), (8, 512, 2304)],
                         ids=["qwen4x512", "mellum8x512"])
def test_prefill_staging_unpacks_without_temporaries(one_chip, b, s, d):
    """The largest prefill's staged inputs, its rows and int32 last
    positions in one packed buffer, unpack by same-width bitcasts: a byte
    buffer of this layout needed 1.6 GB of temporaries and compiled for
    minutes."""
    from repro.runtime import packed
    rows = jax.ShapeDtypeStruct((b, s, d), jnp.float32)
    last = jax.ShapeDtypeStruct((b,), jnp.int32)
    layout = ((rows.shape, "float32", 0),
              (last.shape, "int32", b * s * d * 4))
    buf = jax.ShapeDtypeStruct((b * s * d + b,), jnp.uint32,
                               sharding=one_chip)
    compiled = packed._unpack_jit(layout).lower(buf).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
