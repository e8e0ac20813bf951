"""SOL device backends (Sec. IV of the paper).

The paper's point — that a backend is ≤3 kLOC because DFP codegen is shared
and only per-op 'flavours' differ — is realised here as a **per-op dispatch
table with capability-based fallback**.  A backend no longer carries static
``dfp_impl``/``dnn_impl`` strings; instead each (backend, OpKind) pair maps to
a list of :class:`Impl` entries and the executor resolves ``node → impl``
through a documented fallback chain:

  tier 0  backend-specific kernel   (``register_impl(backend, op, fn)``)
  tier 1  shared Pallas kernel      (``register_shared_impl`` — admitted only
                                     when the impl's ``requires`` capabilities
                                     are a subset of the backend's)
  tier 2  XLA/jnp reference         (``register_reference_impl`` — always
                                     available; registered by core.executor)

Adding a device backend therefore means: one ``register_backend`` call with a
:class:`HardwareSpec`, plus optional ``register_impl`` overrides — and **zero
edits to core.executor** (see ``backends/host_cpu.py`` for the proof).

Backends also keep the paper's per-device layout preferences (Linear weights
(out,in) on CPUs vs (in,out) on the long-vector machine; NCHW vs NHWC convs)
and the hardware constants the cost model / roofline uses.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..core.autotune import Tunable
from ..core.ir import Node, OpKind


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float        # FLOP/s per chip
    hbm_bandwidth: float          # bytes/s per chip
    ici_bandwidth: float          # bytes/s per link
    hbm_bytes: int                # capacity per chip
    vmem_bytes: int               # on-chip scratch
    mxu_dim: int = 128            # systolic array tile
    lanes: int = 128              # VPU lane count
    sublanes: int = 8

    # roofline terms — shared by the implementation-election pass
    # (core.passes), benchmarks/roofline.py and launch/dryrun.py
    def compute_s(self, flops: float) -> float:
        return flops / self.peak_flops_bf16

    def memory_s(self, nbytes: float) -> float:
        return nbytes / self.hbm_bandwidth

    def collective_s(self, nbytes: float) -> float:
        return nbytes / self.ici_bandwidth

    def roofline_s(self, flops: float, nbytes: float,
                   ici_bytes: float = 0.0) -> float:
        """Time lower bound: the dominant of compute / memory / interconnect."""
        return max(self.compute_s(flops), self.memory_s(nbytes),
                   self.collective_s(ici_bytes))


# Peaks per chip from Google Cloud's "TPU v5e" documentation: 197 TFLOP/s
# bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect (four links
# of 50 GB/s).  128 MiB is the physical VMEM; kernels compile under the
# smaller scoped budget ``kernels._util.VMEM_LIMIT_BYTES``.
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    hbm_bytes=16 * 1024 ** 3,
    vmem_bytes=128 * 1024 ** 2,
)

# The chips ``pallas_tpu`` knows, keyed by ``jax.Device.device_kind``.  A
# kind missing here is an error, never a silent v5e default.
TPU_SPECS: Dict[str, HardwareSpec] = {"TPU v5 lite": TPU_V5E}

HOST_CPU = HardwareSpec(
    name="host_cpu",
    peak_flops_bf16=0.2e12,
    hbm_bandwidth=40e9,
    ici_bandwidth=10e9,
    hbm_bytes=64 * 1024 ** 3,
    vmem_bytes=32 * 1024 ** 2,   # ~LLC slice; DFP cache-residency analogue
    mxu_dim=16,                  # AVX-512-ish tile, no systolic array
    lanes=16,
    sublanes=1,
)


# ---------------------------------------------------------------------------
# per-op implementations
# ---------------------------------------------------------------------------

# fn(node, vals, backend) -> Array; vals are the lowered inputs of the node
# (for FUSED nodes: the side inputs, in node.inputs order).
ImplFn = Callable[[Node, Sequence[Any], "Backend"], Any]

# grad_fn(node, res, ct, backend) -> tuple of cotangents, one per node input
# (entries for integer-dtype inputs are ignored by the executor, which
# substitutes float0 zeros).  ``res`` is the residual pair saved by the
# forward pass of the executor's ``jax.custom_vjp`` wrapper:
# ``(primal_inputs_tuple, primal_output)``.  Backward impls are free to
# recompute anything else they need from the primals (remat-style).
GradFn = Callable[[Node, Tuple[Tuple[Any, ...], Any], Any, "Backend"], Any]

TIER_BACKEND = 0      # backend-specific kernel
TIER_SHARED = 1       # shared Pallas kernel (capability-gated)
TIER_REFERENCE = 2    # XLA/jnp reference lowering


@dataclasses.dataclass(frozen=True)
class Impl:
    """One implementation 'flavour' of an op (the paper's per-device kernel
    choice, e.g. Listing 3's AveragePooling variants)."""

    name: str                                    # e.g. "pallas.dfp_fused"
    op: OpKind
    fn: ImplFn
    tier: int
    requires: frozenset = frozenset()            # backend capabilities needed
    supports: Optional[Callable[[Node], bool]] = None   # per-node capability
    backend: Optional[str] = None                # tier-0 owner; None = any
    # memory behaviour for the roofline cost model: 'streamed' impls touch
    # HBM once per input/output (depth-first); 'roundtrip' impls materialize
    # every intermediate (op-at-a-time composition).
    memory: str = "streamed"
    # tuning declaration (core.autotune.Tunable): candidate config space +
    # the node.attrs key measured winners are pinned under
    tunable: Optional[Tunable] = None

    def admissible(self, backend: "Backend", node: Node) -> bool:
        if self.backend is not None and self.backend != backend.name:
            return False    # another backend's private kernel
        if not self.requires <= backend.capabilities:
            return False
        if self.supports is not None and not self.supports(node):
            return False
        return True


_BACKEND_IMPLS: Dict[Tuple[str, OpKind], List[Impl]] = {}
_SHARED_IMPLS: Dict[OpKind, List[Impl]] = {}
_REFERENCE_IMPLS: Dict[OpKind, Impl] = {}
_IMPLS_BY_NAME: Dict[str, Impl] = {}

# backward (gradient) dispatch tables — same Impl dataclass, same tiers, same
# capability gating, but the stored ``fn`` follows the GradFn signature.  Kept
# as parallel tables (not a slot on the forward Impl) so a node's forward and
# backward elections are independent: the fastest forward kernel and the
# fastest backward kernel need not come from the same family member, and the
# autotune cache keys them separately (op key ``f"{op.value}_bwd"``).
_GRAD_BACKEND_IMPLS: Dict[Tuple[str, OpKind], List[Impl]] = {}
_GRAD_SHARED_IMPLS: Dict[OpKind, List[Impl]] = {}
_GRAD_REFERENCE_IMPLS: Dict[OpKind, Impl] = {}
_GRAD_IMPLS_BY_NAME: Dict[str, Impl] = {}


def _index(impl: Impl) -> Impl:
    _IMPLS_BY_NAME[impl.name] = impl
    return impl


def register_impl(backend: str, op: OpKind, fn: ImplFn, *,
                  name: Optional[str] = None,
                  supports: Optional[Callable[[Node], bool]] = None,
                  memory: str = "streamed",
                  tunable: Optional[Tunable] = None) -> Impl:
    """Register a backend-specific implementation (tier 0).  Newest wins
    within the tier, so a later registration overrides an earlier one."""
    impl = _index(Impl(name or f"{backend}.{op.value}", op, fn, TIER_BACKEND,
                       supports=supports, backend=backend, memory=memory,
                       tunable=tunable))
    _BACKEND_IMPLS.setdefault((backend, op), []).insert(0, impl)
    return impl


def register_shared_impl(op: OpKind, fn: ImplFn, *, name: str,
                         requires: Sequence[str] = (),
                         supports: Optional[Callable[[Node], bool]] = None,
                         memory: str = "streamed",
                         tunable: Optional[Tunable] = None) -> Impl:
    """Register a shared kernel (tier 1), admitted for any backend whose
    capabilities cover ``requires``."""
    impl = _index(Impl(name, op, fn, TIER_SHARED,
                       requires=frozenset(requires), supports=supports,
                       memory=memory, tunable=tunable))
    _SHARED_IMPLS.setdefault(op, []).insert(0, impl)
    return impl


def register_reference_impl(op: OpKind, fn: ImplFn, *,
                            name: Optional[str] = None,
                            memory: str = "streamed") -> Impl:
    """Register the always-available XLA/jnp reference (tier 2)."""
    impl = _index(Impl(name or f"ref.{op.value}", op, fn, TIER_REFERENCE,
                       memory=memory))
    _REFERENCE_IMPLS[op] = impl
    return impl


def get_impl(name: str) -> Optional[Impl]:
    _load_entry_points()
    return _IMPLS_BY_NAME.get(name)


_ENTRY_POINTS_STATE = "unloaded"     # unloaded | loading | loaded


def _load_entry_points() -> None:
    """Import the modules that populate the dispatch table: the executor's
    reference lowerings and the kernel families' entry points (each ops.py
    registers its own impls at import).  A failed import resets the state so
    the real error resurfaces on the next dispatch call instead of leaving a
    silently half-populated table."""
    global _ENTRY_POINTS_STATE
    if _ENTRY_POINTS_STATE != "unloaded":
        return
    _ENTRY_POINTS_STATE = "loading"
    try:
        from ..core import executor
        executor._register_reference_impls()
        from ..kernels.avgpool import ops as _a              # noqa: F401
        from ..kernels.decode_attention import ops as _da    # noqa: F401
        from ..kernels.dfp_fused import ops as _d            # noqa: F401
        from ..kernels.flash_attention import ops as _f      # noqa: F401
        from ..kernels.matmul import ops as _m               # noqa: F401
        from ..kernels.moe import ops as _moe                # noqa: F401
        from ..kernels.rglru_scan import ops as _g           # noqa: F401
        from ..kernels.rwkv6_scan import ops as _r           # noqa: F401
        # backward entry points (each grad.py registers its impls at import)
        from ..kernels.avgpool import grad as _ag            # noqa: F401
        from ..kernels.decode_attention import grad as _dcg  # noqa: F401
        from ..kernels.dfp_fused import grad as _dg          # noqa: F401
        from ..kernels.flash_attention import grad as _fg    # noqa: F401
        from ..kernels.matmul import grad as _mg             # noqa: F401
        from ..kernels.rglru_scan import grad as _gg         # noqa: F401
        from ..kernels.rwkv6_scan import grad as _rg         # noqa: F401
    except BaseException:
        _ENTRY_POINTS_STATE = "unloaded"
        raise
    _ENTRY_POINTS_STATE = "loaded"


def tunables_for(op: OpKind) -> List[Tunable]:
    """Every Tunable any impl (any backend, any tier) declares for ``op`` —
    the election pass clears all of them before pinning, so re-electing a
    graph on a backend where the tuned impl is inadmissible still drops the
    stale pin."""
    _load_entry_points()
    out: List[Tunable] = []
    for (_b, o), impls in _BACKEND_IMPLS.items():
        if o is op:
            out += [i.tunable for i in impls if i.tunable is not None]
    out += [i.tunable for i in _SHARED_IMPLS.get(op, ())
            if i.tunable is not None]
    return out


def candidates(backend: "Backend", node: Node) -> List[Impl]:
    """All admissible impls for (backend, node) in fallback-chain order:
    backend-specific → shared → reference."""
    _load_entry_points()
    out: List[Impl] = []
    for impl in _BACKEND_IMPLS.get((backend.name, node.op), []):
        if impl.admissible(backend, node):
            out.append(impl)
    for impl in _SHARED_IMPLS.get(node.op, []):
        if impl.admissible(backend, node):
            out.append(impl)
    ref = _REFERENCE_IMPLS.get(node.op)
    if ref is not None and ref.admissible(backend, node):
        out.append(ref)
    return out


def resolve(backend: "Backend", node: Node) -> Impl:
    """First admissible impl in the fallback chain; the executor uses this
    when the election pass did not annotate the node."""
    cands = candidates(backend, node)
    if not cands:
        raise NotImplementedError(
            f"no implementation of {node.op} for backend {backend.name!r}")
    return cands[0]


# ---------------------------------------------------------------------------
# backward implementations — first-class registry citizens (ISSUE 10)
# ---------------------------------------------------------------------------

GRAD_SUFFIX = "_bwd"


def grad_cache_op(op: OpKind) -> str:
    """Autotune-cache op key for a backward impl of ``op`` — suffixed so
    backward timings/configs never collide with forward entries."""
    return f"{op.value}{GRAD_SUFFIX}"


def register_grad_impl(backend: str, op: OpKind, fn: GradFn, *,
                       name: Optional[str] = None,
                       supports: Optional[Callable[[Node], bool]] = None,
                       memory: str = "streamed",
                       tunable: Optional[Tunable] = None) -> Impl:
    """Register a backend-specific backward kernel (tier 0)."""
    impl = Impl(name or f"{backend}.{op.value}{GRAD_SUFFIX}", op, fn,
                TIER_BACKEND, supports=supports, backend=backend,
                memory=memory, tunable=tunable)
    _GRAD_IMPLS_BY_NAME[impl.name] = impl
    _GRAD_BACKEND_IMPLS.setdefault((backend, op), []).insert(0, impl)
    return impl


def register_shared_grad_impl(op: OpKind, fn: GradFn, *, name: str,
                              requires: Sequence[str] = (),
                              supports: Optional[Callable[[Node], bool]] = None,
                              memory: str = "streamed",
                              tunable: Optional[Tunable] = None) -> Impl:
    """Register a shared backward kernel (tier 1, capability-gated)."""
    impl = Impl(name, op, fn, TIER_SHARED, requires=frozenset(requires),
                supports=supports, memory=memory, tunable=tunable)
    _GRAD_IMPLS_BY_NAME[impl.name] = impl
    _GRAD_SHARED_IMPLS.setdefault(op, []).insert(0, impl)
    return impl


def register_reference_grad_impl(op: OpKind, fn: GradFn, *,
                                 name: Optional[str] = None,
                                 memory: str = "roundtrip") -> Impl:
    """Register the always-available backward reference (tier 2) — usually
    ``jax.vjp`` of the forward reference lowering, recomputed from primals."""
    impl = Impl(name or f"ref.{op.value}{GRAD_SUFFIX}", op, fn,
                TIER_REFERENCE, memory=memory)
    _GRAD_IMPLS_BY_NAME[impl.name] = impl
    _GRAD_REFERENCE_IMPLS[op] = impl
    return impl


def get_grad_impl(name: str) -> Optional[Impl]:
    _load_entry_points()
    return _GRAD_IMPLS_BY_NAME.get(name)


def grad_tunables_for(op: OpKind) -> List[Tunable]:
    """Every Tunable any backward impl declares for ``op`` (cleared before
    the backward election pins its winner)."""
    _load_entry_points()
    out: List[Tunable] = []
    for (_b, o), impls in _GRAD_BACKEND_IMPLS.items():
        if o is op:
            out += [i.tunable for i in impls if i.tunable is not None]
    out += [i.tunable for i in _GRAD_SHARED_IMPLS.get(op, ())
            if i.tunable is not None]
    return out


def grad_candidates(backend: "Backend", node: Node) -> List[Impl]:
    """Admissible backward impls for (backend, node) that may stand for
    election: backend-specific first, then shared.

    The reference backward (``jax.vjp`` of the op's reference forward) is
    deliberately NOT a candidate when any kernel-tier backward is
    admissible: it materializes the intermediates the kernels exist to
    avoid (the S×S attention matrix, every recurrent hidden state), so a
    timing race on a dev box would elect it at toy shapes and then blow
    device memory at real ones.  It remains the capability *fallback* —
    when no kernel backward is admissible it is returned alone, keeping
    every op differentiable on every backend."""
    _load_entry_points()
    out: List[Impl] = []
    for impl in _GRAD_BACKEND_IMPLS.get((backend.name, node.op), []):
        if impl.admissible(backend, node):
            out.append(impl)
    for impl in _GRAD_SHARED_IMPLS.get(node.op, []):
        if impl.admissible(backend, node):
            out.append(impl)
    if not out:
        ref = _GRAD_REFERENCE_IMPLS.get(node.op)
        if ref is not None and ref.admissible(backend, node):
            out.append(ref)
    return out


def resolve_grad(backend: "Backend", node: Node) -> Optional[Impl]:
    """First admissible backward impl, or None — an op with no registered
    backward differentiates through its (jnp) forward impl via plain JAX AD,
    so absence is not an error."""
    cands = grad_candidates(backend, node)
    return cands[0] if cands else None


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    interpret: bool               # Pallas interpret mode
    hw: HardwareSpec
    # layout preferences — the paper's per-device layout election
    linear_weight_layout: str     # 'oi' (out,in) vs 'io' (in,out)
    conv_layout: str              # 'nchw' vs 'nhwc'
    # capability set gating shared impls ('pallas' admits the Pallas kernels,
    # 'mxu' the systolic-array matmul path, ...)
    capabilities: frozenset = frozenset({"xla"})
    # mesh qualifier for the autotune cache (``distributed.sharding.
    # mesh_backend`` sets it, e.g. "data2model2").  Dispatch-table matching
    # stays on ``name`` — a mesh view admits exactly the impls the flat
    # backend does — but every cache read/write goes through ``cache_name``,
    # so per-shard (post-partition) timings can NEVER collide with
    # global-shape timings of the flat backend: a local pow2 shape divided
    # by a pow2 mesh axis lands in some other global bucket, and only the
    # qualifier keeps those two worlds apart.
    shard_tag: str = ""

    @property
    def cache_name(self) -> str:
        """The autotune-cache backend key: ``name`` on a single device,
        ``name@shard_tag`` under a mesh — measured timings, pinned Tunable
        configs and ``strict_provenance`` all key on per-shard shapes via
        this name, never on the flat backend's global-shape entries."""
        return f"{self.name}@{self.shard_tag}" if self.shard_tag else self.name

    def preferred_layout(self, node: Node) -> str:
        if node.op in (OpKind.LINEAR, OpKind.MATMUL):
            return self.linear_weight_layout
        if node.op is OpKind.CONV2D:
            return self.conv_layout
        return self.conv_layout  # DFP ops follow the surrounding data layout

    def candidates(self, node: Node) -> List[Impl]:
        return candidates(self, node)

    def resolve(self, node: Node) -> Impl:
        return resolve(self, node)


_REGISTRY: Dict[str, Backend] = {}


def register_backend(b: Backend) -> Backend:
    _REGISTRY[b.name] = b
    return b


def tpu_spec(device_kind: str) -> HardwareSpec:
    """The :class:`HardwareSpec` of one TPU generation, by device kind."""
    if device_kind not in TPU_SPECS:
        raise ValueError(f"no HardwareSpec for device kind {device_kind!r}; "
                         f"known TPU kinds: {sorted(TPU_SPECS)}")
    return TPU_SPECS[device_kind]


def tpu_backend(device_kind: str) -> Backend:
    """The real-TPU backend: the Pallas kernels compiled for the chip named
    by ``device_kind``, with that chip's hardware spec."""
    return Backend(
        name="pallas_tpu",
        interpret=False,
        hw=tpu_spec(device_kind),
        linear_weight_layout="io",
        conv_layout="nhwc",
        capabilities=frozenset({"xla", "pallas", "mxu"}),
    )


def get_backend(name: str) -> Backend:
    """A registered backend by name.  ``pallas_tpu`` is built on first use
    from the local device's kind (an unknown kind raises), and
    ``pallas_interpret`` is refused on a TPU host, where it would run every
    kernel in the interpreter."""
    if name in ("pallas_tpu", "pallas_interpret"):
        import jax
        platform = jax.default_backend()
        if name == "pallas_interpret" and platform == "tpu":
            raise ValueError("pallas_interpret runs the Pallas kernels in "
                             "interpret mode; on a TPU select 'pallas_tpu'")
        if name == "pallas_tpu" and name not in _REGISTRY:
            register_backend(tpu_backend(jax.devices()[0].device_kind))
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def set_layout_preference(name: str, *, linear: Optional[str] = None,
                          conv: Optional[str] = None) -> Backend:
    """Session-scoped layout override: re-register ``name`` with measured
    layout winners (``benchmarks/layouts.py --apply`` feeds the benchmark's
    elected layouts back here, replacing the static strings)."""
    b = get_backend(name)
    return register_backend(dataclasses.replace(
        b,
        linear_weight_layout=linear or b.linear_weight_layout,
        conv_layout=conv or b.conv_layout))


def available_backends() -> Dict[str, Backend]:
    return dict(_REGISTRY)


# CPU-like backend: XLA does the fusion, einsum hits the host BLAS.  Mirrors
# the paper's X86 backend (ISPC + DNNL) in role: 'vendor stack does the work'.
register_backend(Backend(
    name="xla",
    interpret=False,
    hw=TPU_V5E,                 # production target of the lowered program
    linear_weight_layout="oi",  # paper: (out,in) fastest on CPUs
    conv_layout="nchw",
    capabilities=frozenset({"xla"}),
))

# TPU Pallas kernels validated on CPU via interpret mode — including the
# MXU matmul path, so 'mxu'-gated impls are electable and testable off-TPU.
register_backend(Backend(
    name="pallas_interpret",
    interpret=True,
    hw=TPU_V5E,
    linear_weight_layout="io",  # paper: (in,out) on the long-vector machine;
    conv_layout="nhwc",         # TPU prefers minor-most channels (lane dim)
    capabilities=frozenset({"xla", "pallas", "mxu"}),
))

# Real-TPU backend: same kernels, compiled — registered by ``get_backend``
# on first use, once the local chip's kind is known (``tpu_backend``).
