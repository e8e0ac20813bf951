"""SOL compiler passes (Sec. III-A of the paper).

Pipeline (mirrors the paper's order):

  1. ``simplify``          — high-level mathematical optimizations on the IR
                             (ReLU⊕MaxPool folding, transpose cancellation,
                             dead-node elimination, identity removal).
  2. ``assign_modules``    — per-layer optimizing-module election: Convolution
                             and Linear → DNN module; everything else → DFP;
                             exception: grouped convolutions with
                             groups == out_channels (depthwise, MobileNet-style)
                             → DFP, because they reduce to a WeightedPooling.
  3. ``form_fusion_groups``— DFP region formation: maximal chains of fusable
                             nodes are collapsed into FUSED nodes, which the
                             backend lowers to a single depth-first kernel
                             (registers/cache in the paper; VMEM on TPU).
  4. ``assign_layouts``    — per-backend memory-layout election (e.g. Linear
                             weights (out,in) on CPU-like backends vs (in,out)
                             on long-vector backends), inserting the minimal
                             number of REORDER nodes.
  5. ``elect_implementations`` — per-node implementation election: each node's
                             admissible impls (backend kernel → shared Pallas
                             kernel → XLA reference, from the backend dispatch
                             table) are costed with the backend's
                             ``HardwareSpec`` roofline terms and the cheapest
                             wins; the choice is recorded on ``node.impl``.

Each pass returns the (mutated) graph so they compose with ``functools.reduce``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .ir import (DFP_FUSABLE, DNN_OPS, SEQUENCE_OPS, SOURCE_OPS, Graph,
                 Module, Node, OpKind, TensorSpec)


# ----------------------------------------------------------------------------
# 1. high-level mathematical simplifications
# ----------------------------------------------------------------------------

def _fold_relu_maxpool(g: Graph) -> int:
    """The paper's flagship example: a ReLU followed or preceded by a
    MaxPooling is removed by clamping the pooling's minimum value to 0
    (max(maxpool(x), 0) == maxpool(max(x, 0)) == maxpool_{min=0}(x))."""
    folded = 0
    cons = g.consumers()
    for n in list(g.topo()):
        if n.op is OpKind.RELU:
            src = n.inputs[0]
            users = cons.get(n, [])
            # relu -> maxpool : fold into the pool
            if len(users) == 1 and users[0].op is OpKind.MAXPOOL:
                pool = users[0]
                pool.attrs["min_value"] = 0.0
                g.replace(n, src)  # pool now reads src directly via rewire
                pool.inputs = [src if i is n else i for i in pool.inputs]
                folded += 1
            # maxpool -> relu : fold into the pool
            elif src.op is OpKind.MAXPOOL and len(cons.get(src, [])) == 1:
                src.attrs["min_value"] = 0.0
                g.replace(n, src)
                folded += 1
    return folded


def _cancel_transposes(g: Graph) -> int:
    """transpose(transpose(x, p), p⁻¹) → x."""
    cancelled = 0
    for n in list(g.topo()):
        if n.op is OpKind.TRANSPOSE and n.inputs[0].op is OpKind.TRANSPOSE:
            inner = n.inputs[0]
            p_out = n.attrs.get("perm")
            p_in = inner.attrs.get("perm")
            if p_out and p_in:
                comp = tuple(p_in[i] for i in p_out)
                if comp == tuple(range(len(comp))):
                    g.replace(n, inner.inputs[0])
                    cancelled += 1
    return cancelled


def _drop_identities(g: Graph) -> int:
    dropped = 0
    for n in list(g.topo()):
        if n.op in (OpKind.IDENTITY, OpKind.DROPOUT) and \
                not n.attrs.get("training", False):
            g.replace(n, n.inputs[0])
            dropped += 1
    return dropped


def simplify(g: Graph) -> Graph:
    g.attrs_log = getattr(g, "attrs_log", [])
    g.attrs_log.append({
        "relu_maxpool_folded": _fold_relu_maxpool(g),
        "transposes_cancelled": _cancel_transposes(g),
        "identities_dropped": _drop_identities(g),
    })
    g.validate()
    return g


# ----------------------------------------------------------------------------
# 2. optimizing-module assignment (DFP vs DNN)
# ----------------------------------------------------------------------------

def assign_modules(g: Graph) -> Graph:
    for n in g.topo():
        if n.op in SOURCE_OPS or n.op is OpKind.OUTPUT:
            continue
        if n.op in DNN_OPS:
            # sequence kernels (attention, linear-recurrence scans) and the
            # routed experts are whole-node dispatch-table ops, like the
            # matmul family
            n.module = Module.DNN
        elif n.op is OpKind.CONV2D:
            groups = n.attrs.get("groups", 1)
            out_c = n.attrs.get("out_channels")
            # depthwise convs reduce to WeightedPooling → DFP (paper Sec III-A)
            if groups > 1 and groups == out_c:
                n.module = Module.DFP
                n.attrs["as_weighted_pool"] = True
            else:
                n.module = Module.DNN
        else:
            n.module = Module.DFP
    return g


# ----------------------------------------------------------------------------
# 3. DFP fusion-group formation
# ----------------------------------------------------------------------------

def form_fusion_groups(g: Graph) -> Graph:
    """Collapse maximal single-consumer chains of fusable DFP nodes into FUSED
    nodes.  The depth-first insight: inside a group, intermediate tensors never
    round-trip to main memory (HBM on TPU) — they live in registers/VMEM."""
    cons = g.consumers()

    def fusable(n: Node) -> bool:
        # SEQUENCE_OPS are hard fusion barriers: attention and the
        # recurrence scans must stay whole nodes for the dispatch table,
        # never disappear into a depth-first elementwise group.
        return (n.module is Module.DFP and n.op in DFP_FUSABLE
                and n.op not in SEQUENCE_OPS and n.op is not OpKind.FUSED)

    visited: set = set()
    for n in g.topo():
        if id(n) in visited or not fusable(n):
            continue
        # grow a chain downstream while the single consumer is fusable
        chain: List[Node] = [n]
        visited.add(id(n))
        cur = n
        while True:
            users = [u for u in cons.get(cur, []) if u.op is not OpKind.OUTPUT]
            if len(users) == 1 and fusable(users[0]) \
                    and id(users[0]) not in visited:
                # all *other* inputs of the next node must come from outside
                # the chain or be params (side inputs are allowed: residuals,
                # bias tensors etc. become extra kernel operands)
                cur = users[0]
                chain.append(cur)
                visited.add(id(cur))
            else:
                break
        if len(chain) < 2:
            continue
        in_chain = {id(c) for c in chain}
        side_inputs: List[Node] = []
        for c in chain:
            for i in c.inputs:
                if id(i) not in in_chain and i not in side_inputs:
                    side_inputs.append(i)
        fused = Node(OpKind.FUSED, side_inputs, chain[-1].spec,
                     attrs={"length": len(chain)},
                     name=f"fused[{'+'.join(c.op.value for c in chain)}]",
                     body=chain)
        fused.module = Module.DFP
        g.replace(chain[-1], fused)
        cons = g.consumers()
    g.validate()
    return g


# ----------------------------------------------------------------------------
# 4. layout assignment
# ----------------------------------------------------------------------------

def assign_layouts(g: Graph, backend: "object") -> Graph:
    """Per-backend layout election.  The backend exposes
    ``preferred_layout(node) -> str`` (e.g. 'oi' vs 'io' for Linear weights,
    'nchw' vs 'nhwc' for convs).  We tag nodes and count the reorders a real
    materialization would need; reorders between adjacent nodes that agree are
    elided (the minimization the paper describes)."""
    prev_layout: Dict[int, str] = {}
    reorders = 0
    for n in g.topo():
        if n.op in SOURCE_OPS:
            continue
        want = backend.preferred_layout(n)
        n.layout = want
        for i in n.inputs:
            have = prev_layout.get(id(i))
            if have is not None and have != want:
                reorders += 1
        prev_layout[id(n)] = want
    g.layout_reorders = reorders
    return g


# ----------------------------------------------------------------------------
# 5. implementation election (per-node 'flavour' choice, paper Sec. IV)
# ----------------------------------------------------------------------------

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int32": 4,
                "int8": 1, "float64": 8}

# FLOPs per element for the memory-bound DFP ops.  The nominal default only
# needs relative magnitudes; ``benchmarks/perf_iter.py --calibrate-ew``
# replaces it with the element-weighted mean measured from compiled
# whole-model HLO (``calibrate_ew_flops`` below), and SOL_EW_FLOPS carries a
# fitted value into a fresh process.
_EW_FLOPS_NOMINAL = 5.0


def _initial_ew_flops() -> float:
    import os
    try:
        v = float(os.environ.get("SOL_EW_FLOPS", ""))
    except ValueError:
        return _EW_FLOPS_NOMINAL
    return v if v > 0 else _EW_FLOPS_NOMINAL


_EW_FLOPS = _initial_ew_flops()


def ew_flops() -> float:
    """The per-element FLOP weight the DFP cost terms currently use."""
    return _EW_FLOPS


def set_ew_flops(value: Optional[float]) -> float:
    """Override the elementwise FLOP weight (``None`` restores the nominal
    default).  Non-positive values are rejected back to the default — a
    degenerate fit must not zero out every DFP node's compute term."""
    global _EW_FLOPS
    _EW_FLOPS = (float(value) if value is not None and value > 0
                 else _EW_FLOPS_NOMINAL)
    return _EW_FLOPS


def fit_ew_flops(samples) -> float:
    """Least squares through the origin of measured elementwise FLOPs onto
    elementwise element counts: each sample is ``(ew_flops, ew_elements)``
    for one whole compiled model (``benchmarks/perf_iter.py`` derives both
    from the HLO).  Returns the fitted FLOPs-per-element, falling back to
    the nominal default when the data is degenerate."""
    num = sum(f * e for f, e in samples if e > 0)
    den = sum(e * e for _f, e in samples if e > 0)
    if den <= 0 or num <= 0:
        return _EW_FLOPS_NOMINAL
    return num / den


def calibrate_ew_flops(samples) -> float:
    """Fit and install the elementwise FLOP weight in one step."""
    return set_ew_flops(fit_ew_flops(samples))


def _node_cost_terms(n: Node) -> Tuple[float, float, float]:
    """Rough roofline terms for one node: (flops, streamed_bytes,
    roundtrip_bytes).  'streamed' assumes inputs and the output cross HBM
    exactly once (a depth-first kernel); 'roundtrip' charges every
    intermediate of a fusion group a full write+read (op-at-a-time
    composition).  For non-FUSED nodes the two coincide."""
    eltsize = _DTYPE_BYTES.get(n.spec.dtype, 4)
    in_bytes = sum(i.spec.size for i in n.inputs) * eltsize
    out_bytes = n.spec.size * eltsize
    streamed = float(in_bytes + out_bytes)

    if n.op in (OpKind.LINEAR, OpKind.MATMUL):
        k = n.inputs[0].spec.shape[-1] if n.inputs[0].spec.shape else 1
        return 2.0 * n.spec.size * k, streamed, streamed
    if n.op is OpKind.CONV2D:
        w = n.inputs[1].spec
        out_c = n.attrs.get("out_channels") or (w.shape[0] if w.shape else 1)
        taps = w.size / max(out_c, 1)       # in_ch/groups · kh · kw
        return 2.0 * n.spec.size * taps, streamed, streamed
    if n.op is OpKind.FUSED:
        flops = sum(b.spec.size for b in n.body) * _EW_FLOPS
        roundtrip = float(in_bytes) + sum(
            2.0 * b.spec.size * eltsize for b in n.body)
        return flops, streamed, roundtrip
    if n.op is OpKind.ATTENTION:
        # (B, S, H, hd): one qkᵀ + one p·v matmul → 4·B·H·S²·hd FLOPs; a
        # roundtrip impl additionally writes+reads the f32 S×S score matrix
        # per head (what flash attention exists to avoid).
        b, s, h, hd = n.spec.shape
        flops = 4.0 * b * h * s * s * hd
        score_bytes = 2.0 * b * h * s * s * 4.0
        return flops, streamed, streamed + score_bytes
    if n.op is OpKind.DECODE_ATTENTION:
        # one query row vs an S-row KV cache: 4·B·H·(S+1)·hd FLOPs; the cache
        # read dominates the streamed bytes, so decode is memory-bound and
        # O(S) in the cache length — never O(S²) like a full re-forward.  A
        # roundtrip impl additionally writes+reads the f32 (B, H, S) scores.
        b, _one, h, hd = n.spec.shape
        s = n.inputs[1].spec.shape[1] if len(n.inputs) > 1 else 1
        flops = 4.0 * b * h * (s + 1) * hd
        score_bytes = 2.0 * b * h * s * 4.0
        return flops, streamed, streamed + score_bytes
    if n.op is OpKind.MOE:
        # rows · top_k pairs, the held share (n_held / n_experts) of them on
        # average, 6·d·f FLOPs each (gate, up, down); every held expert's
        # weights read once at most
        n_held, d, f = n.inputs[2].spec.shape
        rows = n.spec.size // d
        pairs = rows * n.attrs["top_k"] * n_held / n.inputs[1].spec.shape[-1]
        return 6.0 * pairs * d * f, streamed, streamed
    if n.op is OpKind.TAKE_ROWS:
        # a gather: the selected rows read once and written once, and the
        # indices; the rows left behind are never touched
        moved = 2.0 * out_bytes + n.inputs[1].spec.size * 4.0
        return 0.0, moved, moved
    if n.op is OpKind.RGLRU_SCAN:
        # h_t = a·h + b: ~2 FLOPs/element; streamed bytes dominate either way
        return 2.0 * n.spec.size, streamed, streamed
    if n.op is OpKind.RWKV6_SCAN:
        # per step each head updates an hd×hd state: ~4·B·S·H·hd² FLOPs; a
        # roundtrip impl spills the f32 state matrix every step.
        b, s, h, hd = n.spec.shape
        flops = 4.0 * b * s * h * hd * hd
        state_bytes = 2.0 * b * s * h * hd * hd * 4.0
        return flops, streamed, streamed + state_bytes
    return n.spec.size * _EW_FLOPS, streamed, streamed


def node_roofline_terms(n: Node, hw: "object",
                        memory: str = "streamed"
                        ) -> Tuple[float, float, float]:
    """Public face of :func:`_node_cost_terms` for the speed-of-light
    report (``core.sol``): the node's (flops, nbytes, bound_s) under the
    given impl memory mode, with the bound computed by the SAME
    ``HardwareSpec.roofline_s`` the election pass costs with — the SOL gap
    is measured against the model that elected the kernel, never a
    parallel formula."""
    flops, streamed, roundtrip = _node_cost_terms(n)
    nbytes = roundtrip if memory == "roundtrip" else streamed
    return flops, nbytes, hw.roofline_s(flops, nbytes)


def elect_implementations(g: Graph, backend: "object") -> Graph:
    """Cost-based per-node impl election over the backend dispatch table.

    Measurements beat models (the AutoTVM/Ansor lesson): when the autotune
    cache (``core.autotune``) holds timings for this (op, shape bucket,
    dtype, backend), the candidate with the best *measured* time wins and
    the node is tagged with ``'measured'`` provenance — including any tuned
    kernel config the measurement carried, pinned through the winning
    impl's ``Tunable`` declaration (``node.attrs['mxu_block']``,
    ``'attn_block'``, ``'dfp_block'``, ``'rglru_block'``, ...).  Every
    tunable attr registered for the op (any backend's) is cleared first, so
    re-electing a graph on a different backend or cache state never leaves
    a stale pin.

    Cold cache falls back to the analytical path: every admissible impl is
    costed with the backend's ``HardwareSpec`` roofline terms — scaled by
    calibrated per-(backend, op) coefficients when ``benchmarks/calibrate``
    has fit them (``'calibrated'`` provenance, else ``'analytical'``) — and
    the cheapest wins; ties break toward the more specific tier.  The
    executor honours ``node.impl`` and falls back along the chain when the
    annotation is absent or inadmissible (e.g. the graph is re-lowered on a
    different backend)."""
    from ..backends import registry as R
    from . import autotune

    cache = autotune.get_cache()
    elections: Dict[str, int] = {}
    by_op: Dict[str, Dict[str, int]] = {}
    provenance: Dict[str, Dict[str, int]] = {}
    pinned: Dict[str, List[Tuple[int, ...]]] = {}
    for n in g.topo():
        if n.op in SOURCE_OPS or n.op is OpKind.OUTPUT:
            continue
        cands = R.candidates(backend, n)
        if not cands:
            raise NotImplementedError(
                f"no implementation of {n.op} for backend {backend.name!r}")
        flops, streamed, roundtrip = _node_cost_terms(n)
        by_name = {c.name: c for c in cands}
        measured = {name: m for name, m in cache.lookup(
            n.op.value, autotune.node_shape(n), n.spec.dtype,
            backend.cache_name).items() if name in by_name}

        cfg = None
        if measured:
            best_name = min(measured,
                            key=lambda nm: (measured[nm].us,
                                            by_name[nm].tier))
            best = by_name[best_name]
            cfg = measured[best_name].config
            source = "measured"
        else:
            cal = cache.calibration(backend.cache_name, n.op.value)

            def cost(impl: "R.Impl") -> Tuple[float, int]:
                nbytes = roundtrip if impl.memory == "roundtrip" else streamed
                if cal:
                    t = cal["s_per_flop"] * flops + cal["s_per_byte"] * nbytes
                else:
                    t = backend.hw.roofline_s(flops, nbytes)
                return (t, impl.tier)

            best = min(cands, key=cost)
            source = "calibrated" if cal else "analytical"
        # re-election must not leave a stale tuned config: clear every
        # tunable attr registered for this op — not just this backend's
        # admissible candidates, or a pin would survive re-electing on a
        # backend where the tuned impl is inadmissible — then pin the
        # winner's measured config
        for t in R.tunables_for(n.op):
            t.bind_config(n, None)
        if cfg and best.tunable is not None:
            best.tunable.bind_config(n, tuple(cfg))
            pinned.setdefault(best.name, []).append(tuple(cfg))
        n.impl = best.name
        elections[best.name] = elections.get(best.name, 0) + 1
        per = by_op.setdefault(n.op.value, {})
        per[best.name] = per.get(best.name, 0) + 1
        src = provenance.setdefault(best.name, {})
        src[source] = src.get(source, 0) + 1
    g.elections = elections
    g.elections_by_op = by_op
    g.election_provenance = provenance
    g.election_pinned = pinned
    return g


def elect_grad_implementations(g: Graph, backend: "object") -> Graph:
    """Backward-impl election — the forward election's exact mirror over the
    gradient dispatch table (``registry.grad_candidates``).

    Measured timings come from the autotune cache under the ``_bwd``-suffixed
    op key (``registry.grad_cache_op``), so forward and backward sweeps never
    collide; the analytical fallback costs a backward as roughly two
    forward-sized programs (dX and dW / dKV and dQ).  Winners land on
    ``node.impl_bwd``, tuned configs pin through the backward impl's own
    ``Tunable`` (attrs suffixed ``_bwd``, so clearing them never drops a
    forward pin), and elections/provenance merge into the graph's existing
    election dicts under the ``_bwd`` op key — ``impl_report`` and
    ``check_provenance`` see the backward program exactly like the forward
    one."""
    from ..backends import registry as R
    from . import autotune

    cache = autotune.get_cache()
    elections: Dict[str, int] = getattr(g, "elections", {}) or {}
    by_op: Dict[str, Dict[str, int]] = getattr(g, "elections_by_op", {}) or {}
    provenance: Dict[str, Dict[str, int]] = \
        getattr(g, "election_provenance", {}) or {}
    pinned: Dict[str, List[Tuple[int, ...]]] = \
        getattr(g, "election_pinned", {}) or {}
    for n in g.topo():
        if n.op in SOURCE_OPS or n.op is OpKind.OUTPUT:
            continue
        cands = R.grad_candidates(backend, n)
        if not cands:
            n.impl_bwd = None     # JAX AD differentiates the jnp forward
            continue
        op_key = R.grad_cache_op(n.op)
        flops, streamed, roundtrip = _node_cost_terms(n)
        flops, streamed, roundtrip = 2 * flops, 2 * streamed, 2 * roundtrip
        by_name = {c.name: c for c in cands}
        measured = {name: m for name, m in cache.lookup(
            op_key, autotune.node_shape(n), n.spec.dtype,
            backend.cache_name).items() if name in by_name}

        cfg = None
        if measured:
            best_name = min(measured,
                            key=lambda nm: (measured[nm].us,
                                            by_name[nm].tier))
            best = by_name[best_name]
            cfg = measured[best_name].config
            source = "measured"
        else:
            cal = cache.calibration(backend.cache_name, op_key)

            def cost(impl: "R.Impl") -> Tuple[float, int]:
                nbytes = roundtrip if impl.memory == "roundtrip" else streamed
                if cal:
                    t = cal["s_per_flop"] * flops + cal["s_per_byte"] * nbytes
                else:
                    t = backend.hw.roofline_s(flops, nbytes)
                return (t, impl.tier)

            best = min(cands, key=cost)
            source = "calibrated" if cal else "analytical"
        for t in R.grad_tunables_for(n.op):
            t.bind_config(n, None)
        if cfg and best.tunable is not None:
            best.tunable.bind_config(n, tuple(cfg))
            pinned.setdefault(best.name, []).append(tuple(cfg))
        n.impl_bwd = best.name
        elections[best.name] = elections.get(best.name, 0) + 1
        per = by_op.setdefault(op_key, {})
        per[best.name] = per.get(best.name, 0) + 1
        src = provenance.setdefault(best.name, {})
        src[source] = src.get(source, 0) + 1
    g.elections = elections
    g.elections_by_op = by_op
    g.election_provenance = provenance
    g.election_pinned = pinned
    return g


# ----------------------------------------------------------------------------
# pipeline
# ----------------------------------------------------------------------------

def run_pipeline(g: Graph, backend: "object",
                 training: bool = False) -> Graph:
    for n in g.topo():
        if n.op is OpKind.DROPOUT:
            n.attrs["training"] = training
    g = simplify(g)
    g = assign_modules(g)
    g = form_fusion_groups(g)
    g = assign_layouts(g, backend)
    g = elect_implementations(g, backend)
    if training:
        g = elect_grad_implementations(g, backend)
    return g
