"""Model layers, written against the SOL backend registry.

The elementwise/norm chains route through the DFP path (fused Pallas kernel
on the pallas backends, XLA fusion on the xla backend); matmuls are the DNN
path (dot_general → MXU).  All functions are pure; params are dict pytrees.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

# attention chunk size for the flash-style scan (queries keep full length,
# keys/values stream in chunks; online softmax carries m/l/acc)
ATTN_CHUNK = 2048
# use the chunked path when kv length exceeds this
ATTN_CHUNK_THRESHOLD = 2048


# ---------------------------------------------------------------------------
# norms / elementwise
# ---------------------------------------------------------------------------

def rmsnorm(x: Array, gain: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * gain


def layernorm(x: Array, gain: Array, bias: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gain + bias


def apply_norm(kind: str, x: Array, p: Dict[str, Array]) -> Array:
    if kind == "layernorm":
        return layernorm(x, p["gain"], p["bias"])
    return rmsnorm(x, p["gain"])


def softcap(x: Array, cap: float) -> Array:
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: (S,) or broadcastable (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., :, None] * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _grouped(q: Array, kv: int) -> Array:
    """(B, S, H, hd) -> (B, S, KV, G, hd): GQA without materializing the
    KV broadcast (the einsums below carry the group dim instead — avoids
    the repeat copy that defeats kv/SP sharding under GSPMD)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv, h // kv, hd)


def _direct_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      window: int, cap: float, q_pos: Array,
                      kv_pos: Array) -> Array:
    """Materialized-logits attention; fine for short sequences.
    q: (B,Sq,H,hd)  k,v: (B,Skv,KV,hd)."""
    kvh = k.shape[2]
    qg = _grouped(q, kvh)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if cap:
        logits = softcap(logits, cap)
    mask = jnp.ones((q.shape[1], k.shape[1]), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if window:
        mask &= q_pos[:, None] - kv_pos[None, :] < window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    b, sq = q.shape[0], q.shape[1]
    return o.reshape(b, sq, -1, q.shape[-1])


def _chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                       window: int, cap: float, q_pos: Array,
                       kv_pos: Array, chunk: int = ATTN_CHUNK) -> Array:
    """Flash-style online-softmax scan over KV chunks (pure jnp — memory
    O(Sq·chunk) instead of O(Sq·Skv); the Pallas flash kernel is the TPU
    flavour of this same algorithm)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    skv = k.shape[1]
    nc = (skv + chunk - 1) // chunk
    pad = nc * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=2 ** 30)
    kc = k.reshape(b, nc, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, chunk, kvh, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(nc, chunk)
    scale = 1.0 / math.sqrt(hd)
    qg = _grouped(q, kvh)                       # (B,Sq,KV,G,hd)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, kb,
                            preferred_element_type=jnp.float32) * scale
        if cap:
            logits = softcap(logits, cap)
        mask = jnp.ones((sq, chunk), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= pb[None, :]
        if window:
            mask &= q_pos[:, None] - pb[None, :] < window
        mask &= pb[None, :] < 2 ** 30
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, kvh, g, sq), -jnp.inf, jnp.float32),
            jnp.zeros((b, kvh, g, sq), jnp.float32),
            jnp.zeros((b, kvh, g, sq, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B,KV,G,Sq,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)


def multihead_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                        window: int = 0, cap: float = 0.0,
                        q_pos: Optional[Array] = None,
                        kv_pos: Optional[Array] = None) -> Array:
    """q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) with KV | H (GQA)."""
    sq, skv = q.shape[1], k.shape[1]
    natural = q_pos is None and kv_pos is None and sq == skv
    if q_pos is None:
        q_pos = jnp.arange(sq)
    if kv_pos is None:
        kv_pos = jnp.arange(skv)
    if skv > ATTN_CHUNK_THRESHOLD and sq > 1:
        if natural:
            # flash path with hand-written VJP: recomputes chunk logits in
            # bwd instead of saving per-chunk probabilities (§Perf attn-1)
            from .flash import flash_mha
            return flash_mha(q, k, v, causal, window, cap, ATTN_CHUNK)
        return _chunked_attention(q, k, v, causal=causal, window=window,
                                  cap=cap, q_pos=q_pos, kv_pos=kv_pos)
    return _direct_attention(q, k, v, causal=causal, window=window, cap=cap,
                             q_pos=q_pos, kv_pos=kv_pos)


def decode_attention(q: Array, k_cache: Array, v_cache: Array, pos: Array,
                     *, window: int = 0, cap: float = 0.0) -> Array:
    """Single-token decode. q: (B,1,H,hd); caches: (B,S,KV,hd); pos: scalar
    current position (index of the token just written).  Works with the cache
    sequence dim sharded (SP): the masked softmax reductions become
    all-reduces under GSPMD (flash-decoding style)."""
    kvh = k_cache.shape[2]
    qg = _grouped(q, kvh)                                  # (B,1,KV,G,hd)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if cap:
        logits = softcap(logits, cap)
    kv_pos = jnp.arange(k_cache.shape[1])
    valid = kv_pos <= pos                                  # (S,)
    if window:
        valid &= (pos - kv_pos) < window
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v_cache)
    b = q.shape[0]
    return o.reshape(b, 1, -1, q.shape[-1])


# ---------------------------------------------------------------------------
# attention block (projections + rope + residual), parameterized
# ---------------------------------------------------------------------------

def attn_proj_qkv(p: Dict[str, Array], x: Array, cfg) -> Tuple[Array, Array, Array]:
    b, s, d = x.shape
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv, cfg.hd)
    return q, k, v


def attn_out(p: Dict[str, Array], o: Array) -> Array:
    b, s = o.shape[:2]
    y = jnp.einsum("bsh,hd->bsd", o.reshape(b, s, -1), p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# FFN / MoE
# ---------------------------------------------------------------------------

def ffn_apply(p: Dict[str, Array], x: Array, kind: str) -> Array:
    if kind == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        u = jnp.einsum("bsd,df->bsf", x, p["wu"])
        h = jax.nn.silu(g) * u
        return jnp.einsum("bsf,fd->bsd", h, p["wd"])
    # gelu MLP
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["w1"]) + p["b1"])
    return jnp.einsum("bsf,fd->bsd", h, p["w2"]) + p["b2"]


def moe_apply(p: Dict[str, Array], x: Array, moe_cfg) -> Tuple[Array, Array]:
    """Entry point: manual-SPMD (shard_map) version under a mesh context,
    dense single-device version otherwise."""
    from ..distributed import ctx as dctx
    mesh = dctx._mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1 \
            and moe_cfg.n_experts % mesh.shape["model"] == 0:
        return _moe_apply_shard_map(p, x, moe_cfg, mesh)
    return _moe_apply_dense(p, x, moe_cfg)


def _moe_apply_shard_map(p, x, moe_cfg, mesh) -> Tuple[Array, Array]:
    """2D-blocked expert parallelism, written as the explicit per-device
    program (shard_map) instead of GSPMD annotations:

      tokens: dp-sharded, model-replicated  (the residual stream already is)
      slot tables: computed locally per dp shard, sliced per model rank
      dispatch gather: LOCAL (zero communication)
      expert FFN: local (E_loc experts per model rank)
      combine: local partial scatter + ONE psum over 'model'
      aux loss: psum-mean over dp

    GSPMD lowers the same math to full-tensor all-reduces around the
    gather/scatter (its scatter partitioner replicates); manual SPMD removes
    every collective except the combine reduction, which is information-
    theoretically required.  See EXPERIMENTS.md §Perf moe-5.
    """
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    b, s, d = x.shape
    e = moe_cfg.n_experts
    e_loc = e // mesh.shape["model"]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    x_spec = P(dp if b % dp_size == 0 else None, None, None)
    w_spec = {"router": P(None, None), "wg": P("model", None, None),
              "wu": P("model", None, None), "wd": P("model", None, None)}

    def local_fn(p_loc, x_loc):
        bl, sl, dl = x_loc.shape
        t = bl * sl
        gs = min(moe_cfg.group_size, t)
        ng = t // gs
        xg = x_loc.reshape(ng, gs, dl)
        gates = jax.nn.softmax(
            jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                       p_loc["router"].astype(jnp.float32)), axis=-1)
        topw, topi = jax.lax.top_k(gates, moe_cfg.top_k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

        me = gates.mean(axis=(0, 1))
        ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
            1.0 / (ng * gs * moe_cfg.top_k))
        aux = e * jnp.sum(me * ce)
        if dp:
            aux = jax.lax.pmean(aux, dp)

        cap = int(math.ceil(gs * moe_cfg.top_k / e *
                            moe_cfg.capacity_factor))
        cap = max(8, ((cap + 7) // 8) * 8)
        slot_tok, slot_w = _slot_tables(topi, topw, ng, gs,
                                        moe_cfg.top_k, e, cap)
        # each model rank handles its own expert block
        e0 = jax.lax.axis_index("model") * e_loc
        st = jax.lax.dynamic_slice(slot_tok, (0, e0, 0), (ng, e_loc, cap))
        sw = jax.lax.dynamic_slice(slot_w, (0, e0, 0), (ng, e_loc, cap))

        xg_pad = jnp.concatenate([xg, jnp.zeros((ng, 1, dl), xg.dtype)],
                                 axis=1)
        xin = xg_pad[jnp.arange(ng)[:, None, None], st]   # local gather
        g = jnp.einsum("gecd,edf->gecf", xin, p_loc["wg"])
        u = jnp.einsum("gecd,edf->gecf", xin, p_loc["wu"])
        h = jax.nn.silu(g) * u
        y = jnp.einsum("gecf,efd->gecd", h, p_loc["wd"])
        # combine in the residual dtype (bf16): halves the psum payload
        yw = y.astype(x_loc.dtype) * sw[..., None].astype(x_loc.dtype)
        out = jnp.zeros((ng, gs + 1, dl), yw.dtype)
        out = out.at[jnp.arange(ng)[:, None, None], st].add(yw, mode="drop")
        out = jax.lax.psum(out, "model")          # the combine reduction
        return out[:, :gs].reshape(bl, sl, dl), aux

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(w_spec, x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )({k: p[k] for k in ("router", "wg", "wu", "wd")}, x)
    return out, aux


def _slot_tables(topi, topw, ng, gs, k, e, cap):
    """(G, E, cap) token-id and weight tables from top-k routing (shared by
    the dense and shard_map paths)."""
    flat_e = topi.reshape(ng, gs * k)
    flat_w = topw.reshape(ng, gs * k)
    flat_t = jnp.broadcast_to(jnp.arange(gs)[:, None],
                              (gs, k)).reshape(gs * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    sorted_t = flat_t[order]
    sorted_w = jnp.take_along_axis(flat_w, order, axis=-1)
    seg_start = jnp.concatenate([
        jnp.zeros((ng, 1), dtype=bool),
        sorted_e[:, 1:] != sorted_e[:, :-1]], axis=-1)
    pos_all = jnp.broadcast_to(jnp.arange(gs * k)[None, :], sorted_e.shape)
    run_first = jnp.where(seg_start, pos_all, 0)
    run_first = jax.lax.associative_scan(jnp.maximum, run_first, axis=-1)
    slot = pos_all - run_first
    slot_oob = jnp.where(slot < cap, slot, cap)
    slot_tok = jnp.full((ng, e, cap), gs, jnp.int32)
    slot_w = jnp.zeros((ng, e, cap), jnp.float32)
    gidx = jnp.broadcast_to(jnp.arange(ng)[:, None], sorted_e.shape)
    slot_tok = slot_tok.at[gidx, sorted_e, slot_oob].set(
        sorted_t, mode="drop")
    slot_w = slot_w.at[gidx, sorted_e, slot_oob].set(
        sorted_w, mode="drop")
    return slot_tok, slot_w


def _moe_apply_dense(p: Dict[str, Array], x: Array, moe_cfg) -> Tuple[Array, Array]:
    """Gather-based top-k MoE with per-group capacity (no one-hot dispatch
    einsum — keeps HLO FLOPs ~= useful expert FLOPs).  Single-device path;
    the distributed path is _moe_apply_shard_map."""
    b, s, d = x.shape
    e, k = moe_cfg.n_experts, moe_cfg.top_k
    gs = min(moe_cfg.group_size, b * s)
    t = b * s
    ng = t // gs
    xg = x.reshape(ng, gs, d)

    gates = jax.nn.softmax(
        jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                   p["router"].astype(jnp.float32)), axis=-1)   # (G,S,E)
    topw, topi = jax.lax.top_k(gates, k)                        # (G,S,k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = gates.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[topi.reshape(-1)].add(
        1.0 / (ng * gs * k))
    aux = e * jnp.sum(me * ce)

    cap = int(math.ceil(gs * k / e * moe_cfg.capacity_factor))
    cap = max(8, ((cap + 7) // 8) * 8)
    slot_tok, slot_w = _slot_tables(topi, topw, ng, gs, k, e, cap)

    xg_pad = jnp.concatenate([xg, jnp.zeros((ng, 1, d), xg.dtype)], axis=1)
    xin = xg_pad[jnp.arange(ng)[:, None, None], slot_tok]        # (G,E,cap,D)

    # expert FFN (SwiGLU), experts stacked on leading dim
    g = jnp.einsum("gecd,edf->gecf", xin, p["wg"])
    u = jnp.einsum("gecd,edf->gecf", xin, p["wu"])
    h = jax.nn.silu(g) * u
    y = jnp.einsum("gecf,efd->gecd", h, p["wd"])                 # (G,E,cap,D)

    # combine: scatter-add back to token positions, weighted
    yw = y * slot_w[..., None].astype(y.dtype)
    out = jnp.zeros((ng, gs + 1, d), y.dtype)
    out = out.at[jnp.arange(ng)[:, None, None],
                 slot_tok].add(yw, mode="drop")
    out = out[:, :gs].reshape(b, s, d)
    return out, aux


# dispatch/combine with sharding-aware custom VJPs: the backward of the
# dispatch gather is the combine scatter and vice versa — writing them
# explicitly lets both directions carry the token-local (dp) constraints,
# which GSPMD's autodiff'd gather/scatter otherwise turns into full-tensor
# all-reduces (measured on olmoe train_4k; EXPERIMENTS.md §Perf moe-4).

@jax.custom_vjp
def _moe_gather(xg_pad: Array, slot_tok: Array) -> Array:
    return _moe_gather_impl(xg_pad, slot_tok)


def _moe_gather_impl(xg_pad, slot_tok):
    from ..distributed.ctx import constrain
    ng = xg_pad.shape[0]
    out = xg_pad[jnp.arange(ng)[:, None, None], slot_tok]
    return constrain(out, ("dp", "model", None, None))


def _moe_gather_fwd(xg_pad, slot_tok):
    return _moe_gather_impl(xg_pad, slot_tok), (slot_tok, xg_pad.shape)


def _moe_gather_bwd(res, ct):
    from ..distributed.ctx import constrain
    slot_tok, shape = res
    ng, gs1, d = shape
    ct = constrain(ct, ("dp", "model", None, None))
    dx = constrain(jnp.zeros(shape, ct.dtype), ("dp", None, None))
    dx = dx.at[jnp.arange(ng)[:, None, None], slot_tok].add(ct, mode="drop")
    return constrain(dx, ("dp", None, None)), None


_moe_gather.defvjp(_moe_gather_fwd, _moe_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _moe_scatter(yw: Array, slot_tok: Array, gs: int) -> Array:
    return _moe_scatter_impl(yw, slot_tok, gs)


def _moe_scatter_impl(yw, slot_tok, gs):
    from ..distributed.ctx import constrain
    ng, e, cap, d = yw.shape
    out = constrain(jnp.zeros((ng, gs + 1, d), yw.dtype),
                    ("dp", None, None))
    out = out.at[jnp.arange(ng)[:, None, None], slot_tok].add(
        yw, mode="drop")
    return constrain(out, ("dp", None, None))


def _moe_scatter_fwd(yw, slot_tok, gs):
    return _moe_scatter_impl(yw, slot_tok, gs), (slot_tok,)


def _moe_scatter_bwd(gs, res, ct):
    from ..distributed.ctx import constrain
    (slot_tok,) = res
    ng = slot_tok.shape[0]
    ct = constrain(ct, ("dp", None, None))
    dyw = ct[jnp.arange(ng)[:, None, None], slot_tok]
    return constrain(dyw, ("dp", "model", None, None)), None


_moe_scatter.defvjp(_moe_scatter_fwd, _moe_scatter_bwd)
