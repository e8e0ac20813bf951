"""SOL serving subsystem: continuous batching ON the elected/tuned graph,
with the forward split into a prefill program and an O(1)-per-token
incremental decode program.

The runtime chapter (paper Sec. IV-C) under real traffic: earlier drivers
served ``models/backbone.py`` directly, bypassing everything the middleware
exists for — elections, pinned autotune configs, Pallas kernels.  This
server routes every forward through ``frontends/optimize.SolModel`` (or a
``frontends/deploy`` artifact, closing the Sec. III-C deployment loop), so
the impls that serve traffic are exactly the impls the conformance matrix
validates and the autotune cache elected.

Two serving programs (``ServeConfig.decode=True``, the default):

* **prefill** (``frontends.extract.extract_prefill``) — one forward over
  the whole prompt; every attention layer's (k, v) projections join the
  graph outputs so the same forward that produces the first token also
  seeds the request's KV-cache slot.
* **decode** (``frontends.extract.extract_decode``) — one token per
  resident request against the cached keys/values through the
  ``DECODE_ATTENTION`` op: inputs are the last token's embedding
  ``(B, 1, D)``, the per-request cache lengths ``(B,) int32`` and the
  cache tensors ``(B, cache_bucket, KV, hd)``, built on the device from
  the arena; outputs are next-token logits plus the new (k, v) rows the
  scheduler writes at position ``lens[b]``, on the device.  Per decoded
  token the work is O(cache) instead of
  the O(T·T) full re-forward — the decode program's cost does not grow
  with how much of the sequence was already generated.

``ServeConfig(decode=False)`` keeps the full-re-forward scheduler of the
previous revision — every step re-runs the whole resident context — as a
measured baseline (``benchmarks/serving.py`` reports both).

Pieces, and which paper mechanism each reproduces:

* :class:`SlotArena` — per-request slots.  The token region is an
  ``AsyncQueue`` allocation on the host: admission ``malloc_async``s it,
  prompt/token writes land via ``memcpy_async`` with virtual-pointer
  arithmetic, and eviction is an async free.  The KV rows live on the
  device, one buffer per cached tensor for every slot, written in place
  from the programs' outputs and gathered there for each decode step.
  Admission blocks when no slot is free — that interleaving is what lets
  prefill and decode share the machine.
* **Bucket padding aligned with the autotune cache** — prefill batches pad
  to ``(batch, seq)`` pow2 buckets; decode batches pad to
  ``(batch, cache_len)`` pow2 buckets.  A power of two is its own cache
  bucket, so every served shape (including every ``DECODE_ATTENTION``
  cache bucket) hits the measured-timing entries and pinned ``Tunable``
  configs exactly, never the roofline fallback.
* **Packed staging** — each prefill forward's embedded rows go
  host→device as ONE DMA via ``runtime.packed.stage_batch``; each decode
  forward's mixed inputs (token rows, int32 lengths, int32 slot ids) go as
  ONE DMA via ``runtime.packed.stage_inputs`` (the VEO-udma gather
  policy).  Only the logits come back.
* **Continuous batching** — the scheduler serves the least-recently-served
  ``max_batch`` residents each step (starvation-free round-robin), then
  partitions them: freshly admitted requests run the prefill program,
  residents run the decode program, in the same tick.
* **Sampling** — logits→token is a host-side policy per request
  (:class:`SamplingParams`: greedy / temperature / top-k / top-p with a
  per-request seed).  Sampling is deterministic given the seed, so a
  deployed-artifact replay reproduces a live run token-for-token.
* **Provenance enforcement** — with ``strict_provenance`` every
  LINEAR/MATMUL/ATTENTION/DECODE_ATTENTION dispatch must have been
  elected from autotune measurements (``SolModel.check_provenance``); a
  cold cache raises :class:`ProvenanceError` instead of silently serving
  roofline guesses.  ``warm_autotune`` measures every admissible impl
  (sweeping declared ``Tunable`` spaces) for every prefill AND decode
  bucket the workload can produce.

Smoke run (what CI executes):

    PYTHONPATH=src python -m repro.launch.serve --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import sys
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backends import get_backend
from ..core import autotune as AT
from ..core import measure, passes
from ..core.ir import OpKind
from ..frontends import nn
from ..frontends.extract import extract, extract_decode, extract_prefill
from ..frontends.optimize import (SolModel, compile_graph, optimize,
                                  provenance_violations)
from ..runtime import packed, telemetry
from ..runtime.async_queue import AsyncQueue
from .compile_cache import use_compile_cache

TOKEN_BYTES = 4                    # int32 tokens in the slot arena
MIN_SEQ_BUCKET = 8                 # smallest padded sequence bucket
SERVED_KINDS = (OpKind.LINEAR, OpKind.MATMUL, OpKind.ATTENTION,
                OpKind.DECODE_ATTENTION, OpKind.MOE)


class ProvenanceError(RuntimeError):
    """A bucket model would serve elections that did not come from autotune
    measurements — the silent-roofline-fallback the smoke run must catch."""


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request token-sampling policy.

    ``temperature <= 0`` is greedy argmax (the default) and consumes no
    randomness.  Otherwise logits are divided by ``temperature``, truncated
    to the ``top_k`` highest (0 = no truncation) and then to the smallest
    set whose probability mass reaches ``top_p``, renormalized, and sampled
    with the request's own ``numpy`` generator seeded from ``seed`` — so a
    given (logits stream, params) pair always produces the same tokens,
    live or from a deployed artifact."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature {self.temperature} must be >= 0")
        if self.top_k < 0:
            raise ValueError(f"top_k {self.top_k} must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p {self.top_p} must be in (0, 1]")


def _softmax(z: np.ndarray) -> np.ndarray:
    z = z - np.max(z)
    e = np.exp(z)
    return e / e.sum()


def sample_token(logits: np.ndarray,
                 params: Optional[SamplingParams] = None,
                 rng: Optional[np.random.Generator] = None) -> int:
    """Host-side logits→token step.  Float64 throughout so the sampled
    distribution is a pure function of the logits bits — the determinism
    the deploy round-trip asserts."""
    logits = np.asarray(logits, np.float64).reshape(-1)
    if params is None or params.temperature <= 0.0:
        return int(np.argmax(logits))
    z = logits / params.temperature
    if params.top_k:
        k = min(params.top_k, z.size)
        kth = np.partition(z, -k)[-k]
        z = np.where(z < kth, -np.inf, z)
    p = _softmax(z)
    if params.top_p < 1.0:
        order = np.argsort(-z, kind="stable")
        csum = np.cumsum(p[order])
        keep = order[: min(z.size, int(np.searchsorted(csum, params.top_p))
                           + 1)]
        masked = np.full_like(z, -np.inf)
        masked[keep] = z[keep]
        p = _softmax(masked)
    if rng is None:
        raise ValueError("temperature sampling needs the request's rng")
    return int(rng.choice(p.size, p=p))


# ---------------------------------------------------------------------------
# serving model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Shape of the served LM + scheduler limits.  ``max_seq`` must be a
    power of two so the largest sequence bucket is exactly the context
    bound.  ``decode=True`` serves residents through the incremental
    single-token decode program; ``decode=False`` keeps the full
    re-forward scheduler as a baseline."""

    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 2
    vocab: int = 128
    max_seq: int = 64              # per-request context bound (pow2)
    max_batch: int = 4             # requests per forward step
    slots: int = 8                 # KV-slot arena size (resident requests)
    backend: str = "xla"
    seed: int = 0
    decode: bool = True            # incremental KV-cache decode program
    mesh: Tuple[int, int] = (1, 1)  # (data, model) axes; (1,1) = one device

    def __post_init__(self):
        if self.max_seq != AT.ceil_pow2(self.max_seq):
            raise ValueError(f"max_seq {self.max_seq} must be a power of "
                             f"two (it is the largest sequence bucket)")
        if self.max_batch < 1 or self.slots < 1:
            raise ValueError("max_batch and slots must be >= 1")
        if len(self.mesh) != 2 or any(int(a) < 1 for a in self.mesh):
            raise ValueError(f"mesh {self.mesh} must be two positive axis "
                             f"sizes (data, model)")


def build_lm(cfg: ServeConfig) -> nn.Sequential:
    """The served module: pre-norm transformer blocks + LM head.  Plain
    framework modules — SOL extracts/optimizes them; the server never calls
    their eager forward."""
    blocks = [nn.transformer_block(cfg.d_model, cfg.n_heads)
              for _ in range(cfg.n_layers)]
    return nn.Sequential(*blocks, nn.Linear(cfg.d_model, cfg.vocab))


def embedding_table(cfg: ServeConfig) -> np.ndarray:
    """Deterministic host-side token embedding.  Token→vector lookup is a
    host gather (the SOL IR starts at dense tensors); everything after it —
    every LINEAR/MATMUL/ATTENTION/DECODE_ATTENTION — runs through the
    elected graph."""
    rng = np.random.default_rng(cfg.seed)
    return (rng.standard_normal((cfg.vocab, cfg.d_model)) * 0.25
            ).astype(np.float32)


def validate_prompt(cfg: ServeConfig, prompt: Sequence[int]) -> np.ndarray:
    """Admission-time prompt validation, shared by ``SolServer.submit`` and
    the fleet router (``launch/fleet.SolFleet.submit``) so a bad request is
    rejected where it is submitted, not replicas later when it is routed."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    if prompt.size == 0:
        raise ValueError("empty prompt")
    if prompt.size >= cfg.max_seq:
        raise ValueError(f"prompt of {prompt.size} tokens leaves no "
                         f"room to decode within max_seq={cfg.max_seq}")
    if np.any(prompt < 0) or np.any(prompt >= cfg.vocab):
        raise ValueError("prompt token out of vocabulary range")
    return prompt


# ---------------------------------------------------------------------------
# requests + KV-slot arena
# ---------------------------------------------------------------------------

def _rids(reqs) -> str:
    """Request ids as a span attribute: ``"3 5 7"`` (a comma would end the
    attribute in the profiler's encoding)."""
    return " ".join(str(r.rid) for r in reqs)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                       # int32 (L,)
    max_new_tokens: int
    submitted: float
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    rng: Optional[np.random.Generator] = None
    slot: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    phase: str = "pending"                   # pending|prefill|decode|done
    admitted_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finished_time: Optional[float] = None
    last_served_step: int = -1
    served_steps: List[int] = dataclasses.field(default_factory=list)
    last_logits: Optional[np.ndarray] = None

    @property
    def length(self) -> int:
        return len(self.prompt) + len(self.generated)

    @property
    def done(self) -> bool:
        return self.phase == "done"

    @property
    def cache_len(self) -> int:
        """Rows of the request's KV cache that hold attended positions.
        Invariant between steps: every token except the newest has been
        folded into the cache, so ``cache_len == length - 1``."""
        return self.length - 1


@dataclasses.dataclass(frozen=True)
class KVRows:
    """Rows ``[0, n)`` of batch entry ``index`` of a bucket program's cache
    outputs: one ``(batch, rows) + row_shape`` array per cached tensor, in
    the arena's tensor order, left where the program put them."""
    outputs: Sequence[Any]
    index: int
    n: int


def _write_rows(bufs, outs, index, slot, start, n):
    """Rows ``[0, n)`` of ``outs[t][index]`` into ``bufs[t][slot]`` from row
    ``start``; the block's rows past ``n`` keep what they held.  Traced per
    buffer set and program bucket; the rest are dynamic scalars."""
    new = []
    for buf, out in zip(bufs, outs):
        rows = jax.lax.dynamic_index_in_dim(out, index, 0).astype(buf.dtype)
        at = (slot, start) + (0,) * (buf.ndim - 2)
        old = jax.lax.dynamic_slice(buf, at, rows.shape)
        keep = (jnp.arange(rows.shape[1]) < n).reshape(
            (1, -1) + (1,) * (buf.ndim - 2))
        new.append(jax.lax.dynamic_update_slice(
            buf, jnp.where(keep, rows, old), at))
    return tuple(new)


def _gather_rows(bufs, slots, rows):
    """Rows ``[0, rows)`` of each listed slot, ``(len(slots), rows) +
    row_shape`` per buffer; a slot id past the last slot reads zeros."""
    return tuple(jnp.take(buf[:, :rows], slots, axis=0, mode="fill",
                          fill_value=0) for buf in bufs)


class SlotArena:
    """Per-request slots (paper Sec. IV-C), with their regions in two places.

    - **Token region, on the host**: ``max_seq`` int32s per slot in the
      async queue's virtual allocator.  Admission ``malloc_async``s it, the
      prompt and each decoded token land by ``memcpy_async`` at
      virtual-pointer offsets, eviction is an async free — the machinery
      the runtime bugfixes harden: snapshot-at-enqueue memcopies, error
      re-raising at ``synchronize``, loud use-after-free.  Prefill reads
      it (``tokens``).
    - **KV regions, on the device** (when ``kv_row_shapes`` is given): one
      float32 buffer per cached tensor, ``(n_slots, max_seq) + row_shape``,
      on ``device`` (a device or a sharding: the server's staging target),
      zero-filled at construction and resident for the arena's life.  Slot
      ``s`` owns row ``s`` of every buffer.  ``write_kv_rows`` updates it
      in place from the programs' outputs and ``gather`` builds a decode
      bucket's cache inputs from it, so cache rows never cross to the
      host."""

    def __init__(self, queue: AsyncQueue, n_slots: int, max_seq: int,
                 kv_row_shapes: Optional[Sequence[Tuple[int, ...]]] = None,
                 device=None):
        self.queue = queue
        self.n_slots = n_slots
        self.max_seq = max_seq
        self._free = list(range(n_slots - 1, -1, -1))
        self._ptr: Dict[int, Any] = {}
        self._len: Dict[int, int] = {}
        self.kv_row_shapes = [tuple(s) for s in (kv_row_shapes or [])]
        zeros = [jnp.zeros((n_slots, max_seq) + s, jnp.float32,
                           device=device) for s in self.kv_row_shapes]
        # committed where they are, so that the first write is compiled for
        # the placement every later write sees
        self._kv = [jax.device_put(z, z.sharding) for z in zeros]
        # a sharded target (mesh mode) pins the outputs' layout, so the
        # donated buffers are updated in place
        out = device if isinstance(device, jax.sharding.Sharding) else None
        self._write = jax.jit(_write_rows, donate_argnums=0,
                              out_shardings=out)
        self._gather = jax.jit(_gather_rows, static_argnums=2,
                               out_shardings=out)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def resident(self) -> int:
        return len(self._ptr)

    def admit(self, tokens: np.ndarray) -> Optional[int]:
        """Allocate a slot's token region and stage the prompt into it;
        None when full (the request waits in the pending queue — admission
        control).  The slot's KV rows are already there."""
        if not self._free:
            return None
        tokens = np.ascontiguousarray(tokens, np.int32)
        if len(tokens) > self.max_seq:
            raise ValueError(f"prompt of {len(tokens)} tokens exceeds the "
                             f"{self.max_seq}-token slot")
        slot = self._free.pop()
        ptr = self.queue.malloc_async(self.max_seq * TOKEN_BYTES)
        self.queue.memcpy_async(ptr, tokens)
        self._ptr[slot] = ptr
        self._len[slot] = len(tokens)
        return slot

    def append(self, slot: int, token: int) -> None:
        """Append one decoded token — virtual-pointer arithmetic into the
        live allocation, no host-side reassembly."""
        n = self._len[slot]
        if n >= self.max_seq:
            raise ValueError(f"slot {slot} is full ({n} tokens)")
        self.queue.memcpy_async(self._ptr[slot] + n * TOKEN_BYTES,
                                np.asarray([token], np.int32))
        self._len[slot] = n + 1

    def tokens(self, slot: int) -> np.ndarray:
        """The slot's current context.  Callers must ``synchronize`` the
        queue first so staged writes have landed."""
        buf = self.queue.allocator.resolve(self._ptr[slot])
        n = self._len[slot]
        return buf[:n * TOKEN_BYTES].view(np.int32).copy()

    def write_kv_rows(self, slot: int, tensor: Optional[int], start_row: int,
                      rows) -> None:
        """Write cache rows ``[start_row, start_row + n)`` of a slot, in
        place on the device: prefill seeds ``[0, L)``, decode appends one
        row at ``lens[b]``.  With ``tensor=None`` one dispatch writes every
        cached tensor from ``rows``, a :class:`KVRows`; an int ``tensor``
        writes that tensor alone from ``rows`` shaped ``(n,) + row_shape``."""
        if tensor is None:
            outs, index, n = tuple(rows.outputs), rows.index, rows.n
            which = range(len(self._kv))
        else:
            outs, index, n = (jnp.asarray(rows, jnp.float32)[None],), 0, \
                len(rows)
            which = [tensor]
        end = start_row + outs[0].shape[1]
        if end > self.max_seq:
            raise ValueError(f"KV write [{start_row}, {end}) overflows the "
                             f"{self.max_seq}-row slot")
        new = self._write(tuple(self._kv[t] for t in which), outs,
                          int(index), int(slot), int(start_row), int(n))
        for t, buf in zip(which, new):
            self._kv[t] = buf

    def gather(self, slots: jax.Array, rows: int) -> List[jax.Array]:
        """A decode bucket's cache inputs, built on the device in one
        dispatch: rows ``[0, rows)`` of each slot in ``slots``, one
        ``(len(slots), rows) + row_shape`` array per cached tensor.  A
        batch-padding entry holds ``n_slots`` and reads zeros.

        Rows at or past a request's length hold whatever an earlier tenant
        of the slot left there.  They weigh exactly 0: DECODE_ATTENTION
        sets every logit at or past ``lens[b]`` to -1e30 before its softmax
        (the Pallas kernel and ``ref.py`` alike), and the buffers only ever
        hold zeros and program outputs, all finite, so 0 x row is 0."""
        return list(self._gather(tuple(self._kv), slots, rows))

    def kv_rows(self, slot: int, tensor: int, n_rows: int) -> np.ndarray:
        """The first ``n_rows`` cache rows of one cached tensor, shaped
        ``(n_rows,) + row_shape``: a device→host read, for tests and
        debugging."""
        return np.asarray(self._kv[tensor][slot, :n_rows])

    def evict(self, slot: int) -> None:
        """Free the slot's token region; its KV rows stay, to be
        overwritten by the next tenant."""
        self.queue.free_async(self._ptr.pop(slot))
        del self._len[slot]
        self._free.append(slot)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------

class SolServer:
    """Continuous-batching server over the SOL pipeline.

    Bucket-model keys are ``(program, batch_bucket, seq_bucket)`` where
    ``program`` is ``"prefill"`` / ``"decode"`` (or ``"full"`` with
    ``decode=False``); for decode the seq bucket is the padded CACHE
    length.  ``deployed`` switches the server to artifact mode: a mapping
    of those keys to deploy blobs / DeployedModels; buckets outside the
    mapping raise instead of silently compiling a parallel live path."""

    def __init__(self, cfg: Optional[ServeConfig] = None,
                 model: Optional[nn.Module] = None, *,
                 deployed: Optional[Dict[Tuple, Any]] = None,
                 strict_provenance: bool = False,
                 device=None):
        self.cfg = cfg or ServeConfig()
        self.backend = get_backend(self.cfg.backend)
        self.strict_provenance = strict_provenance
        self._device = device
        # mesh mode: one server, many devices — every bucket model compiles
        # under shard_map and every autotune key carries the mesh tag, so
        # measured timings / pinned configs / strict provenance all hold on
        # PER-SHARD shapes (the arena and scheduler stay host-global)
        self.mesh = None
        if tuple(self.cfg.mesh) != (1, 1):
            from ..distributed import sharding as shd
            from .mesh import make_debug_mesh
            data_ax, model_ax = (int(a) for a in self.cfg.mesh)
            self.mesh = make_debug_mesh(data=data_ax, model=model_ax)
            self.backend = shd.mesh_backend(self.backend, self.mesh)
            if device is None:
                # packed DMA staging broadcasts the single buffer to every
                # shard; SolModel.forward then lays inputs out per-spec
                self._device = packed.replicated(self.mesh)
        # smallest batch bucket that still shards the batch dim: smaller
        # buckets would silently fall back to a replicated batch (no DP)
        self._min_batch = 1
        if self.mesh is not None:
            from ..distributed import sharding as shd
            self._min_batch = shd.axis_size(self.mesh, shd.dp_axes(self.mesh))
        self.embed = embedding_table(self.cfg)
        self.queue = AsyncQueue()
        self._models: Dict[Tuple, Any] = {}
        self._deploy_only = deployed is not None
        self.served_elections: Dict[Tuple, Dict[str, Any]] = {}
        self.model = model if model is not None else (
            None if self._deploy_only else build_lm(self.cfg))
        if self.cfg.decode:
            # the decode program's cache-input specs fix the arena's KV row
            # shapes; a throwaway minimal extraction (no compile) reads them
            spec_model = self.model if self.model is not None \
                else build_lm(self.cfg)
            g = extract_decode(spec_model, 1, self.cfg.max_seq,
                               self.cfg.d_model)
            self._kv_row_shapes = [tuple(n.spec.shape[2:])
                                   for n in g.inputs[2:]]
        else:
            self._kv_row_shapes = []
        self.arena = SlotArena(self.queue, self.cfg.slots, self.cfg.max_seq,
                               kv_row_shapes=self._kv_row_shapes,
                               device=self._device)
        if deployed is not None:
            from ..frontends import deploy as D
            for key, art in deployed.items():
                m = D.load(art, device) if isinstance(art, bytes) else art
                self._models[tuple(key)] = self._audit(m, tuple(key))
        self._pending: "deque[Request]" = deque()
        self._active: List[Request] = []
        self._finished: List[Request] = []
        self._next_rid = 0
        self._step = 0
        self._t0: Optional[float] = None
        self._t_last: Optional[float] = None
        # prefill_positions / prefill_real: bucket positions (batch x seq)
        # against prompt tokens; decode_rows / decode_real: bucket rows
        # against residents served; d2h_bytes: program outputs brought to
        # the host (the logits); kv_rows_written: cache positions written on
        # the device (each in every cached tensor); kv_host_bytes: cache
        # bytes that crossed host<->device (0 unless the programs hand back
        # host arrays); compiles / compile_s: bucket programs built and run
        # for the first time, and the seconds that took
        self.stats = {"steps": 0, "forwards": 0, "dmas": 0, "tokens": 0,
                      "prefills": 0, "decodes": 0, "admitted": 0,
                      "evicted": 0, "buckets": {},
                      "prefill_positions": 0, "prefill_real": 0,
                      "decode_rows": 0, "decode_real": 0, "d2h_bytes": 0,
                      "kv_rows_written": 0, "kv_host_bytes": 0,
                      "compiles": 0, "compile_s": 0.0}

    # -- request lifecycle ---------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None) -> Request:
        prompt = validate_prompt(self.cfg, prompt)
        sampling = sampling or SamplingParams()
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max(1, int(max_new_tokens)),
                      submitted=time.perf_counter(), sampling=sampling,
                      rng=np.random.default_rng(sampling.seed))
        self._next_rid += 1
        self._pending.append(req)
        return req

    def step(self) -> List[int]:
        """One scheduler tick: admit → select the LRU batch → run the
        prefill forward for new admissions and the decode forward for
        residents (one packed DMA each) → sample/append/evict.  Returns
        the rids served this step."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        # admission: pending requests claim free KV slots.  Who is admitted
        # and who is served are decided up front, so that the step's span
        # carries its request ids.
        admit = list(itertools.islice(self._pending, self.arena.free_slots))
        # fairness: least-recently-served first (rid FIFO tiebreak) — every
        # resident request is served at least once per ceil(R/max_batch)
        # steps, so nothing starves
        batch = sorted(self._active + admit,
                       key=lambda r: (r.last_served_step, r.rid)
                       )[: self.cfg.max_batch]
        if not batch:
            return []
        with telemetry.span("sol.step", step=self._step + 1,
                            rids=_rids(batch)):
            if admit:
                with telemetry.span("sol.admit", rids=_rids(admit)):
                    for req in admit:
                        self._pending.popleft()
                        req.slot = self.arena.admit(req.prompt)
                        req.admitted_time = time.perf_counter()
                        req.phase = "prefill"
                        self._active.append(req)
                        self.stats["admitted"] += 1
            # flush staged slot writes; a failed async op re-raises HERE
            with telemetry.span("sol.arena.sync"):
                self.queue.synchronize()
            self._step += 1
            self.stats["steps"] += 1
            if self.cfg.decode:
                results = (self._forward_prefill(
                               [r for r in batch if r.phase == "prefill"])
                           + self._forward_decode(
                               [r for r in batch if r.phase == "decode"]))
            else:
                results = self._forward_full(batch)
            with telemetry.span("sol.sample",
                                rids=_rids(r for r, _ in results)):
                self._sample(results)
        self._t_last = time.perf_counter()
        return [r.rid for r in batch]

    def _sample(self, results: List[Tuple[Request, np.ndarray]]) -> None:
        """Sample each served row's token, append it, evict the finished."""
        now = time.perf_counter()
        for req, row in results:
            req.last_logits = row
            tok = sample_token(row, req.sampling, req.rng)
            if req.phase == "prefill":
                req.first_token_time = now
                req.phase = "decode"
                self.stats["prefills"] += 1
            else:
                self.stats["decodes"] += 1
            req.generated.append(tok)
            req.last_served_step = self._step
            req.served_steps.append(self._step)
            self.stats["tokens"] += 1
            if (len(req.generated) >= req.max_new_tokens
                    or req.length >= self.cfg.max_seq):
                req.phase = "done"
                req.finished_time = now
                self.arena.evict(req.slot)
                req.slot = None
                self.stats["evicted"] += 1
                self._active.remove(req)
                self._finished.append(req)
            else:
                self.arena.append(req.slot, tok)

    # -- the three forward programs ------------------------------------------

    def _forward_full(self, batch: List[Request]
                      ) -> List[Tuple[Request, np.ndarray]]:
        """Baseline scheduler (``decode=False``): every step re-runs the
        whole resident context through the plain forward graph."""
        lens = [r.length for r in batch]
        bb, sb = self._bucket(len(batch), max(lens))
        with telemetry.span("sol.gather"):
            rows = self._prompt_rows(batch, bb, sb)
        x = packed.stage_batch(rows, self._device)     # ONE DMA
        self.stats["dmas"] += 1
        self.stats["forwards"] += 1
        logits, _ = self._fetch([self._run(("full", bb, sb), x)], None)
        self._bucket_stat(f"{bb}x{sb}")
        return [(r, logits[i, lens[i] - 1].copy())
                for i, r in enumerate(batch)]

    def _forward_prefill(self, reqs: List[Request]
                         ) -> List[Tuple[Request, np.ndarray]]:
        """Prompt forward through the prefill program: produces the first
        token's logits AND the (k, v) rows that seed each request's KV
        slot — rows ``[0, L)`` of every cached tensor, written on the
        device from the program's outputs."""
        if not reqs:
            return []
        lens = [r.length for r in reqs]
        bb, sb = self._bucket(len(reqs), max(lens))
        real = sum(lens)
        self.stats["prefill_positions"] += bb * sb
        self.stats["prefill_real"] += real
        with telemetry.span("sol.prefill", bucket=f"{bb}x{sb}",
                            rids=_rids(reqs), real=real,
                            padded=bb * sb - real) as sp:
            with telemetry.span("sol.gather"):
                rows = self._prompt_rows(reqs, bb, sb)
            x = packed.stage_batch(rows, self._device)     # ONE DMA
            self.stats["dmas"] += 1
            self.stats["forwards"] += 1
            # logits (bb, sb, vocab), then (k, v) rows (bb, sb, KV, hd)
            logits, kv = self._fetch(self._run(("prefill", bb, sb), x), sp)
            self._write_kv(reqs, kv, [0] * len(reqs), lens)
            # copy: a bare slice would pin the whole step's logits tensor in
            # memory for as long as the request lives
            results = [(r, logits[i, lens[i] - 1].copy())
                       for i, r in enumerate(reqs)]
        self._bucket_stat(f"{bb}x{sb}")
        return results

    def _forward_decode(self, reqs: List[Request]
                        ) -> List[Tuple[Request, np.ndarray]]:
        """One token per resident request through the decode program: stage
        the token rows, cache lengths and slot ids as ONE packed DMA, build
        the (batch, cache) bucket's caches from the arena's device buffers,
        and write the returned (k, v) rows at position ``lens[b]`` there."""
        if not reqs:
            return []
        lens = [r.cache_len for r in reqs]
        db, cb = self._bucket(len(reqs), max(lens))
        self.stats["decode_rows"] += db
        self.stats["decode_real"] += len(reqs)
        with telemetry.span("sol.decode", bucket=f"{db}x{cb}",
                            rids=_rids(reqs), real=len(reqs),
                            padded=db - len(reqs)) as sp:
            x = np.zeros((db, 1, self.cfg.d_model), np.float32)
            lens_arr = np.zeros((db,), np.int32)
            # a padding entry's slot id is out of range: it reads zeros
            slots = np.full((db,), self.arena.n_slots, np.int32)
            for i, r in enumerate(reqs):
                x[i, 0] = self.embed[r.generated[-1]]
                lens_arr[i] = lens[i]
                slots[i] = r.slot
            x, lens_arr, slots = packed.stage_inputs(
                [x, lens_arr, slots], self._device)          # ONE DMA
            self.stats["dmas"] += 1
            self.stats["forwards"] += 1
            with telemetry.span("sol.gather"):
                caches = self.arena.gather(slots, cb)
            # logits (db, 1, vocab), then one (k, v) row per request
            logits, kv = self._fetch(
                self._run(("decode", db, cb), x, lens_arr, *caches), sp)
            self._write_kv(reqs, kv, lens, [1] * len(reqs))
            results = [(r, logits[i, 0].copy()) for i, r in enumerate(reqs)]
        self._bucket_stat(f"d{db}x{cb}")
        return results

    def _write_kv(self, reqs: List[Request], kv: Sequence[Any],
                  starts: List[int], counts: List[int]) -> None:
        """Write each request's new cache rows into its slot, one arena
        call per request covering every cached tensor: rows ``[0,
        counts[i])`` of batch entry ``i`` go to rows ``starts[i]`` on."""
        host = sum(int(o.nbytes) for o in kv if not isinstance(o, jax.Array))
        if host or self.mesh is not None:
            # host arrays (transparent offloading) came down with the
            # outputs and go back once; on a mesh the programs lay their
            # cache outputs out per shard, and the arena's buffers are
            # replicated
            kv = jax.device_put(kv, self._device)
        self.stats["kv_host_bytes"] += 2 * host
        with telemetry.span("sol.kv_write"):
            for i, r in enumerate(reqs):
                self.arena.write_kv_rows(r.slot, None, starts[i],
                                         KVRows(kv, i, counts[i]))
                self.stats["kv_rows_written"] += counts[i]

    def _prompt_rows(self, reqs: List[Request], bb: int, sb: int
                     ) -> List[np.ndarray]:
        """Each request's context from its arena slot, embedded and padded
        to ``sb`` rows, plus zero rows up to the batch bucket ``bb``."""
        rows = []
        for r in reqs:
            padded = np.zeros(sb, np.int32)
            t = self.arena.tokens(r.slot)
            padded[: len(t)] = t
            rows.append(self.embed[padded])            # (sb, d_model) f32
        for _ in range(bb - len(reqs)):
            rows.append(np.zeros((sb, self.cfg.d_model), np.float32))
        return rows

    def _run(self, key: Tuple, *args):
        """Call a bucket program.  Its first call builds it (extract, elect,
        lower), compiles or loads it and waits for its outputs, all inside
        ``sol.compile``."""
        with telemetry.span("sol.forward"):
            model = self._models.get(key)
            if model is not None:
                return model(*args)
            program, b, s = key
            with telemetry.span("sol.compile", program=program,
                                bucket=f"{b}x{s}") as sp:
                out = jax.block_until_ready(self._model_for(key)(*args))
            self.stats["compiles"] += 1
            self.stats["compile_s"] += sp.t1 - sp.t0
            return out

    def _fetch(self, outs: Sequence[Any], sp: Optional[telemetry.span]
               ) -> Tuple[np.ndarray, List[Any]]:
        """Split a prefill or decode program's outputs into the logits,
        brought to the host (this waits for the program), and its cache
        rows, which stay on the device.  A model with MOE layers returns
        their counts last: they come down with the logits, and their sums
        over the layers go on the forward's span ``sp`` as ``moe_pairs``
        (pairs routed to a held expert) and ``moe_touched`` (held experts
        that received any)."""
        logits, *rest = outs
        kv, counts = rest[:len(self._kv_row_shapes)], \
            rest[len(self._kv_row_shapes):]
        nbytes = sum(int(o.nbytes) for o in [logits] + counts)
        self.stats["d2h_bytes"] += nbytes
        with telemetry.span("sol.fetch", bytes=nbytes):
            logits, *counts = jax.device_get([logits] + counts)
        for c in counts:
            pairs, touched = np.asarray(c).sum(0)
            sp.attrs.update(moe_pairs=int(pairs), moe_touched=int(touched))
        return np.asarray(logits), kv

    def _bucket_stat(self, key: str) -> None:
        self.stats["buckets"][key] = self.stats["buckets"].get(key, 0) + 1

    def run(self, max_steps: int = 100_000) -> Dict[str, Any]:
        while self._pending or self._active:
            if self._step >= max_steps:
                raise RuntimeError(f"serving exceeded {max_steps} steps "
                                   f"with requests still in flight")
            self.step()
        return self.summary()

    def close(self) -> None:
        self.queue.close()

    @property
    def depth(self) -> int:
        """Requests in flight (queued + resident) — the router's
        per-replica queue-depth signal."""
        return len(self._pending) + len(self._active)

    @property
    def in_flight(self) -> List[Request]:
        """Every submitted-but-unfinished request, in admission order —
        what the fleet router re-queues when this replica dies."""
        return list(self._pending) + list(self._active)

    # -- buckets + models ----------------------------------------------------

    def _bucket(self, n_rows: int, max_len: int) -> Tuple[int, int]:
        """The (batch, seq) pow2 bucket a physical batch is padded to —
        aligned with ``core.autotune`` keying so served shapes hit measured
        cache entries exactly.  For decode, ``max_len`` is the longest
        resident CACHE length and the second element is the cache bucket."""
        sb = min(self.cfg.max_seq,
                 max(min(MIN_SEQ_BUCKET, self.cfg.max_seq),
                     AT.ceil_pow2(max_len)))
        return (AT.ceil_pow2(max(n_rows, self._min_batch)), sb)

    def _seq_buckets(self, max_len: int) -> List[int]:
        smax = min(self.cfg.max_seq,
                   max(min(MIN_SEQ_BUCKET, self.cfg.max_seq),
                       AT.ceil_pow2(max_len)))
        out = []
        s = min(MIN_SEQ_BUCKET, self.cfg.max_seq)
        while s <= smax:
            out.append(s)
            s *= 2
        return out

    def _batch_buckets(self) -> List[int]:
        out = []
        b = AT.ceil_pow2(self._min_batch)
        while b <= AT.ceil_pow2(max(self.cfg.max_batch, self._min_batch)):
            out.append(b)
            b *= 2
        return out

    def _workload_maxima(self, max_len: Optional[int] = None
                         ) -> Tuple[int, int]:
        """(longest prompt, longest total context) the current workload can
        produce — the prefill and decode bucket spaces derive from them."""
        if max_len is not None:
            return max_len, max_len
        reqs = list(self._pending) + self._active
        if not reqs:
            raise ValueError("no requests to derive the bucket space "
                             "from; pass max_len explicitly")
        prompts = [len(r.prompt) for r in reqs]
        totals = [min(self.cfg.max_seq, r.length
                      + (r.max_new_tokens - len(r.generated)))
                  for r in reqs]
        return max(prompts), max(totals)

    def bucket_space(self, max_len: Optional[int] = None
                     ) -> List[Tuple[int, int]]:
        """Every (batch, seq) bucket the current workload can produce
        through the full-re-forward program — what ``warm_autotune``
        measures ahead of serving with ``decode=False``."""
        _, max_total = self._workload_maxima(max_len)
        return [(b, s) for b in self._batch_buckets()
                for s in self._seq_buckets(max_total)]

    def _warm_graphs(self, max_len: Optional[int]) -> Iterator:
        """Every program graph whose buckets the workload can open: the
        plain forward per (batch, seq) bucket with ``decode=False``;
        otherwise the prefill program per (batch, prompt) bucket plus the
        decode program per (batch, cache) bucket (caches peak one row
        short of the total context — the newest token is never cached)."""
        d = self.cfg.d_model
        if not self.cfg.decode:
            for bb, sb in self.bucket_space(max_len):
                yield extract(self.model, (bb, sb, d))
            return
        max_prompt, max_total = self._workload_maxima(max_len)
        for bb in self._batch_buckets():
            for sb in self._seq_buckets(max_prompt):
                yield extract_prefill(self.model, (bb, sb, d))
        for db in self._batch_buckets():
            for cb in self._seq_buckets(max(1, max_total - 1)):
                yield extract_decode(self.model, db, cb, d)

    def _model_for(self, key: Tuple):
        m = self._models.get(key)
        if m is not None:
            return m
        if self._deploy_only:
            raise KeyError(
                f"bucket {key} not among the deployed artifacts "
                f"{sorted(self._models)} — deploy-mode serving never "
                f"falls back to a live compile")
        program, b, s = key
        if program == "full":
            sol = optimize(self.model, (b, s, self.cfg.d_model),
                           backend=self.backend, mesh=self.mesh)
        elif program == "prefill":
            sol = compile_graph(
                self.model,
                extract_prefill(self.model, (b, s, self.cfg.d_model)),
                self.backend, mesh=self.mesh)
        else:
            sol = compile_graph(
                self.model,
                extract_decode(self.model, b, s, self.cfg.d_model),
                self.backend, mesh=self.mesh)
        self._models[key] = self._audit(sol, key)
        return sol

    def _audit(self, model, key: Tuple):
        """Record (and under ``strict_provenance`` enforce) which impls the
        bucket model serves."""
        kinds = tuple(k.value for k in SERVED_KINDS)
        self.served_elections[key] = {
            "by_op": {k: dict(v) for k, v in
                      model.impl_report(by_kind=True).items()
                      if k in kinds},
            "provenance": model.impl_report(provenance=True),
        }
        if self.strict_provenance:
            viol = provenance_violations(model.impl_report(by_kind=True),
                                         model.impl_report(provenance=True),
                                         kinds=kinds)
            if isinstance(model, SolModel):
                viol += self._exact_bucket_violations(model)
            if viol:
                raise ProvenanceError(
                    f"bucket {key} would serve unmeasured elections "
                    f"(warm the autotune cache first): {viol}")
        return model

    def _exact_bucket_violations(self, model: SolModel) -> List[str]:
        """An election can carry 'measured' provenance via the cache's
        nearest-bucket fallback — timings from a *different* shape.  Strict
        serving requires every served-kind node's EXACT bucket to hold
        measurements (a late-submitted request that opens a new bucket
        needs another ``warm_autotune()`` call, which skips
        already-measured buckets)."""
        cache = AT.get_cache()
        out = []
        for node in model.graph.topo():
            if node.op not in SERVED_KINDS:
                continue
            shape = AT.node_shape(node)
            if not cache.has_bucket(node.op.value, shape, node.spec.dtype,
                                    self.backend.cache_name):
                out.append(f"{node.op.value}@{shape}: measured via "
                           f"nearest-bucket fallback, not this bucket")
        return out

    def export_artifacts(self) -> Dict[Tuple, bytes]:
        """Deploy every live bucket model (Sec. III-C): the returned blobs
        feed ``SolServer(deployed=...)`` for artifact serving.  Input specs
        come from each program's graph, so the multi-input decode program
        exports the same way the single-input programs do."""
        from ..frontends import deploy as D
        if self.mesh is not None:
            raise RuntimeError(
                "export_artifacts: mesh-compiled bucket models cannot "
                "round-trip through jax.export + single-device "
                "DeployedModel staging — serve them live, or compile "
                "with mesh=(1, 1) for artifact export (per-shard "
                "artifacts are the serving-fleet step)")
        out = {}
        for key, m in self._models.items():
            if isinstance(m, SolModel):
                out[key] = D.deploy(m)
        return out

    # -- autotune warmup -----------------------------------------------------

    def warm_autotune(self, max_len: Optional[int] = None, *,
                      warmup: int = 1, iters: int = 3) -> Dict[str, int]:
        """Measure every admissible impl of every served-kind node
        (LINEAR/MATMUL/ATTENTION/DECODE_ATTENTION) — sweeping declared
        ``Tunable`` config spaces — for every prefill and decode bucket
        the workload can produce, and record the timings into the election
        cache.  After this, bucket compiles elect from measurements
        ('measured'/'pinned' provenance), exactly like
        ``benchmarks/autotune.py`` but scoped to the served graphs.

        Measurements land in the process-wide ``autotune.get_cache()`` —
        the cache the election pass and the strict audit read; install a
        different one with ``autotune.set_cache`` BEFORE warming."""
        if self._deploy_only:
            raise RuntimeError("deploy-mode serving has no live graphs to "
                               "warm; tune before deploying instead")
        cache = AT.get_cache()
        counts = {"nodes": 0, "impls": 0, "skipped": 0}
        seen = set()
        for g in self._warm_graphs(max_len):
            if self.mesh is not None:
                # partition BEFORE the pipeline, exactly like the serving
                # compile: measurements then key on per-shard shapes (each
                # timed on one device — the local work a shard executes)
                from ..distributed import sharding as shd
                g = shd.shard_graph(g, self.mesh)
            g = passes.run_pipeline(g, self.backend)
            for node in g.topo():
                if node.op not in SERVED_KINDS:
                    continue
                shape = AT.node_shape(node)
                key = (node.op.value, shape, node.spec.dtype)
                if key in seen:
                    continue
                seen.add(key)
                if cache.has_bucket(node.op.value, shape, node.spec.dtype,
                                    self.backend.cache_name):
                    counts["skipped"] += 1
                    continue
                counts["nodes"] += 1
                counts["impls"] += _measure_node(
                    node, self.backend, cache, warmup=warmup, iters=iters)
        return counts

    # -- reporting -----------------------------------------------------------

    def summary(self) -> Dict[str, Any]:
        done = self._finished
        lat = [1e3 * (r.finished_time - r.submitted) for r in done
               if r.finished_time is not None]
        ttft = [1e3 * (r.first_token_time - r.submitted) for r in done
                if r.first_token_time is not None]
        # wall clock of the serving itself (first step → last step), so the
        # metric is stable however long after run() summary() is called
        wall = ((self._t_last - self._t0)
                if self._t0 is not None and self._t_last is not None
                else 0.0)

        def pct(xs, q):
            return float(np.percentile(xs, q)) if xs else 0.0

        return {
            "mode": "decode" if self.cfg.decode else "reforward",
            "mesh": list(self.cfg.mesh),
            "requests": len(done),
            "tokens": self.stats["tokens"],
            "tokens_per_s": self.stats["tokens"] / wall if wall else 0.0,
            "steps": self.stats["steps"],
            "forwards": self.stats["forwards"],
            "dmas": self.stats["dmas"],
            "prefills": self.stats["prefills"],
            "decodes": self.stats["decodes"],
            "latency_ms": {"p50": pct(lat, 50), "p99": pct(lat, 99)},
            "ttft_ms": {"p50": pct(ttft, 50), "p99": pct(ttft, 99)},
            "buckets": dict(self.stats["buckets"]),
            "queue": self.queue.stats(),
        }


def _measure_node(node, backend, cache: AT.AutotuneCache, *,
                  warmup: int, iters: int) -> int:
    """Time every admissible impl of one node (all tunable configs) through
    the shared sweep (``core.measure.sweep_node`` — the same code path as
    ``benchmarks/autotune.py``) and return how many impls were recorded.
    Integer inputs (the decode program's ``lens``) get worst-case values:
    every row attends a full cache, so the recorded timing bounds the
    served cost."""
    keys = iter(jax.random.split(jax.random.PRNGKey(0), len(node.inputs)))
    vals = []
    for inp in node.inputs:
        key = next(keys)
        if inp.spec.dtype.startswith("int"):
            fill = (node.inputs[1].spec.shape[1]
                    if node.op is OpKind.DECODE_ATTENTION else 1)
            vals.append(jnp.full(inp.spec.shape, fill, jnp.int32))
        else:       # drawn where they are used: no host RNG, no transfer
            vals.append(jax.random.normal(key, inp.spec.shape, jnp.float32))
    return len(measure.sweep_node(node, vals, backend, cache,
                                  warmup=warmup, iters=iters))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _fleet_smoke(cfg: ServeConfig, n_replicas: int, n_requests: int,
                 gen: int) -> int:
    """``--fleet N`` smoke: serve the workload through a ``SolFleet`` of N
    strict-provenance replicas with ONE injected mid-stream replica kill,
    then verify against an undisturbed same-seed fleet on the same
    weights: every request must complete (re-queued included) with
    token-identical output.  What CI's fleet step runs."""
    from .fleet import FleetConfig, SolFleet

    model = build_lm(cfg)
    workload = _smoke_workload(cfg, n_requests, gen)
    samplings = [SamplingParams(temperature=0.8, seed=1000 + i)
                 for i in range(len(workload))]

    fleet = SolFleet(cfg, FleetConfig(n_replicas=n_replicas), model=model,
                     strict_provenance=True)
    reqs = [fleet.submit(p, g, sampling=sp)
            for (p, g), sp in zip(workload, samplings)]
    t0 = time.perf_counter()
    counts = fleet.warm_autotune()
    print(f"[fleet] autotune warmup on {cfg.backend}: {counts['impls']} "
          f"impl timings over {counts['nodes']} keys "
          f"({counts['skipped']} already cached) in "
          f"{time.perf_counter() - t0:.1f}s — shared by all "
          f"{n_replicas} replicas")
    for _ in range(2):              # get requests mid-stream before the kill
        fleet.tick()
    killed = fleet.kill()
    print(f"[fleet] injected kill of replica {killed} at tick "
          f"{fleet.stats['ticks']}")
    s = fleet.run()
    fleet.close()
    print(f"[fleet] {s['requests']} requests, {s['tokens']} tokens over "
          f"{s['replicas']} replicas in {s['ticks']} ticks "
          f"({s['tokens_per_s']:.1f} tok/s); requeued={s['requeued']} "
          f"respawns={s['respawns']} recovery={s['recovery_s']['max'] * 1e3:.1f}ms; "
          f"served_by={s['served_by']}")
    dropped = [r.fid for r in reqs if r.generated is None]
    if dropped:
        print(f"[fleet] DROPPED requests after kill: {dropped}",
              file=sys.stderr)
        return 1

    base = SolFleet(cfg, FleetConfig(n_replicas=1), model=model,
                    strict_provenance=True)
    breqs = [base.submit(p, g, sampling=sp)
             for (p, g), sp in zip(workload, samplings)]
    base.run()
    base.close()
    diverged = [r.fid for r, b in zip(reqs, breqs)
                if r.generated != b.generated]
    if diverged:
        print(f"[fleet] kill-recovery DIVERGED from the undisturbed "
              f"same-seed run for requests {diverged}", file=sys.stderr)
        return 1
    print(f"[fleet] token output identical to the undisturbed same-seed "
          f"run for all {len(reqs)} requests "
          f"({s['requeued']} re-queued across the kill)")
    return 0


def _smoke_workload(cfg: ServeConfig, n_requests: int, gen: int,
                    seed: int = 1) -> List[Tuple[np.ndarray, int]]:
    hi = min(24, cfg.max_seq - gen - 1)    # prompts leave room to decode
    if hi <= 4:
        raise ValueError(
            f"gen={gen} leaves no room for prompts within "
            f"max_seq={cfg.max_seq}; lower --gen or raise --max-seq")
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_requests):
        plen = int(rng.integers(4, hi))
        out.append((rng.integers(0, cfg.vocab, plen, dtype=np.int32)
                    .astype(np.int32), gen))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + strict measured-provenance audit "
                         "over prefill AND decode buckets + deploy "
                         "round-trip; what CI runs")
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=6)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--no-decode", action="store_true",
                    help="serve with the full re-forward baseline instead "
                         "of the incremental decode program")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through a SolFleet of N replicas with one "
                         "injected mid-stream kill + token-identity check "
                         "vs an undisturbed fleet (launch/fleet.py)")
    ap.add_argument("--mesh", default="1,1", metavar="DATA,MODEL",
                    help="serve across a debug mesh of data,model devices "
                         "(default 1,1 = single device); needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count "
                         "on CPU")
    ap.add_argument("--json", help="write the serve summary to this path")
    ap.add_argument("--no-deploy-roundtrip", action="store_true",
                    help="skip the artifact round-trip leg of --smoke")
    args = ap.parse_args(argv)
    use_compile_cache()

    try:
        mesh = tuple(int(a) for a in args.mesh.split(","))
        if len(mesh) != 2:
            raise ValueError
    except ValueError:
        print(f"--mesh wants 'data,model' (got {args.mesh!r})",
              file=sys.stderr)
        return 2

    if args.smoke:
        cfg = ServeConfig(d_model=32, n_heads=2, n_layers=1, vocab=64,
                          max_seq=32, max_batch=4, slots=4,
                          backend=args.backend, decode=not args.no_decode,
                          mesh=mesh)
        args.requests, args.gen = min(args.requests, 6), min(args.gen, 6)
    else:
        cfg = ServeConfig(d_model=args.d_model, n_heads=args.n_heads,
                          n_layers=args.layers, vocab=args.vocab,
                          max_seq=args.max_seq, max_batch=args.max_batch,
                          slots=args.slots, backend=args.backend,
                          decode=not args.no_decode, mesh=mesh)

    if args.fleet:
        if args.fleet < 1 or mesh != (1, 1):
            print("--fleet wants N >= 1 replicas on mesh 1,1 (a replica "
                  "may itself be a mesh once per-replica meshes get their "
                  "own devices)", file=sys.stderr)
            return 2
        return _fleet_smoke(cfg, args.fleet,
                            max(args.requests, 4 * args.fleet), args.gen)

    server = SolServer(cfg, strict_provenance=True)
    workload = _smoke_workload(cfg, args.requests, args.gen)
    for prompt, g in workload:
        server.submit(prompt, g)

    t0 = time.perf_counter()
    counts = server.warm_autotune()
    print(f"[serve] autotune warmup on {cfg.backend}: "
          f"{counts['impls']} impl timings over {counts['nodes']} "
          f"(op, shape) keys ({counts['skipped']} already cached) in "
          f"{time.perf_counter() - t0:.1f}s")

    summary = server.run()
    print(f"[serve] mode={summary['mode']}: {summary['requests']} "
          f"requests, {summary['tokens']} tokens in {summary['steps']} "
          f"steps / {summary['forwards']} forwards "
          f"({summary['tokens_per_s']:.1f} tok/s, one packed DMA per "
          f"forward: {summary['dmas']})")
    print(f"[serve] latency p50/p99 = {summary['latency_ms']['p50']:.1f}/"
          f"{summary['latency_ms']['p99']:.1f} ms; ttft p50 = "
          f"{summary['ttft_ms']['p50']:.1f} ms; buckets "
          f"{summary['buckets']}")

    failures = []
    for bucket, rec in sorted(server.served_elections.items()):
        prov = rec["provenance"]
        for kind, impls in rec["by_op"].items():
            for name in impls:
                entry = prov.get(name, {})
                srcs = entry.get("sources", {})
                pins = entry.get("pinned", "")
                print(f"[serve] bucket {bucket} {kind} → {name} "
                      f"sources={srcs}"
                      + (f" pinned={pins}" if pins else ""))
                if set(srcs) - {"measured"} or not srcs:
                    failures.append(f"{bucket}:{kind}->{name}:{srcs}")
    if failures:
        print(f"[serve] unmeasured elections served: {failures}",
              file=sys.stderr)
        return 1

    if args.smoke and mesh != (1, 1) and not args.no_deploy_roundtrip:
        print("[serve] mesh run: skipping the deploy round-trip leg "
              "(mesh-compiled models are served live, not exported)")
    elif args.smoke and not args.no_deploy_roundtrip:
        arts = server.export_artifacts()
        replay = SolServer(cfg, deployed=arts, strict_provenance=True)
        reqs = [replay.submit(p, g) for p, g in workload]
        replay.run()
        live_by_rid = {r.rid: r.generated for r in server._finished}
        for r in reqs:
            if r.generated != live_by_rid[r.rid]:
                print(f"[serve] deploy round-trip DIVERGED for request "
                      f"{r.rid}: {r.generated} != {live_by_rid[r.rid]}",
                      file=sys.stderr)
                return 1
        print(f"[serve] deploy round-trip: {len(arts)} bucket artifacts "
              f"served {len(reqs)} requests bit-identically")
        replay.close()

    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"[serve] wrote {args.json}")
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
