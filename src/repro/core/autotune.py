"""Measured autotuning (ROADMAP: per-backend autotune cache).

AutoTVM/Ansor-style closed loop for the paper's "pick the best impl per
device" story: instead of trusting the analytical roofline alone, the
benchmark driver (``benchmarks/autotune.py``) times every registered impl of
an op through the dispatch table and persists the results here; the election
pass (``passes.elect_implementations``) prefers those measurements and falls
back to the (optionally calibrated) roofline when the cache is cold.

The Tunable protocol — any kernel, not just the matmul:

A dispatch-table impl may declare a :class:`Tunable` at registration
(``register_shared_impl(..., tunable=Tunable(attr, space))``):

* ``tune_space(node, hw)`` yields the candidate configs for one node —
  integer tuples keyed off the backend's ``HardwareSpec`` (MXU tile sizes,
  attention block sizes, DFP block rows / fusion-split sizes, scan block
  lengths) and clamped/deduplicated against the node's shape;
* ``bind_config(node, cfg)`` pins one config on the node under the
  tunable's ``node.attrs[attr]`` key (``cfg=None`` clears it) — the impl
  reads the same attr at lowering time, so a pinned election reaches the
  kernel with zero extra plumbing.

The sweep in ``benchmarks/autotune.py`` iterates whatever the registry
declares: for every admissible impl it measures each config in the tune
space and records the winner's config next to its time; the election pass
re-binds that config whenever the measurement wins (and *clears* every
candidate's tunable attr first, so re-election never leaves a stale pin).

Cache keying — (op kind, canonicalized shape bucket, dtype, backend, impl):

* shapes canonicalize to **nearest-power-of-two buckets** per dim, so one
  measurement covers a neighbourhood of shapes and the file stays small;
* unseen buckets resolve by **nearest-bucket lookup**: among same-rank
  buckets for the same (op, dtype, backend), minimize L1 distance in
  log2-space;
* LINEAR/MATMUL key on the problem (M, K, N) — leading batch dims collapse
  into M — every other op keys on its output shape.

File format (JSON, schema-versioned):

    {"schema": 1,
     "entries": {"matmul|float32|pallas_tpu|256x256x256":
                   {"pallas.matmul_mxu": {"us": 12.3,
                                          "config": [128, 128, 128],
                                          "flops": 3.4e7, "nbytes": 7.9e5},
                    "ref.matmul": {"us": 20.1, ...}}},
     "calibration": {"pallas_tpu": {"matmul":
                   {"s_per_flop": 5e-15, "s_per_byte": 1.2e-12, "n": 6}}}}

Determinism guarantees: ``save`` is atomic (tmp + ``os.replace``), and a file
whose ``schema`` does not match :data:`SCHEMA_VERSION` is *ignored* on load
(the cache comes back empty with ``stale=True``), never misread.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 1

# process-wide cache consulted by the election pass; empty unless the user
# opts in via SOL_AUTOTUNE_CACHE or set_cache()/load_cache()
_CACHE: Optional["AutotuneCache"] = None

EntryKey = Tuple[str, str, str]                  # (op, dtype, backend)
Bucket = Tuple[int, ...]
Config = Tuple[int, ...]                         # one tunable kernel config


@dataclasses.dataclass(frozen=True)
class Tunable:
    """A kernel impl's tuning declaration (see module docstring).

    ``attr``  — the ``node.attrs`` key configs are pinned under; one key per
                kernel family (``'mxu_block'``, ``'attn_block'``, ...), so
                clearing and pinning never collide across impls.
    ``space`` — ``space(node, hw) -> [config, ...]``: candidate configs for
                one node on one ``HardwareSpec``; may be empty (nothing to
                sweep for this shape).
    ``bind``  — optional override of the default pin/clear behaviour.
    ``refine``— optional override of :meth:`refine_space` — the neighborhood
                the gap-driven planner probes AROUND a winning config, which
                may step outside the initial ``space`` (families with
                divisibility constraints override this to stay legal).
    """

    attr: str
    space: Callable[[object, object], Sequence[Config]]
    bind: Optional[Callable[[object, Optional[Config]], None]] = None
    refine: Optional[Callable[[object, object, Config],
                              Sequence[Config]]] = None

    def tune_space(self, node, hw) -> List[Config]:
        return [tuple(int(d) for d in cfg) for cfg in self.space(node, hw)]

    def bind_config(self, node, cfg: Optional[Config]) -> None:
        if self.bind is not None:
            self.bind(node, cfg)
        elif cfg is None:
            node.attrs.pop(self.attr, None)
        else:
            node.attrs[self.attr] = tuple(int(d) for d in cfg)

    def refine_space(self, node, hw, winning_cfg: Config) -> List[Config]:
        """Candidate configs *around* ``winning_cfg`` for the SOL-gap
        refinement planner (``benchmarks/autotune.refine_plan``).  The
        default probes the power-of-two neighborhood — every combination of
        halving / keeping / doubling each dimension — minus the winner
        itself and anything already in the initial ``tune_space`` (those
        were measured by the sweep; re-measuring them wastes the planner's
        budget).  Kernels clamp configs defensively at call time (gcd /
        min-max), so stepping outside the declared space is safe; families
        whose clamp would collapse most neighbors (divisor-constrained
        blocks) override ``refine`` with a legal neighborhood."""
        win = tuple(int(d) for d in winning_cfg)
        if self.refine is not None:
            cands = [tuple(int(d) for d in c)
                     for c in self.refine(node, hw, win)]
        else:
            import itertools
            axes = [sorted({max(1, d // 2), d, 2 * d}) for d in win]
            cands = [c for c in itertools.product(*axes) if c != win]
        seen = set(self.tune_space(node, hw)) | {win}
        out: List[Config] = []
        for c in cands:
            if c not in seen and all(d >= 1 for d in c):
                seen.add(c)
                out.append(c)
        return out


def bucket_dim(d: int) -> int:
    """Nearest power of two (ties round up via round-half-even on the log)."""
    if d <= 1:
        return 1
    return 2 ** int(round(math.log2(d)))


def bucket_shape(shape: Tuple[int, ...]) -> Bucket:
    return tuple(bucket_dim(int(d)) for d in shape)


def ceil_pow2(d: int) -> int:
    """Smallest power of two ≥ ``d`` — the SERVING bucket.

    ``bucket_dim`` rounds to the *nearest* pow2 (fine for cache keying,
    where a measurement covers a neighbourhood), but a server must pad a
    request UP, never truncate it; and because a power of two is its own
    bucket (``bucket_dim(ceil_pow2(d)) == ceil_pow2(d)``), a batch padded
    with ``ceil_pow2`` hits the measured-timing cache and any pinned
    ``Tunable`` configs exactly instead of falling back to the roofline."""
    if d <= 1:
        return 1
    return 2 ** math.ceil(math.log2(d))


def pad_shape(shape: Sequence[int]) -> Tuple[int, ...]:
    """Per-dim ``ceil_pow2`` — the shape a served batch is padded to."""
    return tuple(ceil_pow2(int(d)) for d in shape)


def node_shape(node) -> Optional[Tuple[int, ...]]:
    """The shape a node is keyed under.  LINEAR/MATMUL/MOE → (M, K, N) with
    leading batch dims folded into M (a MOE node's work depends on its rows
    alone); DECODE_ATTENTION → (B, S, H, hd) from
    the KV-cache operand, so each decode cache bucket gets its own timings
    (the output shape is (B, 1, H, hd) for *every* cache length and would
    alias all buckets); everything else → the output shape."""
    from .ir import OpKind
    if node.op is OpKind.DECODE_ATTENTION:
        if len(node.inputs) < 2 or len(node.spec.shape) != 4:
            return tuple(node.spec.shape) or None
        b, _one, h, hd = node.spec.shape
        s = node.inputs[1].spec.shape[1]          # k_cache is (B, S, KV, hd)
        return (b, s, h, hd)
    if node.op in (OpKind.LINEAR, OpKind.MATMUL, OpKind.MOE):
        xs = node.inputs[0].spec.shape if node.inputs else ()
        if not xs or not node.spec.shape:
            return None
        k = xs[-1]
        m = 1
        for d in xs[:-1]:
            m *= d
        return (m, k, node.spec.shape[-1])
    return tuple(node.spec.shape) or None


@dataclasses.dataclass
class Measurement:
    us: float                                    # best measured wall time
                                                 # (min over iters — see
                                                 # core.measure docstring)
    config: Optional[Tuple[int, ...]] = None     # winning tunable config
    flops: float = 0.0                           # analytic terms of the node
    nbytes: float = 0.0                          # bytes for this impl's
                                                 # memory mode (calibration)
    mean_us: float = 0.0                         # mean over the same iters

    def to_json(self) -> dict:
        d = {"us": self.us}
        if self.config is not None:
            d["config"] = list(self.config)
        if self.flops:
            d["flops"] = self.flops
        if self.nbytes:
            d["nbytes"] = self.nbytes
        if self.mean_us:
            d["mean_us"] = self.mean_us
        return d

    @classmethod
    def from_json(cls, d: dict) -> "Measurement":
        cfg = d.get("config")
        return cls(us=float(d["us"]),
                   config=tuple(cfg) if cfg else None,
                   flops=float(d.get("flops", 0.0)),
                   nbytes=float(d.get("nbytes", 0.0)),
                   mean_us=float(d.get("mean_us", 0.0)))


class AutotuneCache:
    """Persistent per-(op, shape bucket, dtype, backend, impl) timings."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.stale = False      # a schema-mismatched file was ignored on load
        self._entries: Dict[EntryKey, Dict[Bucket, Dict[str, Measurement]]] = {}
        self._calibration: Dict[Tuple[str, str], Dict[str, float]] = {}

    # -- measurements -------------------------------------------------------

    def record(self, op: str, shape: Tuple[int, ...], dtype: str,
               backend: str, impl: str, us: float, *,
               config: Optional[Tuple[int, ...]] = None,
               flops: float = 0.0, nbytes: float = 0.0,
               mean_us: float = 0.0) -> None:
        """Keep the best (lowest) time per (key, bucket, impl)."""
        bucket = bucket_shape(shape)
        per = self._entries.setdefault((op, dtype, backend), {}) \
                           .setdefault(bucket, {})
        prev = per.get(impl)
        if prev is None or us < prev.us:
            per[impl] = Measurement(us=float(us),
                                    config=tuple(config) if config else None,
                                    flops=float(flops), nbytes=float(nbytes),
                                    mean_us=float(mean_us))

    def lookup(self, op: str, shape: Optional[Tuple[int, ...]], dtype: str,
               backend: str) -> Dict[str, Measurement]:
        """Measurements for the exact bucket, else the nearest same-rank
        bucket (L1 in log2-space), else {}."""
        return self.lookup_with_confidence(op, shape, dtype, backend)[0]

    def lookup_with_confidence(self, op: str,
                               shape: Optional[Tuple[int, ...]], dtype: str,
                               backend: str
                               ) -> Tuple[Dict[str, Measurement], str]:
        """Like :meth:`lookup`, plus WHERE the hit came from: ``"exact"``
        (the shape's own bucket holds measurements), ``"nearest"`` (resolved
        to the nearest same-rank bucket — a neighbourhood estimate, never to
        be reported as an exact measurement), or ``""`` (miss)."""
        if shape is None:
            return {}, ""
        buckets = self._entries.get((op, dtype, backend))
        if not buckets:
            return {}, ""
        want = bucket_shape(shape)
        hit = buckets.get(want)
        if hit is not None:
            return dict(hit), "exact"
        same_rank = [b for b in buckets if len(b) == len(want)]
        if not same_rank:
            return {}, ""

        def dist(b: Bucket) -> float:
            return sum(abs(math.log2(x) - math.log2(y))
                       for x, y in zip(b, want))

        return dict(buckets[min(same_rank, key=dist)]), "nearest"

    def has_bucket(self, op: str, shape: Tuple[int, ...], dtype: str,
                   backend: str) -> bool:
        """Whether the EXACT bucket of ``shape`` holds measurements (no
        nearest-bucket fallback) — the serving warmup uses this to skip
        shapes an earlier run already measured."""
        buckets = self._entries.get((op, dtype, backend))
        return bool(buckets) and bucket_shape(shape) in buckets

    def entries(self) -> List[Tuple[EntryKey, Bucket, str, Measurement]]:
        """Flat iteration for the calibration fit and reporting."""
        out = []
        for key, buckets in sorted(self._entries.items()):
            for bucket, impls in sorted(buckets.items()):
                for impl, m in sorted(impls.items()):
                    out.append((key, bucket, impl, m))
        return out

    def __len__(self) -> int:
        return sum(len(impls) for buckets in self._entries.values()
                   for impls in buckets.values())

    # -- calibration coefficients -------------------------------------------

    def set_calibration(self, backend: str, op: str,
                        coeffs: Dict[str, float]) -> None:
        self._calibration[(backend, op)] = dict(coeffs)

    def calibration(self, backend: str, op: str) -> Optional[Dict[str, float]]:
        return self._calibration.get((backend, op))

    def calibrations(self) -> Dict[Tuple[str, str], Dict[str, float]]:
        return dict(self._calibration)

    # -- persistence ---------------------------------------------------------

    def to_json(self) -> dict:
        entries = {}
        for (op, dtype, backend), buckets in sorted(self._entries.items()):
            for bucket, impls in sorted(buckets.items()):
                key = "|".join((op, dtype, backend,
                                "x".join(str(d) for d in bucket)))
                entries[key] = {impl: m.to_json()
                                for impl, m in sorted(impls.items())}
        calibration: Dict[str, Dict[str, dict]] = {}
        for (backend, op), coeffs in sorted(self._calibration.items()):
            calibration.setdefault(backend, {})[op] = coeffs
        return {"schema": SCHEMA_VERSION, "entries": entries,
                "calibration": calibration}

    def save(self, path: Optional[str] = None) -> str:
        """Atomic write: serialize to a tmp file in the target directory,
        then ``os.replace`` — readers never observe a torn cache."""
        path = path or self.path
        if not path:
            raise ValueError("no cache path given")
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(self.to_json(), f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        self.path = path
        return path

    @classmethod
    def load(cls, path: str) -> "AutotuneCache":
        """Load a cache file; a missing file or one written by a different
        schema version yields an *empty* cache (``stale=True`` for the
        latter) rather than an error or a misread."""
        cache = cls(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            return cache
        if doc.get("schema") != SCHEMA_VERSION:
            cache.stale = True
            return cache
        for key, impls in doc.get("entries", {}).items():
            parts = key.split("|")
            if len(parts) != 4:
                continue
            op, dtype, backend, bucket_s = parts
            bucket = tuple(int(d) for d in bucket_s.split("x"))
            per = cache._entries.setdefault((op, dtype, backend), {}) \
                                .setdefault(bucket, {})
            for impl, m in impls.items():
                per[impl] = Measurement.from_json(m)
        for backend, ops in doc.get("calibration", {}).items():
            for op, coeffs in ops.items():
                cache._calibration[(backend, op)] = {
                    k: float(v) for k, v in coeffs.items()}
        return cache


# ---------------------------------------------------------------------------
# process-wide cache
# ---------------------------------------------------------------------------

def get_cache() -> AutotuneCache:
    """The cache the election pass consults.  Starts empty; a warm cache is
    an explicit opt-in (SOL_AUTOTUNE_CACHE env var, or load_cache/set_cache),
    so elections stay deterministic by default."""
    global _CACHE
    if _CACHE is None:
        path = os.environ.get("SOL_AUTOTUNE_CACHE")
        _CACHE = AutotuneCache.load(path) if path else AutotuneCache()
    return _CACHE


def set_cache(cache: Optional[AutotuneCache]) -> Optional[AutotuneCache]:
    global _CACHE
    _CACHE = cache
    return cache


def load_cache(path: str) -> AutotuneCache:
    """Load ``path`` and install it as the process-wide cache."""
    return set_cache(AutotuneCache.load(path))
