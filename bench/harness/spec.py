"""Where the benchmark finds what a cell is made of.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
harness finds every piece by that name and needs no edit for a new one:

- ``BENCHMARK.json`` ``configs[i].file``: the configuration's sizes;
- ``bench/traffic/<mix>.json``: the traffic mix;
- ``bench/limits/<cell>.json``: the limits of the comparison that decides
  ``correct``;
- ``bench/metrics/<metric>.py``: one reader per per-layer metric;
- ``bench/models/<name>.py``: the architecture a configuration names
  under ``lm.model``: ``build(lm)``, its weight table ``weights(lm)``,
  ``prefill_flops``, ``decode_flops`` and ``kernel_work`` (and optionally
  ``embedding`` and ``server``; see ``harness.model``);
- ``bench/references/<name>.py``: the plain reference a configuration
  names under ``lm.reference``.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]


class SpecError(ValueError):
    """A cell, configuration, mix or metric that the benchmark cannot find
    or read."""


def _read_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from None


class Spec:
    """``BENCHMARK.json`` and the files its names lead to, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.doc = _read_json(self.root / "BENCHMARK.json")

    def _entry(self, key: str, name: str) -> Dict[str, Any]:
        for e in self.doc.get(key, []):
            if e["name"] == name:
                return e
        raise SpecError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> Dict[str, Any]:
        return self._entry("workloads", name)

    def config(self, name: str) -> Dict[str, Any]:
        return _read_json(self.root / self._entry("configs", name)["file"])

    def traffic(self, name: str) -> Dict[str, Any]:
        return _read_json(self.bench / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> Dict[str, Any]:
        return _read_json(self.bench / "limits" / f"{cell}.json")

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """The per-layer metrics a ``--trace 1`` run of ``cell`` reports:
        those that list the cell, and those without a list whose moved
        end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        return load_module(self.bench / "metrics" / f"{metric}.py").read

    def reference(self, name: str):
        return load_module(self.bench / "references" / f"{name}.py")

    def model(self, name: str):
        return load_module(self.bench / "models" / f"{name}.py")


def load_module(path: Path):
    """Import a file whose name may hold dots (``sched.queue_wait_ms.py``)."""
    if not path.is_file():
        raise SpecError(f"no file {path}")
    mod_name = "bench_" + re.sub(r"\W", "_", str(path.relative_to(
        path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lm_widths(config: Dict[str, Any]) -> Dict[str, Any]:
    """The served LM's widths, from the configuration's ``lm`` block;
    ``head_dim`` is ``d_model // n_heads`` where the block gives none."""
    lm = dict(config["lm"])
    lm.setdefault("head_dim", lm["d_model"] // lm["n_heads"])
    return lm


def seed_words(seed: int, stream: Optional[int] = None) -> List[int]:
    """``--seed`` (any whole number, also past 32 bits) as the entropy of a
    numpy generator, kept apart per ``stream``."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, int(seed < 0)]
    return words + ([stream] if stream is not None else [])
