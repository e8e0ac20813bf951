"""Helpers of the benchmark's own tests: a throwaway checkout that holds a
copy of ``bench/`` and ``BENCHMARK.json`` (plus whatever a test adds) and
the program, and mixes scaled to a tiny model."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))

TINY_LM = {"d_model": 32, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
           "d_ff": 64, "vocab": 64, "dtype": "float32",
           "matmul_precision": "default", "model": "pre_ln_gelu_lm",
           "reference": "pre_ln_gelu_lm"}


class Checkout:
    """A checkout in ``root``: the benchmark's files copied, the program
    linked, and helpers to add a configuration, a mix and a cell."""

    def __init__(self, root: Path):
        self.root = root
        shutil.copytree(REPO / "bench", root / "bench",
                        ignore=shutil.ignore_patterns(".cache", ".scratch",
                                                      "__pycache__"))
        shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
        (root / "src").symlink_to(REPO / "src")

    @property
    def doc(self):
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def write(self, rel: str, obj) -> Path:
        p = self.root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(obj))
        return p

    def add_cell(self, name: str, config: str, traffic: str,
                 lm=None, mix=None, limits=None, ttft: bool = False):
        """New files and a new ``workloads`` entry; nothing that is there
        is edited but ``BENCHMARK.json``'s lists, which gain entries."""
        doc = self.doc
        if lm is not None:
            self.write(f"bench/configs/{config}.json",
                       {"name": config, "lm": lm})
            doc["configs"].append({"name": config, "source": "test",
                                   "file": f"bench/configs/{config}.json",
                                   "reduced": [], "why": "test"})
        if mix is not None:
            self.write(f"bench/traffic/{traffic}.json", mix)
        self.write(f"bench/limits/{name}.json",
                   limits or {"logit_rel_mse": 1e-8, "tokens_min": 4})
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "test"})
        if ttft:        # the metrics only open-loop cells report
            for m in doc["end_to_end"] + doc["per_layer"]:
                if "workloads" in m:
                    m["workloads"].append(name)
        (self.root / "BENCHMARK.json").write_text(json.dumps(doc))


def tiny_mix(base: dict, **server) -> dict:
    """A mix file scaled to a tiny model: the same loop and distributions,
    lengths that fit a 32-token context."""
    mix = json.loads(json.dumps(base))
    lim = {"max_seq": 32, "max_batch": 2, "slots": 4}
    lim.update(server)
    mix["server"] = lim
    mix["prompt_len"] = {"dist": mix["prompt_len"]["dist"], "median": 8,
                         "sigma": 0.5, "min": 4, "max": 12}
    mix["output_len"] = {"dist": mix["output_len"]["dist"], "median": 4,
                         "sigma": 0.5, "min": 3, "max": 8}
    if mix["loop"] == "open":
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=20.0)
        mix["lead_in_s"] = 0.2
    else:
        mix["clients"] = 2
        mix["think_s"] = {"dist": "exponential", "mean": 0.02}
    mix["check"] = {"requests": 3}
    return mix
