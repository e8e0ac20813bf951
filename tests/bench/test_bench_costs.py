"""The yardstick's arithmetic against numbers worked out by hand, for both
configurations (the ``pre_ln_gelu_lm`` model module's counts), and the
peaks table."""
import dataclasses
import json

import numpy as np
import pytest
from benchkit import REPO

from harness import peaks, weights
from harness.readers import RunData
from harness.spec import Spec, lm_widths

SPEC = Spec(REPO)
costs = SPEC.model("pre_ln_gelu_lm")
QWEN, GPT2 = (lm_widths(json.loads((REPO / f"bench/configs/{c}.json")
                                   .read_text()))
              for c in ("qwen2-1.5b-widths", "gpt2-large"))


@pytest.mark.parametrize("lm,params", [(QWEN, 1_158_835_584),
                                       (GPT2, 772_582_737)])
def test_parameter_count(lm, params):
    """Per qwen2 layer: two LayerNorms 6,144, q and o 2 x 2,359,296, k and
    v 2 x 393,216, the MLP 2 x 13,762,560 + 10,496 of biases; the head
    151,936 x 1,536 + 151,936.  GPT-2 large: 19,672,320 a layer x 36 and a
    head of 50,257 x 1,280 + 50,257."""
    total = sum(int(np.prod(s))
                for s in weights.shapes(costs.weights(lm)).values())
    assert total == params


def test_required_flops_gpt2_one_token_prompt():
    """36 layers of 39,321,600 projection and MLP operations and 5,120 of
    attention, and the head's 128,657,920 once."""
    assert costs.prefill_flops(GPT2, 1) == 1_544_419_840
    assert costs.decode_flops(GPT2, 0) == costs.prefill_flops(GPT2, 1)
    # qwen2: grouped KV heads make k and v 256 wide
    per_layer = 2 * (1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536
                     + 2 * 1536 * 8960)
    assert costs.decode_flops(QWEN, 9) == \
        28 * (per_layer + 4 * 12 * 128 * 10) + 2 * 1536 * 151936


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


@dataclasses.dataclass
class _Req:
    prompt: list


@dataclasses.dataclass
class _Rec:
    req: _Req
    tokens: list


@dataclasses.dataclass
class _Win:
    t0: float
    t1: float
    records: list


@dataclasses.dataclass
class _Cell:
    lm: dict
    arch: object = costs


def test_mfu_counts_real_tokens_inside_the_window():
    """One request of a 100-token prompt: its first token (the prefill)
    and two decoded tokens fall in a 2 s window, a third after it."""
    rec = _Rec(_Req([0] * 100), [0.5, 1.0, 1.5, 2.5])
    run = RunData(cell=_Cell(GPT2), window=_Win(0.0, 2.0, [rec]), spans=[],
                  setup={}, records=None, summary=None,
                  peaks=peaks.peaks("TPU v5 lite"))
    want = (costs.prefill_flops(GPT2, 100) + costs.decode_flops(GPT2, 100)
            + costs.decode_flops(GPT2, 101))
    got = SPEC.reader("mfu.serve")(run)
    assert got == pytest.approx(100 * want / (2.0 * 197e12), rel=1e-12)


def test_every_metric_has_a_reader_and_every_mix_a_file():
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in doc["per_layer"]:
        assert callable(SPEC.reader(m["name"]))
    for c in doc["workloads"]:
        assert SPEC.traffic(c["traffic"])["loop"] in ("open", "closed")
        assert SPEC.limits(c["name"])["logit_rel_mse"] > 0
