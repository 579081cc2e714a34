"""FLOP and byte counts against hand counts for one layer of each model
the benchmark serves."""
import json

import pytest

from benchlib import flops
from benchlib.families import dense
from tiny import BENCH


def _model(config, role):
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    r = c["roles"][role]
    return r["model"], r["batch"], r["seq"]


# hand counts: q/o projections 2*D*H*Dh, k/v 2*D*Kh*Dh, MLP 3*D*F;
# causal pairs S(S+1)/2; 2 FLOPs per multiply-add, QK and PV both
CASES = [
    # qwen3-4b, 1 x 512: D 2560, H 32, Kh 8, Dh 128, F 9728
    ("hi-qwen3-4b.lo-stablelm-1.6b", "hi",
     2560 * 4096 * 2 + 2560 * 1024 * 2 + 3 * 2560 * 9728,
     2 * 100_925_440 * 512 + 4 * 32 * 128 * (512 * 513 // 2),
     100_925_440 * 2 + 2 * 512 * 2560 * 2),
    # stablelm-2-1.6b, 4 x 512: D 2048, H = Kh = 32, Dh 64, F 5632
    ("hi-qwen3-4b.lo-stablelm-1.6b", "lo",
     2048 * 2048 * 4 + 3 * 2048 * 5632,
     2 * 51_380_224 * 2048 + 4 * 4 * 32 * 64 * (512 * 513 // 2),
     51_380_224 * 2 + 2 * 4 * 512 * 2048 * 2),
    # h2o-danube3-4b, 4 x 1024: D 3840, H 32, Kh 8, Dh 120, F 10240;
    # the 4096 window is longer than the prompt
    ("hi-stablelm-1.6b.lo-danube3-4b", "lo",
     3840 * 3840 * 2 + 3840 * 960 * 2 + 3 * 3840 * 10240,
     2 * 154_828_800 * 4096 + 4 * 4 * 32 * 120 * (1024 * 1025 // 2),
     154_828_800 * 2 + 2 * 4 * 1024 * 3840 * 2),
]


@pytest.mark.parametrize("config,role,params,layer_flops,layer_bytes",
                         CASES)
def test_layer_counts_match_hand_counts(config, role, params, layer_flops,
                                        layer_bytes):
    m, b, s = _model(config, role)
    assert dense.layer_params(m) == params
    assert dense.layer_flops(m, b, s) == layer_flops
    assert dense.layer_bytes(m, b, s) == layer_bytes


def test_head_and_request_counts():
    m, b, s = _model("hi-qwen3-4b.lo-stablelm-1.6b", "hi")
    head = 2 * 512 * 2560 * 151936
    assert dense.head_flops(m, b, s) == head
    assert flops.request_flops(dense, m, b, s) == 36 * dense.layer_flops(
        m, b, s) + head


def test_window_shorter_than_prompt_counts_only_window_pairs():
    assert flops._attended_keys(8, None) == 36
    assert flops._attended_keys(8, 3) == 6 + 5 * 3
    assert flops._attended_keys(8, 8) == 36


def test_least_time_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(200, 10, peak) == (2.0, "compute")
    assert flops.least_time(100, 50, peak) == (5.0, "memory")
