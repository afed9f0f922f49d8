"""Model FLOPs per token against hand counts, and the peaks table."""
import json
import os

import pytest

from bench import flops, harness, spec
from bench.tests import tiny


def _conf(name):
    return json.load(open(os.path.join(tiny.BENCH, "configs", name)))


def test_switch_l1_hand_count():
    # attn 4*768^2 + top-1 expert 2*768*3072 + router 768*128 + head
    # 768*4016 = 10,260,480 -> 6N = 61,562,880; + 12*1*12*64*512
    assert flops.flops_per_token(_conf("switch-base-128.l1.json"), 512) \
        == 66_281_472


def test_gpt_ep4_hand_count():
    # per layer 4*1024^2 + 2*2*1024*2048 + 1024*96 = 12,681,216, x4, + head
    # 1024*50304 -> 6N = 613,416,960; + 12*4*16*64*1024 = 50,331,648
    assert flops.flops_per_token(_conf("fastmoe-gpt.ep4.json"), 1024) \
        == 663_748_608


def test_gpt_l1_hand_count():
    # the Open question's one-chip cut: 1 layer, vocab 50304 / 8 = 6288
    conf = dict(_conf("fastmoe-gpt.ep4.json"), num_layers=1, vocab_size=6288)
    assert round(flops.flops_per_token(conf, 1024) / 1e5) == 1273


@pytest.mark.parametrize("workload, count", [
    ("switch.train.l1", 66_281_472),
    ("gpt.train.ep4", 663_748_608),
    # per layer 12,681,216 + head 1024*6288 -> 6N = 114,720,768;
    # + 12*1*16*64*1024 = 12,582,912
    ("gpt.train.l1", 127_303_680),
])
def test_each_cell_counted_by_its_reference(workload, count):
    assert harness.flops_per_token(spec.resolve(tiny.REPO, workload)) == count


def test_peaks_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1.6e12
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
