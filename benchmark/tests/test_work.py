"""``benchmark/work.py`` against hand counts for both configurations."""
import json
import os

import pytest

from rehearsal import REPO
from benchmark import chips, work


def config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt_1p3b_18l_hand_count():
    arch = config("gpt-1.3b-18l")
    p = work.gpt_matmul_params(arch)
    # a block: qkv 3*2048^2 + proj 2048^2 + two FFN matrices 2*2048*8192
    assert p["blocks"] == 18 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
    assert p["blocks"] == 905_969_664
    assert p["head"] == 50304 * 2048 == 103_022_592
    f = work.gpt_train_flops(arch, batch=4, seq=2048)
    assert f["blocks"] == 6 * 905_969_664 * 8192
    assert f["head"] == 6 * 103_022_592 * 4 * 2047
    # attention: 6 products' worth of 2*S*S*H a layer and row, halved
    assert f["attention"] == 18 * 4 * 6 * 2048 * 2048 * 2048
    assert f["total"] == f["blocks"] + f["head"] + f["attention"]
    per_token = f["total"] / 8192
    assert per_token == pytest.approx(6.507e9, rel=1e-3)   # ISSUE 27: 6.5 G


def test_gpt_125m_hand_count():
    arch = config("gpt-125m")
    p = work.gpt_matmul_params(arch)
    assert p["blocks"] == 12 * (4 * 768 * 768 + 2 * 768 * 3072) == 84_934_656
    assert p["head"] == 50304 * 768 == 38_633_472
    f = work.gpt_train_flops(arch, batch=16, seq=1024)
    assert f["attention"] == 12 * 16 * 6 * 1024 * 1024 * 768
    per_token = f["total"] / 16384
    assert per_token == pytest.approx(0.7976e9, rel=1e-3)  # ISSUE 27: 0.80 G
    # head + loss is 31% of the weights that multiply a token
    assert p["head"] / (p["head"] + p["blocks"]) == pytest.approx(0.3126,
                                                                 abs=1e-3)


def test_attention_call_counts_and_roofline():
    # one row, 16 heads of 128, 2048 tokens, forward, causal half:
    # 2 products * 2*S*S*H / 2
    assert work.attention_flops(2048, 2048, backward=False) == \
        2 * 2048 * 2048 * 2048
    assert work.attention_flops(2048, 2048, backward=True) == \
        2 * work.attention_flops(2048, 2048, backward=False)
    assert work.attention_bytes(2048, 2048, backward=False) == \
        4 * 2048 * 2048 * 2
    chip = chips.chip_for("TPU v5 lite")
    t, by = work.roofline_seconds(
        work.attention_flops(2048, 2048, False),
        work.attention_bytes(2048, 2048, False), chip)
    assert by == "compute"
    assert t == pytest.approx(2 * 2048**3 / 197e12)
    t, by = work.roofline_seconds(1.0, 819e9, chip)
    assert (t, by) == (1.0, "memory")


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        chips.chip_for("TPU v9")
    assert chips.chip_for("TPU v5 lite").peak_flops == 197e12
