"""Operation and byte counts against hand counts, and the peaks table."""

import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402

QWEN = json.loads((BENCH / "configs" / "qwen2.5-32b-4l.json")
                  .read_text())
LENET = json.loads((BENCH / "configs" / "lenet5.json").read_text())


def test_matmul_by_hand():
    w = work.matmul(4, 8, 16)                  # bf16 by default
    assert w.flops == 2 * 4 * 8 * 16
    assert w.bytes == 8 * 16 * 2 + (4 * 8 + 4 * 16) * 2


def test_decoder_matmuls_of_one_qwen_token():
    mm = work.decoder_matmuls(QWEN, 1)
    assert len(mm) == 7 * 4 + 1
    d, f, v = 5120, 27648, 19008
    per_layer = d * d + 2 * d * 1024 + d * d + 3 * d * f
    assert sum(m.flops for m in mm) == 2 * (4 * per_layer + d * v)
    # at one row the bytes are the bf16 weights plus a row in and out each
    weights = 2 * (4 * per_layer + d * v)
    assert weights < sum(m.bytes for m in mm) < weights * 1.001


def test_decode_attention_by_hand():
    # 4 layers in the file: 3 slots of 10, 20, 30 keys
    w = work.decode_attention(QWEN, [10, 20, 30])
    keys = 60
    assert w.flops == 4 * 4 * 40 * 128 * keys
    assert w.bytes == 4 * (2 * 8 * 128 * 2 * keys + 2 * 3 * 40 * 128 * 2)


def test_prefill_attention_counts_the_causal_triangle():
    w = work.prefill_attention(QWEN, 4)
    assert w.flops == 4 * 4 * 40 * 128 * (1 + 2 + 3 + 4)
    p = work.prefill(QWEN, 4)
    assert p.flops == w.flops + sum(
        m.flops for m in work.decoder_matmuls(QWEN, 4)[:-1])


def test_decode_tick_is_matmuls_plus_attention():
    t = work.decode_tick(QWEN, [5, 7])
    mm = sum(m.flops for m in work.decoder_matmuls(QWEN, 2))
    assert t.flops == mm + work.decode_attention(QWEN, [5, 7]).flops


def test_lenet_step_by_hand():
    # forward per image: conv1 24*24*25*6, conv2 8*8*150*16, fc 256*64,
    # 64*35, 35*10 multiply-adds
    fwd = 2 * (24 * 24 * 25 * 6 + 8 * 8 * 150 * 16 + 256 * 64 + 64 * 35
               + 35 * 10)
    conv1 = 2 * 24 * 24 * 25 * 6
    assert work.lenet_step_flops_per_image(LENET) == 3 * fwd - conv1
    mm = work.lenet_step_matmuls(LENET, 2)
    assert len(mm) == 5 * 3 - 1
    assert sum(m.flops for m in mm) == 2 * (3 * fwd - conv1)


def test_peaks_table_and_unknown_device():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    # a memory-bound product: the bytes set the least time
    w = work.matmul(1, 5120, 5120)
    assert work.least_seconds(w, pk) == pytest.approx(w.bytes / 819e9)
