"""Work counts against known totals and against hand sums at small shapes."""
import json

import pytest

from chipbench.harness import BENCH, load_module
from chipbench.work import Work, least_time, total

RESNET = load_module(BENCH / "configs" / "resnet18.py")
QWEN = load_module(BENCH / "configs" / "qwen2-0.5b.py")
PEAKS = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_resnet18_macs_per_image():
    w = RESNET.forward_work(cfg("resnet18"), batch=1)
    assert w.int8_ops == 2 * 1_717_735_424          # 1.718 GMAC per image
    assert len(RESNET.kernel_calls(cfg("resnet18"), 1)) == 48


def test_resnet_hand_sum_small():
    c = cfg("resnet18")
    c.update(in_channels=1, stage_channels=[2, 4], blocks_per_stage=[1, 1], num_classes=3)
    c["stem"].update(input_hw=4, channels=2)
    # stem 1->2 on 4x4: 16*2*9; stage 1 (2x2, 2->2): 2 convs of 4*2*2*9;
    # stage 2 (1x1, 2->4, stride 2): conv1 1*4*2*9, conv2 1*4*4*9, proj 1*4*2;
    # head 4*3
    macs = 16 * 2 * 9 + 2 * (4 * 2 * 2 * 9) + 4 * 2 * 9 + 4 * 4 * 9 + 4 * 2 + 4 * 3
    assert RESNET.forward_work(c, batch=5).int8_ops == 2 * 5 * macs
    # bytes of the stem conv: 1-byte image in, 1-byte weights, int32 out
    stem = RESNET.kernel_calls(c, batch=5)[0]
    assert stem.bytes == 5 * 1 * 16 * 1 + 2 * 1 * 9 * 1 + 5 * 2 * 16 * 4


def test_qwen2_param_counts():
    p = QWEN.param_counts(cfg("qwen2-0.5b"))
    assert p["total"] == 494_032_768
    assert p["matmul"] == 357_826_560
    assert p["embed"] == 136_134_656


def test_qwen_hand_sum_small():
    c = cfg("qwen2-0.5b")
    c.update(hidden_size=8, intermediate_size=16, num_attention_heads=2,
             num_key_value_heads=1, num_hidden_layers=3, vocab_size=10)
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 128 + 128 + 128
    assert QWEN.param_counts(c)["matmul"] == 3 * per_layer
    # one decode token at position 5 (6 keys): linears, attention (q_dim 8,
    # scores and readout in 3 layers), tied logits 8x10
    w = QWEN.token_work(c, pos=5, logits=True)
    assert w.int8_ops == 2 * 3 * per_layer
    assert w.bf16_ops == 2 * 2 * 3 * 8 * 6 + 2 * 8 * 10
    # a prefill of 4 tokens: attention over 1+2+3+4 keys, logits once
    p = QWEN.prefill_work(c, batch=2, prompt_len=4)
    assert p.int8_ops == 2 * 4 * 2 * 3 * per_layer
    assert p.bf16_ops == 2 * (2 * 2 * 3 * 8 * 10 + 2 * 8 * 10)


def test_least_time_takes_the_binding_bound():
    compute = Work("c", PEAKS["int8_ops"], PEAKS["bf16_flops"], 1.0)
    assert least_time(compute, PEAKS) == pytest.approx(2.0)
    memory = Work("m", 1.0, 0.0, PEAKS["hbm_bytes_per_s"] * 3)
    assert least_time(memory, PEAKS) == pytest.approx(3.0)
    assert total([compute, memory]).bytes == 1.0 + PEAKS["hbm_bytes_per_s"] * 3
