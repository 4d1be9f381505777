"""flops.py against a hand count for a 2-layer toy."""
import pytest

from perfbench import flops

TOY = {"n_embd": 8, "n_layer": 2, "n_head": 2, "n_inner": 16,
       "vocab_size": 10, "n_positions": 4}


def test_matmul_params_by_hand():
    # a layer: q, k, v, o are 8x8 each (256), the MLP 8x16 twice (256)
    assert flops.matmul_params(TOY) == {"blocks": 2 * 512, "head": 80}


def test_causal_attention_counts_only_the_visible_half():
    # t=4: 4*5/2 = 10 visible pairs; per pair and head 2 matmuls of
    # head_dim 4 multiply-adds: 2 * 2 * 4 = 16 operations; 2 heads
    assert flops.attention_forward_flops(4, 2, 4) == 10 * 16 * 2
    assert flops.attention_forward_flops(4, 2, 4, causal=False) == 16 * 16 * 2


def test_train_flops_per_token_by_hand():
    dense = 6 * (1024 + 80)
    attn = 3 * 2 * (10 * 16 * 2) / 4      # fwd + 2x bwd, 2 layers, a token
    assert flops.train_flops_per_token(TOY, 4) == pytest.approx(dense + attn)


def test_forward_flops_by_hand():
    # 3 tokens processed, 7 attended pairs
    assert flops.forward_flops(TOY, 3, 7) == pytest.approx(
        2 * 1104 * 3 + 2 * 4 * 7 * 4 * 2)


def test_published_sizes():
    import os
    from perfbench import harness
    conf = harness.load_json(os.path.join(harness.HERE, "configs",
                                          "cerebras-gpt-590m.json"))
    per_token = flops.train_flops_per_token(conf, 2048)
    # 6 x 587 M matmul parameters + attention at 2,048: 3.86 GFLOP a token
    assert per_token == pytest.approx(3.86e9, rel=0.01)
    assert conf["parameters"]["blocks"] == pytest.approx(
        flops.matmul_params(conf)["blocks"], rel=0.001)


def test_flash_call_and_roofline_bound():
    call = flops.flash_forward_call(rows=2, t=4, head_dim=4)
    assert call["flops"] == 2 * 4 * 10 * 4
    assert call["bytes"] == 2 * (4 * 4 * 4 * 2 + 4 * 4)
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000.0, 10.0, peaks) == (10.0, "compute")
    assert flops.least_seconds(10.0, 1000.0, peaks) == (100.0, "memory")
