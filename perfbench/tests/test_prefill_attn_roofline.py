"""The reader of `prefill_attn_roofline.serve_moe` on hand-built traces:
the visible pairs' operations of the kernel's calls over the peak and their
device time (ISSUE 38)."""
import os

import pytest

from perfbench import flops_smallthinker as fl
from perfbench import harness

CONF = harness.load_json(os.path.join(
    harness.HERE, "configs", "smallthinker-21b-a3b-stage12.json"))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _call(name, width):
    return (f"%{name} = bf16[{width},3584]{{1,0:T(8,128)(2,1)}} "
            f"custom-call(%constant.3, %constant.4, %constant.5, %q, %k, "
            f"%v), {MOSAIC}, backend_config=...")


class _Trace:
    def __init__(self, events):
        self.device_ops = {0: events}


def _read(events, peaks=PEAKS):
    return harness.load_reader("prefill_attn_roofline.serve_moe").read(
        {"conf": CONF, "peaks": peaks, "trace": _Trace(events)})


def test_global_and_window_calls_at_two_widths():
    events = [(0.0, 0.002, _call("prefill_attn.3", 3072)),
              (0.002, 0.004, _call("prefill_attn_w4096.7", 3072)),
              (0.010, 0.016, _call("prefill_attn.41", 8192)),
              (0.020, 0.025, "  ROOT " + _call("prefill_attn_w4096.12",
                                               8192)),
              # not the kernel: another Mosaic call, a fusion of the name
              (0.03, 0.04, "%ragged-dot.1 = f32[12288,1536]{1,0} "
                           f"custom-call(%a, %xs, %w), {MOSAIC}"),
              (0.04, 0.05, "%prefill_attn_like.1 = f32[3072,3584] fusion()")]
    # 28 heads of 128: 4 x 128 x 28 operations a pair
    per_pair = 4 * 128 * 28
    pairs = (3072 * 3073 / 2) * 2 + 8192 * 8193 / 2 \
        + (4096 * 4097 / 2 + 4096 * 4096)
    want = 100 * pairs * per_pair / 197e12 / 0.015
    assert _read(events) == pytest.approx(want)
    assert fl.attention_flops(CONF, pairs) == pairs * per_pair


def test_a_window_call_at_8192_counts_capped_pairs():
    capped = 4096 * 4097 / 2 + (8192 - 4096) * 4096
    full = 8192 * 8193 / 2
    one = _read([(0.0, 0.001, _call("prefill_attn_w4096.1", 8192))])
    assert one == pytest.approx(100 * capped * 4 * 128 * 28 / 197e12 / 0.001)
    whole = _read([(0.0, 0.001, _call("prefill_attn.1", 8192))])
    assert one / whole == pytest.approx(capped / full)


def test_no_kernel_is_no_number():
    # the parent: the admission's attention in plain XLA, no such call
    assert _read([(0.0, 0.1, "%fusion.12 = f32[4,21504,2048]{2,1,0} "
                              "fusion(bf16[4,21504,128] %q)")]) is None
    assert _read([]) is None
    assert _read([(0.0, 0.1, _call("prefill_attn.1", 3072))],
                 peaks=None) is None
