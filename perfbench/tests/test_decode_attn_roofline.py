"""The reader of `decode_attn_roofline.serve` on hand-built ticks and traces:
the live K and V bytes of the traced ticks over the HBM peak, over the
kernel's device time. No number here is a measurement."""
import pytest

from perfbench import harness

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
MOSAIC = 'custom_call_target="tpu_custom_call"'
# the 590M cell's decoder: 18 layers x 16 positions x 1,536 x 2 bytes, K and V
KV = {"m@1": {"block_bytes": 18 * 16 * 1536 * 2 * 2, "block_tokens": 16}}


def _call(name):
    return (f"%{name} = f32[40,12,128]{{2,1,0:T(8,128)}} custom-call(%t, "
            f"%pos, %lo, %q, %ck, %cv), {MOSAIC}, backend_config=...")


class _Trace:
    def __init__(self, events):
        self.device_ops = {0: events}


def _tick(**attrs):
    return {"name": "serve.batch", "attrs": dict(kind="decode.paged", **attrs)}


def _read(ticks, events, kv=KV, peaks=PEAKS):
    return harness.load_reader("decode_attn_roofline.serve").read(
        {"peaks": peaks, "kv": kv, "traced": {"ticks": ticks},
         "trace": _Trace(events)})


def test_live_bytes_over_the_kernels_time():
    ticks = [_tick(attend="kernel", kv_live=16000),
             _tick(attend="kernel", kv_live=20000)]
    events = [(0.0, 0.004, _call("decode_attn.3")),
              (0.01, 0.014, "  ROOT " + _call("decode_attn")),
              # not the kernel: another Mosaic call, a fusion of the name
              (0.02, 0.03, f"%flash_fwd.1 = bf16[12,512,128] custom-call(), "
                           f"{MOSAIC}"),
              (0.03, 0.04, "%decode_attn_like.1 = f32[40,12,128] fusion()")]
    per_position = 18 * 1536 * 2 * 2
    want = 100 * 36000 * per_position / 819e9 / 0.008
    assert _read(ticks, events) == pytest.approx(want)


def test_no_kernel_no_number():
    kernel = [_tick(attend="kernel", kv_live=100)]
    events = [(0.0, 0.001, _call("decode_attn.1"))]
    # the parent: ticks without the attribute, and no event of the kernel
    assert _read([_tick(kv_live=100, kv_read=200)], []) is None
    # a tick that gathered, among ticks of the kernel
    assert _read(kernel + [_tick(attend="gather", kv_live=100)],
                 events) is None
    assert _read(kernel, []) is None
    assert _read([], events) is None
    assert _read(kernel, events, peaks=None) is None
    assert _read(kernel, events, kv={}) is None
    assert harness.load_reader("decode_attn_roofline.serve").read(
        {"peaks": PEAKS, "kv": KV, "traced": None}) is None


def test_listed_for_the_cells_whose_tick_runs_the_kernel():
    entry = [m for m in harness.load_manifest()["per_layer"]
             if m["name"] == "decode_attn_roofline.serve"]
    assert len(entry) == 1
    assert entry[0]["layer"] == "kernels"
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert set(entry[0]["workloads"]) == {
        "serve-590m-chat", "serve-smallthinker-stage12-mixed",
        "serve-qwen3-next-stage8-longdoc"}
