"""The reader of `kv_live_share.serve` over hand-built ticks, over a program
without the attributes, and its entry in the manifest. No number here is a
measurement."""
import pytest

from perfbench import harness


def _tick(**attrs):
    return {"name": "serve.batch", "span_id": 1, "parent_id": None,
            "t_wall": 0.0, "t_mono": 10.0, "duration_s": 0.05,
            "attrs": dict(kind="decode.paged", **attrs)}


def read(ctx):
    return harness.load_reader("kv_live_share.serve").read(ctx)


def test_share_is_summed_over_the_windows_ticks():
    ticks = [_tick(kv_live=300, kv_read=1280), _tick(kv_live=500, kv_read=1280),
             _tick(kv_live=100, kv_read=640)]
    assert read({"spans": ticks}) == pytest.approx(100 * 900 / 3200)


def test_a_program_without_the_attributes_gives_no_number():
    # the parent: serve.batch with neither attribute; no tick at all; one
    # tick of several without them
    assert read({"spans": [_tick(lanes=31, admits=0)]}) is None
    assert read({"spans": []}) is None
    assert read({}) is None
    assert read({"spans": [_tick(kv_live=3, kv_read=8), _tick(lanes=2)]}) is None
    assert read({"spans": [_tick(kv_live=0, kv_read=0)]}) is None


def _listed():
    # found by name: later PRs append to the manifest and list further cells
    entry = [m for m in harness.load_manifest()["per_layer"]
             if m["name"] == "kv_live_share.serve"]
    assert len(entry) == 1
    return entry[0]


def test_manifest_lists_it_for_serve_cells_only():
    entry = _listed()
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "kv_live_share.serve", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "decoder",
        "moves": "serve_tokens_per_s"}
    assert "serve-590m-chat" in entry["workloads"]
    assert not any(w.startswith("train") for w in entry["workloads"])


def test_toy_serve_run_reports_the_share():
    """The reader against the program's real ring: a toy traced serve run on
    the CPU with the metric listed for the toy cell."""
    import json
    import os
    import time

    from perfbench import run

    base = os.path.join(harness.HERE, "tests", "data")
    manifest = harness.load_json(os.path.join(base, "BENCHMARK.json"))
    manifest["per_layer"].append(dict(_listed(), workloads=["tiny-serve"]))
    result = run.run_cell("tiny-serve", 2**31 + 7, 2.0, True,
                          manifest=manifest, base=base,
                          t_start=time.perf_counter())
    line = json.loads(json.dumps(result))
    assert line["correct"] is True
    got = line["metrics"]["kv_live_share.serve"]
    assert got["unit"] == "%" and 0 < got["value"] <= 100
