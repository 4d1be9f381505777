"""The gate sees the benchmark's manifest (ISSUE 37; left over from PR 36):
`perfbench/check_manifest.check` on the repo's own BENCHMARK.json, every
per-layer metric's reader file, every cell's files, and what ISSUE 37 added
(the fifth configuration and its one cell) as its own entries say it.

Nothing here runs a cell or touches a device.

Reference anchor: none in the reference; the manifest's rules are the
builder's contract and perfbench/check_manifest.py.
"""
import os

import pytest

from perfbench import check_manifest, harness

CELL = "serve-smallthinker-stage12-mixed"
CONFIG = "smallthinker-21b-a3b-stage12"


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_the_repos_manifest_is_sound(manifest):
    assert check_manifest.check(manifest) == []
    assert len(manifest["workloads"]) == 5
    assert [w["chips"] for w in manifest["workloads"]] == [1] * 5


def test_every_per_layer_metric_has_a_reader(manifest):
    for m in manifest["per_layer"]:
        path = os.path.join(harness.HERE, "metrics", m["name"] + ".py")
        assert os.path.isfile(path), m["name"]
        assert callable(harness.load_reader(m["name"]).read), m["name"]


def test_every_cell_has_its_files_and_its_job(manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) \
            == (w["config"], w["traffic"], w["chips"])
        assert os.path.isfile(os.path.join(
            harness.HERE, "job_" + cell["job"] + ".py")), cell["job"]
        e2e = {m["name"] for m in harness.cell_metrics(
            manifest, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = harness.cell_metrics(manifest, w["name"], "per_layer",
                                         sorted(e2e))
        assert per_layer and {m["moves"] for m in per_layer} <= e2e


def test_the_routed_expert_cell_is_entered_as_the_issue_names_it(manifest):
    config = manifest["configs"][-1]
    assert config["name"] == CONFIG and config["reduced"] == ["n_layer"]
    assert config["source"] == ("https://huggingface.co/PowerInfer/"
                                "SmallThinker-21BA3B-Instruct/blob/main/"
                                "config.json")
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, CONFIG, "chat-mixed-8k", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                   "end_to_end")}
    # `ttft_p90_ms` is not listed: its six runs spread over half its bound
    # (PERF.md section 2, PR 37), and a cell is admitted only under it
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "per_layer", sorted(e2e))}
    only_here = {m["name"] for m in manifest["per_layer"]
                 if m.get("workloads") == [CELL]}
    # PR 38 added the admission attention kernel's roofline
    assert only_here == {"step_mfu.serve_moe",
                         "moe_experts_roofline.serve_moe",
                         "moe_experts_ms_per_tick.serve_moe",
                         "prefill_attn_roofline.serve_moe"}
    # the host gap's split by cause and the stream's tail, in every serve
    # cell
    host_gap = {"idle_after_dispatch.serve", "idle_in_tick_upload.serve",
                "idle_in_tick_dispatch.serve", "idle_in_tick_readback.serve",
                "idle_in_tick_booking.serve", "stream_tail_p50_ms.serve"}
    assert mine == only_here | host_gap | {
        "tokens_per_tick.serve", "device_idle.serve",
        "tick_host_p50_ms.serve", "idle_in_admit.serve",
        "idle_in_tick_host.serve", "idle_unattributed.serve",
        "kv_live_share.serve", "setup_build_s.serve", "setup_warm_s.serve"}
    # the cells that were there report what they reported
    hybrid = {m["name"] for m in harness.cell_metrics(
        manifest, "serve-granite-h-micro-chat", "per_layer",
        ["serve_tokens_per_s", "ttft_p90_ms", "setup_s"])}
    assert len(hybrid) == 14 + len(host_gap) and not hybrid & only_here
    assert host_gap <= hybrid


def test_the_configuration_holds_every_published_key_and_the_cut():
    conf = harness.load_cell(CELL)["conf"]
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert {k: conf[k] for k in published} == published
    assert conf["rope_layout"] == conf["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert conf["n_layer"] == 12 and conf["reduced"] == ["n_layer"]
    assert conf["weights_dtype"] == "bfloat16"
    # by hand: attention, router, two norms, 64 experts of three matrices
    layer = (2560 * (3584 + 512 + 512) + 3584 * 2560) + 2560 * 64 \
        + 2 * 2560 + 64 * 3 * 2560 * 768
    assert layer == 398_627_840 == conf["parameters"]["layer"]
    total = 12 * layer + 2 * 151936 * 2560 + 2560
    assert total == 5_561_448_960 == conf["parameters"]["total"]
    mix = harness.load_cell(CELL)["mix"]
    assert (mix["clients"], mix["system_tokens"], mix["greedy_share"]) \
        == (32, 0, 0.5)
    assert mix["user_tokens"] == {"distribution": "lognormal",
                                  "median": 1536, "sigma": 1.0,
                                  "min": 128, "max": 7680}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 192, "sigma": 0.7,
                                    "min": 64, "max": 512}
