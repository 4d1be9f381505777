"""The gate sees the benchmark's manifest (ISSUE 37; left over from PR 36):
`perfbench/check_manifest.check` on the repo's own BENCHMARK.json, every
per-layer metric's reader file, every cell's files, and the fifth and sixth
configurations and their one cell each as their own entries say them.

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
    assert len(manifest["workloads"]) == 7
    assert [w["chips"] for w in manifest["workloads"]] == [1] * 7


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
    config = [c for c in manifest["configs"] if c["name"] == CONFIG][0]
    assert config["reduced"] == ["n_layer"]
    assert config["source"] == ("https://huggingface.co/PowerInfer/"
                                "SmallThinker-21BA3B-Instruct/blob/main/"
                                "config.json")
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "chat-mixed-8k", 1)
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
        "kv_live_share.serve", "setup_build_s.serve", "setup_warm_s.serve",
        # the decode tick's attention kernel, where the tick runs it
        "decode_attn_roofline.serve"}
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


GDN_CELL = "serve-qwen3-next-stage8-longdoc"
GDN_CONFIG = "qwen3-next-80b-a3b-stage8-ep4"


def _entry(manifest, group, name):
    return next(e for e in manifest[group] if e["name"] == name)


def test_the_gdn_cell_is_entered_as_its_configuration_states_it(manifest):
    config = _entry(manifest, "configs", GDN_CONFIG)
    cell = _entry(manifest, "workloads", GDN_CELL)
    assert config["name"] == GDN_CONFIG
    assert config["reduced"] == ["n_layer", "num_experts", "vocab_size"]
    assert config["source"] == ("https://huggingface.co/Qwen/"
                                "Qwen3-Next-80B-A3B-Instruct/blob/main/"
                                "config.json")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (GDN_CELL, GDN_CONFIG, "longdoc-16k", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, GDN_CELL,
                                                   "end_to_end")}
    assert {"serve_tokens_per_s", "setup_s"} <= e2e
    mine = {m["name"] for m in harness.cell_metrics(
        manifest, GDN_CELL, "per_layer", sorted(e2e))}
    only_here = {m["name"] for m in manifest["per_layer"]
                 if m.get("workloads") == [GDN_CELL]}
    assert only_here == {"step_mfu.serve_gdn", "gdn_step_roofline.serve_gdn",
                         "gdn_step_ms_per_tick.serve_gdn",
                         "moe_experts_roofline.serve_gdn"}
    # and the serve cells' span and idle readers, as in the routed-expert
    # cell
    routed = {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "per_layer", ["serve_tokens_per_s", "setup_s"])}
    shared = {m for m in routed if m.endswith(".serve")}
    assert shared <= mine and not mine & {m for m in routed
                                          if m.endswith(".serve_moe")}


def test_the_gdn_configuration_holds_every_published_key_and_the_cut():
    """Every number of the catalog row's config under its own key, as
    published, but the three keys the cut changes, which `reduced` names
    and `published` states."""
    conf = harness.load_cell(GDN_CELL)["conf"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: conf[k] for k in published} == published
    assert (conf["n_layer"], conf["num_experts"], conf["vocab_size"]) \
        == (8, 128, 37_984)
    assert conf["published"] == {"num_hidden_layers": 48,
                                 "num_experts": 512, "vocab_size": 151_936}
    assert (conf["router_num_experts"], conf["expert_first"]) == (512, 0)
    assert conf["reduced"] == ["n_layer", "num_experts", "vocab_size"]
    # by hand: 6 GDN and 2 attention layers, a quarter of the vocabulary
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2 * 2048
    experts = 128 * 3 * 2048 * 512
    total = 6 * (gdn + ffn + experts) + 2 * (attn + ffn + experts) \
        + 2 * 37_984 * 2048 + 2048
    assert total == 3_667_251_328 == conf["parameters"]["total"]
    mix = harness.load_cell(GDN_CELL)["mix"]
    assert (mix["clients"], mix["system_tokens"], mix["greedy_share"]) \
        == (32, 0, 0.5)
    assert (mix["user_tokens"]["median"], mix["user_tokens"]["max"],
            mix["output_tokens"]["max"]) == (4096, 15360, 1024)


SAMBAY_CELL = "serve-phi4-mini-flash-agent16k"
SAMBAY_CONFIG = "phi-4-mini-flash-reasoning"


def test_the_sambay_cell_is_entered_as_the_issue_names_it(manifest):
    """One configuration and one cell, appended last; the four readers of
    its own; the serve cells' span and idle readers and the attention
    kernel's roofline; no tail among its end-to-end metrics."""
    config, cell = manifest["configs"][-1], manifest["workloads"][-1]
    assert config["name"] == SAMBAY_CONFIG and config["reduced"] == []
    assert config["source"] == ("https://huggingface.co/microsoft/"
                                "Phi-4-mini-flash-reasoning/blob/main/"
                                "config.json")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (SAMBAY_CELL, SAMBAY_CONFIG, "agent-turns-16k", 1)
    e2e = {m["name"] for m in harness.cell_metrics(manifest, SAMBAY_CELL,
                                                   "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    mine = {m["name"] for m in harness.cell_metrics(
        manifest, SAMBAY_CELL, "per_layer", sorted(e2e))}
    only_here = {m["name"] for m in manifest["per_layer"]
                 if m.get("workloads") == [SAMBAY_CELL]}
    assert only_here == {"step_mfu.serve_sambay",
                         "shared_kv_roofline.serve_sambay",
                         "selective_step_ms_per_tick.serve_sambay",
                         "selective_scan_roofline.serve_sambay"}
    gdn = {m["name"] for m in harness.cell_metrics(
        manifest, GDN_CELL, "per_layer", ["serve_tokens_per_s", "setup_s"])}
    assert {m for m in gdn if m.endswith(".serve")} <= mine


def test_the_sambay_configuration_holds_every_published_key_whole():
    """Every number of the catalog row's config under its own key, as
    published, nothing cut; the sizes the config lacks stated beside them;
    the whole model's count by hand."""
    conf = harness.load_cell(SAMBAY_CELL)["conf"]
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: conf[k] for k in published} == published
    assert conf["published"] == published and conf["reduced"] == []
    assert (conf["mamba_d_state"], conf["mamba_d_conv"],
            conf["mamba_expand"], conf["mamba_dt_rank"]) == (16, 4, 2, 160)
    # by hand, this repo's bytes: 9 Mamba-1, 9 attention layers that store
    # KV, 7 cross-attention and 7 GMU layers, each with its MLP and two
    # LayerNorms; the tied embedding; the final LayerNorm
    around = 3 * 2560 * 10240 + 4 * 2560
    mamba = 2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 \
        + 5120 + 16 * 5120 + 5120 + 5120 * 2560
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    cross = 2 * 2560 * 2560 + 2 * 2560 + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    assert (mamba, attn, cross, gmu) \
        == (41_241_600, 19_668_864, 13_112_704, 26_214_400)
    total = 9 * (mamba + around) + 9 * (attn + around) \
        + 7 * (cross + around) + 7 * (gmu + around) + 200064 * 2560 \
        + 2 * 2560
    assert total == 3_852_562_944 == conf["parameters"]["total"]
    assert conf["parameters"]["bytes_bfloat16"] == 7_705_125_888
    mix = harness.load_cell(SAMBAY_CELL)["mix"]
    assert (mix["clients"], mix["system_tokens"], mix["greedy_share"]) \
        == (48, 0, 0.5)
    assert mix["user_tokens"] == {"distribution": "lognormal",
                                  "median": 6144, "sigma": 0.7,
                                  "min": 1024, "max": 15360}
    assert mix["output_tokens"] == {"distribution": "lognormal",
                                    "median": 512, "sigma": 0.5,
                                    "min": 128, "max": 1024}
