"""The manifest checker: the committed BENCHMARK.json passes, and the faults
a PR was once lost to are caught."""
import copy
import json

import pytest

from perfbench import check_manifest, harness


@pytest.fixture()
def manifest():
    return harness.load_manifest()


def test_committed_manifest_is_sound(manifest):
    assert check_manifest.check(manifest) == []
    assert check_manifest.main([]) == 0


def test_every_layer_is_one_of_the_five(manifest):
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert layers <= {"engine", "decoder", "model_step", "kernels", "device"}


def test_set_up_says_where_it_went_in_the_serve_cells(manifest):
    rows = {m["name"]: m for m in manifest["per_layer"]}
    serve = [w["name"] for w in manifest["workloads"]
             if w["name"].startswith("serve")]
    for name in ("setup_build_s.serve", "setup_warm_s.serve"):
        m = rows[name]
        assert (m["moves"], m["layer"], m["source"], m["unit"]) == (
            "setup_s", "engine", "host_clock", "s")
        assert m["workloads"] == serve
    build, warm = (harness.load_reader(n).read for n in
                   ("setup_build_s.serve", "setup_warm_s.serve"))
    ctx = {"setup": {"imports_weights_s": 9.5, "engine_s": 0.5,
                     "warm_s": 4.0, "programs": {"programs": 12}}}
    assert build(ctx) == 10.0 and warm(ctx) == 4.0
    # a job that stamps nothing (the train job) gives no number
    assert build({}) is None and warm({}) is None


def test_the_serve_cells_say_when_their_window_opens(manifest):
    for w in manifest["workloads"]:
        cell = harness.load_cell(w["name"])
        if cell["job"].startswith("serve"):
            assert cell["warm_in_seconds"] == 3
            assert "warm_in_seconds" in cell["assumed"]
            assert "window after a 3 s warm-in" in w["why"]
    tails = [m for m in manifest["end_to_end"]
             if m["name"].startswith("ttft_")]
    assert len(tails) == 1 and tails[0]["bound"] <= 0.1
    moved = {m["moves"] for m in manifest["per_layer"]
             if m["moves"].startswith("ttft_")}
    assert moved == {tails[0]["name"]}


def _broken(manifest, edit):
    m = copy.deepcopy(manifest)
    edit(m)
    return check_manifest.check(m)


@pytest.mark.parametrize("edit, word", [
    (lambda m: m["per_layer"][0].update(layer="model step"), "layer"),
    (lambda m: m["per_layer"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["per_layer"][0].update(moves="no_such_metric"), "moves"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m["end_to_end"][0].update(bound={"cell": 0.01}), "bound"),
    (lambda m: m["end_to_end"][0].update(absolute_bound=3.0), "not allowed"),
    (lambda m: m["workloads"][0].update(name="no-such-cell"), "does not exist"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["configs"][-1].update(reduced=["n_embd"]), "width"),
    (lambda m: m["per_layer"][0].update(why="because"), "not allowed"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m.update(command=["python3", "../x.py"]), "command"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
])
def test_faults_are_caught(manifest, edit, word):
    bad = _broken(manifest, edit)
    assert bad and any(word in b for b in bad), bad


def test_a_metric_must_be_reported_where_it_moves(manifest):
    # a per-layer metric listing a cell that does not report its `moves`
    def edit(m):
        m["end_to_end"][0]["workloads"] = [m["workloads"][0]["name"]]
        m["per_layer"][0]["workloads"] = [w["name"] for w in m["workloads"]]
    if len(manifest["workloads"]) < 2:
        pytest.skip("needs two cells")
    bad = _broken(manifest, edit)
    assert any("do not report" in b for b in bad), bad


def test_main_fails_on_a_broken_file(tmp_path, manifest):
    manifest["per_layer"][0]["layer"] = "model step"
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(manifest))
    # files are looked up beside the manifest: none there, and the layer
    assert check_manifest.main([str(p)]) == 1
