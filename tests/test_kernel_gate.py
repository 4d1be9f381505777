"""Measured-win gate (ops/kernel_gate.py): default-on requires a committed
on-chip PALLAS_BENCH.json row beating the XLA twin."""

import json

import pytest

from deeplearning4j_tpu.ops import kernel_gate


@pytest.fixture
def artifact(tmp_path, monkeypatch):
    path = tmp_path / "PALLAS_BENCH.json"
    monkeypatch.setattr(kernel_gate, "_ARTIFACT", str(path))
    kernel_gate.reload()
    yield path
    kernel_gate.reload()


def test_no_artifact_defaults_off(artifact):
    assert not kernel_gate.measured_win("attention", "ring_local_flash")
    assert kernel_gate.measured_win("attention", "x", default=True)


def test_tpu_win_row_enables(artifact):
    artifact.write_text(json.dumps(
        {"attention": {"ring_local_flash":
                       {"speedup": 1.4, "backend": "tpu"}}}))
    kernel_gate.reload()
    assert kernel_gate.measured_win("attention", "ring_local_flash")


def test_loss_row_disables(artifact):
    artifact.write_text(json.dumps(
        {"attention": {"ring_local_flash":
                       {"speedup": 0.9, "backend": "tpu"}}}))
    kernel_gate.reload()
    assert not kernel_gate.measured_win("attention", "ring_local_flash")


def test_cpu_or_interpret_rows_do_not_count(artifact):
    artifact.write_text(json.dumps(
        {"attention": {"a": {"speedup": 2.0, "backend": "cpu"},
                       "b": {"speedup": 2.0, "interpret": True,
                             "backend": "tpu"}}}))
    kernel_gate.reload()
    assert not kernel_gate.measured_win("attention", "a")
    assert not kernel_gate.measured_win("attention", "b")


def test_record_win_merges_and_enables(artifact):
    artifact.write_text(json.dumps(
        {"lstm_legacy": {"keep": {"speedup": 9.9}}}))
    kernel_gate.reload()
    kernel_gate.record_win("attention", "masked_flash",
                           {"speedup": 1.2, "backend": "tpu"})
    assert kernel_gate.measured_win("attention", "masked_flash")
    data = json.loads(artifact.read_text())
    assert data["lstm_legacy"]["keep"]["speedup"] == 9.9  # preserved


def test_force_env_overrides(artifact, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_FORCE", "1")
    assert kernel_gate.measured_win("attention", "anything")


class TestLstmWinTable:
    def test_nearest_shape_class_decides(self, artifact):
        artifact.write_text(json.dumps({"lstm": {
            "small": {"n": 32, "t": 128, "h": 128, "speedup": 0.93,
                      "backend": "tpu", "interpret": False},
            "large": {"n": 128, "t": 512, "h": 512, "speedup": 2.2,
                      "backend": "tpu", "interpret": False},
        }}))
        kernel_gate.reload()
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_kernel_wins

        assert not lstm_kernel_wins(32, 128, 128)   # nearest: losing row
        assert lstm_kernel_wins(128, 512, 512)      # nearest: winning row
        assert lstm_kernel_wins(256, 512, 1024)     # beyond largest: wins

    def test_legacy_cases_rows_parse(self, artifact):
        artifact.write_text(json.dumps({"cases": [
            {"n": 64, "t": 256, "h": 256, "scan_ms": 2.4, "pallas_ms": 1.5,
             "pallas_interpret_mode": False,
             "scan_speedup_over_pallas": 0.63},
        ]}))
        kernel_gate.reload()
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_kernel_wins

        assert lstm_kernel_wins(64, 256, 256)

    def test_no_rows_defaults_off(self, artifact):
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_kernel_wins

        assert not lstm_kernel_wins(64, 256, 256)

    def test_committed_artifact_small_class_off_large_on(self):
        """The REAL committed artifact (round-2 chip rows): scan won the
        smallest class (ratio 1.07), kernel won the larger two."""
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_kernel_wins

        kernel_gate.reload()
        assert not lstm_kernel_wins(32, 128, 128)
        assert lstm_kernel_wins(64, 256, 256)
        assert lstm_kernel_wins(128, 512, 512)


def test_bench_ring_attention_leg_executes():
    """Smoke the on-chip ring bench leg here (interpret kernel, tiny
    shapes, CPU) so a code bug can't burn a chip run. The recorded row is
    redirected to a temp artifact."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        import deeplearning4j_tpu.ops.kernel_gate as kg

        old = kg._ARTIFACT
        kg._ARTIFACT = f"{d}/PALLAS_BENCH.json"
        kg.reload()
        try:
            out = bench.bench_ring_attention(n=1, t=256, h=2, d=32, steps=1,
                                             interpret=True)
        finally:
            kg._ARTIFACT = old
            kg.reload()
    assert "ring_einsum_ms" in out and "ring_flash_ms" in out
    assert out["flash_speedup"] > 0
