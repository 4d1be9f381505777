"""bench.py contracts that need no chip.

HBM preflight for the MFU-chase leg: an untested d2048 L8 b16 config must
not OOM away a chip run. The estimator must be exact on params/optimizer
(jax.eval_shape against the real init) and conservative enough to downsize
the batch.

The command line (ISSUE 21): `--only` legs run in this process, one JSON
line, and the exit status is non-zero when a leg fails or finds no chip it
needs — a CPU number never lands under a device metric's name, and a failed
leg never exits 0.
"""

import json

import numpy as np
import pytest

import bench


class TestTransformerHbmPreflight:
    def test_big_config_b16_rejected_b8_accepted(self):
        """The round-3 planned config (b16 d2048 L8) estimates past 16GB —
        exactly the first-contact OOM the preflight exists to prevent —
        while b8 fits with headroom."""
        fits16, rep16 = bench.transformer_hbm_preflight(16, 1024, 2048, 8, 32)
        fits8, rep8 = bench.transformer_hbm_preflight(8, 1024, 2048, 8, 32)
        assert not fits16
        assert fits8
        assert rep16["total_gb_est"] > rep8["total_gb_est"]

    def test_param_bytes_exact(self):
        """params_gb comes from eval_shape on the real init_params — cross
        check against a hand count of the dominant matrices (embedding +
        per-layer attn/mlp) to within 5% (norms/bias are the remainder)."""
        _, rep = bench.transformer_hbm_preflight(8, 1024, 2048, 8, 32)
        d, v, layers = 2048, 8192, 8
        dominant = v * d + layers * (4 * d * d + 2 * d * 4 * d)
        assert rep["params_gb"] >= dominant * 4 / 2**30 * 0.95
        assert rep["opt_gb"] >= 2 * rep["params_gb"] * 0.95  # adam m+v

    def test_scales_down_with_batch(self):
        ests = [bench.transformer_hbm_preflight(b, 1024, 2048, 8, 32)[1][
            "total_gb_est"] for b in (16, 8, 4)]
        assert ests[0] > ests[1] > ests[2]
        # fixed state (params+opt+grads) is batch-independent
        fixed = [bench.transformer_hbm_preflight(b, 1024, 2048, 8, 32)[1]
                 for b in (16, 4)]
        for key in ("params_gb", "opt_gb", "grads_gb"):
            assert fixed[0][key] == fixed[1][key]

    def test_tiny_config_fits_easily(self):
        fits, rep = bench.transformer_hbm_preflight(4, 256, 256, 2, 4,
                                                    vocab=1024)
        assert fits
        assert rep["total_gb_est"] < 1.0

    def test_b32_d2048_accepted_under_remat(self):
        """ISSUE 4 acceptance: the b32 config that the estimate puts past
        usable HBM un-rematted is accepted under a remat rung."""
        fits_none, _ = bench.transformer_hbm_preflight(32, 1024, 2048, 8, 32)
        fits_block, rep = bench.transformer_hbm_preflight(
            32, 1024, 2048, 8, 32, remat="block")
        assert not fits_none
        assert fits_block
        assert rep["remat"] == "block" and rep["batch"] == 32

    def test_auto_fit_arms_b32_with_remat(self):
        """The transformer_lm_big ladder: auto-fit keeps the largest
        batch by climbing the remat ladder instead of shrinking to b16."""
        from deeplearning4j_tpu.ops.memory import auto_fit_transformer

        cfg = bench._transformer_bench_cfg(1024, 2048, 8, 32)
        choice = auto_fit_transformer(cfg, batches=(32, 16, 8, 4),
                                      accum_steps=(1,), hbm_gb=16.0)
        assert choice is not None
        assert choice["batch"] == 32
        assert choice["remat"] in ("dots", "block")

    def test_accum_shrinks_activation_estimate(self):
        """accum_steps sizes activations/logits per microbatch (and
        doubles the grad tree) — the composing axis of the auto-fit
        sizer."""
        _, rep1 = bench.transformer_hbm_preflight(16, 1024, 2048, 8, 32)
        _, rep4 = bench.transformer_hbm_preflight(16, 1024, 2048, 8, 32,
                                                  accum_steps=4)
        assert rep4["activations_gb_est"] < rep1["activations_gb_est"]
        assert rep4["logits_gb"] < rep1["logits_gb"]
        assert rep4["grads_gb"] == 2 * rep1["grads_gb"]


# host-side legs earlier PRs registered (control planes, schedulers, byte
# contracts): each must be runnable by name and must NOT demand a chip
HOST_SIDE_LEGS = ("autoscale", "serving_decode", "decode_amortize",
                  "serving_fleet", "serving_resilience", "serving_mesh",
                  "checkpoint_overhead", "input_pipeline", "elastic_dp",
                  "online_loop", "lowprec", "retrieval")


class TestBenchCommandLine:
    @pytest.mark.parametrize("leg", HOST_SIDE_LEGS)
    def test_host_side_leg_registered(self, leg):
        assert leg in bench._legs(quick=True)
        assert leg not in bench._CHIP_LEGS

    def test_chip_legs_are_known_legs(self):
        assert bench._CHIP_LEGS <= set(bench._legs(quick=False))

    def test_chip_leg_without_a_chip_exits_nonzero(self, capsys):
        """A leg that measures the device, on this CPU backend: refused
        before it runs, reported, and the process status says so."""
        rc = bench.main(["--only=mxu_calibration", "--quick"])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc != 0
        assert out["failed"] == ["mxu_calibration"]
        assert "platform 'cpu'" in out["mxu_calibration"]["error"]
        assert out["mxu_calibration"]["device"]["platform"] == "cpu"

    def test_raising_leg_exits_nonzero(self, monkeypatch, capsys):
        def boom(**kw):
            raise RuntimeError("leg blew up")

        monkeypatch.setattr(
            bench, "_legs", lambda quick: {"native_feed": (boom, {})})
        rc = bench.main(["--only=native_feed"])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1 and out["failed"] == ["native_feed"]
        assert "leg blew up" in out["native_feed"]["error"]

    def test_error_row_exits_nonzero(self, monkeypatch, capsys):
        """A leg that RETURNS an error row (the child-process legs do) is
        a failure too — never a field of a JSON that exits 0."""
        monkeypatch.setattr(
            bench, "_legs",
            lambda quick: {"native_feed": (lambda: {"error": "child"}, {}),
                           "lowprec": (lambda: {"rows": 1}, {})})
        rc = bench.main(["--only=native_feed", "--only=lowprec"])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 1 and out["failed"] == ["native_feed"]
        assert out["lowprec"]["rows"] == 1

    def test_passing_leg_exits_zero(self, monkeypatch, capsys):
        monkeypatch.setattr(
            bench, "_legs", lambda quick: {"lowprec": (lambda: {"r": 1}, {})})
        assert bench.main(["--only=lowprec"]) == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["failed"] == []

    def test_only_is_required_and_checked(self, capsys):
        assert bench.main([]) == 2
        assert bench.main(["--only=no_such_leg"]) == 2
        assert "no_such_leg" in capsys.readouterr().err

    def test_backend_choosing_child_refused_after_jax_import(self):
        """One process per chip: this process has imported jax, so a child
        that would take whatever backend jax gives it is not started."""
        import jax  # noqa: F401 — the point of the test

        parsed, err = bench._run_subprocess_json(
            ["/bin/false"], 5, picks_backend=True)
        assert parsed is None and "already imported jax" in err
        row = bench.bench_dispatch_overhead(steps=1)
        assert "refused" in row["error"]

    def test_unknown_device_kind_has_no_peak(self, monkeypatch):
        """_peak_flops_per_chip reads the one peaks table; on this CPU
        backend the device_kind is not in it and that is an error, not a
        197e12 default."""
        with pytest.raises(ValueError, match="not in the peaks table"):
            bench._peak_flops_per_chip()
