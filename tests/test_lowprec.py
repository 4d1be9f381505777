"""Low-precision plane (ISSUE 15): calibrated int8 serving + bf16
loss-scaled training (ops/lowprec.py + etl/calibrate.py).

Contracts:

  * int8 accuracy — QuantizedNet output stays within the
    DL4J_TPU_QUANT_MAX_DELTA gate of the f32 record on an MLP and on a
    conv net (where only the dense head quantizes — per-layer fallback);
  * fail-safe gate — a quantized record past the bar lands BROKEN
    through ModelRegistry.load's isolation and the serving default never
    moves (the PR 8 rollback primitive, applied to precision);
  * bf16 loss scaling — training reaches f32-class loss; a chaos-forced
    overflow (resilience/chaos.LowPrecChaos, config-driven never
    ambient) halves the scale and SKIPS the step (master weights
    untouched); clean streaks double the scale on schedule;
  * kill/resume — bf16 training killed at step k and resumed is
    BIT-exact vs uninterrupted (the loss-scale state rides the
    checkpoint through training_state());
  * flagships — TransformerLM carries the scale inside the opt tree
    (save/load round-trips it); the ring/pipeline paths reject the knob
    loudly instead of silently dropping it;
  * serving — DL4J_TPU_SERVE_KV_DTYPE=bf16 halves kv_block_bytes so the
    same HBM budget admits ~2x tokens, and the paged tick takes the
    gather path (kernel verdicts were measured at compute dtype).

Reference anchor: the reference's only dtype story is the global ND4J
buffer type switch (SURVEY.md, nd4j-api DataBuffer.Type) — calibration,
accuracy gating and loss scaling are beyond-parity.
"""

import os
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.etl.calibrate import (
    QuantCalibrator,
    QuantSpec,
    quant_spec_from_json,
)
from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import env, lowprec
from deeplearning4j_tpu.resilience import (
    ChaosConfig,
    ChaosMonkey,
    CheckpointManager,
    InjectedKill,
    LowPrecChaos,
    LowPrecChaosConfig,
    ResilientTrainer,
)

ENV_BF16 = "DL4J_TPU_BF16"
ENV_SCALE = "DL4J_TPU_LOSS_SCALE"
ENV_QUANT = "DL4J_TPU_QUANT"
ENV_DELTA = "DL4J_TPU_QUANT_MAX_DELTA"
ENV_KV = "DL4J_TPU_SERVE_KV_DTYPE"

_RNG = np.random.default_rng(0)
X = _RNG.standard_normal((48, 6)).astype(np.float32)
Y = np.eye(3, dtype=np.float32)[_RNG.integers(0, 3, 48)]


def build_mln() -> MultiLayerNetwork:
    conf = (
        NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
        .updater("adam").list()
        .layer(0, DenseLayer(n_in=6, n_out=8, activation="tanh"))
        .layer(1, OutputLayer(n_in=8, n_out=3, activation="softmax",
                              loss_function="mcxent"))
        .build()
    )
    return MultiLayerNetwork(conf)


def build_cg() -> ComputationGraph:
    conf = (
        NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
        .updater("adam").graph_builder().add_inputs("in")
        .add_layer("d", DenseLayer(n_in=6, n_out=8, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                      loss_function="mcxent"), "d")
        .set_outputs("out").build()
    )
    return ComputationGraph(conf)


def build_conv_net() -> MultiLayerNetwork:
    """Conv stack + dense head (the LeNet shape at smoke scale): only the
    head is int8-eligible, the conv/pool layers must fall back."""
    from deeplearning4j_tpu.nn.conf import ConvolutionLayer, SubsamplingLayer
    from deeplearning4j_tpu.nn.conf.preprocessors import (
        CnnToFeedForwardPreProcessor,
    )

    conf = (
        NeuralNetConfiguration.builder().seed(11).learning_rate(0.05)
        .updater("sgd").weight_init("xavier").list()
        .layer(0, ConvolutionLayer(n_in=1, n_out=3, kernel_size=(3, 3),
                                   stride=(1, 1), activation="relu"))
        .layer(1, SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                   stride=(2, 2)))
        .layer(2, OutputLayer(n_in=3 * 3 * 3, n_out=2, activation="softmax",
                              loss_function="mcxent"))
        .input_preprocessor(2, CnnToFeedForwardPreProcessor(3, 3, 3))
        .build()
    )
    return MultiLayerNetwork(conf).init(input_shape=(8, 8, 1))


def tiny_lm_cfg(**over):
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    kw = dict(vocab_size=29, d_model=16, n_layers=2, n_heads=2, d_ff=32,
              max_len=16, learning_rate=1e-3, seed=3, use_flash=False)
    kw.update(over)
    return TransformerConfig(**kw)


def params_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def fitted_net_and_spec():
    """A briefly-trained MLP plus its calibrated QuantSpec."""
    net = build_mln().init()
    for i in range(0, 48, 8):
        net.fit(X[i:i + 8], Y[i:i + 8])
    spec = QuantCalibrator().fit(
        net, ListDataSetIterator(X, Y, batch=8)).spec(net)
    return net, spec


# ---------------------------------------------------------------------------
# knob walk: every new knob reads through the ops/env.py table
# ---------------------------------------------------------------------------


class TestKnobs:
    def test_all_registered(self):
        for name in (ENV_QUANT, ENV_DELTA, ENV_BF16, ENV_SCALE, ENV_KV):
            assert env.is_registered(name), name

    def test_quant_mode(self, monkeypatch):
        monkeypatch.delenv(ENV_QUANT, raising=False)
        assert lowprec.quant_mode() == "auto"
        monkeypatch.setenv(ENV_QUANT, "0")
        assert lowprec.quant_mode() == "off"
        monkeypatch.setenv(ENV_QUANT, "force")
        assert lowprec.quant_mode() == "force"

    def test_loss_scale_spec(self, monkeypatch):
        monkeypatch.delenv(ENV_SCALE, raising=False)
        assert lowprec.loss_scale_config() == (32768.0, 2000)
        monkeypatch.setenv(ENV_SCALE, "1024:4")
        assert lowprec.loss_scale_config() == (1024.0, 4)
        monkeypatch.setenv(ENV_SCALE, "garbage:junk")
        assert lowprec.loss_scale_config() == (32768.0, 2000)

    def test_quant_max_delta(self, monkeypatch):
        monkeypatch.delenv(ENV_DELTA, raising=False)
        assert lowprec.quant_max_delta() == pytest.approx(0.05)
        monkeypatch.setenv(ENV_DELTA, "0.2")
        assert lowprec.quant_max_delta() == pytest.approx(0.2)

    def test_kv_dtype(self, monkeypatch):
        cfg = tiny_lm_cfg()
        monkeypatch.delenv(ENV_KV, raising=False)
        assert jnp.dtype(lowprec.kv_dtype(cfg)) == jnp.dtype(jnp.float32)
        monkeypatch.setenv(ENV_KV, "bf16")
        assert jnp.dtype(lowprec.kv_dtype(cfg)) == jnp.dtype(jnp.bfloat16)
        monkeypatch.setenv(ENV_KV, "f32")
        assert jnp.dtype(lowprec.kv_dtype(cfg)) == jnp.dtype(jnp.float32)


# ---------------------------------------------------------------------------
# int8 value contracts
# ---------------------------------------------------------------------------


class TestInt8:
    def test_quantize_weight_roundtrip(self):
        w = _RNG.standard_normal((6, 4)).astype(np.float32)
        wq, scale = lowprec.quantize_weight(w)
        assert np.asarray(wq).dtype == np.int8
        assert np.abs(np.asarray(wq)).max() <= 127
        deq = np.asarray(wq, np.float32) * np.asarray(scale)
        # per-channel symmetric scheme: worst-case error is half an LSB
        assert np.max(np.abs(deq - w)) <= float(np.asarray(scale).max())

    def test_mlp_within_gate(self):
        net, spec = fitted_net_and_spec()
        qnet = lowprec.QuantizedNet(net, spec)
        assert qnet.quantized_layers() == [0, 1]
        delta = np.max(np.abs(np.asarray(qnet.output(X))
                              - np.asarray(net.output(X))))
        assert 0.0 < delta <= lowprec.quant_max_delta()

    def test_conv_head_quantizes_rest_falls_back(self):
        net = build_conv_net()
        xs = _RNG.standard_normal((16, 8, 8, 1)).astype(np.float32)
        spec = QuantCalibrator().fit(net, xs).spec(net)
        qnet = lowprec.QuantizedNet(net, spec)
        assert qnet.quantized_layers() == [2]  # conv + pool fall back
        delta = np.max(np.abs(np.asarray(qnet.output(xs))
                              - np.asarray(net.output(xs))))
        assert delta <= lowprec.quant_max_delta()

    def test_calibrator_audit_and_gate_sample(self):
        net, spec = fitted_net_and_spec()
        assert spec.sample is not None and spec.sample.shape == (32, 6)
        assert all(s is not None and s > 0 for s in spec.act_scales)
        assert all(a["absmax"] >= a["std"] for a in spec.audit)

    def test_spec_json_roundtrip(self):
        _, spec = fitted_net_and_spec()
        back = quant_spec_from_json(spec.to_json())
        assert back.act_scales == pytest.approx(spec.act_scales)
        np.testing.assert_array_equal(back.sample, spec.sample)
        assert back.meta["layers"] == spec.meta["layers"]

    def test_quant_json_rides_the_model_zip(self, tmp_path):
        from deeplearning4j_tpu.utils.serialization import (
            ModelSerializer,
            read_quant,
        )

        net, spec = fitted_net_and_spec()
        path = str(tmp_path / "model.zip")
        ModelSerializer.write_model(net, path, quant=spec)
        with zipfile.ZipFile(path) as z:
            assert "quant.json" in z.namelist()
        back = read_quant(path)
        assert back.act_scales == pytest.approx(spec.act_scales)


# ---------------------------------------------------------------------------
# registry gate: fail-safe by construction
# ---------------------------------------------------------------------------


class TestQuantGate:
    def test_auto_pass_serves_int8(self):
        from deeplearning4j_tpu.serving.registry import ModelRegistry

        net, spec = fitted_net_and_spec()
        reg = ModelRegistry()
        rec = reg.load("m", model=net, quant=spec)
        assert rec.precision == "int8"
        assert rec.quant["verdict"] == "ok"
        assert rec.quant["delta"] <= rec.quant["max_delta"]
        assert rec.quant["layers"] == [0, 1]
        desc = [d for d in reg.describe() if d["version"] == rec.version][0]
        assert desc["precision"] == "int8" and desc["quant"]["verdict"] == "ok"

    def test_gate_failure_lands_broken_default_unmoved(self, monkeypatch):
        from deeplearning4j_tpu.serving.registry import ModelRegistry

        net, spec = fitted_net_and_spec()
        reg = ModelRegistry()
        reg.load("m", model=net)
        reg.serve("m", 1)
        # an impossible bar: any real rounding error trips the gate
        monkeypatch.setenv(ENV_DELTA, "1e-12")
        with pytest.raises(lowprec.QuantGateError):
            reg.load("m", model=build_mln().init(), quant=spec)
        default = reg.default()
        assert (default.name, default.version) == ("m", 1)
        assert default.precision == "f32"
        broken = [d for d in reg.describe() if d["version"] == 2]
        assert broken and broken[0]["state"] == "broken"
        assert "gate failed" in broken[0]["error"]

    def test_off_serves_f32(self, monkeypatch):
        from deeplearning4j_tpu.serving.registry import ModelRegistry

        net, spec = fitted_net_and_spec()
        monkeypatch.setenv(ENV_QUANT, "0")
        rec = ModelRegistry().load("m", model=net, quant=spec)
        assert rec.precision == "f32" and rec.quant is None

    def test_force_past_bar_is_audited(self, monkeypatch):
        from deeplearning4j_tpu.serving.registry import ModelRegistry

        net, spec = fitted_net_and_spec()
        monkeypatch.setenv(ENV_DELTA, "1e-12")
        monkeypatch.setenv(ENV_QUANT, "force")
        rec = ModelRegistry().load("m", model=net, quant=spec)
        assert rec.precision == "int8"
        assert rec.quant["verdict"] == "forced"
        assert rec.quant["delta"] > 1e-12  # measured and reported, not hidden

    def test_sampleless_spec_is_ungated_f32(self):
        from deeplearning4j_tpu.serving.registry import ModelRegistry

        net, spec = fitted_net_and_spec()
        blind = QuantSpec(spec.act_scales, sample=None)
        rec = ModelRegistry().load("m", model=net, quant=blind)
        assert rec.precision == "f32"
        assert rec.quant["verdict"] == "ungated"

    def test_zip_quant_autopickup(self, tmp_path):
        from deeplearning4j_tpu.serving.registry import ModelRegistry
        from deeplearning4j_tpu.utils.serialization import ModelSerializer

        net, spec = fitted_net_and_spec()
        path = str(tmp_path / "model.zip")
        ModelSerializer.write_model(net, path, quant=spec)
        rec = ModelRegistry().load("m", model_path=path)
        assert rec.precision == "int8" and rec.quant["verdict"] == "ok"


# ---------------------------------------------------------------------------
# bf16 loss-scaled training
# ---------------------------------------------------------------------------


class TestBf16Training:
    def test_reaches_f32_class_loss(self, monkeypatch):
        f32 = build_mln().init()
        f32_losses = [f32.fit(X[i % 48:i % 48 + 8], Y[i % 48:i % 48 + 8])
                      for i in range(0, 160, 8)]
        monkeypatch.setenv(ENV_BF16, "1")
        bf16 = build_mln().init()
        bf16_losses = [bf16.fit(X[i % 48:i % 48 + 8], Y[i % 48:i % 48 + 8])
                       for i in range(0, 160, 8)]
        assert all(np.isfinite(bf16_losses))
        assert bf16_losses[-1] < bf16_losses[0]
        # bf16-class convergence: same neighborhood as the f32 run
        assert abs(bf16_losses[-1] - f32_losses[-1]) < 0.15
        # master weights stay f32; the scale state never skipped
        assert all(np.asarray(l).dtype == np.float32
                   for l in jax.tree_util.tree_leaves(bf16.params))
        snap = bf16.loss_scale
        assert snap["skipped"] == 0

    def test_scale_doubles_on_clean_streak(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        monkeypatch.setenv(ENV_SCALE, "1024:4")
        net = build_mln().init()
        for i in range(8):  # 8 clean steps at growth 4 = two doublings
            net.fit(X[:8], Y[:8])
        snap = net.loss_scale
        assert snap["scale"] == 4096.0
        assert snap["skipped"] == 0 and snap["good"] == 0

    def test_chaos_overflow_halves_and_skips(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        monkeypatch.setenv(ENV_SCALE, "1024:1000")  # no doublings in-window
        chaos = LowPrecChaos(LowPrecChaosConfig(overflow_at_step=4))
        net = build_mln().init()
        before = None
        for step in range(1, 9):
            feats = chaos.poison(step, X[:8])
            if step == 4:
                before = jax.tree_util.tree_map(np.asarray, net.params)
            loss = net.fit(feats, Y[:8])
        assert chaos.log == [(4, "overflow:inf")]
        snap = net.loss_scale
        assert snap["skipped"] == 1
        assert snap["scale"] == 512.0  # exactly one halving
        # the poisoned step was SKIPPED: master weights untouched by it
        # (steps 5..8 then moved them on)
        assert np.isfinite(loss)
        assert all(np.isfinite(np.asarray(l)).all()
                   for l in jax.tree_util.tree_leaves(net.params))
        assert before is not None
        # loss_scale property syncs the skip count into dispatch_stats
        assert net.dispatch_stats.snapshot()["loss_scale_skips"] == 1

    def test_skip_leaves_master_weights_untouched(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        net = build_mln().init()
        net.fit(X[:8], Y[:8])  # one clean step so state is warm
        frozen = jax.tree_util.tree_map(np.asarray, net.params)
        upd_frozen = jax.tree_util.tree_map(np.asarray, net.updater_state)
        bad = LowPrecChaos(
            LowPrecChaosConfig(overflow_at_step=1, mode="nan")).poison(
                1, X[:8])
        net.fit(bad, Y[:8])
        assert params_equal(net.params, frozen)
        assert params_equal(net.updater_state, upd_frozen)
        assert net.loss_scale["skipped"] == 1

    def test_cg_bf16_trains(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        cg = build_cg().init()
        losses = [cg.fit(X[:16], Y[:16]) for _ in range(6)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        assert cg.loss_scale["skipped"] == 0

    def test_fit_batches_scan_carries_scale(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        monkeypatch.setenv(ENV_SCALE, "1024:2")
        net = build_mln().init()
        xs = np.stack([X[:8]] * 4)
        ys = np.stack([Y[:8]] * 4)
        losses = net.fit_batches(xs, ys)
        assert np.isfinite(np.asarray(losses)).all()
        # the scale state advances INSIDE the scan: 4 clean steps at
        # growth 2 = two doublings
        assert net.loss_scale["scale"] == 4096.0


# ---------------------------------------------------------------------------
# bf16 kill/resume: bit-exact, loss scale rides the checkpoint
# ---------------------------------------------------------------------------


class TestBf16Resume:
    def test_resume_equivalence_bf16(self, monkeypatch, tmp_path):
        monkeypatch.setenv(ENV_BF16, "1")
        monkeypatch.setenv(ENV_SCALE, "1024:4")  # scale moves mid-run

        def mk_it():
            return ListDataSetIterator(X, Y, batch=8)

        baseline = ResilientTrainer(build_mln())
        baseline.fit(mk_it(), num_epochs=3)

        mgr = CheckpointManager(str(tmp_path), every_steps=4, keep_last=3)
        killed = ResilientTrainer(
            build_mln(), mgr, chaos=ChaosMonkey(ChaosConfig(kill_at_step=10)))
        with pytest.raises(InjectedKill):
            killed.fit(mk_it(), num_epochs=3)
        mgr.close()

        mgr2 = CheckpointManager(str(tmp_path), every_steps=4, keep_last=3)
        resumed = ResilientTrainer(build_mln(), mgr2)
        resumed.fit(mk_it(), num_epochs=3)
        mgr2.close()

        assert resumed.resumed_step is not None
        stitched = killed.losses[:resumed.resumed_step] + resumed.losses
        assert stitched == baseline.losses
        assert params_equal(baseline.net.params, resumed.net.params)
        assert params_equal(baseline.net.updater_state,
                            resumed.net.updater_state)
        # the loss-scale state itself resumed exactly
        assert baseline.net.loss_scale == resumed.net.loss_scale
        assert baseline.net.loss_scale["scale"] > 1024.0  # it DID move


# ---------------------------------------------------------------------------
# flagships: the scale rides the opt tree
# ---------------------------------------------------------------------------


class TestFlagshipBf16:
    def test_transformer_opt_carries_scale(self, monkeypatch, tmp_path):
        monkeypatch.setenv(ENV_BF16, "1")
        from deeplearning4j_tpu.models.transformer import TransformerLM

        lm = TransformerLM(tiny_lm_cfg())
        assert set(lowprec.OPT_SCALE_KEYS) <= set(lm.opt)
        rng = np.random.default_rng(5)
        toks = rng.integers(0, 29, (4, 16)).astype(np.int32)
        tgts = np.roll(toks, -1, axis=1)
        losses = [float(lm.fit(toks, tgts)) for _ in range(3)]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        assert int(lm.opt["t"]) == 3
        assert int(lm.opt["ls_skipped"]) == 0
        assert all(np.asarray(l).dtype == np.float32
                   for l in jax.tree_util.tree_leaves(lm.params))

        # save/load round-trips the scale state through the opt npz
        path = str(tmp_path / "lm.zip")
        lm.save(path)
        lm2 = TransformerLM.load(path)
        assert float(lm2.opt["loss_scale"]) == float(lm.opt["loss_scale"])
        assert int(lm2.opt["t"]) == 3
        # resumed step is bit-exact vs continuing the original
        l_a = float(lm.fit(toks, tgts))
        l_b = float(lm2.fit(toks, tgts))
        assert l_a == l_b

    def test_transformer_accum_composes(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        from deeplearning4j_tpu.models.transformer import TransformerLM

        lm = TransformerLM(tiny_lm_cfg(accum_steps=2))
        rng = np.random.default_rng(6)
        toks = rng.integers(0, 29, (4, 16)).astype(np.int32)
        loss = float(lm.fit(toks, np.roll(toks, -1, axis=1)))
        assert np.isfinite(loss)
        assert int(lm.opt["ls_skipped"]) == 0

    def test_bert_opt_carries_scale(self, monkeypatch):
        monkeypatch.setenv(ENV_BF16, "1")
        from deeplearning4j_tpu.models.bert import BertConfig, BertMLM

        mlm = BertMLM(BertConfig(vocab_size=31, d_model=16, n_layers=1,
                                 n_heads=2, d_ff=32, max_len=16,
                                 learning_rate=1e-3, seed=4))
        assert set(lowprec.OPT_SCALE_KEYS) <= set(mlm.opt)
        rng = np.random.default_rng(7)
        tokens = rng.integers(4, 31, (4, 16)).astype(np.int32)
        loss = float(mlm.fit(tokens))
        assert np.isfinite(loss)
        assert int(mlm.opt["ls_skipped"]) == 0

    def test_parallel_paths_reject_loudly(self, monkeypatch):
        from deeplearning4j_tpu.models.transformer import _reject_lowprec

        monkeypatch.delenv(ENV_BF16, raising=False)
        _reject_lowprec("sequence-parallel")  # off: no-op
        monkeypatch.setenv(ENV_BF16, "1")
        with pytest.raises(ValueError, match="sequence-parallel"):
            _reject_lowprec("sequence-parallel")


# ---------------------------------------------------------------------------
# serving plane: bf16 KV arena + precision surfacing
# ---------------------------------------------------------------------------


class TestKvDtype:
    def test_block_bytes_halve(self):
        from deeplearning4j_tpu.ops import memory as memory_mod

        cfg = tiny_lm_cfg()
        f32b = memory_mod.kv_block_bytes(cfg, 16, dtype=jnp.float32)
        bf16b = memory_mod.kv_block_bytes(cfg, 16, dtype=jnp.bfloat16)
        assert f32b == 2 * bf16b

    def test_same_budget_admits_2x_blocks(self):
        from deeplearning4j_tpu.ops import memory as memory_mod

        cfg = tiny_lm_cfg()
        # budget small enough that neither side hits the max_blocks clamp
        f32n = memory_mod.kv_arena_blocks(cfg, 16, hbm_gb=0.005,
                                          dtype=jnp.float32)
        bf16n = memory_mod.kv_arena_blocks(cfg, 16, hbm_gb=0.005,
                                           dtype=jnp.bfloat16)
        assert bf16n == 2 * f32n

    def test_paged_decoder_bf16_arena(self, monkeypatch):
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.serving.paged import (
            PagedDecoder,
            attention_path,
        )

        monkeypatch.setenv(ENV_KV, "bf16")
        lm = TransformerLM(tiny_lm_cfg(max_len=32))
        # off a chip every arena takes the gather path
        assert attention_path(lm._run_cfg, 8) == "gather"
        d = PagedDecoder(lm, block_tokens=8, n_blocks=16)
        try:
            assert d.kv_dtype == jnp.dtype(jnp.bfloat16)
            assert d.kv_capacity()["kv_dtype"] == "bfloat16"
            out = d.generate(np.asarray([[1, 5, 2, 9]]), 6, temperature=0.0)
            assert len(out[0]) == 6
        finally:
            d.stop()

    def test_precision_labels(self):
        net, spec = fitted_net_and_spec()
        assert lowprec.precision_of(net) == "f32"
        assert lowprec.precision_of(
            lowprec.QuantizedNet(net, spec)) == "int8"


class TestMemoryAccounting:
    def test_preflight_train_dtype_and_activation_halving(self, monkeypatch):
        from deeplearning4j_tpu.ops import memory as memory_mod

        # big enough that the ANALYTIC activation estimate is non-zero at
        # the report's GB rounding; measure_aot=False keeps it pure math
        cfg = tiny_lm_cfg(d_model=1024, n_layers=8, n_heads=8, d_ff=4096,
                          max_len=512, vocab_size=32000)
        monkeypatch.delenv(ENV_BF16, raising=False)
        _, f32r = memory_mod.transformer_preflight(
            cfg, 32, hbm_gb=16.0, measure_aot=False)
        monkeypatch.setenv(ENV_BF16, "1")
        _, bf16r = memory_mod.transformer_preflight(
            cfg, 32, hbm_gb=16.0, measure_aot=False)
        assert f32r["train_dtype"] == "f32"
        assert bf16r["train_dtype"] == "bf16"
        # bf16 item bytes halve the activation estimate
        assert bf16r["activations_gb_est"] == pytest.approx(
            f32r["activations_gb_est"] / 2, rel=0.01)
