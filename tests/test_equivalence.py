"""North-star equivalence harness tests (BASELINE.json north_star; SURVEY.md
§7 "Hard parts"). On the CPU-only test environment the CPU-vs-default
comparison degenerates to a two-run determinism check: curves must match
EXACTLY (bitwise) — the strongest form of the bar, validating that RNG
streams and compiled programs are reproducible. The real CPU-vs-TPU
deviation is measured by bench.py on hardware."""

import numpy as np

from deeplearning4j_tpu.utils.equivalence import (
    char_batches,
    compare_backends,
    loss_curve,
    mnist_batches,
)


def _lenet_builder():
    from deeplearning4j_tpu.models.lenet import build_lenet5

    return build_lenet5(seed=12345)


def test_lenet_curve_deterministic_and_decreasing():
    batches = mnist_batches(n_steps=12, batch=32)
    res = compare_backends(_lenet_builder, batches)
    assert res["same_backend"]  # cpu test env
    assert res["max_abs_deviation"] == 0.0, res  # bitwise reproducible
    curve = np.asarray(res["curve_cpu"])
    assert curve[-1] < curve[0], "loss did not decrease over 12 steps"


def test_char_rnn_curve_deterministic():
    from deeplearning4j_tpu.models.char_rnn import char_rnn_conf
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def builder():
        return MultiLayerNetwork(
            char_rnn_conf(20, lstm_size=16, num_layers=1, seed=3,
                          tbptt_length=8)
        ).init(input_shape=(1, 20))

    res = compare_backends(builder, char_batches(n_steps=6, batch=8, seq=16, vocab=20))
    assert res["max_abs_deviation"] == 0.0, res


def test_matmul_precision_context_applies():
    """float32-strict vs default precision produce (at minimum) a valid
    curve each; on CPU both are f32 so they agree — the context must not
    break compilation."""
    batches = mnist_batches(n_steps=3, batch=16)
    c_strict = loss_curve(_lenet_builder, batches, matmul_precision="float32")
    c_native = loss_curve(_lenet_builder, batches, matmul_precision=None)
    assert np.isfinite(c_strict).all() and np.isfinite(c_native).all()
    np.testing.assert_allclose(c_strict, c_native, rtol=1e-6)


class TestStrictConv3Pass:
    def test_decomposition_matches_highest_precision_conv(self):
        """bf16x3 conv (ops/precision.py) must be f32-class accurate vs the
        true f32 conv — the bound that makes the strict north-star leg
        honest."""
        import jax.numpy as jnp
        from jax import lax

        from deeplearning4j_tpu.ops.precision import conv_f32_3pass

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 12, 12, 3)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(5, 5, 3, 8)) * 0.2, jnp.float32)
        kwargs = dict(window_strides=(1, 1), padding=[(0, 0), (0, 0)],
                      dimension_numbers=("NHWC", "HWIO", "NHWC"))
        exact = lax.conv_general_dilated(
            x, w, precision=lax.Precision.HIGHEST, **kwargs)
        approx = conv_f32_3pass(x, w, **kwargs)
        rel = float(jnp.max(jnp.abs(approx - exact))
                    / jnp.max(jnp.abs(exact)))
        assert rel < 1e-5, f"bf16x3 conv relative error {rel}"

    def test_strict_context_engages_layer_path(self):
        """Under strict_conv_3pass() the conv LAYER output changes by at
        most the decomposition bound and by at least something nonzero
        (proves the 3-pass path actually ran)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf.layers import ConvolutionLayer
        from deeplearning4j_tpu.nn.layers.factory import create_layer
        from deeplearning4j_tpu.ops.precision import strict_conv_3pass

        conf = ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3),
                                weight_init="xavier", activation="identity")
        impl = create_layer(conf)
        params, state, _ = impl.initialize(jax.random.PRNGKey(0), (8, 8, 3))
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(2, 8, 8, 3)),
            jnp.float32)
        y_plain, _ = impl.apply(params, state, x)
        with strict_conv_3pass():
            y_strict, _ = impl.apply(params, state, x)
        dev = float(jnp.max(jnp.abs(y_plain - y_strict)))
        scale = float(jnp.max(jnp.abs(y_plain)))
        assert dev > 0.0, "3-pass path did not engage (outputs identical)"
        assert dev / scale < 1e-5

    def test_north_star_strict_cpu_determinism_with_3pass(self):
        """Two same-backend strict runs (both through the decomposition)
        must be bit-identical — the determinism bar with the new conv
        path engaged."""
        from deeplearning4j_tpu.utils.equivalence import (
            compare_backends,
            mnist_batches,
        )
        from deeplearning4j_tpu.models.lenet import build_lenet5

        res = compare_backends(lambda: build_lenet5(seed=3),
                               mnist_batches(3, batch=16))
        assert res["same_backend"]
        assert res["max_abs_deviation"] == 0.0
