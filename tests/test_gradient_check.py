"""Gradient-check suite — the numerical-correctness backbone
(reference: GradientCheckTests, CNNGradientCheckTest, BNGradientCheckTest,
GradientCheckTestsMasking — SURVEY.md section 4). Validates the loss/forward
plumbing (losses, masking, regularization, conv, recurrence) against central
differences in float64."""

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import (
    ConvolutionLayer,
    DenseLayer,
    GravesLSTM,
    GRU,
    NeuralNetConfiguration,
    OutputLayer,
    RnnOutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.conf.preprocessors import CnnToFeedForwardPreProcessor
from deeplearning4j_tpu.utils.gradient_check import check_network_gradients

RNG = np.random.default_rng(12345)


def random_classification(n, nin, nout):
    x = RNG.standard_normal((n, nin))
    y = np.eye(nout)[RNG.integers(0, nout, n)]
    return x, y


@pytest.mark.parametrize("activation", ["sigmoid", "tanh", "relu"])
@pytest.mark.parametrize(
    "loss,out_act",
    [("mcxent", "softmax"), ("mse", "identity"), ("xent", "sigmoid")],
)
def test_mlp_gradients(activation, loss, out_act):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(12345)
        .list()
        .layer(0, DenseLayer(n_in=4, n_out=5, activation=activation))
        .layer(
            1, OutputLayer(n_in=5, n_out=3, activation=out_act, loss_function=loss)
        )
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x, y = random_classification(6, 4, 3)
    ok, max_rel = check_network_gradients(net, x, y)
    assert ok, f"max relative error {max_rel}"


def test_mlp_gradients_with_l1_l2():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(1)
        .l1(0.01)
        .l2(0.02)
        .list()
        .layer(0, DenseLayer(n_in=4, n_out=5, activation="tanh"))
        .layer(1, OutputLayer(n_in=5, n_out=3, activation="softmax"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x, y = random_classification(5, 4, 3)
    ok, max_rel = check_network_gradients(net, x, y)
    assert ok, f"max relative error {max_rel}"


def test_cnn_gradients():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(42)
        .list()
        .layer(
            0,
            ConvolutionLayer(
                n_in=1, n_out=2, kernel_size=(2, 2), stride=(1, 1),
                activation="tanh",
            ),
        )
        .layer(1, SubsamplingLayer(pooling_type="max", kernel_size=(2, 2), stride=(2, 2)))
        .layer(2, OutputLayer(n_in=8, n_out=2, activation="softmax"))
        .input_preprocessor(2, CnnToFeedForwardPreProcessor(2, 2, 2))
        .build()
    )
    net = MultiLayerNetwork(conf).init(input_shape=(5, 5, 1))
    x = RNG.standard_normal((3, 5, 5, 1))
    y = np.eye(2)[RNG.integers(0, 2, 3)]
    ok, max_rel = check_network_gradients(net, x, y, max_params_per_leaf=20)
    assert ok, f"max relative error {max_rel}"


def test_lstm_gradients():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(7)
        .list()
        .layer(0, GravesLSTM(n_in=3, n_out=4, activation="tanh"))
        .layer(1, RnnOutputLayer(n_in=4, n_out=2, activation="softmax"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = RNG.standard_normal((2, 4, 3))
    y = np.eye(2)[RNG.integers(0, 2, (2, 4))]
    ok, max_rel = check_network_gradients(net, x, y, max_params_per_leaf=25)
    assert ok, f"max relative error {max_rel}"


def test_gru_gradients():
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(8)
        .list()
        .layer(0, GRU(n_in=3, n_out=4, activation="tanh"))
        .layer(1, RnnOutputLayer(n_in=4, n_out=2, activation="softmax"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = RNG.standard_normal((2, 4, 3))
    y = np.eye(2)[RNG.integers(0, 2, (2, 4))]
    ok, max_rel = check_network_gradients(net, x, y, max_params_per_leaf=25)
    assert ok, f"max relative error {max_rel}"


def test_mha_gradients():
    """Central-difference check for the MultiHeadAttention layer's dense
    path. The attention softmax
    upcast is at-least-f32 (ops/dtypes.softmax_dtype), so the whole check
    runs in true f64 like the MLP/CNN/LSTM checks."""
    from deeplearning4j_tpu.nn.conf.layers import MultiHeadAttention
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(11)
        .list()
        .layer(0, MultiHeadAttention(n_in=4, n_out=4, num_heads=2,
                                     causal=True, activation="identity"))
        .layer(1, RnnOutputLayer(n_in=4, n_out=2, activation="softmax"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = RNG.standard_normal((2, 5, 4))
    y = np.eye(2)[RNG.integers(0, 2, (2, 5))]
    ok, max_rel = check_network_gradients(net, x, y,
                                          max_params_per_leaf=20)
    assert ok, f"max relative error {max_rel}"


def test_moe_ffn_gradients():
    """Central-difference check for one MoE FFN block
    (models/transformer._moe_ffn: routing + expert MLP + load-balance aux
    — the expert_parallel math). top_k == n_experts keeps every expert
    selected, so the discrete routing structure is locally constant and
    the objective is differentiable at the probe point; gradients flow
    through the gate softmax (at-least-f32 upcast, f64 here), the
    combine weights, and the aux loss."""
    import jax

    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig,
        _moe_ffn,
        init_params,
    )
    from deeplearning4j_tpu.utils.gradient_check import check_gradients

    cfg = TransformerConfig(vocab_size=13, d_model=8, n_layers=1,
                            n_heads=2, d_ff=8, max_len=8, moe_experts=2,
                            moe_top_k=2, seed=5)
    blocks = init_params(cfg)["blocks"]
    bp0 = {k: jax.tree_util.tree_map(lambda a: a[0], blocks[k])
           for k in ("Wg", "W1", "b1", "W2", "b2")}
    h = jnp.asarray(RNG.standard_normal((2, 4, 8)))

    def loss(p):
        out, aux = _moe_ffn(p, h.astype(p["W1"].dtype), cfg)
        return (out ** 2).mean() + cfg.moe_aux_coef * aux

    ok, max_rel = check_gradients(loss, bp0, max_params_per_leaf=15)
    assert ok, f"max relative error {max_rel}"


def test_bert_mlm_loss_gradients():
    """Central-difference check for the BERT masked-LM loss
    (models/bert.mlm_loss: bidirectional encoder + selected-position
    cross-entropy). The loss's log-softmax upcast is at-least-f32
    (ops/dtypes.softmax_dtype — a hard f32 pin quantized the x64 loss
    below central-difference resolution: numeric grads read exactly 0
    against analytic 1e-4 before the fix), so this runs in true f64."""
    from deeplearning4j_tpu.models.bert import (
        BertConfig,
        init_params,
        mask_tokens,
        mlm_loss,
    )
    from deeplearning4j_tpu.utils.gradient_check import check_gradients

    cfg = BertConfig(vocab_size=17, d_model=8, n_layers=1, n_heads=2,
                     d_ff=16, max_len=6, mlm_prob=0.3, pad_token_id=0,
                     mask_token_id=16, seed=3)
    params = init_params(cfg)
    rng = np.random.default_rng(7)
    tokens = rng.integers(1, 16, (2, 6))
    inputs, targets, weights = mask_tokens(tokens, cfg, rng)

    def loss(p):
        return mlm_loss(p, jnp.asarray(inputs), jnp.asarray(targets),
                        jnp.asarray(weights), cfg)

    ok, max_rel = check_gradients(loss, params, max_params_per_leaf=10)
    assert ok, f"max relative error {max_rel}"


def test_rnn_masked_gradients():
    """Masked-timestep gradients (reference GradientCheckTestsMasking)."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (
        NeuralNetConfiguration.builder()
        .seed(9)
        .list()
        .layer(0, GravesLSTM(n_in=2, n_out=3, activation="tanh"))
        .layer(1, RnnOutputLayer(n_in=3, n_out=2, activation="softmax"))
        .build()
    )
    net = MultiLayerNetwork(conf).init()
    x = RNG.standard_normal((2, 5, 2))
    y = np.eye(2)[RNG.integers(0, 2, (2, 5))]
    mask = np.array([[1, 1, 1, 1, 1], [1, 1, 1, 0, 0]], dtype=np.float64)
    ok, max_rel = check_network_gradients(
        net, x, y, mask=jnp.asarray(mask), max_params_per_leaf=25
    )
    assert ok, f"max relative error {max_rel}"
