"""Ring attention / sequence parallelism tests on the 8-device CPU mesh —
the distributed==serial equivalence pattern from SURVEY.md section 4 applied
to long-context: the ring result must EXACTLY match single-device attention."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.sequence_parallel import (
    SEQ_AXIS,
    mha_apply,
    multi_head_attention,
    ring_attention_sharded,
    ulysses_attention_sharded,
)


def make_qkv(n=2, t=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(0, 1, (n, t, h, d)).astype(np.float32))
        for _ in range(3)
    )


def seq_mesh(n_dev=8):
    devs = jax.devices()[:n_dev]
    return Mesh(np.array(devs), (SEQ_AXIS,))


class TestRingAttention:
    def test_matches_single_device_full(self):
        q, k, v = make_qkv()
        mesh = seq_mesh()
        out_ring = ring_attention_sharded(q, k, v, mesh, causal=False)
        out_ref = multi_head_attention(q, k, v, causal=False)
        np.testing.assert_allclose(out_ring, out_ref, rtol=2e-5, atol=2e-6)

    def test_matches_single_device_causal(self):
        q, k, v = make_qkv(seed=3)
        mesh = seq_mesh()
        out_ring = ring_attention_sharded(q, k, v, mesh, causal=True)
        out_ref = multi_head_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out_ring, out_ref, rtol=2e-5, atol=2e-6)

    def test_two_device_ring(self):
        q, k, v = make_qkv(t=16, seed=5)
        mesh = seq_mesh(2)
        out_ring = ring_attention_sharded(q, k, v, mesh, causal=True)
        out_ref = multi_head_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out_ring, out_ref, rtol=2e-5, atol=2e-6)

    def test_indivisible_length_rejected(self):
        q, k, v = make_qkv(t=30)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention_sharded(q, k, v, seq_mesh(8))

    def test_gradients_flow_through_ring(self):
        q, k, v = make_qkv(t=16, seed=7)
        mesh = seq_mesh(4)

        def loss_ring(q, k, v):
            return jnp.sum(ring_attention_sharded(q, k, v, mesh, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=True) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-5)


class TestAttentionLayer:
    def test_layer_in_network_trains(self):
        from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import (
            MultiHeadAttention,
            RnnOutputLayer,
        )
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (
            NeuralNetConfiguration.builder().seed(1).learning_rate(0.01)
            .updater("adam").list()
            .layer(0, MultiHeadAttention(n_in=6, n_out=8, num_heads=2,
                                         causal=True, activation="identity"))
            .layer(1, RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                     loss_function="mcxent"))
            .build()
        )
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(4, 10, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (4, 10))]
        first = net.fit(x, y)
        for _ in range(10):
            last = net.fit(x, y)
        assert float(last) < float(first)

    def test_ulysses_matches_single_device(self):
        q, k, v = make_qkv(t=32, h=8)
        mesh = seq_mesh()
        for causal in (False, True):
            out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
            ref = multi_head_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)

    def test_ulysses_matches_ring(self):
        q, k, v = make_qkv(t=32, h=8, seed=3)
        mesh = seq_mesh()
        out_u = ulysses_attention_sharded(q, k, v, mesh, causal=True)
        out_r = ring_attention_sharded(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(out_u, out_r, rtol=2e-5, atol=2e-6)

    def test_ulysses_head_divisibility_rejected(self):
        q, k, v = make_qkv(t=32, h=4)  # 4 heads on 8 devices
        with pytest.raises(ValueError):
            ulysses_attention_sharded(q, k, v, seq_mesh(), causal=False)

    def test_ulysses_gradients_flow(self):
        q, k, v = make_qkv(t=16, h=8, seed=5)
        mesh = seq_mesh()

        def loss_u(q, k, v):
            return jnp.sum(
                ulysses_attention_sharded(q, k, v, mesh, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(multi_head_attention(q, k, v, causal=True) ** 2)

        gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gu, gr):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_heads_divisibility_validated(self):
        from deeplearning4j_tpu.nn.conf.layers import MultiHeadAttention

        with pytest.raises(ValueError, match="divisible"):
            MultiHeadAttention(n_in=6, n_out=7, num_heads=2)

    def test_mha_apply_causal_prefix_property(self):
        """Causal attention output at position t must not change when future
        positions change."""
        rng = np.random.default_rng(1)
        x1 = rng.normal(size=(1, 8, 4)).astype(np.float32)
        x2 = x1.copy()
        x2[:, 5:] += 1.0  # perturb the future
        params = {
            "Wq": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wk": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wv": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wo": jnp.asarray(rng.normal(0, 0.3, (8, 4)).astype(np.float32)),
        }
        y1 = mha_apply(params, jnp.asarray(x1), 2, causal=True)
        y2 = mha_apply(params, jnp.asarray(x2), 2, causal=True)
        np.testing.assert_allclose(y1[:, :5], y2[:, :5], rtol=1e-5)
        assert not np.allclose(y1[:, 5:], y2[:, 5:])

    def test_padded_keys_excluded_by_mask(self):
        """A padded timestep must not influence valid positions' outputs
        (the finding the LSTM path already guarantees via state freezing)."""
        rng = np.random.default_rng(2)
        x_short = rng.normal(size=(1, 3, 4)).astype(np.float32)
        x_padded = np.zeros((1, 5, 4), np.float32)
        x_padded[:, :3] = x_short
        x_padded[:, 3:] = 99.0  # garbage in the padding
        mask = np.array([[1, 1, 1, 0, 0]], np.float32)
        params = {
            "Wq": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wk": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wv": jnp.asarray(rng.normal(0, 0.3, (4, 8)).astype(np.float32)),
            "Wo": jnp.asarray(rng.normal(0, 0.3, (8, 4)).astype(np.float32)),
        }
        y_short = mha_apply(params, jnp.asarray(x_short), 2)
        y_padded = mha_apply(params, jnp.asarray(x_padded), 2,
                             key_mask=jnp.asarray(mask))
        np.testing.assert_allclose(y_padded[:, :3], y_short, rtol=1e-5,
                                   atol=1e-6)

    def test_streaming_step_matches_batch_causal(self):
        """KV-cache streaming (rnnTimeStep analog) equals batch causal
        attention position by position."""
        from deeplearning4j_tpu.nn.conf.layers import MultiHeadAttention
        from deeplearning4j_tpu.nn.layers.factory import create_layer

        conf = MultiHeadAttention(n_in=4, n_out=8, num_heads=2, causal=True,
                                  weight_init="xavier", activation="identity")
        impl = create_layer(conf)
        params, state, _ = impl.initialize(jax.random.PRNGKey(0), (6, 4))
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(2, 6, 4)).astype(np.float32))
        y_batch, _ = impl.apply(params, state, x)
        st = {}
        outs = []
        for t in range(6):
            y_t, st = impl.step(params, st, x[:, t])
            outs.append(y_t)
        y_stream = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(y_stream, y_batch, rtol=1e-4, atol=1e-5)


class TestRingFlashComposition:
    """The flash kernel engaged INSIDE the ring
    (local block product through pallas, interpret mode on the CPU mesh)."""

    def _qkv(self, n=2, t=512, h=2, d=32, seed=0):
        rng = np.random.default_rng(seed)
        return [jnp.asarray(rng.standard_normal((n, t, h, d)), jnp.float32)
                for _ in range(3)]

    def test_ring_flash_matches_dense(self):
        from jax.sharding import Mesh

        q, k, v = self._qkv()
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        for causal in (False, True):
            ring = ring_attention_sharded(q, k, v, mesh, causal=causal,
                                          use_flash=True, interpret=True)
            ref = multi_head_attention(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                       atol=5e-5,
                                       err_msg=f"causal={causal}")

    def test_ring_flash_with_key_mask(self):
        from jax.sharding import Mesh

        q, k, v = self._qkv(seed=2)
        rng = np.random.default_rng(3)
        km = rng.random((2, 512)) > 0.25
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        ring = ring_attention_sharded(q, k, v, mesh, causal=True,
                                      key_mask=km, use_flash=True,
                                      interpret=True)
        ref = multi_head_attention(q, k, v, causal=True,
                                   key_mask=jnp.asarray(km))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   atol=5e-5)

    def test_ring_einsum_with_key_mask(self):
        """The non-flash ring path also honors the rotating mask shard."""
        from jax.sharding import Mesh

        q, k, v = self._qkv(t=64, seed=4)
        rng = np.random.default_rng(5)
        km = rng.random((2, 64)) > 0.25
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        ring = ring_attention_sharded(q, k, v, mesh, causal=True,
                                      key_mask=km, use_flash=False)
        ref = multi_head_attention(q, k, v, causal=True,
                                   key_mask=jnp.asarray(km))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ref),
                                   atol=1e-5)

    def test_ring_flash_gradients_match_dense(self):
        from jax.sharding import Mesh

        q, k, v = self._qkv(n=1, t=256, h=1, d=32, seed=6)
        mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))

        def f_ring(q, k, v):
            return (ring_attention_sharded(
                q, k, v, mesh, causal=True, use_flash=True,
                interpret=True) ** 2).mean()

        def f_ref(q, k, v):
            return (multi_head_attention(q, k, v, causal=True) ** 2).mean()

        g = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, err_msg=f"d{name}")

    @pytest.mark.parametrize("use_flash", [True, False])
    def test_all_masked_rows_zero_output_finite_grads(self, use_flash):
        """Regression: a query row whose visible keys are ALL masked must
        output exactly 0 with finite gradients. Guards two coupled fixes:
        the ext kernel's lse = -inf (not a finite ~-69 sentinel) for
        no-visible-key rows, and the ring combiner's where-based safe
        denominator (maximum(l, 1e-30) NaNs the backward via (1e-30)^2
        f32 underflow in -o/denom^2 when l = 0)."""
        q, k, v = self._qkv(seed=8)
        t = q.shape[1]
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        t_local = t // 4
        km = np.ones((2, t), bool)
        km[:, :t_local] = False  # first shard fully masked: causal rows
        # 0..t_local-1 see no key at all
        km = jnp.asarray(km)

        out = ring_attention_sharded(q, k, v, mesh, causal=True,
                                     key_mask=km, use_flash=use_flash,
                                     interpret=use_flash)
        out = np.asarray(out)
        assert np.isfinite(out).all()
        np.testing.assert_array_equal(out[:, :t_local], 0.0)

        def loss(q, k, v):
            o = ring_attention_sharded(q, k, v, mesh, causal=True,
                                       key_mask=km, use_flash=use_flash,
                                       interpret=use_flash)
            return (o.astype(jnp.float32) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for gi, name in zip(g, "qkv"):
            assert np.isfinite(np.asarray(gi)).all(), f"d{name} non-finite"

    def test_mha_apply_ring_with_mask(self):
        """mha_apply on a seq mesh now supports key_mask (previously a
        ValueError): padded garbage cannot leak into valid positions."""
        from jax.sharding import Mesh

        rng = np.random.default_rng(7)
        x = np.zeros((2, 64, 8), np.float32)
        x[:, :48] = rng.normal(size=(2, 48, 8)).astype(np.float32)
        x[:, 48:] = 99.0
        mask = np.zeros((2, 64), np.float32)
        mask[:, :48] = 1.0
        params = {
            "Wq": jnp.asarray(rng.normal(0, 0.3, (8, 8)), jnp.float32),
            "Wk": jnp.asarray(rng.normal(0, 0.3, (8, 8)), jnp.float32),
            "Wv": jnp.asarray(rng.normal(0, 0.3, (8, 8)), jnp.float32),
            "Wo": jnp.asarray(rng.normal(0, 0.3, (8, 8)), jnp.float32),
        }
        mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
        y_ring = mha_apply(params, jnp.asarray(x), 2, mesh=mesh,
                           key_mask=jnp.asarray(mask))
        y_serial = mha_apply(params, jnp.asarray(x), 2,
                             key_mask=jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(y_ring[:, :48]),
                                   np.asarray(y_serial[:, :48]),
                                   rtol=1e-4, atol=1e-5)
