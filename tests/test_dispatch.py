"""Dispatch-efficiency layer tests (ops/dispatch.py).

Covers the zero-retrace hot path end to end on the virtual CPU mesh:
  - bucket policy unit math
  - retrace counter: ragged batch sizes {96, 100, 128} through fit_iterator
    compile the train step at most TWICE bucketed (one per bucket) vs once
    per shape unbucketed — the acceptance bar of the dispatch PR
  - bucketing numerics: mask-corrected padding preserves the training
    math (exact-bucket batches keep bit-identical params; padded batches
    agree to reduction-reassociation tolerance)
  - buffer donation: forced donation on CPU (this jax implements it for
    real — the superseded arrays are deleted) is bit-exact against the
    non-donated step for one updater per family, never re-reads donated
    buffers, and clone() survives it
  - persistent compile cache round-trip across OS processes
  - the solver oracles' donation GUARD (they re-read the flat param
    vector by design and must never donate it)
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    DenseLayer,
    NeuralNetConfiguration,
    OutputLayer,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import dispatch
from deeplearning4j_tpu.optimize.listeners import DispatchStatsListener

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mlp(seed=3, updater="sgd", lr=0.1, algo="stochastic_gradient_descent"):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .learning_rate(lr)
        .updater(updater)
        .optimization_algo(algo)
        .list()
        .layer(0, DenseLayer(n_in=12, n_out=8, activation="tanh"))
        .layer(1, OutputLayer(n_in=8, n_out=3, activation="softmax",
                              loss_function="mcxent"))
        .build()
    )
    return MultiLayerNetwork(conf).init()


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 12)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


@pytest.fixture
def bucketing_on(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_BUCKET, "1")


@pytest.fixture
def bucketing_off(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_BUCKET, "0")


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------


def test_bucket_size_policy():
    # powers of two and 1.5x powers of two; identity on bucket members
    for n, want in [(1, 1), (2, 2), (3, 3), (4, 4), (5, 6), (6, 6), (7, 8),
                    (8, 8), (9, 12), (12, 12), (13, 16), (17, 24), (25, 32),
                    (95, 96), (96, 96), (97, 128), (100, 128), (128, 128),
                    (129, 192), (200, 256)]:
        assert dispatch.bucket_size(n) == want, (n, dispatch.bucket_size(n))
    # padding waste is bounded: bucket < 1.5x the real batch (worst case
    # sits just above a power of two, e.g. 17 -> 24)
    for n in range(1, 600):
        b = dispatch.bucket_size(n)
        assert n <= b < n * 1.5, (n, b)


# ---------------------------------------------------------------------------
# retrace counter (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_one_trace_per_bucket_through_fit_iterator(monkeypatch):
    """{96, 100, 128} -> at most TWO train-step compiles (96 is a bucket;
    100 pads to 128; 128 joins the padded signature), repeats are cache
    hits — verified by the new retrace counter. Runs in the DEFAULT
    bucketing mode ("auto": the fit_iterator loop buckets out of the
    box, no env knob needed)."""
    monkeypatch.delenv(dispatch.ENV_BUCKET, raising=False)
    assert dispatch.bucketing_mode() == "auto"
    net = mlp()
    x, y = _data(324)
    offs = {96: 0, 100: 96, 128: 196}
    for b in (96, 100, 128, 100, 96, 128):
        i = offs[b]
        net.fit_iterator(ListDataSetIterator(x[i:i + b], y[i:i + b], b))
    s = net.dispatch_stats
    assert s.traces["train_step"] == 2, dict(s.traces)
    assert s.calls["train_step"] == 6
    assert s.cache_hits("train_step") == 4
    assert s.padded_batches == 2  # the two 100-row batches
    assert s.padded_examples == 2 * 28


def test_unbucketed_traces_once_per_shape(bucketing_off):
    """Seed behavior: every distinct batch shape is a full retrace."""
    net = mlp()
    x, y = _data(324)
    offs = {96: 0, 100: 96, 128: 196}
    for b in (96, 100, 128, 100):
        i = offs[b]
        net.fit(x[i:i + b], y[i:i + b])
    assert net.dispatch_stats.traces["train_step"] == 3
    assert net.dispatch_stats.cache_hits("train_step") == 1


def test_direct_fit_stays_unpadded_in_auto_mode(monkeypatch):
    """Default ("auto") mode leaves DIRECT fit() calls byte-exact — the
    equivalence contracts (fit_batches == K serial fits, distributed ==
    serial) compare direct-fit trajectories at tight tolerance."""
    monkeypatch.delenv(dispatch.ENV_BUCKET, raising=False)
    net = mlp()
    x, y = _data(100)
    net.fit(x, y)
    assert net.dispatch_stats.padded_batches == 0
    # no row mask was attached either: the unpadded signature (trailing
    # False = the lowprec train policy rides the cache key, off here)
    assert ("train_step", False, False, False, None, False) in net._jit_cache


def test_output_buckets_and_slices(bucketing_on):
    net = mlp()
    x, y = _data(128)
    net.fit(x, y)
    out_full = np.asarray(net.output(x))
    out_ragged = np.asarray(net.output(x[:100]))
    assert out_ragged.shape == (100, 3)
    # pad rows cannot leak into real rows in inference
    np.testing.assert_array_equal(out_ragged, out_full[:100])
    # 128 and padded-100 share one compiled program
    assert net.dispatch_stats.traces["output"] == 1
    assert net.dispatch_stats.calls["output"] == 2


def test_graph_container_buckets(bucketing_on):
    conf = (
        NeuralNetConfiguration.builder()
        .seed(11)
        .learning_rate(0.1)
        .graph_builder()
        .add_inputs("in")
        .add_layer("d", DenseLayer(n_in=12, n_out=8, activation="tanh"), "in")
        .add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                      loss_function="mcxent"), "d")
        .set_outputs("out")
        .build()
    )
    net = ComputationGraph(conf).init()
    x, y = _data(324)
    offs = {96: 0, 100: 96, 128: 196}
    for b in (96, 100, 128, 100):
        i = offs[b]
        net.fit(x[i:i + b], y[i:i + b])
    s = net.dispatch_stats
    assert s.traces["train_step"] == 2, dict(s.traces)
    assert s.padded_batches == 2
    out = np.asarray(net.output(x[:100])[0])
    assert out.shape == (100, 3)


# ---------------------------------------------------------------------------
# bucketing numerics (mask-corrected padding preserves the training math)
# ---------------------------------------------------------------------------


def test_exact_bucket_batch_trains_bit_identical(monkeypatch):
    """An exact-bucket batch (the all-ones row mask — bucketing's uniform
    jit signature) must not perturb training AT ALL: the masked mean
    reduces to the plain mean and the parameter trajectory is bit-equal."""
    x, y = _data(128)
    monkeypatch.setenv(dispatch.ENV_BUCKET, "1")
    a = mlp(updater="adam", lr=0.05)
    for _ in range(5):
        a.fit(x, y)
    monkeypatch.setenv(dispatch.ENV_BUCKET, "0")
    b = mlp(updater="adam", lr=0.05)
    for _ in range(5):
        b.fit(x, y)
    for pa, pb in zip(a.params, b.params):
        for k in pa:
            np.testing.assert_array_equal(np.asarray(pa[k]),
                                          np.asarray(pb[k]))


def test_padded_batch_trains_equivalent(monkeypatch):
    """A ragged batch (100 -> 128 pad) preserves the mathematical loss and
    gradients exactly; the committed tolerance covers float32 reduction
    reassociation only (measured ~1e-7 relative on this backend)."""
    x, y = _data(100)
    monkeypatch.setenv(dispatch.ENV_BUCKET, "1")
    a = mlp(updater="adam", lr=0.05)
    la = [float(np.asarray(a.fit(x, y))) for _ in range(5)]
    assert a.dispatch_stats.padded_batches == 5
    monkeypatch.setenv(dispatch.ENV_BUCKET, "0")
    b = mlp(updater="adam", lr=0.05)
    lb = [float(np.asarray(b.fit(x, y))) for _ in range(5)]
    np.testing.assert_allclose(la, lb, rtol=1e-5)
    for pa, pb in zip(a.params, b.params):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]), np.asarray(pb[k]),
                                       rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("updater", ["sgd", "adam", "rmsprop"])
def test_donated_step_bit_exact_per_updater_family(monkeypatch, updater):
    """Donation changes buffer aliasing, never math: the donated step must
    be bit-exact against the non-donated seed step (acceptance bar, one
    optimizer per family)."""
    x, y = _data(64)
    monkeypatch.setenv(dispatch.ENV_DONATE, "force")
    a = mlp(updater=updater)
    la = [float(np.asarray(a.fit(x, y))) for _ in range(4)]
    assert a.dispatch_stats.donated_steps == 4
    assert a.dispatch_stats.copied_steps == 0
    monkeypatch.setenv(dispatch.ENV_DONATE, "0")
    b = mlp(updater=updater)
    lb = [float(np.asarray(b.fit(x, y))) for _ in range(4)]
    assert b.dispatch_stats.donated_steps == 0
    assert b.dispatch_stats.copied_steps == 4
    assert la == lb
    for pa, pb in zip(a.params, b.params):
        for k in pa:
            np.testing.assert_array_equal(np.asarray(pa[k]),
                                          np.asarray(pb[k]))


def test_donation_consumes_old_buffers_and_never_rereads(monkeypatch):
    """The smoke test of the donation contract: after a donated step the
    SUPERSEDED params/updater-state arrays are deleted (donation is real on
    this jax even on CPU), and the training loop keeps working because it
    re-binds instead of re-reading."""
    monkeypatch.setenv(dispatch.ENV_DONATE, "force")
    x, y = _data(64)
    net = mlp(updater="adam")
    net.fit(x, y)  # builds + runs the donated step once
    step = net._get_train_step(False, False)
    assert step.donated_argnums == (0, 1, 2)
    old_params, old_upd = net.params, net.updater_state
    net.fit(x, y)
    deleted = [leaf.is_deleted()
               for tree in (old_params, old_upd)
               for leaf in jax.tree_util.tree_leaves(tree)]
    assert deleted and all(deleted), "donated inputs were not consumed"
    # the loop itself never touches the dead buffers: more steps work and
    # the current state is readable
    net.fit(x, y)
    assert np.isfinite(float(np.asarray(net._score_dev)))


def test_donation_default_off_on_cpu_platform(monkeypatch):
    """Platform default (no env): CPU skips donation — the equivalence
    substrate re-reads params trees (models/transformer._donation_kwargs
    rationale, now shared via dispatch.donation_enabled)."""
    monkeypatch.delenv(dispatch.ENV_DONATE, raising=False)
    assert not dispatch.donation_enabled()  # conftest pins jax_platforms=cpu
    net = mlp()
    x, y = _data(32)
    net.fit(x, y)
    assert net._get_train_step(False, False).donated_argnums == ()


def test_clone_survives_donation(monkeypatch):
    monkeypatch.setenv(dispatch.ENV_DONATE, "force")
    x, y = _data(64)
    net = mlp(updater="adam")
    net.fit(x, y)
    twin = net.clone()
    net.fit(x, y)  # donates the original's buffers
    # the clone's leaves are REAL copies, still alive and trainable (under
    # leaf-sharing the donated originals would now be deleted arrays)
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(twin.params))
    np.asarray(twin.params[0]["W"])  # readable
    twin.fit(x, y)
    assert np.isfinite(float(np.asarray(twin._score_dev)))


def test_solver_oracles_never_donate(monkeypatch):
    """The donation GUARD: line-search oracles re-read the flat param
    vector (backtrack probes x + step*d while x stays live), so they must
    opt out even under forced donation."""
    monkeypatch.setenv(dispatch.ENV_DONATE, "force")
    net = mlp(updater="sgd", algo="conjugate_gradient")
    x, y = _data(32)
    net.fit(x, y)
    vg, v = net._jit_cache[("solver_vg", False, False)]
    assert vg.donated_argnums == ()
    assert v.donated_argnums == ()
    assert net.dispatch_stats.traces["solver_vg"] >= 1
    # params remained readable throughout (the optimizers re-read them)
    assert np.isfinite(float(np.asarray(net.params[0]["W"]).sum()))


# ---------------------------------------------------------------------------
# telemetry surfacing
# ---------------------------------------------------------------------------


def test_dispatch_stats_listener_snapshots():
    net = mlp()
    lst = DispatchStatsListener(frequency=1)
    net.set_listeners(lst)
    x, y = _data(32)
    for _ in range(3):
        net.fit(x, y)
    assert len(lst.snapshots) == 3
    snap = lst.snapshots[-1]
    for key in ("traces", "calls", "cache_hits", "donated_steps",
                "copied_steps", "padded_batches", "iteration"):
        assert key in snap
    assert snap["traces"].get("train_step") == 1


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------

_CACHE_CHILD = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.ops import dispatch
d = dispatch.enable_compile_cache()
assert d == sys.argv[1] == jax.config.jax_compilation_cache_dir, d
import jax.numpy as jnp
f = jax.jit(lambda a, b: jnp.tanh(a @ b).sum())
x = jnp.ones((32, 32))
val = float(f(x, x))
print(json.dumps({"val": val, "entries": sorted(os.listdir(sys.argv[1]))}))
"""


def _run_cache_child(cache_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    # jax's own variables are the one way to place and tune the cache
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD, cache_dir],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_round_trip(tmp_path):
    """Two fresh OS processes share one cache dir: the first populates it,
    the second compiles the same program and adds NO new entries (same
    cache key -> served from disk) while computing the same value."""
    d = str(tmp_path / "cache")
    os.makedirs(d)
    first = _run_cache_child(d)
    assert first["entries"], "first process wrote no cache entries"
    second = _run_cache_child(d)
    assert second["val"] == first["val"]
    cache_files = [e for e in first["entries"] if e.endswith("-cache")]
    cache_files2 = [e for e in second["entries"] if e.endswith("-cache")]
    assert cache_files2 == cache_files, (
        "second process missed the persistent cache (new entries appeared)")


# ---------------------------------------------------------------------------
# fusion policy: the XLA:CPU scan-of-conv guard (ISSUE 4 satellite)
# ---------------------------------------------------------------------------

def _tiny_conv_net(seed=11):
    from deeplearning4j_tpu.nn.conf import (
        ConvolutionLayer,
        SubsamplingLayer,
    )
    from deeplearning4j_tpu.nn.conf.preprocessors import (
        CnnToFeedForwardPreProcessor,
    )

    conf = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .learning_rate(0.05)
        .updater("sgd")
        .weight_init("xavier")
        .list()
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


def _conv_data(k=2, n=4, seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((k, n, 8, 8, 1)).astype(np.float32)
    ys = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (k, n))]
    return xs, ys


class TestScanOfConvGuard:
    def test_policy_unit(self, monkeypatch):
        monkeypatch.delenv(dispatch.ENV_FUSE, raising=False)
        # non-conv programs always fuse; conv-in-scan is CPU-gated
        assert dispatch.fusion_enabled(scanned_conv=False)
        assert not dispatch.fusion_enabled(scanned_conv=True)  # CPU substrate
        monkeypatch.setenv(dispatch.ENV_FUSE, "force")
        assert dispatch.fusion_enabled(scanned_conv=True)
        monkeypatch.setenv(dispatch.ENV_FUSE, "1")  # _ON siblings == force
        assert dispatch.fusion_enabled(scanned_conv=True)
        monkeypatch.setenv(dispatch.ENV_FUSE, "0")
        assert not dispatch.fusion_enabled(scanned_conv=False)

    def test_conv_fit_batches_falls_back_per_step(self, monkeypatch):
        """On the CPU backend a conv fit_batches drains through per-step
        fit() (XLA:CPU runs scan-of-conv far slower than
        the per-step program) with IDENTICAL semantics — fit_batches is
        defined as K serial fits — and the fallback is visible in
        dispatch_stats."""
        monkeypatch.delenv(dispatch.ENV_FUSE, raising=False)
        xs, ys = _conv_data()

        serial = _tiny_conv_net()
        serial_losses = [float(serial.fit(xs[k], ys[k]))
                         for k in range(xs.shape[0])]

        net = _tiny_conv_net()
        losses = net.fit_batches(xs, ys)
        assert net.dispatch_stats.fused_fallbacks == 1
        # the scanned program was never built, the per-step one was
        assert net.dispatch_stats.traces.get("fit_batches", 0) == 0
        assert net.dispatch_stats.traces.get("train_step", 0) >= 1
        np.testing.assert_allclose(losses, serial_losses, rtol=1e-6)
        assert net.iteration == serial.iteration == xs.shape[0]
        for p_s, p_f in zip(serial.params, net.params):
            for name in p_s:
                np.testing.assert_allclose(
                    np.asarray(p_f[name]), np.asarray(p_s[name]),
                    rtol=1e-6, atol=1e-7, err_msg=name)

    def test_force_keeps_fused_program(self, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_FUSE, "force")
        xs, ys = _conv_data()
        net = _tiny_conv_net()
        losses = net.fit_batches(xs, ys)
        assert losses.shape == (xs.shape[0],)
        assert net.dispatch_stats.fused_fallbacks == 0
        assert net.dispatch_stats.traces.get("fit_batches", 0) == 1

    def test_dense_nets_unaffected(self, monkeypatch):
        monkeypatch.delenv(dispatch.ENV_FUSE, raising=False)
        x, y = _data(24)
        net = mlp()
        losses = net.fit_batches(np.stack([x[:12], x[12:]]),
                                 np.stack([y[:12], y[12:]]))
        assert losses.shape == (2,)
        assert net.dispatch_stats.fused_fallbacks == 0
        assert net.dispatch_stats.traces.get("fit_batches", 0) == 1


# ---------------------------------------------------------------------------
# per-trace wall-seconds (compile-time triage telemetry, ISSUE 4 satellite)
# ---------------------------------------------------------------------------

class TestTraceSeconds:
    def test_trace_seconds_accrue_only_on_traces(self):
        net = mlp()
        x, y = _data(32)
        net.fit(x, y)
        s = net.dispatch_stats
        first = s.trace_seconds.get("train_step", 0.0)
        assert first > 0.0
        net.fit(x, y)  # cache hit: no new trace, no new seconds
        assert s.trace_seconds["train_step"] == first
        net.fit(x[:16], y[:16])  # new shape: retrace accrues more
        assert s.trace_seconds["train_step"] > first

    def test_snapshot_and_listener_carry_trace_seconds(self):
        net = mlp()
        x, y = _data(16)
        lst = DispatchStatsListener(frequency=1)
        net.listeners.append(lst)
        net.fit(x, y)
        snap = net.dispatch_stats.snapshot()
        assert snap["trace_seconds"]["train_step"] > 0.0
        assert snap["fused_fallbacks"] == 0
        assert lst.snapshots and "trace_seconds" in lst.snapshots[-1]
