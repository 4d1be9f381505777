"""Fused SGNS kernel contracts (ISSUE 13, quick tier).

The rent ops/pallas_sgns.py pays before it may ever go default-on:

  * f64 gradcheck — ``sgns_fused_step`` (interpret mode, requested
    here by argument) matches nlp/word2vec._neg_body to 1e-8 in float64 on a
    batch with DELIBERATE row collisions (repeated context rows and
    repeated target rows), pinning the two-phase stale-gather /
    sequential-RMW design to XLA's exact ``.at[].add()`` semantics;
  * epoch contract — ``_skipgram_epoch(sgns_kernel=True)`` ==
    ``sgns_kernel=False`` through the full stacked-batch scan at the
    production f32 dtype (1e-5; syn1 — the HS table the kernel never
    touches — stays BIT-identical);
  * gate contract — knob 0 always off, force = VMEM fit only, '' auto
    stays off on this substrate (no real-chip measured-win row in
    PALLAS_BENCH.json's sgns group).
"""

import numpy as np

import jax
import jax.numpy as jnp


def _case(seed=3, v=50, d=36, b=16, k1=6, dtype=jnp.float64):
    """A pair batch with forced collisions: contexts[5] == contexts[4]
    (colliding syn0 rows), targets[3] == targets[2] row-wise (colliding
    syn1neg rows), plus dead negatives (live == 0, the reference's
    ``continue`` on target == center) and one fully-dead pair row."""
    rng = np.random.default_rng(seed)
    syn0 = jnp.asarray(rng.standard_normal((v, d)) * 0.1, dtype)
    syn1neg = jnp.asarray(rng.standard_normal((v, d)) * 0.1, dtype)
    contexts = rng.integers(0, v, size=(b,)).astype(np.int32)
    contexts[5] = contexts[4]
    targets = rng.integers(0, v, size=(b, k1)).astype(np.int32)
    targets[3] = targets[2]
    labels = np.zeros((b, k1), np.float64)
    labels[:, 0] = 1.0
    live = np.ones((b, k1), np.float64)
    live[1, 2] = 0.0                      # a dead negative
    live[7, :] = 0.0                      # a fully-padded pair row
    return (syn0, syn1neg, jnp.asarray(contexts), jnp.asarray(targets),
            jnp.asarray(labels, dtype), jnp.asarray(live, dtype))


class TestSgnsFusedStep:
    def test_f64_gradcheck_vs_neg_body(self):
        from deeplearning4j_tpu.nlp.word2vec import _neg_body
        from deeplearning4j_tpu.ops.pallas_sgns import sgns_fused_step

        syn0, syn1neg, cx, tgt, lbl, live = _case()
        alpha = 0.025
        # both the XLA step and the aliased kernel donate their tables:
        # hand each its own copy
        r0, r1 = _neg_body(jnp.array(syn0), jnp.array(syn1neg),
                           cx, tgt, lbl, live, alpha)
        k0, k1_ = sgns_fused_step(jnp.array(syn0), jnp.array(syn1neg),
                                  cx, tgt, lbl, live, alpha,
                                  interpret=True)
        assert float(jnp.max(jnp.abs(r0 - k0))) < 1e-8
        assert float(jnp.max(jnp.abs(r1 - k1_))) < 1e-8

    def test_f64_gradcheck_saturated_dots(self):
        """The MAX_EXP saturation branches (dot > 6 -> labels-1,
        dot < -6 -> labels) — scale the tables up so saturation actually
        fires on a meaningful fraction of the pairs."""
        from deeplearning4j_tpu.nlp.word2vec import _neg_body
        from deeplearning4j_tpu.ops.pallas_sgns import sgns_fused_step

        syn0, syn1neg, cx, tgt, lbl, live = _case(seed=11)
        syn0, syn1neg = syn0 * 40.0, syn1neg * 40.0
        dots = jnp.einsum("bd,bkd->bk", syn0[cx], syn1neg[tgt])
        assert bool(jnp.any(jnp.abs(dots) > 6.0))  # the branch is live
        alpha = 0.025
        r0, r1 = _neg_body(jnp.array(syn0), jnp.array(syn1neg),
                           cx, tgt, lbl, live, alpha)
        k0, k1_ = sgns_fused_step(jnp.array(syn0), jnp.array(syn1neg),
                                  cx, tgt, lbl, live, alpha,
                                  interpret=True)
        assert float(jnp.max(jnp.abs(r0 - k0))) < 1e-8
        assert float(jnp.max(jnp.abs(r1 - k1_))) < 1e-8


class TestSgnsEpochScan:
    def test_epoch_kernel_equals_xla(self):
        """The full production surface: _skipgram_epoch's stacked-batch
        scan with the kernel swapped in for _neg_body, f32 tables,
        device-drawn negatives — embeddings agree to 1e-5 and the HS
        table (untouched by the NS branch) is bit-identical."""
        from deeplearning4j_tpu.nlp.word2vec import _skipgram_epoch

        rng = np.random.default_rng(5)
        v, vh, d, l = 30, 40, 24, 4
        nb, b, k = 3, 8, 5
        syn0 = rng.standard_normal((v, d)).astype(np.float32) * 0.1
        syn1 = rng.standard_normal((vh, d)).astype(np.float32) * 0.1
        syn1neg = rng.standard_normal((v, d)).astype(np.float32) * 0.1
        P = jnp.asarray(rng.integers(0, vh, size=(v, l)), jnp.int32)
        C = jnp.asarray(rng.integers(0, 2, size=(v, l)), jnp.float32)
        M = jnp.asarray(rng.integers(0, 2, size=(v, l)), jnp.float32)
        table = jnp.asarray(rng.integers(0, v, size=(64,)), jnp.int32)
        cens = jnp.asarray(rng.integers(0, v, size=(nb, b)), jnp.int32)
        cxs = jnp.asarray(rng.integers(0, v, size=(nb, b)), jnp.int32)
        plive = jnp.ones((nb, b), jnp.float32).at[2, 6:].set(0.0)
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(nb)])
        alphas = jnp.full((nb,), 0.025, jnp.float32)

        def run(use_kernel):
            # the epoch jit donates the tables: fresh copies per run
            return _skipgram_epoch(
                jnp.array(syn0), jnp.array(syn1), jnp.array(syn1neg),
                P, C, M, table, cens, cxs, plive, keys, alphas,
                use_neg=True, negative_k=k,
                sgns_kernel=use_kernel, sgns_interpret=use_kernel)

        x0, x1, xn = run(False)
        p0, p1, pn = run(True)
        np.testing.assert_array_equal(np.asarray(x1), np.asarray(p1))
        assert float(jnp.max(jnp.abs(x0 - p0))) < 1e-5
        assert float(jnp.max(jnp.abs(xn - pn))) < 1e-5


class TestSgnsGate:
    def test_knob_zero_disables(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_sgns import sgns_kernel_enabled

        monkeypatch.setenv("DL4J_TPU_PALLAS_SGNS", "0")
        assert not sgns_kernel_enabled(128, 6, 100)

    def test_force_respects_vmem_budget(self, monkeypatch):
        from deeplearning4j_tpu.ops.pallas_sgns import (
            _VMEM_BUDGET_FLOATS,
            sgns_kernel_enabled,
        )

        monkeypatch.setenv("DL4J_TPU_PALLAS_SGNS", "force")
        assert sgns_kernel_enabled(128, 6, 100)
        # force bypasses the measured-win table, never the VMEM fit
        assert not sgns_kernel_enabled(_VMEM_BUDGET_FLOATS, 6, 100)

    def test_auto_stays_off_without_chip_row(self, monkeypatch):
        """'' auto on this CPU substrate: PALLAS_BENCH.json's sgns group
        has no real-chip row, so word2vec must keep the XLA _neg_body
        step (the default-off half of the rent contract)."""
        from deeplearning4j_tpu.ops.pallas_sgns import sgns_kernel_enabled

        monkeypatch.delenv("DL4J_TPU_PALLAS_SGNS", raising=False)
        assert not sgns_kernel_enabled(128, 6, 100)
