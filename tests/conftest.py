"""Test env: force a virtual 8-device CPU platform BEFORE jax initializes.

Mirrors the reference's distributed-without-a-cluster test strategy
(SURVEY.md section 4: Spark local[N] in BaseSparkTest.java:90) — multi-chip
logic is tested on a virtual CPU mesh of 8 devices
(``jax_num_cpu_devices``).

float64 is enabled for the gradient-check suite (the reference enforces
double precision there, GradientCheckUtil.java).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell env may point at a TPU

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the config wins over any device-count flag inherited through XLA_FLAGS
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

# ---------------------------------------------------------------------------
# Test tiers: `-m quick` is the ~2-minute gate — the
# highest-value correctness tests (closed-form updater math, weight-init
# stats, conf round-trip, MLP/CNN gradient checks, MultiLayerNetwork core
# equivalences) — so changes can be validated without the ~38-minute full
# suite. The full suite remains the bar; quick is triage.
# ---------------------------------------------------------------------------

_QUICK_FILES = {
    "test_updaters.py",
    "test_weight_init.py",
    "test_conf_serde.py",
    "test_kernel_gate.py",
    "test_bench_preflight.py",
    # chip bring-up (ISSUE 21): chip_smoke.py's rehearsal + its refusal
    # to run off a TPU, the compile-cache placement contract, the peaks
    # table — seconds, plus one ~40s rehearsal subprocess
    "test_chip_smoke.py",
    "test_gradient_check.py",
    "test_multilayer.py",
    "test_dispatch.py",
    # remat==no-remat value contracts + the AOT memory ladder (ISSUE 4)
    "test_remat.py",
    # the whole resilience suite (incl. the subprocess SIGTERM preemption
    # leg, ~6s) fits the quick budget — crash-recovery is exactly the kind
    # of contract a mid-round change can silently break
    "test_resilience.py",
    # ETL plane (ISSUE 5): transform/normalizer value contracts plus the
    # pipeline==serial byte-equivalence and kill/resume-through-pipeline
    # contracts — both files run in seconds on tiny nets
    "test_etl.py",
    "test_input_pipeline.py",
    # elastic fleet (ISSUE 6): the headline worker-loss/rejoin == replay
    # bit-exactness + == serial contracts (~15s on tiny nets); the
    # OS-process-worker leg is excluded below (full tier covers it)
    "test_fleet.py",
    # observability plane (ISSUE 7): obs-off == obs-on bit-exactness, the
    # ledger-registration convention, Prometheus golden exposition, the
    # five-ledgers-in-one-scrape contract — seconds on tiny nets
    "test_obs.py",
    # serving resilience plane (ISSUE 8): chaos-driven breaker/watchdog/
    # drain/isolation contracts — deterministic injected faults on tiny
    # nets, the serving third of the crash-recovery convention
    "test_serving_resilience.py",
    # paged-KV serving plane (ISSUE 11): block-pool request independence
    # (solo==coscheduled across prefix sharing/preemption), crash
    # eviction, SLO shed, streaming, arena sizing — ~15s on tiny LMs
    "test_serving_paged.py",
    # serving fleet (ISSUE 12): router+replicas byte-identity vs a solo
    # engine, chaos-killed replica => zero failed admitted requests,
    # rollout auto-rollback never moving a serving default, fleet-wide
    # SLO shed, breaker eject/half-open re-admit — deterministic chaos
    # on tiny nets, in-process replicas (~20s); OS-process replicas are
    # full tier (test_serving_fleet_process.py)
    "test_serving_fleet.py",
    # graftlint (ISSUE 10): per-rule fixture contracts + the repo-wide
    # clean sweep + the knob-table↔CLAUDE.md consistency gate — pure-AST,
    # jax-free, seconds for the fixtures and ~15s for the sweep
    "test_analysis.py",
    # kernel rent program (ISSUE 13): interpret-mode CPU equivalence for
    # the paged-decode attention + fused SGNS kernels (value, tick/epoch,
    # forced-transcript, and gate contracts) — tiny shapes, ~30s
    "test_pallas_paged.py",
    "test_pallas_sgns.py",
    # online learning loop (ISSUE 14): kill/resume through a live
    # StreamSource bit-exactness, zero-failed-request promotion swap,
    # deterministic drift veto, mirror byte-invisibility — tiny nets,
    # ~15s
    "test_online.py",
    # low-precision plane (ISSUE 15): int8 value/gate fail-safe contracts,
    # bf16 loss-scaling (chaos-forced halving, kill/resume bit-exactness,
    # flagship opt-tree scale state), bf16 KV arena sizing — tiny nets,
    # ~40s
    "test_lowprec.py",
    # decode amortization (ISSUE 16): k-tick == k x 1-tick byte-identity
    # across the paged contract matrix, speculative greedy == target-only
    # greedy (chaos all-reject included), acceptance ledger arithmetic,
    # knob registration — tiny LMs, ~30s
    "test_speculate.py",
    # embedding & retrieval plane (ISSUE 17): /embed batcher==direct
    # byte-equivalence (pad rows inert), exact-index vs numpy oracle,
    # MEASURED IVF recall, zero-failed-/search across a generation swap,
    # drift veto, knob/ledger registration — tiny nets, ~20s
    "test_retrieval.py",
    # mesh-sharded inference plane (ISSUE 18): sharded tick == solo tick
    # byte-identity across the paged contract matrix (prefix sharing /
    # preemption / crash eviction / streaming), loud incompatibility
    # gates, per-device arena closed forms, role-aware router dispatch +
    # the prefill->decode handoff, knob/ledger registration — tiny LMs
    # on the virtual CPU mesh, ~40s
    "test_serving_mesh.py",
    # autoscaling plane (ISSUE 20): deterministic scale-decision replay,
    # chaos load wave -> scale-up -> scale-down racing live /predict +
    # /generate with zero failed admitted requests, tenant-bucket
    # fairness, FFD placement + affinity 503 loudness, goodbye ordering,
    # knob/ledger/leg registration — tiny nets, ~40s
    "test_autoscale.py",
}
# float64 recurrent gradchecks cost ~2 min alone — full-suite only; the
# attention/MoE/BERT checks cost ~80s together and
# join them outside the quick budget
_QUICK_EXCLUDE = {"test_rnn_masked_gradients", "test_lstm_gradients",
                  "test_gru_gradients", "test_mha_gradients",
                  "test_moe_ffn_gradients", "test_bert_mlm_loss_gradients",
                  # 3 subprocess coordinators + workers (~30s): full tier
                  "test_corrupt_checkpoint_fleet_restore_multiprocess"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: fast high-value gate (see CLAUDE.md test tiers)")
    config.addinivalue_line(
        "markers", "examples: subprocess smoke runs of every stock "
        "examples/*.py entrypoint (tiny shapes, forced CPU)")


def pytest_collection_modifyitems(config, items):
    seen_files = set()
    seen_names = set()
    for item in items:
        base = os.path.basename(str(item.fspath))
        if base in _QUICK_FILES:
            seen_files.add(base)
            name = item.name.split("[")[0]
            seen_names.add(name)
            if name not in _QUICK_EXCLUDE:
                item.add_marker(pytest.mark.quick)
    # Stale-exclusion guard (ADVICE r5): a renamed/removed slow test must
    # fail collection LOUDLY, not silently re-enter the 2-minute quick
    # gate. Only enforced when every quick file was collected (a partial
    # run — one file, a -k filter — legitimately misses names).
    if seen_files >= _QUICK_FILES:
        stale = _QUICK_EXCLUDE - seen_names
        if stale:
            raise pytest.UsageError(
                f"_QUICK_EXCLUDE entries never seen in collection: "
                f"{sorted(stale)} — the excluded tests were renamed or "
                "removed; update tests/conftest.py so the quick tier "
                "stays honest"
            )
