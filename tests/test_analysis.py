"""graftlint contracts (ISSUE 10): per-rule positive/negative/suppression
fixtures, the repo-wide clean sweep, and the knob-table↔CLAUDE.md
consistency gate.

Fixture style: each rule gets synthetic snippets written to tmp_path and
parsed through the real ``engine.parse_file`` pipeline with a
plane-appropriate ``rel`` (scoped rules key off the repo-relative path).
The snippets deliberately SPELL violations — which is exactly why
``tests/`` is outside the linter's DEFAULT_TARGETS and why the repo-wide
sweep must stay clean while these fixtures fire.

Everything here is pure-AST and jax-free (the analysis package never
imports jax), so the whole file fits the quick tier.
"""

import os
import subprocess
import sys
import textwrap

from deeplearning4j_tpu.analysis import engine
from deeplearning4j_tpu.analysis.engine import (
    DEFAULT_TARGETS,
    parse_file,
    rule_names,
    run_paths,
)
from deeplearning4j_tpu.analysis.rules_conventions import (
    DocstringProvenance,
    LedgerRegistration,
    PallasRent,
    SignalHandlerSafety,
)
from deeplearning4j_tpu.analysis.rules_env import ChaosAmbient, EnvKnobRegistry
from deeplearning4j_tpu.analysis.rules_threads import (
    HostSyncUnderLock,
    ThreadSharedState,
)
from deeplearning4j_tpu.analysis.rules_jit import (
    DonationThroughDispatch,
    NondeterminismInJit,
)
from deeplearning4j_tpu.ops.env import KNOBS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(tmp_path, source, rule_cls,
          rel="deeplearning4j_tpu/serving/fixture_mod.py"):
    """Write a snippet, parse it as ``rel``, run one rule; returns
    (unsuppressed findings, parsed file)."""
    p = tmp_path / "fixture_mod.py"
    p.write_text(textwrap.dedent(source))
    pf = parse_file(str(p), rel, rule_names())
    found = [f for f in rule_cls().check(pf)
             if not pf.is_suppressed(f.rule, f.line)]
    return found, pf


# ---------------------------------------------------------------------------
# donation-through-dispatch
# ---------------------------------------------------------------------------


def test_direct_donation_fires_outside_dispatch(tmp_path):
    found, _ = _lint(tmp_path, """\
        import jax
        step = jax.jit(f, donate_argnums=(0,))
        """, DonationThroughDispatch)
    assert len(found) == 1


def test_partial_jit_decorator_donation_fires(tmp_path):
    # the functools.partial(jax.jit, ...) decorator idiom must be caught
    found, _ = _lint(tmp_path, """\
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(a, b):
            return a + b
        """, DonationThroughDispatch)
    assert len(found) == 1


def test_donation_inside_dispatch_is_the_sanctioned_home(tmp_path):
    found, _ = _lint(tmp_path, """\
        import jax
        step = jax.jit(f, donate_argnums=(0,))
        """, DonationThroughDispatch,
        rel="deeplearning4j_tpu/ops/dispatch.py")
    assert found == []


# ---------------------------------------------------------------------------
# nondeterminism-in-jit
# ---------------------------------------------------------------------------


def test_wall_clock_inside_jitted_fn_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        import time
        import jax

        @jax.jit
        def step(x):
            return x * time.time()
        """, NondeterminismInJit)
    assert len(found) == 1


def test_nondet_via_jit_call_by_name_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        import jax
        import numpy as np

        def step(x):
            return x + np.random.randn()

        fast = jax.jit(step)
        """, NondeterminismInJit)
    assert len(found) == 1


def test_nondet_outside_traced_code_is_clean(tmp_path):
    found, _ = _lint(tmp_path, """\
        import time

        def host_timer():
            return time.time()
        """, NondeterminismInJit)
    assert found == []


# ---------------------------------------------------------------------------
# env-knob-registry
# ---------------------------------------------------------------------------


def test_direct_environ_read_of_knob_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        import os
        v = os.environ.get("DL4J_TPU_DONATE")
        """, EnvKnobRegistry)
    assert len(found) == 1
    assert "ops.env" in found[0].message


def test_knob_typo_literal_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        NAME = "DL4J_TPU_DONAET"
        """, EnvKnobRegistry)
    assert len(found) == 1
    assert "not a registered knob" in found[0].message


def test_registered_literal_and_env_write_are_clean(tmp_path):
    # writes stay legal (tests/bench pin knobs for subprocesses), and a
    # registered name as a literal is how call sites name knobs
    found, _ = _lint(tmp_path, """\
        import os
        os.environ["DL4J_TPU_DONATE"] = "force"
        os.environ.setdefault("DL4J_TPU_OFFLINE", "1")
        NAME = "DL4J_TPU_DONATE"
        """, EnvKnobRegistry)
    assert found == []


def test_knob_table_and_claude_md_agree():
    # the project-level two-way diff the CLI runs — kept as its own test
    # so doc drift fails here by name, not just in the sweep
    findings = EnvKnobRegistry().check_project(REPO, [])
    assert findings == [], "\n".join(f.format() for f in findings)


def test_every_knob_documented_in_claude_md():
    with open(os.path.join(REPO, "CLAUDE.md"), encoding="utf-8") as f:
        text = f.read()
    missing = [k for k in KNOBS if k not in text]
    assert missing == [], f"knobs undocumented in CLAUDE.md: {missing}"


# ---------------------------------------------------------------------------
# chaos-ambient
# ---------------------------------------------------------------------------


def test_chaos_config_at_import_time_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        from deeplearning4j_tpu.resilience.chaos import FleetChaosConfig
        CHAOS = FleetChaosConfig(kill_worker=1)
        """, ChaosAmbient)
    assert len(found) == 1
    assert "import time" in found[0].message


def test_chaos_config_as_param_default_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        def fit(chaos=ServingChaosConfig()):
            return chaos
        """, ChaosAmbient)
    assert len(found) == 1
    assert "parameter default" in found[0].message


def test_chaos_config_inside_test_body_is_clean(tmp_path):
    found, _ = _lint(tmp_path, """\
        def test_kill():
            chaos = FleetChaosConfig(kill_worker=2)
            return chaos
        """, ChaosAmbient)
    assert found == []


# ---------------------------------------------------------------------------
# ledger-registration
# ---------------------------------------------------------------------------


def test_unregistered_ledger_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        class Net:
            def __init__(self):
                self.shiny_stats = object()
        """, LedgerRegistration, rel="deeplearning4j_tpu/nn/fixture.py")
    assert len(found) == 1
    assert "register_net" in found[0].message


def test_ledger_with_registration_hook_is_clean(tmp_path):
    found, _ = _lint(tmp_path, """\
        from deeplearning4j_tpu.obs.registry import register_net

        class Net:
            def __init__(self):
                self.shiny_stats = object()
                register_net(self)
        """, LedgerRegistration, rel="deeplearning4j_tpu/nn/fixture.py")
    assert found == []


# ---------------------------------------------------------------------------
# signal-handler-safety
# ---------------------------------------------------------------------------


def test_lock_taking_signal_handler_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        import signal

        def on_term(signum, frame):
            with state_lock:
                flags.append(signum)

        signal.signal(signal.SIGTERM, on_term)
        """, SignalHandlerSafety)
    assert len(found) == 1
    assert "deadlock" in found[0].message


def test_minimal_flag_handler_is_clean(tmp_path):
    found, _ = _lint(tmp_path, """\
        import signal

        def on_term(signum, frame):
            global preempted
            preempted = True

        signal.signal(signal.SIGTERM, on_term)
        """, SignalHandlerSafety)
    assert found == []


# ---------------------------------------------------------------------------
# host-sync-under-lock / thread-shared-state
# ---------------------------------------------------------------------------


def test_readback_under_lock_warns_in_threaded_plane(tmp_path):
    found, _ = _lint(tmp_path, """\
        import numpy as np

        class Batcher:
            def flush(self):
                with self._lock:
                    out = np.asarray(self._device_buf)
                return out
        """, HostSyncUnderLock)
    assert len(found) == 1
    assert found[0].severity == "warning"


def test_readback_outside_lock_and_outside_scope_is_clean(tmp_path):
    src = """\
        import numpy as np

        class Batcher:
            def flush(self):
                with self._lock:
                    buf = self._device_buf
                return np.asarray(buf)
        """
    found, _ = _lint(tmp_path, src, HostSyncUnderLock)
    assert found == []
    # same violation OUTSIDE the threaded planes is out of scope
    found, _ = _lint(tmp_path, """\
        import numpy as np

        class C:
            def f(self):
                with self._lock:
                    return np.asarray(self.x)
        """, HostSyncUnderLock, rel="deeplearning4j_tpu/nn/fixture.py")
    assert found == []


def test_racing_writes_across_thread_entries_warn(tmp_path):
    found, _ = _lint(tmp_path, """\
        import threading

        class Pool:
            def start(self):
                threading.Thread(target=self._worker).start()
                threading.Thread(target=self._reaper).start()

            def _worker(self):
                self.inflight = self.inflight + 1

            def _reaper(self):
                self.inflight -= 1
        """, ThreadSharedState)
    assert len(found) == 1
    assert "inflight" in found[0].message


def test_constant_flag_and_locked_writes_are_sanctioned(tmp_path):
    found, _ = _lint(tmp_path, """\
        import threading

        class Pool:
            def start(self):
                threading.Thread(target=self._worker).start()
                threading.Thread(target=self._reaper).start()

            def _worker(self):
                self.draining = True
                with self._lock:
                    self.inflight = self.inflight + 1

            def _reaper(self):
                self.draining = False
                with self._lock:
                    self.inflight -= 1
        """, ThreadSharedState)
    assert found == []


# ---------------------------------------------------------------------------
# docstring-provenance
# ---------------------------------------------------------------------------


def test_uncited_public_class_in_parity_dir_warns(tmp_path):
    found, _ = _lint(tmp_path, """\
        class ShinyLayer:
            \"\"\"A layer with no provenance at all.\"\"\"
        """, DocstringProvenance, rel="deeplearning4j_tpu/nn/fixture.py")
    assert len(found) == 1
    assert found[0].severity == "warning"


def test_cited_class_and_beyond_reference_plane_are_clean(tmp_path):
    src = """\
        class ShinyLayer:
            \"\"\"Parity port of DenseLayer.java:42.\"\"\"
        """
    found, _ = _lint(tmp_path, src, DocstringProvenance,
                     rel="deeplearning4j_tpu/nn/fixture.py")
    assert found == []
    # beyond-reference planes (serving/ etc.) are exempt by design
    found, _ = _lint(tmp_path, """\
        class Breaker:
            \"\"\"No citation needed here.\"\"\"
        """, DocstringProvenance)
    assert found == []


# ---------------------------------------------------------------------------
# pallas-rent
# ---------------------------------------------------------------------------


def test_pallas_call_outside_ops_pallas_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        from jax.experimental import pallas as pl

        def hot_path(x):
            return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
        """, PallasRent, rel="deeplearning4j_tpu/serving/fixture.py")
    assert len(found) == 1
    assert "outside ops/pallas_" in found[0].message


def test_pallas_module_without_interpret_param_fires(tmp_path):
    found, _ = _lint(tmp_path, """\
        from jax.experimental import pallas as pl

        def kernel_wrapper(x):
            return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
        """, PallasRent, rel="deeplearning4j_tpu/ops/pallas_fixture.py")
    assert len(found) == 1
    assert "interpret" in found[0].message


def test_pallas_module_with_interpret_fallback_is_clean(tmp_path):
    found, _ = _lint(tmp_path, """\
        from jax.experimental import pallas as pl

        def kernel_wrapper(x, *, interpret=False):
            return pl.pallas_call(lambda r, o: None, out_shape=x,
                                  interpret=interpret)(x)
        """, PallasRent, rel="deeplearning4j_tpu/ops/pallas_fixture.py")
    assert found == []
    # no pallas_call at all: nothing to check, wherever the file lives
    found, _ = _lint(tmp_path, """\
        def plain(x):
            return x
        """, PallasRent, rel="deeplearning4j_tpu/serving/fixture.py")
    assert found == []


def test_pallas_rent_suppression_is_honored(tmp_path):
    found, _ = _lint(tmp_path, """\
        from jax.experimental import pallas as pl

        def hot_path(x):
            # graftlint: disable=pallas-rent -- fixture: migration shim, kernel moving to ops/pallas_x.py
            return pl.pallas_call(lambda r, o: None, out_shape=x)(x)
        """, PallasRent, rel="deeplearning4j_tpu/serving/fixture.py")
    assert found == []


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------


def test_standalone_suppression_covers_next_code_line(tmp_path):
    found, pf = _lint(tmp_path, """\
        import jax
        # graftlint: disable=donation-through-dispatch -- fixture: single-owner buffer

        step = jax.jit(f, donate_argnums=(0,))
        """, DonationThroughDispatch)
    assert found == []
    assert pf.bad_suppressions == []


def test_suppression_without_justification_is_itself_a_finding(tmp_path):
    _, pf = _lint(tmp_path, """\
        import jax
        step = jax.jit(f, donate_argnums=(0,))  # graftlint: disable=donation-through-dispatch
        """, DonationThroughDispatch)
    assert len(pf.bad_suppressions) == 1
    assert pf.bad_suppressions[0].rule == "bad-suppression"
    assert "justification" in pf.bad_suppressions[0].message


def test_suppression_of_unknown_rule_is_a_finding(tmp_path):
    _, pf = _lint(tmp_path, """\
        x = 1  # graftlint: disable=no-such-rule -- because
        """, DonationThroughDispatch)
    assert len(pf.bad_suppressions) == 1
    assert "unknown rule" in pf.bad_suppressions[0].message


def test_disable_file_covers_every_line(tmp_path):
    found, pf = _lint(tmp_path, """\
        # graftlint: disable-file=donation-through-dispatch -- fixture: whole file exempt
        import jax
        a = jax.jit(f, donate_argnums=(0,))
        b = jax.jit(g, donate_argnums=(1,))
        """, DonationThroughDispatch)
    assert found == []
    assert pf.bad_suppressions == []


# ---------------------------------------------------------------------------
# the repo-wide gate + CLI contract
# ---------------------------------------------------------------------------


def test_repo_surface_is_lint_clean():
    """THE gate: the committed tree has zero unsuppressed findings."""
    report = run_paths(root=REPO)
    assert report.clean, "\n".join(f.format() for f in report.findings)
    assert report.files_scanned > 100  # the surface really was scanned


def test_default_targets_exist():
    # a renamed entrypoint must not silently shrink the scanned surface
    missing = [t for t in DEFAULT_TARGETS
               if not os.path.exists(os.path.join(REPO, t))]
    assert missing == [], f"DEFAULT_TARGETS entries missing: {missing}"


def test_cli_exit_codes(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("import jax\nf = jax.jit(g, donate_argnums=(0,))\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "--json",
         str(dirty)], capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "donation-through-dispatch" in r.stdout
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    r = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", str(clean)],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


def test_rule_registry_is_well_formed():
    names = rule_names()
    assert "bad-suppression" in names
    # the device-probe and completion-fence rules are gone (ISSUE 21):
    # block_until_ready IS the completion fence, and probing a device is
    # only wrong in a parent that then starts a child needing the chip
    assert not any("probe" in n or "fence" in n for n in names)
    assert "chip_smoke.py" in DEFAULT_TARGETS
    for rule in engine.all_rules():
        assert rule.name and rule.doc
        assert rule.severity in engine.SEVERITIES
