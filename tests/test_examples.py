"""Example smoke tier (`-m examples`): every stock entrypoint must RUN.

An entrypoint no test runs is rot waiting to be discovered by a user.
The reference keeps its equivalent surface alive through
its suite (deeplearning4j-core/.../MultiLayerTest.java); here each script
runs in a SUBPROCESS exactly as a user would launch it (`python -u
examples/<name>.py` from the repo root), under the tiny-shape smoke knob
(DL4J_TPU_EXAMPLE_SMOKE=1) so the entrypoints cost minutes, not hours.
The single-device scripts take whatever backend jax gives them; the tier
runs them under JAX_PLATFORMS=cpu, so it never touches an accelerator.
"""

import glob
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = sorted(
    os.path.basename(p)
    for p in glob.glob(os.path.join(REPO, "examples", "*.py")))

# generous per-script cap: a healthy smoke run is seconds to ~2 min; the
# cap exists to turn a genuine hang into a failure, not to race the host
TIMEOUT_S = 600


def _run_example(name: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["DL4J_TPU_EXAMPLE_SMOKE"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    # a leftover multihost env (e.g. from an aborted worker) must not
    # leak a distributed contract into single-process examples
    for k in ("DL4J_TPU_COORDINATOR", "DL4J_TPU_NUM_PROCESSES",
              "DL4J_TPU_PROCESS_ID"):
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, "-u", os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env,
        cwd=REPO)


def test_every_example_is_covered():
    """The parametrized list below is generated from the directory, so a
    NEW example is auto-covered; this guard only ensures the glob still
    sees the directory at all."""
    assert len(EXAMPLES) >= 11, EXAMPLES


@pytest.mark.examples
@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    r = _run_example(name)
    assert r.returncode == 0, (
        f"{name} exited {r.returncode}\n--- stdout ---\n{r.stdout[-4000:]}"
        f"\n--- stderr ---\n{r.stderr[-4000:]}")
    # every example prints SOMETHING (loss lines, samples, eval stats) —
    # an empty stdout means the entrypoint silently did nothing
    assert r.stdout.strip(), f"{name} produced no output"
