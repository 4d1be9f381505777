"""Multi-host runtime exercised across REAL OS processes.

The reference's distributed plane is genuinely multi-process (Spark
executors + Aeron broadcast; SURVEY.md section 2.3/2.7). Until round 4
`parallel/multihost.py` was validated only single-process; this harness
spawns a 2-process jax.distributed CPU cluster (2 local devices each, 4
global, collectives over Gloo) wired through the SAME env-var contract
the TPU pod provisioner injects, and asserts the framework's actual DP
training path (ParallelWrapper.fit and the fused fit_batches scan) is
bit-identical to serial training — the
TestCompareParameterAveragingSparkVsSingleMachine property, across
process boundaries.
"""
import os
import socket
import subprocess
import sys


from deeplearning4j_tpu.parallel import multihost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_dp_training_matches_serial():
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env[multihost.COORDINATOR_ENV] = f"127.0.0.1:{port}"
        env[multihost.NUM_PROCESSES_ENV] = "2"
        env[multihost.PROCESS_ID_ENV] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err[-2000:]}"
        assert "MH_OK" in out, out
        assert "max_param_dev=0.0" in out, out
    # both processes saw the same replicated final loss
    losses = {line.split("loss=")[1].split()[0]
              for _, out, _ in outs for line in out.splitlines()
              if "MH_OK" in line}
    assert len(losses) == 1, losses
