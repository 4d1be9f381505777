"""OS-process serving-fleet replicas (ISSUE 12, full tier): the
production shape of serving/fleet.run_replica — real processes started
via ``python -m deeplearning4j_tpu.serving.fleet`` under
``JAX_PLATFORMS=cpu``, joining the
membership board from separate PIDs, answering traffic through the
router, SIGTERM -> engine drain -> deregister GOODBYE, SIGKILL -> board
expiry. The in-process contracts live in tests/test_serving_fleet.py
(quick tier); this file proves the same semantics hold across process
boundaries, like tests/test_fleet.py's OS-process-worker leg does for
the training fleet (reference anchor: the scaleout tree per SURVEY —
the serving side never existed there).
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np

from deeplearning4j_tpu.parallel.fleet import FileMembershipBoard
from deeplearning4j_tpu.serving.router import (
    FleetRouter,
    read_replica_addr,
)
from deeplearning4j_tpu.utils.serialization import ModelSerializer

from test_serving_fleet import small_net

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_replica(fleet_dir, rid, model_path, heartbeat_s=0.5):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [sys.executable, "-m", "deeplearning4j_tpu.serving.fleet",
         "--fleet-dir", str(fleet_dir), "--replica-id", rid,
         "--model-path", str(model_path),
         "--heartbeat-s", str(heartbeat_s)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def _wait_addr(fleet_dir, rid, deadline_s=90.0):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        url = read_replica_addr(str(fleet_dir), rid)
        if url is not None:
            try:
                with urllib.request.urlopen(url + "/health",
                                            timeout=5) as r:
                    if r.status == 200:
                        return url
            except OSError:
                pass
        time.sleep(0.2)
    raise AssertionError(f"replica {rid} never came up")


def test_process_replicas_goodbye_and_expiry(tmp_path):
    net = small_net()
    model_path = tmp_path / "model.zip"
    ModelSerializer.write_model(net, str(model_path))
    fleet_dir = tmp_path / "fleet"
    fleet_dir.mkdir()

    procs = {rid: _spawn_replica(fleet_dir, rid, model_path)
             for rid in ("r0", "r1")}
    router = None
    try:
        for rid in procs:
            _wait_addr(fleet_dir, rid)
        router = FleetRouter(
            board=FileMembershipBoard(str(fleet_dir),
                                      heartbeat_timeout=0.5),
            poll_s=0.2)
        router.start()
        assert sorted(router.describe_replicas()) == ["r0", "r1"]

        rng = np.random.default_rng(5)
        rows = rng.normal(size=(4, 4)).astype(np.float32)
        body = json.dumps({"batch": rows.tolist()}).encode()
        # both OS processes answer byte-identically (same zip, same
        # substrate) — collect enough round-robin turns to hit both
        bodies = set()
        for _ in range(4):
            status, _, data = router.proxy_predict(body)
            assert status == 200
            bodies.add(data)
        assert len(bodies) == 1
        out = np.asarray(json.loads(bodies.pop())["outputs"], np.float32)
        assert out.shape == (4, 3)
        np.testing.assert_allclose(
            out, np.asarray(net.output(rows), np.float32),
            rtol=0, atol=1e-6)

        # SIGTERM r1: engine drain, then the deregister GOODBYE — a
        # clean leave with NO breaker evidence
        procs["r1"].send_signal(signal.SIGTERM)
        assert procs["r1"].wait(timeout=60) == 0
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            router.refresh()
            if sorted(router.describe_replicas()) == ["r0"]:
                break
            time.sleep(0.1)
        assert sorted(router.describe_replicas()) == ["r0"]
        assert read_replica_addr(str(fleet_dir), "r1") is None
        status, _, _ = router.proxy_predict(body)
        assert status == 200
        assert router.stats.snapshot()["breaker_opens"] == 0

        # SIGKILL r0: no goodbye possible — board expiry is the only
        # witness, and the router's poll scrubs the corpse
        procs["r0"].kill()
        procs["r0"].wait(timeout=30)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            router.refresh()
            if not router.describe_replicas():
                break
            time.sleep(0.1)
        assert not router.describe_replicas()
    finally:
        if router is not None:
            router.stop()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
