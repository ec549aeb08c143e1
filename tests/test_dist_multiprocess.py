"""Multi-process distributed tests: real localhost worker processes.

Reference analog: ``test_dist_base.py`` — ``_run_cluster``:629 spawns
trainer subprocesses, ``check_with_place``:828 asserts per-step loss parity
between the distributed run and a local single-process run; pserver tests
kill processes to exercise failure detection. Here the workers bootstrap
with ``fleet.init`` -> ``jax.distributed.initialize`` over a localhost
coordinator (CPU backend, Gloo collectives) and train the same model
data-parallel; the kill test exercises HeartbeatMonitor / coordination-
service failure detection.

These tests manage their own subprocesses (each with its own single-device
CPU backend), independent of the in-process 8-device fixture.
"""

import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest
pytestmark = pytest.mark.slow  # excluded from the quick CI gate


_WORKER = os.path.join(os.path.dirname(__file__), "dist_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _env():
    from paddle_tpu.testing import subprocess_env
    env = subprocess_env()
    # a virtual-device-count flag from the parent suite would give every
    # worker 8 local devices and break the 2-process topology
    if "XLA_FLAGS" in env:
        env["XLA_FLAGS"] = " ".join(
            f for f in env["XLA_FLAGS"].split()
            if "xla_force_host_platform_device_count" not in f)
    return env


def _spawn(rank, nproc, port, out, *, steps=5, mode="parity", die_at=-1):
    # stderr goes to a file, not a pipe: an undrained pipe can fill and
    # block the child (spurious timeout); the file is read on failure
    errlog = open(out + ".stderr", "w")
    proc = subprocess.Popen(
        [sys.executable, _WORKER, "--rank", str(rank), "--nproc",
         str(nproc), "--port", str(port), "--out", out, "--steps",
         str(steps), "--mode", mode, "--die-at", str(die_at)],
        env=_env(), stdout=subprocess.DEVNULL, stderr=errlog)
    errlog.close()
    proc.errlog_path = out + ".stderr"
    return proc


def _wait_all(procs, timeout=180):
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            p.wait(timeout=max(1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.poll() is None:
                    q.kill()
            pytest.fail("distributed worker timed out")


class TestDistLossParity:
    def test_two_process_matches_single(self, tmp_path):
        """2-worker dp run must produce the same per-step losses as a
        single-process run on the same global batches (the reference's
        check_with_place delta assert, delta -> exact here: same arithmetic,
        psum mean over the same global batch)."""
        steps = 5
        # distributed: 2 processes
        port = _free_port()
        outs = [str(tmp_path / f"w{r}.json") for r in range(2)]
        procs = [_spawn(r, 2, port, outs[r], steps=steps) for r in range(2)]
        # local baseline: 1 process, full batch
        out1 = str(tmp_path / "single.json")
        single = _spawn(0, 1, _free_port(), out1, steps=steps)
        _wait_all(procs + [single])
        for p in procs + [single]:
            assert p.returncode == 0, open(p.errlog_path).read()[-800:]

        dist = [json.load(open(o)) for o in outs]
        base = json.load(open(out1))
        assert len(base["losses"]) == steps
        for w in dist:
            assert len(w["losses"]) == steps
            np.testing.assert_allclose(w["losses"], base["losses"],
                                       rtol=1e-5, atol=1e-6)
        # losses actually decreased (the run trained, not just agreed)
        assert base["losses"][-1] < base["losses"][0]

    def test_elastic_gang_restart_resumes_from_checkpoint(self, tmp_path):
        """Full fault-tolerance loop: rank 1 crashes mid-run, the
        ElasticCoordinator kills and respawns the gang, workers resume
        from the latest checkpoint, and the final per-step loss history
        is IDENTICAL to an uninterrupted run (deterministic data by step
        index). Reference: §5.3 restart policy over heart_beat_monitor
        detection."""
        from paddle_tpu.fleet import ElasticCoordinator

        steps = 6
        # baseline: uninterrupted 2-process run
        bport = _free_port()
        bouts = [str(tmp_path / f"base{r}.json") for r in range(2)]
        procs = [_spawn(r, 2, bport, bouts[r], steps=steps)
                 for r in range(2)]
        _wait_all(procs)
        base = json.load(open(bouts[0]))["losses"]
        assert len(base) == steps

        # elastic: crash rank 1 at step 3 on attempt 0
        ckpt = str(tmp_path / "elastic.ckpt")
        outs = [str(tmp_path / f"e{r}.json") for r in range(2)]
        ports = {}

        def spawn(rank, attempt):
            if attempt not in ports:
                ports[attempt] = _free_port()  # fresh coordinator per gang
            p = subprocess.Popen(
                [sys.executable, _WORKER, "--rank", str(rank), "--nproc",
                 "2", "--port", str(ports[attempt]), "--out", outs[rank],
                 "--steps", str(steps), "--mode", "elastic", "--die-at",
                 "3", "--ckpt", ckpt, "--attempt", str(attempt)],
                env=_env(), stdout=subprocess.DEVNULL,
                stderr=open(outs[rank] + f".a{attempt}.stderr", "w"))
            return p

        coord = ElasticCoordinator(spawn, 2, max_restarts=2,
                                   log_fn=lambda m: None)
        assert coord.run(timeout_s=240), "elastic job did not finish"
        assert coord.restarts == 1           # exactly one gang restart

        rec = json.load(open(outs[0]))
        assert any(e["kind"] == "resumed" and e["step"] == 3
                   for e in rec["events"]), rec["events"]
        np.testing.assert_allclose(rec["losses"], base, rtol=1e-6)

    def test_worker_death_is_detected(self, tmp_path):
        """Kill rank 1 mid-run; rank 0 must DETECT the failure (heartbeat
        stall callback or coordination-service error) and record it, not
        hang (test_dist_base kills pserver subprocesses similarly)."""
        port = _free_port()
        out0 = str(tmp_path / "w0.json")
        out1 = str(tmp_path / "w1.json")
        p0 = _spawn(0, 2, port, out0, steps=200, mode="stall", die_at=-1)
        p1 = _spawn(1, 2, port, out1, steps=200, mode="stall", die_at=3)
        _wait_all([p0, p1], timeout=180)
        assert p1.returncode == 9          # simulated crash
        assert p0.returncode in (3, 4), open(p0.errlog_path).read()[-800:]
        rec = json.load(open(out0))
        kinds = {e["kind"] for e in rec["events"]}
        assert kinds & {"stall_detected", "peer_failure"}, rec
        # some steps ran before the crash was noticed
        assert len(rec["losses"]) >= 1
