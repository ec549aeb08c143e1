"""Parameter-server RPC substrate tests (listen_and_serv/send-recv analog).

Reference analog: fluid dist tests spawn real pserver processes and run
trainers against them (test_dist_base.py pserver path;
listen_and_serv_op.cc:110). Here: the native TCP KV server serves a
subprocess-resident table; RemoteKVStore is a drop-in HostKVStore, so the
whole DeepFM sparse pipeline trains against the remote pserver unchanged.
"""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel.host_kv import HostKVEmbedding, HostKVStore
from paddle_tpu.parallel.kv_server import KVServer, RemoteKVStore


class TestInProcessServer:
    def test_pull_push_roundtrip(self):
        srv = KVServer(4, optimizer="sgd", init_scale=0.0)
        c = RemoteKVStore("localhost", srv.port)
        ids = np.array([1, 2, 1 << 40], np.int64)
        c.push(ids, np.full((3, 4), 2.0, np.float32), lr=0.5)
        np.testing.assert_allclose(c.pull(ids), -1.0)
        assert len(c) == 3
        c.close()
        srv.stop()

    def test_matches_local_store_exactly(self):
        """Same ops against a local HostKVStore and a remote server with
        identical seeds must produce identical tables (the wire adds no
        semantics)."""
        srv = KVServer(3, optimizer="adagrad", init_scale=0.05, seed=7)
        remote = RemoteKVStore("localhost", srv.port)
        local = HostKVStore(3, optimizer="adagrad", init_scale=0.05, seed=7)
        rng = np.random.default_rng(0)
        for _ in range(5):
            ids = rng.integers(0, 50, size=(8,)).astype(np.int64)
            ids = np.unique(ids)
            np.testing.assert_allclose(remote.pull(ids), local.pull(ids),
                                       rtol=1e-6)
            g = rng.normal(size=(ids.size, 3)).astype(np.float32)
            remote.push(ids, g, lr=0.1)
            local.push(ids, g, lr=0.1)
        all_ids = np.arange(50, dtype=np.int64)
        np.testing.assert_allclose(remote.pull(all_ids),
                                   local.pull(all_ids), rtol=1e-6)
        remote.close()
        srv.stop()

    def test_stop_with_live_client_does_not_hang(self):
        """A trainer that never disconnected must not deadlock server
        shutdown (Stop unblocks serve threads, then joins lock-free)."""
        import threading

        srv = KVServer(2, optimizer="sgd")
        c = RemoteKVStore("localhost", srv.port)
        c.pull(np.array([1], np.int64))    # connection alive & idle
        done = threading.Event()
        t = threading.Thread(target=lambda: (srv.stop(), done.set()))
        t.start()
        assert done.wait(timeout=20), "server stop hung with live client"
        t.join()
        c.close()

    def test_pulled_rows_are_writable(self):
        srv = KVServer(3, optimizer="sgd", init_scale=0.0)
        c = RemoteKVStore("localhost", srv.port)
        rows = c.pull(np.array([5, 6], np.int64))
        rows[0, 0] = 42.0                  # HostKVStore drop-in contract
        assert rows[0, 0] == 42.0
        c.close()
        srv.stop()

    def test_concurrent_async_clients(self):
        srv = KVServer(2, optimizer="sgd", init_scale=0.0)
        c = RemoteKVStore("localhost", srv.port, pool_size=4)
        ids = np.arange(100, dtype=np.int64)
        for _ in range(20):
            c.push(ids, np.ones((100, 2), np.float32), lr=0.1, wait=False)
        handles = [c.pull_async(ids) for _ in range(4)]
        for h in handles:
            assert h.wait().shape == (100, 2)
        c.flush()
        np.testing.assert_allclose(c.pull(ids), -2.0, rtol=1e-5)
        c.close()
        srv.stop()


def _spawn_pserver(dim):
    from paddle_tpu.testing import subprocess_env
    env = subprocess_env()
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.parallel.kv_server",
         "--dim", str(dim), "--port", "0", "--optimizer", "adagrad"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    line = proc.stdout.readline().strip()
    assert line.startswith("PORT "), line
    return proc, int(line.split()[1])


class TestPserverProcess:
    def test_deepfm_trains_against_remote_pserver(self):
        """The composed pipeline with the table in ANOTHER PROCESS:
        trainer pulls/pushes over TCP each batch (prefetch-overlapped),
        loss decreases — the fluid pserver CTR job shape."""
        from paddle_tpu import optimizer as opt
        from paddle_tpu.models.deepfm import DeepFMHostKV
        from paddle_tpu.parallel.host_kv import (build_kv_train_step,
                                                 run_kv_epoch)

        D = 4
        proc, port = _spawn_pserver(1 + D)
        try:
            store = RemoteKVStore("localhost", port)
            model = DeepFMHostKV(num_fields=5, embed_dim=D, hidden=(16,))
            optimizer = opt.Adam(learning_rate=5e-3)
            params = model.init(jax.random.PRNGKey(0))
            state = {"params": params, "opt": optimizer.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            step = jax.jit(build_kv_train_step(
                lambda p, rows, inv, label: model.loss(p, rows, inv, label),
                optimizer))
            emb = HostKVEmbedding(store, lr=0.1, min_bucket=128)

            rng = np.random.default_rng(0)

            def batches():
                for _ in range(8):
                    hot = rng.integers(0, 32, size=(64, 1))
                    tail = rng.integers(32, 5000, size=(64, 4))
                    ids = np.concatenate([hot, tail], 1).astype(np.int64)
                    label = (hot[:, 0] < 16).astype(np.float32)
                    yield dict(feat_ids=ids, label=jnp.asarray(label))

            losses = []
            for _ in range(5):
                state, hist = run_kv_epoch(step, state, emb, batches(),
                                           ids_key="feat_ids",
                                           prefetch=True)
                losses.append(np.mean([float(m["loss"]) for m in hist]))
            assert len(store) > 0
            assert losses[-1] < losses[0] - 0.05, losses
            store.close()
        finally:
            proc.terminate()
            proc.wait(timeout=30)

    def test_pserver_survives_client_churn(self):
        proc, port = _spawn_pserver(2)
        try:
            for i in range(3):
                c = RemoteKVStore("localhost", port)
                c.push(np.array([i], np.int64),
                       np.ones((1, 2), np.float32), lr=1.0)
                c.close()
            c = RemoteKVStore("localhost", port)
            assert len(c) == 3
            c.close()
        finally:
            proc.terminate()
            proc.wait(timeout=30)


class TestPserverFaultInjection:
    """Kill the pserver mid-training; detection via PSMonitor pings and
    elastic recovery from the KV snapshot (composes the heartbeat and
    snapshot pieces the way heart_beat_monitor.cc + checkpoint_notify do
    in the reference)."""

    def _train_epochs(self, state, step, emb, n_epochs, rng, seed_base=0):
        from paddle_tpu.parallel.host_kv import run_kv_epoch

        def batches():
            for _ in range(6):
                hot = rng.integers(0, 32, size=(64, 1))
                tail = rng.integers(32, 3000, size=(64, 4))
                ids = np.concatenate([hot, tail], 1).astype(np.int64)
                label = (hot[:, 0] < 16).astype(np.float32)
                yield dict(feat_ids=ids, label=jnp.asarray(label))

        losses = []
        for _ in range(n_epochs):
            state, hist = run_kv_epoch(step, state, emb, batches(),
                                       ids_key="feat_ids", prefetch=True)
            losses.append(np.mean([float(m["loss"]) for m in hist]))
        return state, losses

    def test_kill_detect_recover_from_snapshot(self, tmp_path):
        from paddle_tpu import optimizer as opt
        from paddle_tpu.models.deepfm import DeepFMHostKV
        from paddle_tpu.parallel.host_kv import build_kv_train_step
        from paddle_tpu.parallel.kv_server import PSMonitor

        D = 4
        snapshot = str(tmp_path / "kv_snapshot.bin")
        proc, port = _spawn_pserver(1 + D)
        store = RemoteKVStore("localhost", port)
        monitor = PSMonitor(store, check_every_s=0.2, misses=2,
                            log_fn=lambda *_: None)
        try:
            model = DeepFMHostKV(num_fields=5, embed_dim=D, hidden=(16,))
            optimizer = opt.Adam(learning_rate=5e-3)
            params = model.init(jax.random.PRNGKey(0))
            state = {"params": params, "opt": optimizer.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            step = jax.jit(build_kv_train_step(
                lambda p, rows, inv, label: model.loss(p, rows, inv, label),
                optimizer))
            emb = HostKVEmbedding(store, lr=0.1, min_bucket=128)
            rng = np.random.default_rng(0)

            # healthy training, then snapshot (periodic-checkpoint analog)
            state, losses_a = self._train_epochs(state, step, emb, 3, rng)
            store.save(snapshot)
            rows_before = len(store)
            assert not monitor.lost.is_set()

            # -- fault: SIGKILL the pserver mid-training ----------------
            proc.kill()
            proc.wait(timeout=30)
            with pytest.raises(Exception):
                # in-flight epoch hits the dead server and surfaces it
                self._train_epochs(state, step, emb, 1, rng)
            assert monitor.lost.wait(timeout=10), \
                "PSMonitor failed to detect the dead pserver"

            # -- elastic recovery: new pserver + snapshot restore -------
            proc2, port2 = _spawn_pserver(1 + D)
            try:
                store2 = RemoteKVStore("localhost", port2)
                assert len(store2) == 0
                store2.load(snapshot)
                assert len(store2) == rows_before
                emb2 = HostKVEmbedding(store2, lr=0.1, min_bucket=128)
                state, losses_b = self._train_epochs(state, step, emb2,
                                                     2, rng)
                # resumed training continues from the snapshot: loss keeps
                # improving relative to the pre-crash curve, no re-warmup
                assert losses_b[-1] < losses_a[0], (losses_a, losses_b)
                store2.close()
            finally:
                proc2.terminate()
                proc2.wait(timeout=30)
        finally:
            monitor.stop()
            try:
                store.close()
            except Exception:
                pass     # pool sockets died with the server
            proc.poll() or proc.terminate()
