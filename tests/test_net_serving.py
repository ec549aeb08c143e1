"""Network serving (ISSUE 17): process-isolated replicas behind the
wire-protocol ReplicaHandle and the streaming front door.

The battery pins the ISSUE acceptance:

- the wire codec round-trips every structure the ReplicaHandle surface
  traffics in (ndarrays, tuples, int-keyed maps, bytes, sets, the
  FullReplay marker), and corruption — bad magic, torn frames, checksum
  mismatches — raises ``WireError`` (a ``ConnectionError``, i.e.
  already inside the router's ``TRANSPORT_ERRORS``);
- structured rejects/errors survive the socket for the FULL
  ``Reject.reason`` vocabulary — a remote shed re-raises client-side
  with its typed verdict intact;
- ``NetReplica`` is indistinguishable from ``LocalReplica`` to the
  ``FleetRouter`` (zero router forks): a mixed net+local fleet produces
  bit-identical greedy outputs;
- heartbeat ages cross the wire as the sender's MONOTONIC deltas
  (patched-wall-clock regression test);
- socket chaos: a hung server opens the breaker and the deliberate
  probe closes it again (full open → half_open → closed over a real
  socket); a dead server is ejected on consecutive transport failures,
  its in-flight requests redriven bit-identically with 0 lost and a
  CLIENT-side postmortem (the remote witness is gone);
- the front door streams >=2 partial deliveries, sheds slow readers
  with a structured ``Reject`` (never a bare disconnect), and its
  crash-safe netlog validates: monotonic frames, every accepted rid
  terminated exactly once.

Subprocess legs (real ``kill -9``, SIGTERM drain → ``EXIT_DRAINED``)
run under ``-m slow`` with the rest of the multi-process tier; the
CI-gated bench (``bench.py --model net_router --dryrun``) exercises
the same battery against real processes on every run.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.resilience.preempt import EXIT_DRAINED
from paddle_tpu.resilience.retry import RetryPolicy
from paddle_tpu.serving import fleet
from paddle_tpu.serving.fleet import net
from paddle_tpu.serving.fleet.net import frontdoor, wire
from paddle_tpu.serving.fleet.router import TRANSPORT_ERRORS
from paddle_tpu.serving.scheduler import LoadShedError, Reject

VOCAB = 64

CODECS = ["json"] + (["msgpack"] if wire.msgpack is not None else [])

# the full structured-shed vocabulary: engine submit/reap sheds, router
# redrive/requeue sheds, and the front door's own slow-reader verdict —
# read from the one registered source of truth so the parametrized wire
# tests can never drift from what the protocol validates
from paddle_tpu.serving.scheduler import REJECT_REASONS  # noqa: E402

FAST_RETRY = RetryPolicy(max_attempts=2, base_delay_s=0.02,
                         max_delay_s=0.1, deadline_s=2.0,
                         retry_on=(OSError, TimeoutError))


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def model_params():
    from serving_taps import tiny_gpt
    return tiny_gpt()


def _engine(model_params, **kw):
    model, params = model_params
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_tokens_per_slot", 48)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_block", 2)
    return serving.ServingEngine(model, params, attn_impl="lax",
                                 registry=obs.MetricsRegistry(), **kw)


def _prompts(n, rng_seed=0, lens=(3, 5, 7)):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(1, VOCAB, int(k)).astype(np.int32)
            for k in rng.choice(lens, n)]


def _drive(handle, rids, max_steps=300):
    """Step ``handle`` until every rid in ``rids`` finished; returns
    the accumulated {rid: tokens} (results are pop-on-read upstream,
    so accumulate from step returns — never re-poll)."""
    done = {}
    for _ in range(max_steps):
        done.update(handle.step())
        if all(r in done for r in rids):
            return done
    raise AssertionError(f"{len(done)}/{len(rids)} finished "
                         f"in {max_steps} steps")


class ServerHarness:
    """A ReplicaServer driven from a plain thread, pausable (a paused
    server IS a hung host: accepted TCP, no replies) and stoppable (a
    stopped server IS a dead host: RST/refused)."""

    def __init__(self, engine, **kw):
        self.srv = net.ReplicaServer(engine, **kw)
        self._stop = threading.Event()
        self._pause = threading.Event()
        self._parked = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            if self._pause.is_set():
                self._parked.set()
                time.sleep(0.005)
                continue
            self.srv.serve_step(0.02)

    @property
    def address(self):
        return self.srv.address

    def pause(self):
        # synchronous: an in-flight serve_step could still answer an RPC
        # sent right after pause() returns, so wait until the loop parks
        self._parked.clear()
        self._pause.set()
        self._parked.wait(timeout=10)

    def resume(self):
        self._pause.clear()
        self._parked.clear()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=10)
        self.srv.close()


@pytest.fixture(scope="module")
def rig(model_params):
    """One warmed engine behind an in-thread ReplicaServer + a second
    warmed engine for local peers — shared across the quick tier."""
    eng_srv = _engine(model_params)
    harness = ServerHarness(eng_srv, name="netrig")
    rep = net.NetReplica(harness.address)
    rep.warmup()
    eng_local = _engine(model_params)
    fleet.LocalReplica(eng_local, name="warmer").warmup()
    yield {"harness": harness, "rep": rep, "eng_local": eng_local}
    rep.close()
    harness.stop()


# ---------------------------------------------------------------------------
# wire protocol
# ---------------------------------------------------------------------------

class TestWire:
    @pytest.mark.parametrize("codec", CODECS)
    def test_payload_roundtrip(self, codec):
        payload = {
            "arr": np.arange(6, dtype=np.float32).reshape(2, 3),
            "ids": np.array([3, 1, 4], dtype=np.int32),
            "blob": b"\x00\xff\x10page",
            "tup": (1, "a", (2.5, None)),
            "intmap": {3: [1, 2], 7: []},
            "reserved": {"__buf__like": 1},
            "aset": {3, 1, 2},
            "replay": fleet.FullReplay([5, 6, 7]),
            "none": None, "flag": True, "f": 1.5,
        }
        dec = wire.MessageDecoder()
        msgs = dec.feed(wire.encode_message(payload, codec=codec))
        assert len(msgs) == 1
        out = msgs[0]
        assert np.array_equal(out["arr"], payload["arr"])
        assert out["arr"].dtype == np.float32
        assert np.array_equal(out["ids"], payload["ids"])
        assert out["blob"] == payload["blob"]
        assert out["tup"] == (1, "a", (2.5, None))
        assert isinstance(out["tup"], tuple)
        assert out["intmap"] == {3: [1, 2], 7: []}
        assert set(out["intmap"]) == {3, 7}          # int keys, not str
        assert out["reserved"] == {"__buf__like": 1}
        assert out["aset"] == frozenset({1, 2, 3})
        assert isinstance(out["replay"], fleet.FullReplay)
        assert list(out["replay"]) == [5, 6, 7]
        assert out["none"] is None and out["flag"] is True
        assert out["f"] == 1.5

    def test_pipelined_messages_in_ragged_chunks(self):
        a = wire.encode_message({"n": 1, "x": np.ones(4, np.int32)})
        b = wire.encode_message({"n": 2})
        stream = a + b
        dec = wire.MessageDecoder()
        got = []
        for i in range(0, len(stream), 7):        # deliberately torn reads
            got.extend(dec.feed(stream[i:i + 7]))
        assert [m["n"] for m in got] == [1, 2]
        assert np.array_equal(got[0]["x"], np.ones(4, np.int32))

    def test_checksum_mismatch_is_wire_error(self):
        msg = bytearray(wire.encode_message(
            {"snap": np.arange(32, dtype=np.float32)}))
        msg[-1] ^= 0xFF                           # corrupt the page bytes
        with pytest.raises(wire.WireError, match="checksum"):
            wire.MessageDecoder().feed(bytes(msg))

    def test_bad_magic_is_wire_error(self):
        with pytest.raises(wire.WireError, match="magic"):
            wire.MessageDecoder().feed(b"HTTP/1.1 200 OK\r\n\r\n")

    def test_frame_bound_is_wire_error(self):
        msg = wire.encode_message({"big": "x" * 1024})
        with pytest.raises(wire.WireError, match="exceeds"):
            wire.MessageDecoder(max_frame_bytes=64).feed(msg)

    def test_wire_error_feeds_the_breaker(self):
        # WireError must land in the router's transport vocabulary
        assert issubclass(wire.WireError, ConnectionError)
        assert issubclass(wire.WireError, TRANSPORT_ERRORS)

    @pytest.mark.parametrize("reason", REJECT_REASONS)
    @pytest.mark.parametrize("codec", CODECS)
    def test_reject_roundtrip_full_vocabulary(self, reason, codec):
        rej = Reject(reason, "interactive", 7, 0.25, 1.5)
        d = wire.reject_to_wire(rej)
        # force it through the actual codec, not just the dict helpers
        [d2] = wire.MessageDecoder().feed(
            wire.encode_message(d, codec=codec))
        assert wire.reject_from_wire(d2) == rej

    @pytest.mark.parametrize("reason", REJECT_REASONS)
    def test_load_shed_error_roundtrip(self, reason):
        rej = Reject(reason, "batch", 3, 1.0, 0.5)
        err = wire.error_from_wire(
            wire.error_to_wire(LoadShedError(rej)))
        assert isinstance(err, LoadShedError)
        assert err.reject == rej

    def test_error_roundtrip_typed_and_unknown(self):
        e = wire.error_from_wire(
            wire.error_to_wire(fleet.ReplicaCrashed("thread died")))
        assert isinstance(e, fleet.ReplicaCrashed)
        assert "thread died" in str(e)
        e = wire.error_from_wire(wire.error_to_wire(ValueError("nope")))
        assert isinstance(e, ValueError)

        class Weird(Exception):
            pass

        e = wire.error_from_wire(wire.error_to_wire(Weird("odd")))
        assert isinstance(e, wire.RemoteError)
        assert "Weird" in str(e) and "odd" in str(e)


# ---------------------------------------------------------------------------
# NetReplica over an in-thread server
# ---------------------------------------------------------------------------

class TestNetReplica:
    def test_hello_handshake(self, rig):
        rep = rig["rep"]
        assert rep.page_size() == 4
        assert rep.remote_pid == os.getpid()      # in-thread server
        assert rep.name == "netrig"               # adopted from the server

    def test_submit_step_parity_vs_local(self, rig, model_params):
        rep = rig["rep"]
        local = fleet.LocalReplica(rig["eng_local"], name="localpeer")
        prompts = _prompts(4, rng_seed=1)
        outs = {}
        for handle in (rep, local):
            rids = [handle.submit(p, 8) for p in prompts]
            done = _drive(handle, rids)
            outs[handle.name] = [np.asarray(done[r]) for r in rids]
        for a, b in zip(outs["netrig"], outs["localpeer"]):
            assert np.array_equal(a, b)           # the socket changed nothing

    def test_health_heartbeat_is_monotonic_delta(self, model_params,
                                                 monkeypatch):
        clk = FakeClock()
        eng = _engine(model_params)
        harness = ServerHarness(eng, name="clocked", clock=clk)
        try:
            rep = net.NetReplica(harness.address)
            rep.submit(np.array([1, 2, 3], np.int32), 4)  # submit beats
            clk.advance(7.25)
            # an NTP step on EITHER host must not fake a hang verdict:
            # jump the wall clock a year and the age must not move
            real_time = time.time
            monkeypatch.setattr(time, "time",
                                lambda: real_time() + 3.15e7)
            h = rep.health()
            assert h["heartbeat_age_s"] == pytest.approx(7.25, abs=0.01)
            assert h["rpcs_total"] >= 3           # hello + submit + health
            assert h["draining"] is False
            rep.close()
        finally:
            harness.stop()

    def test_progress_full_replay_over_wire(self, rig):
        rep = rig["rep"]
        rid = rep.submit(np.array([5, 6, 7, 8], np.int32), 8)
        live = {}
        for _ in range(200):                      # step to MID-flight
            rep.step()
            live = rep.progress()
            if len(live.get(rid, ())) >= 2:
                break
        assert len(live.get(rid, ())) >= 2
        # stale cursors (desync, post-restore rewind) answer with the
        # marked FULL stream — and the marker survives the socket
        for bogus in (10_000, -3):
            replay = rep.progress(since={rid: bogus})[rid]
            assert isinstance(replay, fleet.FullReplay)
            assert replay.full_replay is True
            assert list(replay) == list(live[rid])
        # a sane cursor still gets the cheap incremental tail
        tail = rep.progress(since={rid: 1})[rid]
        assert not isinstance(tail, fleet.FullReplay)
        assert list(tail) == list(live[rid])[1:]
        _drive(rep, [rid])                        # leave the rig idle

    def test_local_progress_stale_cursor_marks_full_replay(self, rig):
        local = fleet.LocalReplica(rig["eng_local"], name="lp2")
        rid = local.submit(np.array([9, 10, 11], np.int32), 6)
        live = {}
        for _ in range(200):
            local.step()
            live = local.progress()
            if len(live.get(rid, ())) >= 2:
                break
        normal = local.progress(since={rid: 1})[rid]
        assert not isinstance(normal, fleet.FullReplay)
        replay = local.progress(since={rid: 99})[rid]
        assert isinstance(replay, fleet.FullReplay)
        assert list(replay) == list(live[rid])
        _drive(local, [rid])

    def test_draining_refuses_submit_structurally(self, rig):
        rep = rig["rep"]
        try:
            rep.request_drain(True)
            assert rep.draining and not rep.can_accept(8)
            with pytest.raises(fleet.ReplicaUnavailable):
                rep.submit(np.array([1, 2], np.int32), 4)
        finally:
            rep.request_drain(False)
        assert rep.can_accept(8)

    def test_remote_error_reraises_typed(self, rig):
        with pytest.raises(ValueError, match="unknown op"):
            rig["rep"]._call("definitely_not_an_op", {})

    def test_timeout_drops_connection_then_reconnects(self, rig):
        harness = rig["harness"]
        rep2 = net.NetReplica(harness.address, name="impatient",
                              call_timeout_s=0.2, retry=FAST_RETRY)
        try:
            harness.pause()
            with pytest.raises(TRANSPORT_ERRORS):
                rep2.idle()
            # the socket died WITH the timed-out call: a late reply can
            # never be mis-paired with the next request
            assert not rep2.connected()
        finally:
            harness.resume()
        assert rep2.idle() in (True, False)       # lazy reconnect worked
        assert rep2.reconnects_total >= 2
        rep2.close()


# ---------------------------------------------------------------------------
# the router cannot tell (zero router forks)
# ---------------------------------------------------------------------------

class TestMixedFleet:
    def test_net_and_local_replicas_bit_identical(self, rig):
        rep_net = rig["rep"]
        rep_local = fleet.LocalReplica(rig["eng_local"], name="mixlocal")
        router = fleet.FleetRouter([rep_net, rep_local], seed=3,
                                   registry=obs.MetricsRegistry())
        prompts = _prompts(8, rng_seed=2)
        frids = [router.submit(p, 8) for p in prompts]
        placed = {router._where[f][0].name for f in frids}
        out = router.run_until_idle(max_steps=2000)
        assert sorted(out) == sorted(frids)
        # greedy decode is deterministic in the weights alone, so every
        # output must equal the single-replica reference regardless of
        # which side of the socket served it
        ref_rep = fleet.LocalReplica(rig["eng_local"], name="ref")
        for p, f in zip(prompts, frids):
            rid = ref_rep.submit(p, 8)
            done = _drive(ref_rep, [rid])
            assert np.array_equal(np.asarray(out[f]),
                                  np.asarray(done[rid]))
        # both transports actually served traffic in ONE router
        assert placed == {"netrig", "mixlocal"}


# ---------------------------------------------------------------------------
# socket chaos (in-thread tier; real subprocesses below under -m slow)
# ---------------------------------------------------------------------------

class TestSocketChaos:
    def test_hung_server_breaker_full_cycle(self, rig):
        harness = rig["harness"]
        rep_c = net.NetReplica(harness.address, name="hungC",
                               call_timeout_s=0.3, retry=FAST_RETRY)
        rep_ok = fleet.LocalReplica(rig["eng_local"], name="okpeer")
        fpol = fleet.FaultPolicy(max_consecutive_failures=10,
                                 probe_timeout_s=120.0,
                                 breaker_threshold=2,
                                 breaker_cooldown_s=0.25, max_redrives=3)
        router = fleet.FleetRouter([rep_c, rep_ok], seed=5, faults=fpol,
                                   registry=obs.MetricsRegistry())

        def trans():
            return [(o, n) for (name, o, n) in router.breaker_transitions
                    if name == "hungC"]

        harness.pause()                 # a hung host, not a dead one
        try:
            for _ in range(6):
                router.step()
                if ("closed", "open") in trans():
                    break
            assert ("closed", "open") in trans(), trans()
        finally:
            harness.resume()
        time.sleep(fpol.breaker_cooldown_s + 0.05)
        frids = [router.submit(np.array([1, 2, 3], np.int32), 4)
                 for _ in range(3)]
        done = router.run_until_idle(max_steps=2000)
        it = iter(trans())
        assert all(t in it for t in               # ordered subsequence
                   [("closed", "open"), ("open", "half_open"),
                    ("half_open", "closed")]), trans()
        assert router.ejected_total == 0          # quarantined, not killed
        for f in frids:
            assert f in done or router.reject_reason(f) is not None
        rep_c.close()

    def test_dead_server_ejected_redriven_bit_identical(self, rig,
                                                        model_params):
        eng_victim = _engine(model_params)
        harness = ServerHarness(eng_victim, name="victim")
        rep_net = net.NetReplica(harness.address, retry=FAST_RETRY,
                                 registry=obs.MetricsRegistry())
        rep_local = fleet.LocalReplica(rig["eng_local"], name="survivor")
        fpol = fleet.FaultPolicy(max_consecutive_failures=3,
                                 probe_timeout_s=120.0,
                                 breaker_threshold=2,
                                 breaker_cooldown_s=0.2, max_redrives=3)
        router = fleet.FleetRouter([rep_net, rep_local], seed=7,
                                   faults=fpol,
                                   registry=obs.MetricsRegistry())
        prompts = _prompts(6, rng_seed=3)
        # failure-free reference first: same prompts, same weights
        frids_clean = [router.submit(p, 8) for p in prompts]
        clean = router.run_until_idle(max_steps=2000)

        frids = [router.submit(p, 8) for p in prompts]   # chaos burst
        victim_frids = [f for f in frids
                        if router._where[f][0] is rep_net]
        assert victim_frids, "routing placed nothing on the victim"
        done = {}
        for _ in range(200):            # let the victim emit some tokens
            done.update(router.step())
            if any(router.progress(f) for f in victim_frids
                   if f not in done):
                break
        harness.stop()                  # the dead socket: RST + refused
        done.update(router.run_until_idle(max_steps=5000))
        missing = [f for f in frids if f not in done]
        verdicts = {f: router.reject_reason(f) for f in missing}
        silently_lost = [f for f, v in verdicts.items() if v is None]
        assert silently_lost == [], f"silently lost {silently_lost}"
        # with a healthy survivor and budget left, every request must
        # actually finish — and bit-identically to the clean run
        assert missing == [], f"shed instead of redriven: {verdicts}"
        for fc, f in zip(frids_clean, frids):
            assert np.array_equal(np.asarray(clean[fc]),
                                  np.asarray(done[f]))
        assert router.ejected_total >= 1
        assert router.redrives_total >= 1
        bundles = router.postmortems()
        assert "eject" in {b.get("reason") for b in bundles}
        for b in bundles:
            obs.validate_postmortem_bundle(b)
        # the remote witness is DEAD, so the eject bundle must be the
        # client-side flight recorder's testimony
        client_side = [b for b in bundles
                       if b.get("reason") == "eject"
                       and b.get("extra", {}).get("remote") is False]
        assert client_side, bundles
        assert client_side[0]["extra"]["transport_error"]
        rep_net.close()


# ---------------------------------------------------------------------------
# front door: streaming, backpressure, netlog
# ---------------------------------------------------------------------------

def _door_router(rig):
    rep = fleet.LocalReplica(rig["eng_local"], name="doorrep")
    return fleet.FleetRouter([rep], registry=obs.MetricsRegistry())


class TestFrontDoor:
    def test_streams_incrementally_with_netlog(self, rig, tmp_path):
        log = str(tmp_path / "door.netlog.jsonl")
        door = net.FrontDoor(_door_router(rig), netlog_path=log).start()
        try:
            results = []
            for i in range(2):
                cli = net.FrontDoorClient(door.address)
                try:
                    results.append(cli.generate(
                        _prompts(1, rng_seed=10 + i)[0], 24,
                        tag=f"t{i}", timeout_s=60.0))
                finally:
                    cli.close()
        finally:
            door.close()
        for r in results:
            assert r["reject"] is None
            assert len(r["tokens"]) == 24
            assert r["partials"] >= 2, "buffered, not streamed"
            # the incremental stream is a strict prefix of the result
            # (the final chunk rides the finished frame)
            assert r["streamed"] == r["tokens"][:len(r["streamed"])]
            assert r["ttft_s"] is not None
        summary = net.validate_netlog_file(log, require_requests=2)
        assert summary["accepted_requests"] == 2
        assert summary["finished"] == 2
        assert summary["stream"] >= 4
        assert summary["shed"] == 0

    def test_bad_request_is_structured_reject(self, rig):
        door = net.FrontDoor(_door_router(rig))
        cli = net.FrontDoorClient(door.address)
        try:
            cli.sock.sendall(wire.encode_message({"op": "nonsense"}))
            for _ in range(100):
                if door.pump():
                    break
                time.sleep(0.01)
            ev = cli.next_event(timeout=5.0)
            assert ev["event"] == "reject"
            assert ev["reason"] == "bad_request"
        finally:
            cli.close()
            door.close()

    def test_slow_reader_is_shed_with_typed_reject(self, rig, tmp_path):
        log = str(tmp_path / "slow.netlog.jsonl")
        door = net.FrontDoor(_door_router(rig), netlog_path=log,
                             max_buffer_frames=2)
        cli = net.FrontDoorClient(door.address)
        try:
            cli.send_generate(_prompts(1, rng_seed=20)[0], 24)
            for _ in range(200):
                door.pump()
                if door.accepted_total == 1:
                    break
            assert door.accepted_total == 1
            conn = next(iter(door._conns.values()))
            real_sock = conn.sock

            class _PluggedPipe:
                """A reader that stopped draining: every send blocks."""

                def send(self, _buf):
                    raise BlockingIOError

                def __getattr__(self, item):
                    return getattr(real_sock, item)

            conn.sock = _PluggedPipe()
            for _ in range(500):
                door.pump()             # decode keeps producing frames
                if door.shed_total >= 1:
                    break
            assert door.shed_total >= 1, "bounded buffer never shed"
            assert conn.closing
            conn.sock = real_sock       # let the final verdict flush
            for _ in range(50):
                door.pump()
                if conn.sock not in door._conns:
                    break
            # the client hears a TYPED verdict, not a bare disconnect
            ev = cli.next_event(timeout=5.0)
            while ev.get("event") != "reject":
                ev = cli.next_event(timeout=5.0)
            assert ev["reason"] == "slow_reader"
            rej = wire.reject_from_wire(ev["reject"])
            assert rej.reason == "slow_reader"
            assert rej.retry_after_s > 0
        finally:
            cli.close()
            door.close()
        summary = net.validate_netlog_file(log, require_requests=1)
        assert summary["shed"] == 1     # terminal accounting still holds

    def test_close_orphans_live_requests_as_redriven(self, rig, tmp_path):
        log = str(tmp_path / "orphan.netlog.jsonl")
        door = net.FrontDoor(_door_router(rig), netlog_path=log)
        cli = net.FrontDoorClient(door.address)
        try:
            cli.send_generate(_prompts(1, rng_seed=30)[0], 24)
            for _ in range(200):
                door.pump()
                if door.accepted_total == 1:
                    break
            assert door.accepted_total == 1
        finally:
            door.close()                # mid-decode shutdown
            cli.close()
        summary = net.validate_netlog_file(log, require_requests=1)
        assert summary["redriven"] == 1  # handed to the router, not lost

    def test_exposition_debug_netlog_route(self, rig, tmp_path):
        import urllib.error
        import urllib.request
        door = net.FrontDoor(_door_router(rig),
                             netlog_path=str(tmp_path / "e.jsonl"),
                             registry=obs.MetricsRegistry())
        srv = door.start_exposition(port=0)
        try:
            with pytest.raises(ValueError, match="reserved"):
                srv.add_json("/metrics", lambda: {})
            body = json.loads(urllib.request.urlopen(
                f"{srv.url}/debug/netlog", timeout=5).read())
            assert body["accepted_total"] == 0
            assert body["netlog_path"].endswith("e.jsonl")

            def sick():
                raise RuntimeError("provider down")

            srv.add_json("/debug/sick", sick)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{srv.url}/debug/sick",
                                       timeout=5)
            assert ei.value.code == 503  # sick provider, live endpoint
        finally:
            srv.stop()
            door.close()


# ---------------------------------------------------------------------------
# netlog validator
# ---------------------------------------------------------------------------

def _nl(frame, event, **fields):
    rec = {"schema": frontdoor.NETLOG_SCHEMA, "frame": frame,
           "ts": 123.0 + frame, "event": event}
    rec.update(fields)
    return json.dumps(rec)


def _write_log(tmp_path, lines, name="log.jsonl"):
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


class TestNetlogValidator:
    def _good(self):
        return [_nl(0, "listen", host="h", port=1),
                _nl(1, "conn_open", conn=1),
                _nl(2, "accept", rid=7, conn=1),
                _nl(3, "stream", rid=7, conn=1, tokens=2),
                _nl(4, "finished", rid=7, conn=1, tokens=8),
                _nl(5, "close")]

    def test_good_log(self, tmp_path):
        s = net.validate_netlog_file(
            _write_log(tmp_path, self._good()), require_requests=1)
        assert s["accepted_requests"] == 1
        assert s["finished"] == 1 and s["stream"] == 1
        assert s["lines"] == 6

    def test_torn_final_line_tolerated(self, tmp_path):
        p = tmp_path / "torn.jsonl"
        p.write_text("\n".join(self._good())
                     + '\n{"schema": "paddle_tpu.net')   # kill -9 here
        s = net.validate_netlog_file(str(p))
        assert s["lines"] == 6

    def test_torn_interior_line_is_corruption(self, tmp_path):
        lines = self._good()
        lines.insert(3, '{"schema": "paddle')
        with pytest.raises(ValueError, match="not JSON"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_non_monotonic_frame(self, tmp_path):
        lines = self._good()
        lines[3] = _nl(1, "stream", rid=7, conn=1)
        with pytest.raises(ValueError, match="not monotonic"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_accepted_without_terminal(self, tmp_path):
        lines = [_nl(0, "listen"), _nl(1, "conn_open", conn=1),
                 _nl(2, "accept", rid=7, conn=1),
                 _nl(3, "stream", rid=7, conn=1, tokens=2),
                 _nl(4, "close")]
        with pytest.raises(ValueError, match="no terminal"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_terminal_for_unaccepted_rid(self, tmp_path):
        lines = [_nl(0, "listen"), _nl(1, "conn_open", conn=1),
                 _nl(2, "accept", rid=7, conn=1),
                 _nl(3, "finished", rid=7, conn=1, tokens=8),
                 _nl(4, "shed", rid=99, reason="x"),
                 _nl(5, "close")]
        with pytest.raises(ValueError, match="never accepted"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_double_terminal(self, tmp_path):
        lines = [_nl(0, "listen"), _nl(1, "conn_open", conn=1),
                 _nl(2, "accept", rid=7, conn=1),
                 _nl(3, "finished", rid=7, conn=1, tokens=8),
                 _nl(4, "shed", rid=7, reason="x"),
                 _nl(5, "close")]
        with pytest.raises(ValueError, match="terminated twice"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_duplicate_accept(self, tmp_path):
        lines = [_nl(0, "listen"), _nl(1, "conn_open", conn=1),
                 _nl(2, "accept", rid=7, conn=1),
                 _nl(3, "accept", rid=7, conn=1),
                 _nl(4, "finished", rid=7, conn=1, tokens=8),
                 _nl(5, "close")]
        with pytest.raises(ValueError, match="accepted twice"):
            net.validate_netlog_file(_write_log(tmp_path, lines))

    def test_unknown_event_and_schema(self, tmp_path):
        lines = self._good()
        lines[3] = _nl(3, "telemetry", rid=7)
        with pytest.raises(ValueError, match="unknown event"):
            net.validate_netlog_file(_write_log(tmp_path, lines))
        bad = json.loads(self._good()[0])
        bad["schema"] = "v0"
        with pytest.raises(ValueError, match="schema"):
            net.validate_netlog_file(
                _write_log(tmp_path, [json.dumps(bad)], name="s.jsonl"))

    def test_require_requests_gate(self, tmp_path):
        p = _write_log(tmp_path, self._good())
        with pytest.raises(ValueError, match="required >= 2"):
            net.validate_netlog_file(p, require_requests=2)

    def test_check_metrics_log_cli(self, script, tmp_path, capsys):
        mod = script("check_metrics_log", "tools")
        good = _write_log(tmp_path, self._good())
        assert mod.main([good, "--netlog", "--require-requests", "1"]) == 0
        assert mod.main([good, "--netlog", "--require-requests", "9"]) == 1
        capsys.readouterr()


# ---------------------------------------------------------------------------
# real processes: kill -9 and SIGTERM drain (the slow tier; the CI-run
# bench dryrun drives the same battery on every run_ci.sh invocation)
# ---------------------------------------------------------------------------

SUBPROC_CONFIG = dict(vocab_size=VOCAB, hidden_size=16, num_layers=2,
                      num_heads=2, ffn_size=32, max_position=64,
                      dropout=0.0, attn_impl="xla")
SUBPROC_ENGINE = dict(num_slots=2, page_size=4, max_tokens_per_slot=48,
                      prefill_chunk=4, decode_block=2, attn_impl="lax")


@pytest.mark.slow
class TestSubprocessChaos:
    def test_kill9_ejects_redrives_bit_identical(self):
        spawned = [net.spawn_replica_server(
            config=SUBPROC_CONFIG, engine=SUBPROC_ENGINE, seed=0,
            name=f"proc{i}", warmup=False) for i in range(2)]
        procs = [p for p, _ in spawned]
        try:
            reps = [net.NetReplica(addr, name=f"proc{i}",
                                   retry=FAST_RETRY)
                    for i, (_p, addr) in enumerate(spawned)]
            fpol = fleet.FaultPolicy(max_consecutive_failures=3,
                                     probe_timeout_s=120.0,
                                     breaker_threshold=2,
                                     breaker_cooldown_s=0.2,
                                     max_redrives=3)
            router = fleet.FleetRouter(reps, seed=11, faults=fpol,
                                       registry=obs.MetricsRegistry())
            prompts = _prompts(6, rng_seed=4)
            frids_clean = [router.submit(p, 8) for p in prompts]
            clean = router.run_until_idle(max_steps=5000)
            ref = [np.asarray(clean[f]) for f in frids_clean]

            frids = [router.submit(p, 8) for p in prompts]
            victim = reps[0]
            victim_frids = [f for f in frids
                            if router._where[f][0] is victim]
            if not victim_frids:        # routing went all-one-way: flip
                victim = reps[1]
                victim_frids = [f for f in frids
                                if router._where[f][0] is victim]
            assert victim_frids
            done = {}
            for _ in range(200):
                done.update(router.step())
                if any(router.progress(f) for f in victim_frids
                       if f not in done):
                    break
            vproc = procs[reps.index(victim)]
            os.kill(vproc.pid, signal.SIGKILL)    # the real dead socket
            vproc.wait(timeout=30)
            done.update(router.run_until_idle(max_steps=10_000))
            missing = [f for f in frids if f not in done]
            assert missing == [], {
                f: router.reject_reason(f) for f in missing}
            assert router.ejected_total >= 1
            assert router.redrives_total >= 1
            for f, r in zip(frids, ref):          # exactly-once, bit-equal
                assert np.array_equal(np.asarray(done[f]), r)
            reasons = {b.get("reason") for b in router.postmortems()}
            assert "eject" in reasons, reasons
            for rep in reps:
                rep.close()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()

    def test_sigterm_drains_to_exit_drained(self):
        proc, addr = net.spawn_replica_server(
            config=SUBPROC_CONFIG, engine=SUBPROC_ENGINE, seed=0,
            name="drainer", warmup=False)
        try:
            rep = net.NetReplica(addr, name="drainer")
            rid = rep.submit(np.array([1, 2, 3, 4], np.int32), 6)
            proc.send_signal(signal.SIGTERM)
            # draining refuses NEW work but finishes what is in flight
            deadline = time.monotonic() + 60
            while not rep.draining and time.monotonic() < deadline:
                rep.health()
                time.sleep(0.02)
            assert rep.draining
            with pytest.raises(fleet.ReplicaUnavailable):
                rep.submit(np.array([5, 6], np.int32), 4)
            done = {}
            while rid not in done and time.monotonic() < deadline:
                done.update(rep.step())
            assert len(done[rid]) == 6            # in-flight work finished
            rep.close()                           # last client leaves...
            proc.wait(timeout=60)                 # ...and the process exits
            assert proc.returncode == EXIT_DRAINED
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
