"""Cross-process serving: VideoStoreServer + RemoteVideoStore.

The contract under test: results over the wire are bit-identical to
in-process ``execute()``, client processes share one scheduler/cache/tuner
(a repeat of another client's scan decodes zero tiles), malformed frames
get an error frame instead of killing the server, and shutdown is clean.
"""
import json
import os
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.codec.encode import EncoderConfig
from repro.core import (NoTilingPolicy, RemoteError, RemoteVideoStore,
                        VideoStore, VideoStoreServer, uniform_layout)
from repro.core import wire
from repro.core.cost import CostModel
from repro.utils import trace

ENC = EncoderConfig(gop=16, qp=8)
MODEL = CostModel(beta=1.4e-8, gamma=1e-5)
MODEL.encode_per_pixel = 3.4e-8
MODEL.encode_per_tile = 1e-4


def fill(store, name, frames, dets, policy=None):
    store.add_video(name, encoder=ENC, policy=policy or NoTilingPolicy(),
                    cost_model=MODEL)
    store.ingest(name, frames)
    store.add_detections(name, {f: d for f, d in enumerate(dets)})


def assert_regions_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[:-1] == rb[:-1]
        np.testing.assert_array_equal(ra[-1], rb[-1])


@pytest.fixture
def served(tmp_path, small_video):
    """One server over a Unix socket, seeded store, one connected client.
    ``owns_store=False`` keeps the in-process store open so tests can
    compare remote results against literal in-process ``execute()``."""
    frames, dets = small_video
    store = VideoStore()
    fill(store, "cam0", frames, dets)
    sock = str(tmp_path / "tasm.sock")
    server = VideoStoreServer(store, path=sock, owns_store=False).start()
    client = RemoteVideoStore(sock)
    yield store, server, client, sock
    client.close()
    server.stop()
    store.close()


# -------------------------------------------------------------- scan RPCs
class TestRemoteScans:
    def test_scan_bit_identical_to_in_process_execute(self, served):
        store, _, client, _ = served
        ref = store.scan("cam0").labels("car").frames(0, 32).execute()
        got = client.scan("cam0").labels("car").frames(0, 32).execute()
        assert_regions_equal(ref.regions, got.regions)
        assert got.stats.regions == ref.stats.regions
        assert got.plan is not None
        assert got.plan.logical == ref.plan.logical

    def test_repeat_scan_shares_cache_across_the_wire(self, served):
        store, _, client, _ = served
        q = client.scan("cam0").labels("car").frames(0, 32)
        r1 = q.execute()
        assert r1.stats.cache_misses > 0
        decoded = store.video("cam0").store.tiles_decoded_total
        r2 = q.execute()
        assert r2.stats.cache_misses == 0
        assert r2.stats.cache_hit_rate == 1.0
        assert store.video("cam0").store.tiles_decoded_total == decoded
        assert_regions_equal(r1.regions, r2.regions)

    def test_execute_many_matches_serial(self, served):
        store, _, client, _ = served
        mk = lambda s: [s.scan("cam0").labels("car").frames(0, 32),
                        s.scan("cam0").labels("person").frames(0, 16),
                        s.scan("cam0").labels("car").frames(16, 32)]
        ref = [q.execute() for q in mk(store)]
        t0 = time.monotonic()
        got = client.execute_many(mk(client))
        recs = trace.window(t0, time.monotonic())
        assert len(got) == 3
        for r, g in zip(ref, got):
            assert_regions_equal(r.regions, g.regions)
        # one reply carries the three results: each is stamped with an
        # even share of the reply's tasm.marshal span, up to its packing
        shares = {g.stats.marshal_s for g in got}
        assert len(shares) == 1 and shares.pop() > 0
        assert any(r.value >= sum(g.stats.marshal_s for g in got)
                   for r in recs if r.name == "tasm.marshal")

    def test_limit_and_estimation_only(self, served):
        store, _, client, _ = served
        ref = store.scan("cam0").labels("car").frames(0, 32).limit(3) \
            .execute()
        got = client.scan("cam0").labels("car").frames(0, 32).limit(3) \
            .execute()
        assert_regions_equal(ref.regions, got.regions)
        est = client.scan("cam0").labels("car").decode(False).execute()
        assert est.regions == [] and est.stats.pixels_decoded > 0

    def test_explain_matches_in_process_lower(self, served):
        store, _, client, _ = served
        q = lambda s: s.scan("cam0").labels("car").frames(0, 32)
        ref, got = q(store).explain(), q(client).explain()
        assert got.describe() == ref.describe()
        assert got.est_pixels == ref.est_pixels
        assert [s.tile_idxs for s in got.sot_scans] == \
            [s.tile_idxs for s in ref.sot_scans]

    def test_multi_video_scan(self, served, small_video):
        store, _, client, _ = served
        frames, dets = small_video
        fill(store, "cam1", frames, dets)
        q = lambda s: s.scan(["cam0", "cam1"]).labels("car").frames(0, 32)
        ref, got = q(store).execute(), q(client).execute()
        assert_regions_equal(ref.regions, got.regions)
        assert sorted(got.regions_by_video) == ["cam0", "cam1"]

    def test_want_plans_false_omits_plan(self, served):
        store, _, _, sock = served
        c = RemoteVideoStore(sock, want_plans=False)
        try:
            ref = store.scan("cam0").labels("car").frames(0, 32).execute()
            got = c.scan("cam0").labels("car").frames(0, 32).execute()
            assert got.plan is None
            assert_regions_equal(ref.regions, got.regions)
        finally:
            c.close()

    def test_serving_session(self, served):
        store, _, client, _ = served
        ref = store.scan("cam0").labels("car").frames(0, 32).execute()
        with client.serve() as session:
            futs = [session.submit(client.scan("cam0").labels("car")
                                   .frames(0, 32)) for _ in range(4)]
            results = [f.result() for f in futs]
        for r in results:
            assert_regions_equal(ref.regions, r.regions)
        with pytest.raises(RuntimeError, match="closed"):
            session.submit(client.scan("cam0").labels("car"))

    def test_concurrent_clients_one_socket_each(self, served, small_video):
        _, _, _, sock = served
        frames, dets = small_video
        clients = [RemoteVideoStore(sock) for _ in range(3)]
        try:
            futs = [c.scan("cam0").labels("car").frames(0, 32).submit()
                    for c in clients]
            results = [f.result() for f in futs]
            for r in results[1:]:
                assert_regions_equal(results[0].regions, r.regions)
        finally:
            for c in clients:
                c.close()


# ----------------------------------------------------------- mutation RPCs
class TestRemoteMutations:
    def test_remote_ingest_matches_local(self, tmp_path, small_video):
        frames, dets = small_video
        sock = str(tmp_path / "t.sock")
        with VideoStoreServer(VideoStore(), path=sock).start() as server:
            with RemoteVideoStore(sock) as client:
                client.add_video("cam0", encoder=ENC,
                                 policy=NoTilingPolicy(), cost_model=MODEL)
                stats = client.ingest("cam0", frames)
                assert stats.encode_s > 0
                client.add_detections("cam0",
                                      {f: d for f, d in enumerate(dets)})
                got = client.scan("cam0").labels("car").frames(0, 32) \
                    .execute()
                with pytest.raises(ValueError, match="already"):
                    client.ingest("cam0", frames)
        local = VideoStore()
        fill(local, "cam0", frames, dets)
        ref = local.scan("cam0").labels("car").frames(0, 32).execute()
        local.close()
        assert_regions_equal(ref.regions, got.regions)

    def test_remote_add_metadata_and_retile(self, served):
        store, _, client, _ = served
        client.add_metadata("cam0", 0, "thing", 8, 8, 40, 40)
        r = client.scan("cam0").labels("thing").frames(0, 8).execute()
        assert len(r.regions) == 1
        before = store.video("cam0").store.sots[0].epoch
        dt = client.retile("cam0", 0, uniform_layout(96, 160, 2, 2))
        assert dt > 0
        assert store.video("cam0").store.sots[0].epoch == before + 1
        # post-retile scans still bit-identical to in-process
        ref = store.scan("cam0").labels("car").frames(0, 16).execute()
        got = client.scan("cam0").labels("car").frames(0, 16).execute()
        assert_regions_equal(ref.regions, got.regions)

    def test_tuner_and_stats_rpcs(self, served):
        store, _, client, _ = served
        ts = client.drain_tuner(timeout=30)
        assert ts.observed == store.tuner_stats().observed
        client.scan("cam0").labels("car").frames(0, 32).execute()
        doc = client.stats()
        assert doc["videos"] == ["cam0"]
        assert doc["tiles_decoded_total"] == \
            store.video("cam0").store.tiles_decoded_total
        assert doc["cache"]["entries"] >= 1
        # the operator's span summary: the served scan queued, fetched and
        # cropped before its reply went out
        for name in ("tasm.queue", "tasm.fetch", "tasm.crop"):
            s = doc["spans"][name]
            assert s["count"] >= 1 and 0 <= s["max"] <= s["total"]
        assert doc["spans"]["tasm.batch_plans"]["max"] >= 1


# ------------------------------------------------------------ error paths
class TestErrorHandling:
    def test_unknown_video_maps_to_key_error(self, served):
        _, _, client, _ = served
        with pytest.raises(KeyError, match="unknown video"):
            client.scan("nope").labels("car").execute()

    def test_unknown_op_maps_to_value_error(self, served):
        _, _, client, _ = served
        with pytest.raises(ValueError, match="unknown op"):
            client._call("no_such_op")

    def test_malformed_frame_gets_error_reply_server_survives(self, served):
        _, _, client, sock = served
        raw = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            raw.connect(sock)
            raw.sendall(struct.pack(">I", 7) + b"garbage")
            resp = wire.read_frame(raw)
            assert resp["ok"] is False and resp["id"] is None
            assert "frame" in resp["error"]["message"] \
                or resp["error"]["type"] == "WireError"
            # the poisoned connection is closed...
            with pytest.raises(wire.WireError):
                while True:
                    wire.read_frame(raw)
        finally:
            raw.close()
        # ...but the server and other connections live on
        assert client.ping()["pong"] is True

    def test_oversized_frame_rejected_without_allocation(self, served):
        _, _, client, sock = served
        raw = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            raw.connect(sock)
            raw.sendall(struct.pack(">I", 1 << 31))  # 2 GiB claim
            resp = wire.read_frame(raw)
            assert resp["ok"] is False
            assert "limit" in resp["error"]["message"]
        finally:
            raw.close()
        assert client.ping()["pong"] is True

    def test_request_without_op_gets_error_frame(self, served):
        _, _, _, sock = served
        raw = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            raw.connect(sock)
            wire.write_frame(raw, {"id": 9, "noop": True})
            resp = wire.read_frame(raw)
            assert resp["id"] == 9 and resp["ok"] is False
            assert resp["error"]["type"] == "ValueError"
            # same connection keeps working (the frame itself was valid)
            wire.write_frame(raw, {"id": 10, "op": "ping"})
            assert wire.read_frame(raw)["ok"] is True
        finally:
            raw.close()

    def test_response_over_frame_limit_maps_to_error(self, tmp_path,
                                                     small_video):
        frames, dets = small_video
        store = VideoStore()
        fill(store, "cam0", frames, dets)
        sock = str(tmp_path / "t.sock")
        # pin the npz transport: this test exercises the oversized-PAYLOAD
        # path, and under shm the crops leave the frame (descriptors only)
        with VideoStoreServer(store, path=sock, transport="socket",
                              max_frame_bytes=32_768).start():
            with RemoteVideoStore(sock) as client:
                # the result (hundreds of KB of crops) breaks the frame
                # limit: the server must answer with an error frame, not
                # drop the connection
                with pytest.raises(RemoteError, match="exceeds"):
                    client.scan("cam0").labels("car").frames(0, 32) \
                        .execute()
                assert client.ping()["pong"] is True

    def test_client_close_fails_pending_and_rejects_new(self, served):
        _, _, _, sock = served
        c = RemoteVideoStore(sock)
        c.close()
        with pytest.raises(RuntimeError, match="closed"):
            c.ping()

    def test_client_timeout_is_connect_only(self, served):
        """Regression: timeout= left armed on the socket fires in the
        reader thread during any idle gap, killing it and poisoning the
        connection."""
        _, _, _, sock = served
        c = RemoteVideoStore(sock, timeout=0.3)
        try:
            assert c.ping()["pong"] is True
            time.sleep(0.6)  # idle longer than the connect timeout
            assert c._reader.is_alive()
            assert c.ping()["pong"] is True
        finally:
            c.close()

    def test_requests_fail_fast_after_server_death(self, tmp_path,
                                                   small_video):
        """Regression: once the reader thread died (server gone), a new
        request must raise instead of parking a future nobody resolves."""
        frames, dets = small_video
        store = VideoStore()
        fill(store, "cam0", frames, dets)
        sock = str(tmp_path / "t.sock")
        server = VideoStoreServer(store, path=sock).start()
        c = RemoteVideoStore(sock)
        assert c.ping()["pong"] is True
        server.stop()
        c._reader.join(timeout=10)
        assert not c._reader.is_alive()
        with pytest.raises((wire.ConnectionClosed, OSError)):
            c.ping()
        c.close()


# --------------------------------------------------- reconnect and epochs
class TestReconnectRetry:
    def _restartable(self, tmp_path, small_video):
        frames, dets = small_video
        store = VideoStore()
        fill(store, "cam0", frames, dets)
        sock = str(tmp_path / "t.sock")
        server = VideoStoreServer(store, path=sock,
                                  owns_store=False).start()
        return store, server, sock

    def test_idempotent_rpcs_retry_across_server_restart(
            self, tmp_path, small_video):
        store, s1, sock = self._restartable(tmp_path, small_video)
        c = RemoteVideoStore(sock, retries=3)
        try:
            ref = c.scan("cam0").labels("car").frames(0, 16).execute()
            s1.stop()
            c._reader.join(timeout=10)
            with VideoStoreServer(store, path=sock,
                                  owns_store=False).start():
                # redials transparently: ping, stats, and a scan all
                # succeed on the fresh connection
                assert c.ping()["pong"] is True
                assert c.stats()["videos"] == ["cam0"]
                got = c.scan("cam0").labels("car").frames(0, 16).execute()
                assert_regions_equal(ref.regions, got.regions)
        finally:
            c.close()
            store.close()

    def test_mutations_never_retry(self, tmp_path, small_video):
        store, s1, sock = self._restartable(tmp_path, small_video)
        c = RemoteVideoStore(sock, retries=3)
        try:
            s1.stop()
            c._reader.join(timeout=10)
            with VideoStoreServer(store, path=sock,
                                  owns_store=False).start():
                # the server may have applied a mutation before the drop:
                # re-sending could double it, so the error surfaces...
                with pytest.raises((wire.ConnectionClosed, OSError)):
                    c.add_metadata("cam0", 0, "x", 0, 0, 8, 8)
                # ...and the next idempotent call heals the connection
                assert c.ping()["pong"] is True
        finally:
            c.close()
            store.close()

    def test_zero_retries_stays_fail_fast(self, tmp_path, small_video):
        store, s1, sock = self._restartable(tmp_path, small_video)
        c = RemoteVideoStore(sock)  # default retries=0
        try:
            s1.stop()
            c._reader.join(timeout=10)
            with VideoStoreServer(store, path=sock,
                                  owns_store=False).start():
                with pytest.raises((wire.ConnectionClosed, OSError)):
                    c.ping()
        finally:
            c.close()
            store.close()


class TestEpochs:
    def test_epochs_rpc_matches_store(self, served):
        store, _, client, _ = served
        assert client.epochs("cam0") == store.epochs("cam0")

    def test_epochs_tracks_retile(self, served):
        _, _, client, _ = served
        before = client.epochs("cam0")
        client.retile("cam0", 0, uniform_layout(96, 160, 2, 2))
        after = client.epochs("cam0")
        assert after[0] == before[0] + 1
        assert all(after[s] == before[s] for s in before if s != 0)

    def test_ingest_ack_carries_epochs(self, served, small_video):
        store, _, client, _ = served
        frames, _ = small_video
        assert client.last_ingest_epochs == {}
        client.add_video("cam9", encoder=ENC, policy=NoTilingPolicy(),
                         cost_model=MODEL)
        client.ingest("cam9", frames)
        assert client.last_ingest_epochs == store.epochs("cam9")
        assert client.last_ingest_epochs == client.epochs("cam9")


# ------------------------------------------------------------- transports
class TestTransports:
    def test_tcp_transport(self, served):
        store, _, _, _ = served
        with VideoStoreServer(store, host="127.0.0.1", port=0,
                              owns_store=False).start() as tcp_server:
            host, port = tcp_server.address
            with RemoteVideoStore(host=host, port=port) as client:
                assert client.ping()["pong"] is True
                ref = store.scan("cam0").labels("car").frames(0, 16) \
                    .execute()
                got = client.scan("cam0").labels("car").frames(0, 16) \
                    .execute()
                assert_regions_equal(ref.regions, got.regions)

    def test_serve_cli_shutdown_rpc_completes_cleanup(self, tmp_path):
        """Regression: the shutdown RPC runs stop() on a daemon thread —
        serve_forever must wait for cleanup to COMPLETE, or the CLI exits
        mid-stop, leaving the socket file behind and the store unflushed."""
        sock = str(tmp_path / "cli.sock")
        root = tmp_path / "root"
        script = os.path.join(os.path.dirname(__file__), "..", "scripts",
                              "tasm_serve.py")
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
        proc = subprocess.Popen(
            [sys.executable, script, "--socket", sock,
             "--store-root", str(root)], env=env)
        try:
            deadline = time.time() + 60
            while not os.path.exists(sock):
                assert proc.poll() is None, "server died early"
                assert time.time() < deadline, "socket never appeared"
                time.sleep(0.05)
            with RemoteVideoStore(sock) as client:
                client.add_video("cam0", encoder=ENC)  # dirties the catalog
                client.shutdown_server()
            assert proc.wait(timeout=60) == 0
            assert not os.path.exists(sock), "socket file left behind"
            # close() ran: the dirty catalog was flushed before exit
            assert (root / "catalog.json").exists()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

    def test_start_refuses_to_hijack_live_socket(self, served):
        """start() recovers stale socket files but must not unlink a LIVE
        server's address (supervisor double-start = silent split-brain)."""
        _, _, client, sock = served
        dup = VideoStoreServer(VideoStore(), path=sock)
        with pytest.raises(OSError, match="in use"):
            dup.start()
        dup.store.close()
        # the live server kept its socket and keeps serving
        assert os.path.exists(sock)
        assert client.ping()["pong"] is True

    def test_shutdown_rpc_stops_server(self, tmp_path, small_video):
        frames, dets = small_video
        store = VideoStore()
        fill(store, "cam0", frames, dets)
        sock = str(tmp_path / "t.sock")
        server = VideoStoreServer(store, path=sock).start()
        with RemoteVideoStore(sock) as client:
            client.shutdown_server()
        deadline = time.time() + 10
        while os.path.exists(sock) and time.time() < deadline:
            time.sleep(0.02)
        assert not os.path.exists(sock)
        server.stop()  # idempotent


# ---------------------------------------------------- real client processes
CLIENT_PROG = """
import json, sys
import numpy as np
from repro.core import RemoteVideoStore
sock, out = sys.argv[1], sys.argv[2]
with RemoteVideoStore(sock) as cli:
    r = cli.scan("cam0").labels("car").frames(0, 32).execute()
np.savez(out + ".npz",
         **{f"px_{j}": px for j, (_, _, px) in enumerate(r.regions)})
with open(out + ".json", "w") as fh:
    json.dump({"regions": [[f, list(b)] for f, b, _ in r.regions],
               "cache_misses": r.stats.cache_misses,
               "tiles_fetched": r.stats.tiles_fetched}, fh)
"""


def run_client_process(sock, out):
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    res = subprocess.run([sys.executable, "-c", CLIENT_PROG, sock, out],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr
    meta = json.loads(open(out + ".json").read())
    npz = np.load(out + ".npz")
    regions = [(f, tuple(b), npz[f"px_{j}"])
               for j, (f, b) in enumerate(meta["regions"])]
    return regions, meta


def test_two_client_processes_share_one_cache(served, tmp_path):
    """The acceptance gate: two real client PROCESSES against one server —
    bit-identical to in-process execute(), and the second client's repeat
    of the first client's scan decodes zero tiles."""
    store, _, _, sock = served
    ref = store.scan("cam0").labels("car").frames(0, 32).execute()

    r1, m1 = run_client_process(sock, str(tmp_path / "c1"))
    assert_regions_equal(ref.regions, r1)
    assert m1["tiles_fetched"] > 0

    decoded = store.video("cam0").store.tiles_decoded_total
    r2, m2 = run_client_process(sock, str(tmp_path / "c2"))
    assert_regions_equal(ref.regions, r2)
    assert m2["cache_misses"] == 0, "second process re-decoded tiles"
    assert store.video("cam0").store.tiles_decoded_total == decoded
