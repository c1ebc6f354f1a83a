"""Multi-process distributed serving (review round 1 item 6).

Reference behaviors under test (``continuous/HTTPSourceV2.scala``):
worker registration with the driver service (:460-468), cross-machine
reply routing (:535+), and epoch replay of work lost to a dead worker
(:488-517) — here as lease expiry. Workers are REAL subprocesses.
"""

import http.client
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.io.http.schema import HTTPResponseData
from mmlspark_tpu.serving import (DistributedServingServer, DriverRegistry,
                                  RegistryClient, ServingServer,
                                  remote_worker_loop, serving_query)

HELPER = os.path.join(os.path.dirname(__file__),
                      "serving_worker_helpers.py")


def _post(addr, body: bytes, timeout=30):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/", body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _spawn_worker(driver_addr, service: str, mode: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, HELPER, f"{driver_addr[0]}:{driver_addr[1]}",
         service, mode], env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.fixture
def driver():
    reg = DriverRegistry().start()
    yield reg
    reg.stop()


def _native_cls():
    from mmlspark_tpu.serving import NativeDistributedServingServer
    return NativeDistributedServingServer


def _front_params():
    """Both ingress fronts (threaded Python and native epoll) run the
    SAME mesh tests — the distributed logic must be front-agnostic
    (r2 weak #8: the two were never driven together)."""
    from mmlspark_tpu.native.loader import get_httpfront
    return [
        pytest.param(DistributedServingServer, id="python"),
        pytest.param(_native_cls(), id="native",
                     marks=pytest.mark.skipif(
                         get_httpfront() is None,
                         reason="native toolchain unavailable")),
    ]


class TestRegistry:
    def test_register_and_lookup(self, driver):
        from mmlspark_tpu.serving import ServiceInfo
        client = RegistryClient(driver.address)
        table = client.register(ServiceInfo(
            name="svc", worker_id="w1", host="127.0.0.1", port=1234))
        assert [i.worker_id for i in table] == ["w1"]
        client.register(ServiceInfo(
            name="svc", worker_id="w2", host="127.0.0.1", port=1235))
        assert {i.worker_id for i in client.workers("svc")} == {"w1", "w2"}
        client.unregister("svc", "w1")
        assert {i.worker_id for i in client.workers("svc")} == {"w2"}


class TestCrossWorkerReply:
    @pytest.mark.parametrize("server_cls", _front_params())
    def test_request_on_a_answered_by_subprocess_b(self, driver,
                                                   server_cls):
        svc = f"xsvc-{server_cls.__name__}"
        server = server_cls(svc, driver.address,
                            lease_timeout=10.0).start()
        worker = _spawn_worker(driver.address, svc, "echo")
        try:
            status, body = _post(server.address, b"hello world")
            assert status == 200
            pid_str, payload = body.split(b":", 1)
            assert payload == b"HELLO WORLD"
            # the reply came from the subprocess, not this process
            assert int(pid_str) == worker.pid
            assert int(pid_str) != os.getpid()
        finally:
            worker.kill()
            worker.wait()
            server.stop()

    def test_reply_to_routes_across_servers(self, driver):
        """Two ingest servers; a reply raised on B for a request owned by
        A must land on A (the replyTo forwarding table)."""
        a = DistributedServingServer("rsvc", driver.address,
                                     worker_id="wa").start()
        b = DistributedServingServer("rsvc", driver.address,
                                     worker_id="wb").start()
        try:
            got = {}

            def client():
                got["resp"] = _post(a.address, b"ping")

            t = threading.Thread(target=client)
            t.start()
            # pull A's request out of its queue directly (we play the
            # processing engine here), then reply THROUGH B
            cached = a.queue.get(timeout=5)
            assert cached.id.startswith("wa/")
            ok = b.reply_to(cached.id, HTTPResponseData(
                status_code=200, entity=b"pong-from-b"))
            assert ok
            t.join(timeout=10)
            assert got["resp"] == (200, b"pong-from-b")
        finally:
            a.stop()
            b.stop()


class TestLeaseReplay:
    @pytest.mark.parametrize("server_cls", _front_params())
    def test_killed_worker_replays_without_client_error(self, driver,
                                                        server_cls):
        """Ingest on A; a hanging worker takes the lease and is SIGKILLed;
        lease expiry replays the request; a healthy worker answers. The
        client sees one clean 200 — no error, no duplicate."""
        svc = f"ksvc-{server_cls.__name__}"
        server = server_cls(svc, driver.address, lease_timeout=1.0,
                            reply_timeout=30.0).start()
        hanger = _spawn_worker(driver.address, svc, "hang")
        result = {}

        def client():
            result["resp"] = _post(server.address, b"precious", timeout=30)

        t = threading.Thread(target=client)
        healthy = None
        try:
            t.start()
            # wait until the hanging worker holds the lease
            deadline = time.monotonic() + 10
            while not server._leases and time.monotonic() < deadline:
                time.sleep(0.02)
            assert server._leases, "hanging worker never leased the request"
            os.kill(hanger.pid, signal.SIGKILL)
            hanger.wait()
            epoch_before = server.epoch
            healthy = _spawn_worker(driver.address, svc, "echo")
            t.join(timeout=25)
            assert not t.is_alive(), "client never got an answer"
            status, body = result["resp"]
            assert status == 200
            assert body.split(b":", 1)[1] == b"PRECIOUS"
            assert server.epoch > epoch_before  # replay bumped the epoch
        finally:
            if healthy is not None:
                healthy.kill()
                healthy.wait()
            if hanger.poll() is None:
                hanger.kill()
            server.stop()
            t.join(timeout=1)

    def test_lease_replay_respects_retry_bound(self, driver):
        """A request that keeps getting leased and dropped is failed with
        500 after max_retries (bounded replay, not an infinite loop)."""
        server = DistributedServingServer(
            "bsvc", driver.address, lease_timeout=0.2, max_retries=2,
            reply_timeout=20.0).start()
        result = {}

        def client():
            result["resp"] = _post(server.address, b"doomed", timeout=20)

        t = threading.Thread(target=client)
        t.start()
        try:
            # play a crashing worker: drain the queue without replying and
            # pre-expire each lease (in-proc "crash")
            deadline = time.monotonic() + 15
            while t.is_alive() and time.monotonic() < deadline:
                try:
                    c = server.queue.get(timeout=0.1)
                except Exception:
                    continue
                server._leases[c.id] = (time.monotonic() - 1,
                                        c)  # instantly-expired lease
            t.join(timeout=5)
            assert not t.is_alive()
            status, _ = result["resp"]
            assert status == 500  # failed after bounded retries
        finally:
            server.stop()
            t.join(timeout=1)


class TestMeshSecret:
    def test_lease_requires_secret(self, driver):
        import json as _json
        server = DistributedServingServer(
            "ssvc", driver.address, mesh_secret="s3cret").start()
        try:
            conn = http.client.HTTPConnection(*server.address, timeout=5)
            conn.request("POST", "/__lease__",
                         body=_json.dumps({"max": 4}).encode())
            assert conn.getresponse().status == 403
            conn.close()
            conn = http.client.HTTPConnection(*server.address, timeout=5)
            conn.request("POST", "/__lease__", body=_json.dumps(
                {"max": 4, "secret": "s3cret"}).encode())
            resp = conn.getresponse()
            assert resp.status == 200 and _json.loads(resp.read()) == []
            conn.close()
        finally:
            server.stop()


class TestQueueBound:
    def test_backpressure_503(self):
        server = ServingServer("qsvc", max_queue=2,
                               reply_timeout=5.0).start()
        try:
            codes = []
            lock = threading.Lock()

            def client():
                try:
                    s, _ = _post(server.address, b"x", timeout=8)
                except Exception:
                    s = -1
                with lock:
                    codes.append(s)

            threads = [threading.Thread(target=client) for _ in range(6)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=15)
            # nobody processes the queue: 2 requests buffered (then 504 on
            # timeout), the overflow must be rejected 503 immediately
            assert codes.count(503) >= 3, codes
        finally:
            server.stop()


class TestInProcessWorkerLoop:
    def test_remote_worker_loop_function(self, driver):
        """remote_worker_loop as a library call (thread instead of
        process) — the N-ingest × M-compute topology in one test."""
        servers = [DistributedServingServer("msvc", driver.address,
                                            worker_id=f"m{i}").start()
                   for i in range(2)]
        stop = threading.Event()

        def transform(df):
            replies = np.empty(len(df), object)
            replies[:] = [HTTPResponseData(
                status_code=200, entity=(r.entity or b"") + b"!")
                for r in df["request"]]
            return df.with_column("reply", replies)

        w = threading.Thread(target=remote_worker_loop,
                             args=(driver.address, "msvc", transform),
                             kwargs={"stop_event": stop}, daemon=True)
        w.start()
        try:
            for i, s in enumerate(servers):
                status, body = _post(s.address, f"req{i}".encode())
                assert (status, body) == (200, f"req{i}!".encode())
        finally:
            stop.set()
            w.join(timeout=5)
            for s in servers:
                s.stop()


class TestTracePropagation:
    """ISSUE 8: one request → ONE cross-process span tree. The client's
    span rides the traceparent header to the ingest server (HTTP hop),
    the lease carries it to a REAL subprocess worker, and the worker's
    spans ride the reply payload home into the driver's flight
    recorder."""

    @pytest.mark.parametrize("server_cls", _front_params())
    def test_driver_worker_reply_tree(self, driver, server_cls):
        from mmlspark_tpu.io.http.clients import send_request
        from mmlspark_tpu.io.http.schema import HTTPRequestData
        from mmlspark_tpu.obs import flight_recorder, tracer
        from mmlspark_tpu.obs.tracing import _PROC

        # the recorder keeps the process's 32 SLOWEST requests: without
        # a clean slate, whether this (fast) request's tree survives
        # depends on which test files served slower ones on this worker
        flight_recorder.clear()
        svc = f"trsvc-{server_cls.__name__}"
        server = server_cls(svc, driver.address,
                            lease_timeout=10.0).start()
        worker = _spawn_worker(driver.address, svc, "echo")
        try:
            url = f"http://{server.address[0]}:{server.address[1]}/"
            with tracer.span("client.request") as client_span:
                tid = client_span.trace_id
                resp = send_request(
                    HTTPRequestData(url=url, method="POST", headers={},
                                    entity=b"trace me"),
                    timeout=30)
            assert resp.status_code == 200
        finally:
            worker.kill()
            worker.wait()
            server.stop()
        tree = flight_recorder.tree(tid)
        assert tree is not None, "request's trace not in the recorder"
        by_id = {s["spanId"]: s for s in tree["spans"]}
        names = {s["name"] for s in tree["spans"]}
        assert {"http.send", "serving.request", "sched.queue",
                "worker.execute", "worker.device"} <= names, names
        # HTTP hop: the server's request span parents into the
        # CLIENT's trace through the traceparent header round-trip
        (req_span,) = [s for s in tree["spans"]
                       if s["name"] == "serving.request"]
        assert by_id[req_span["parentId"]]["name"] == "http.send"
        assert req_span["attrs"]["status"] == 200
        # mesh hop: the worker's spans hang under the request span and
        # really came from the OTHER process
        (wex,) = [s for s in tree["spans"]
                  if s["name"] == "worker.execute"]
        assert wex["parentId"] == req_span["spanId"]
        assert wex["proc"] and wex["proc"] != _PROC
        (wdev,) = [s for s in tree["spans"]
                   if s["name"] == "worker.device"]
        assert wdev["parentId"] == wex["spanId"]
        # queue wait is the driver's: same process as the request span
        (qspan,) = [s for s in tree["spans"]
                    if s["name"] == "sched.queue"]
        assert qspan["parentId"] == req_span["spanId"]
        assert qspan["proc"] == _PROC

    def test_lease_payload_carries_trace_context(self, driver):
        """The __lease__ wire format: an item leased for a traced
        request carries {trace_id, span_id}; untraced items carry no
        trace key (old workers keep parsing)."""
        import json as _json

        server = DistributedServingServer("lsvc", driver.address).start()
        try:
            got = {}

            def client():
                got["resp"] = _post(server.address, b"traced-lease")

            t = threading.Thread(target=client)
            t.start()
            deadline = time.monotonic() + 10
            while server.queue.empty() and time.monotonic() < deadline:
                time.sleep(0.01)
            # direct lease pull (we play the worker)
            conn = http.client.HTTPConnection(*server.address,
                                              timeout=5)
            conn.request("POST", "/__lease__", body=b'{"max": 4}')
            items = _json.loads(conn.getresponse().read())
            conn.close()
            assert items, "nothing leased"
            entry = items[0]
            assert "trace" in entry
            cached = server._leases[entry["id"]][1]
            assert entry["trace"]["trace_id"] == cached.span.trace_id
            assert entry["trace"]["span_id"] == cached.span.span_id
            # answer it so the client thread finishes
            server.reply_to(entry["id"], HTTPResponseData(
                status_code=200, entity=b"done"))
            t.join(timeout=10)
            assert got["resp"] == (200, b"done")
        finally:
            server.stop()


class TestDslDistributed:
    def test_read_stream_distributed_server(self):
        """readStream.distributedServer() loads a registry-backed server
        whose requests compute workers can lease (reference
        IOImplicits.distributedServer)."""
        from mmlspark_tpu.serving import read_stream
        from mmlspark_tpu.serving.dsl import _default_registry

        stream = (read_stream().distributedServer()
                  .address("127.0.0.1", 0, "dslapi").load())
        server = stream.server
        try:
            assert isinstance(server, DistributedServingServer)
            server.start()
            # registered with the shared registry under the api name
            reg = _default_registry()
            assert any(i.worker_id == server.worker_id
                       for i in reg.workers("dslapi"))
            # a worker answers requests ingested through the DSL server
            stop = threading.Event()

            def transform(df):
                import numpy as np

                from mmlspark_tpu.io.http.schema import HTTPResponseData
                replies = np.empty(len(df), object)
                replies[:] = [HTTPResponseData(
                    status_code=200, entity=b"dsl!") for _ in df["request"]]
                return df.with_column("reply", replies)

            t = threading.Thread(
                target=remote_worker_loop,
                args=(reg.address, "dslapi", transform),
                kwargs={"stop_event": stop}, daemon=True)
            t.start()
            conn = http.client.HTTPConnection(*server.address, timeout=10)
            conn.request("POST", "/dslapi", body=b"hi")
            resp = conn.getresponse()
            assert (resp.status, resp.read()) == (200, b"dsl!")
            conn.close()
            stop.set()
            t.join(timeout=5)
        finally:
            server.stop()
