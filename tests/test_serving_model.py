"""Serving REAL models (review round 3 Missing #5): the reference's serving
story is "the same ML pipeline as a web service"
(``continuous/HTTPSourceV2.scala:475+``, ``docs/mmlspark-serving.md:9-12``,
BASELINE configs[5] names a ResNet endpoint) — these tests drive a
fitted GBDT booster and a zoo-backed ImageFeaturizer through the
serving plane, including the native front + driver registry + lease
replay acting TOGETHER on one request."""

import http.client
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core import DataFrame
from mmlspark_tpu.io.http.schema import HTTPResponseData
from mmlspark_tpu.lightgbm import LightGBMClassifier
from mmlspark_tpu.serving import DriverRegistry, remote_worker_loop, \
    serving_query


def _post(addr, body: bytes, timeout=30):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/", body=body)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


@pytest.fixture(scope="module")
def gbdt_model():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(1200, 10)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] * x[:, 2] > 0).astype(np.float32)
    df = DataFrame({"features": x, "label": y})
    return LightGBMClassifier(numIterations=5, numLeaves=15,
                              seed=0).fit(df), x


def _gbdt_transform(model):
    """ServingQuery contract: request body = one float32 feature row →
    reply body = float32 probability-of-class-1."""
    def run(df):
        rows = np.stack([
            np.frombuffer(r.entity, np.float32) for r in df["request"]])
        prob = model.transform(
            DataFrame({"features": rows}))[model.getProbabilityCol()]
        replies = np.empty(len(df), object)
        replies[:] = [HTTPResponseData(
            status_code=200,
            entity=np.float32(p[1]).tobytes()) for p in prob]
        return df.with_column("reply", replies)
    return run


class TestGBDTServing:
    def test_fitted_booster_served(self, gbdt_model):
        """A fitted LightGBM pipeline behind the one-call server: wire
        answers must match offline model.transform exactly."""
        model, x = gbdt_model
        expected = model.transform(
            DataFrame({"features": x[:5]}))[model.getProbabilityCol()]
        query = serving_query("gbdt-svc", _gbdt_transform(model),
                              reply_timeout=30.0, backend="auto")
        try:
            for i in range(5):
                status, body = _post(query.server.address,
                                     x[i].tobytes())
                assert status == 200
                got = np.frombuffer(body, np.float32)[0]
                assert abs(got - expected[i][1]) < 1e-6
        finally:
            query.stop()

    def test_native_front_registry_and_replay_together(self, gbdt_model):
        """The full distributed story on ONE request: native epoll
        ingress + driver registry + a worker that leases and dies +
        lease-expiry replay answered by a surviving worker running the
        REAL model (reference: ``HTTPSourceV2.scala:488-517`` epoch
        replay; :460-468 registration)."""
        from mmlspark_tpu.native.loader import get_httpfront
        if get_httpfront() is None:
            pytest.skip("native toolchain unavailable")
        from mmlspark_tpu.serving import NativeDistributedServingServer

        model, x = gbdt_model
        expected = model.transform(
            DataFrame({"features": x[:1]}))[model.getProbabilityCol()]
        driver = DriverRegistry().start()
        server = NativeDistributedServingServer(
            "gbdt-mesh", driver.address, lease_timeout=0.6,
            reply_timeout=30.0).start()
        stop = threading.Event()
        worker = None
        try:
            result = {}

            def client():
                result["resp"] = _post(server.address, x[0].tobytes())

            ct = threading.Thread(target=client)
            ct.start()
            # wait until the request is queued, then steal its lease and
            # never answer — the dying-worker half
            import json
            deadline = time.monotonic() + 5
            stolen = []
            while time.monotonic() < deadline and not stolen:
                status, body = _lease(server.address)
                stolen = json.loads(body)
            assert stolen, "request never became leasable"
            # now start the surviving worker with the real model; the
            # lease monitor must replay the stolen request to it
            worker = threading.Thread(
                target=remote_worker_loop,
                args=(f"{driver.address[0]}:{driver.address[1]}",
                      "gbdt-mesh", _gbdt_transform(model)),
                kwargs={"stop_event": stop}, daemon=True)
            worker.start()
            ct.join(timeout=20)
            assert not ct.is_alive(), "client never got an answer"
            status, body = result["resp"]
            assert status == 200
            got = np.frombuffer(body, np.float32)[0]
            assert abs(got - expected[0][1]) < 1e-6
            assert server.epoch >= 1  # the replay wave actually happened
        finally:
            stop.set()
            if worker is not None:
                worker.join(timeout=5)
            server.stop()
            driver.stop()


def _lease(addr):
    conn = http.client.HTTPConnection(*addr, timeout=10)
    try:
        conn.request("POST", "/__lease__", body=b'{"max": 4}')
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class TestImageFeaturizerServing:
    def test_resnet_featurizer_served(self):
        """Zoo ResNet (device-resident weights, fixed shapes) as a
        feature service — BASELINE configs[5]'s endpoint shape. Wire
        features must match offline transform."""
        import jax.numpy as jnp

        from mmlspark_tpu.image import ImageFeaturizer
        from mmlspark_tpu.models import ModelDownloader

        loaded = ModelDownloader().download_by_name(
            "ResNet18", allow_random_init=True, dtype=jnp.float32)
        feat = ImageFeaturizer(model=loaded, cutOutputLayers=1,
                               inputCol="image", outputCol="features",
                               autoResize=False, miniBatchSize=4)
        rng = np.random.default_rng(3)
        imgs = rng.normal(size=(3, 64, 64, 3)).astype(np.float32)
        offline = np.stack(list(
            feat.transform(DataFrame({"image": imgs}))["features"]))

        def run(df):
            arrs = np.stack([
                np.frombuffer(r.entity, np.float32).reshape(64, 64, 3)
                for r in df["request"]])
            out = feat.transform(DataFrame({"image": arrs}))["features"]
            replies = np.empty(len(df), object)
            replies[:] = [HTTPResponseData(
                status_code=200, entity=np.asarray(f).tobytes())
                for f in out]
            return df.with_column("reply", replies)

        query = serving_query("resnet-svc", run, reply_timeout=60.0,
                              backend="auto")
        try:
            for i in range(3):
                status, body = _post(query.server.address,
                                     imgs[i].tobytes(), timeout=60)
                assert status == 200
                got = np.frombuffer(body, np.float32)
                np.testing.assert_allclose(got, offline[i], atol=1e-5)
        finally:
            query.stop()
