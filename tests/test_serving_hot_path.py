"""serve_model's device-resident hot path: route byte-identity + soak.

The fast lane (io_http/serving._HotPath) may route a live batch through
three different scoring engines — the original handler path, the native
C++ tree walk, and the device-resident fused executor. The serving
contract is that a client can NEVER tell which one answered: reply bytes
must match exactly at every batch size the bucket ladder can mint,
including ragged tails, through the gateway, and across a zero-downtime
swap. The soak asserts the perf facts the ISSUE promises: zero
steady-state recompiles once warm and at most one host<->device round
trip per resident request.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.dataplane import cache_stats, reset_cache_stats
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.gbdt.estimators import GBDTRegressor
from mmlspark_tpu.io_http.serving import serve_model

COLS = ["x0", "x1", "x2", "x3"]


def _train_model(seed: int = 7):
    """A deterministically-trained GBDT on f32-representable features —
    two calls with the same seed produce byte-identical boosters (the
    rolling-swap test depends on it)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(256, 4)).astype(np.float32).astype(np.float64)
    y = X @ np.asarray([1.0, -2.0, 0.5, 3.0]) + rng.normal(
        scale=0.1, size=256)
    return GBDTRegressor(num_iterations=5, num_leaves=7).fit(
        Table({"features": X, "label": y}))


def _payload(i: int) -> dict:
    # float32-exact values: the resident route's check_ready precondition
    # (device binning requires f32-representable features) must pass
    return {c: float(np.float32(0.25 * i + 0.125 * j))
            for j, c in enumerate(COLS)}


def _requests(n: int):
    from mmlspark_tpu.io_http.schema import HTTPRequestData

    return [HTTPRequestData.from_json("/", _payload(i)) for i in range(n)]


def _warm_request():
    from mmlspark_tpu.io_http.schema import HTTPRequestData

    return HTTPRequestData.from_json("/", _payload(3))


def _post_raw(url: str, payload: dict, timeout=30) -> bytes:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _get(url: str, timeout=10) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _wait_ready(srv, timeout_s: float = 120.0):
    import time

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if srv.ready:
            return
        time.sleep(0.02)
    raise TimeoutError(
        f"server never became ready; hot_path="
        f"{srv.hot_path.snapshot() if srv.hot_path else None}")


@pytest.fixture(scope="module")
def hot_server():
    """One warmed serve_model server shared by the identity tests —
    max_batch_size=256 so the ladder covers every ISSUE batch size."""
    srv = serve_model(_train_model(), COLS, max_batch_size=256,
                      warmup_request=_warm_request())
    _wait_ready(srv)
    yield srv
    srv.stop()


class TestThreeRouteByteIdentity:
    @pytest.mark.parametrize("n", [1, 5, 32, 200, 256])
    def test_routes_agree_at_every_ladder_size(self, hot_server, n):
        """Host handler vs native tree walk vs device-resident executor,
        at the ISSUE's batch sizes (1/32/256) plus ragged tails (5 -> pad
        8, 200 -> pad 256): identical reply ENTITY BYTES, request for
        request."""
        srv = hot_server
        hp = srv.hot_path
        assert hp is not None and hp.disabled is None, hp and hp.snapshot()
        assert hp.native_fn is not None
        reqs = _requests(n)
        target = srv.bucketer.bucket_for(n)

        # host route: the handler path exactly as _score_batch drives it
        # (pad by repeating the last request, slice the replies)
        padded = reqs + [reqs[-1]] * (target - n)
        host = [r.entity
                for r in srv.handler(Table({"request": padded}))["reply"]][:n]

        feats = hp.decoder.decode(reqs, target)
        assert feats is not None
        assert not hp.executor.check_ready(Table({hp.feature_col: feats}))
        resident = [r.entity
                    for r in hp.replies_for(hp.resident_values(feats, n))]
        native = [r.entity
                  for r in hp.replies_for(hp.native_values(feats[:n]))]

        assert host == resident, f"resident diverges from host at n={n}"
        assert host == native, f"native diverges from host at n={n}"

    def test_routes_agree_over_http(self, hot_server):
        """The same identity observed by a real client: force each route
        in turn and compare raw response bodies."""
        srv = hot_server
        bodies = {}
        for path in ("host", "native", "resident"):
            srv.hot_path.force_path = path
            try:
                bodies[path] = [_post_raw(srv.url, _payload(i))
                                for i in range(7)]
            finally:
                srv.hot_path.force_path = None
        assert bodies["host"] == bodies["native"] == bodies["resident"]
        snap = srv.hot_path.snapshot()
        assert snap["paths"]["resident"] >= 7
        assert snap["paths"]["native"] >= 7

    def test_warmup_learned_the_full_ladder(self, hot_server):
        """/readyz flips only after the resident executable is compiled
        and the native/resident crossover measured on EVERY rung."""
        srv = hot_server
        snap = srv.hot_path.snapshot()
        assert snap["enabled"], snap
        ladder = [str(b) for b in srv.bucketer.ladder]
        assert sorted(snap["crossover"], key=int) == ladder
        for rung, t in snap["timings_ms"].items():
            assert "resident" in t and t["resident"] > 0, (rung, t)
        info = _get(srv.url)
        assert info["hot_path"]["enabled"]
        assert info["hot_path"]["crossover"] == snap["crossover"]

    def test_non_schema_request_falls_back_byte_identically(self, hot_server):
        """A request outside the cached schema (an extra field is fine;
        a MISSING field is not) must not 500 — the decoder declines and
        the handler path answers it, resident forced or not."""
        srv = hot_server
        ok = dict(_payload(2), extra="ignored")
        srv.hot_path.force_path = "resident"
        try:
            assert _post_raw(srv.url, ok) == _post_raw(srv.url, _payload(2))
            # a non-f32-representable float: resident's device precondition
            # declines the batch, the native walk answers it exactly
            odd = dict(_payload(2), x0=0.1)
            body = json.loads(_post_raw(srv.url, odd))
            assert set(body) == {"prediction"}
        finally:
            srv.hot_path.force_path = None


class TestWarmupFailuresAreLoud:
    """Nothing hides the device: a compile/dispatch error in warm-up is
    not a 'divergence' that quietly switches the lane off."""

    def test_dispatch_error_propagates_and_is_reported(self, hot_server,
                                                       monkeypatch):
        srv, hp = hot_server, hot_server.hot_path

        def refused(*a, **k):
            raise RuntimeError("mosaic says no")

        monkeypatch.setattr(hp.executor, "dispatch", refused)
        with pytest.raises(RuntimeError, match="mosaic says no"):
            srv.warmup()
        assert hp.disabled is None
        try:
            srv._warmup_async()   # the start() thread's entry point
            assert "mosaic says no" in srv.health()["warmup_error"]
        finally:
            srv.warmup_error = None

    def test_disabled_lane_and_missing_lane_say_so(self, hot_server,
                                                   caplog):
        import logging

        from mmlspark_tpu.gbdt.estimators import GBDTClassifier
        from mmlspark_tpu.io_http.serving import _HotPath

        logging.getLogger("mmlspark_tpu").propagate = True
        try:
            with caplog.at_level(logging.WARNING, logger="mmlspark_tpu"):
                hp = hot_server.hot_path
                probe = _HotPath(hp.executor, hp.decoder, "features",
                                 "prediction")
                probe._disable("resident replies diverge at rung 1")
                rng = np.random.default_rng(0)
                X = rng.normal(size=(64, 4))
                clf = GBDTClassifier(num_iterations=2, num_leaves=4).fit(
                    Table({"features": X,
                           "label": (X[:, 0] > 0).astype(float)}))
                srv = serve_model(clf, COLS)
                srv.stop()
        finally:
            logging.getLogger("mmlspark_tpu").propagate = False
        assert srv.hot_path is None
        text = caplog.text
        assert "hot path disabled" in text and "diverge at rung 1" in text
        assert "no resident hot path" in text
        assert "float64 on host" in text   # the stage's own reason


class TestSteadyStateSoak:
    def test_concurrent_soak_no_recompiles_one_round_trip(self):
        """High-concurrency soak on a warm server: 8 clients x 30
        requests. Steady state must hold the ISSUE's perf facts — ZERO
        executable recompiles, path counters that only grow, and <= 1
        host round trip per resident-scored request."""
        srv = serve_model(_train_model(), COLS, max_batch_size=32,
                          warmup_request=_warm_request())
        try:
            _wait_ready(srv)
            hp = srv.hot_path
            assert hp is not None and hp.disabled is None
            # route everything resident so the soak exercises dispatch/
            # readback under load (the CPU crossover would pick native)
            hp.force_path = "resident"
            reset_cache_stats()
            mid = {"snap": None}
            results, errors = [], []

            def client(k: int):
                try:
                    for i in range(30):
                        body = json.loads(_post_raw(srv.url, _payload(i)))
                        results.append((i, body["prediction"]))
                        if k == 0 and i == 15:
                            mid["snap"] = hp.snapshot()
                except Exception as e:  # noqa: BLE001 — collected below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors[:3]
            assert len(results) == 240
            # same payload -> same prediction regardless of which batch
            # composition scored it
            by_i = {}
            for i, v in results:
                by_i.setdefault(i, set()).add(v)
            assert all(len(vs) == 1 for vs in by_i.values())

            exe = cache_stats()
            assert exe["recompiles"] == 0, exe
            snap = hp.snapshot()
            assert snap["paths"]["resident"] == 240, snap
            # monotone counters: the mid-soak snapshot never exceeds the end
            assert mid["snap"] is not None
            for path, n in mid["snap"]["paths"].items():
                assert n <= snap["paths"][path]
            assert mid["snap"]["resident_batches"] <= snap["resident_batches"]
            # continuous batching coalesces, so batches <= requests and
            # each batch spends exactly one upload+readback round trip
            assert 0 < snap["round_trips_per_resident_request"] <= 1.0, snap
            assert snap["resident_batches"] <= 240
        finally:
            srv.stop()


class TestGatewaySwap:
    def test_swap_through_gateway_is_byte_identical(self):
        """Zero-downtime swap behind the gateway: replica A (hot path on
        its measured routing) answers, replica B (same deterministic
        model, forced resident) is admitted and A removed — client bytes
        through the gateway never change. This is the gateway-level
        rolling_swap contract with the device-resident route live."""
        from mmlspark_tpu.io_http.gateway import ServingGateway

        a = serve_model(_train_model(), COLS, max_batch_size=8,
                        warmup_request=_warm_request())
        b = serve_model(_train_model(), COLS, max_batch_size=8,
                        warmup_request=_warm_request())
        gw = None
        try:
            _wait_ready(a)
            _wait_ready(b)
            b.hot_path.force_path = "resident"
            gw = ServingGateway(urls=[a.url]).start()
            before = [_post_raw(gw.url, _payload(i)) for i in range(5)]
            # the rolling-swap sequence: publish the warm successor, then
            # retire the old replica — the pool never goes empty
            gw.admit(b.url)
            gw.remove(a.url)
            a.stop()
            after = [_post_raw(gw.url, _payload(i)) for i in range(5)]
            assert before == after
            assert b.hot_path.snapshot()["paths"]["resident"] >= 5
        finally:
            if gw is not None:
                gw.stop()
            for srv in (a, b):
                try:
                    srv.stop()
                except Exception:  # noqa: BLE001 — already stopped
                    pass


class TestBinaryWireServing:
    """Content-negotiated binary protocol on the scoring routes: a framed
    request scores to a framed reply, JSON clients keep byte-identical
    replies, and a malformed frame degrades to an HTTP error without
    dropping the connection."""

    def _post_binary(self, srv, row, timeout=30):
        from mmlspark_tpu.io_http import wire

        req = urllib.request.Request(
            srv.url, data=wire.encode_features_request(row),
            headers={"Content-Type": wire.WIRE_CONTENT_TYPE,
                     "Accept": wire.WIRE_CONTENT_TYPE})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.headers.get("Content-Type"), r.read()

    def test_binary_request_scores_to_binary_reply(self, hot_server):
        from mmlspark_tpu.io_http import wire

        srv = hot_server
        row = np.asarray([_payload(5)[c] for c in COLS])
        ct, entity = self._post_binary(srv, row)
        assert wire.is_wire_content_type(ct)
        col, vals = wire.decode_reply(entity)
        assert col == "prediction" and vals.shape[0] == 1
        # the framed value is BIT-identical to what the JSON path says
        json_val = json.loads(_post_raw(srv.url, _payload(5)))["prediction"]
        assert float(np.asarray(vals).ravel()[0]) == json_val

    def test_json_replies_byte_identical_around_binary_traffic(
            self, hot_server):
        srv = hot_server
        before = [_post_raw(srv.url, _payload(i)) for i in range(5)]
        for i in range(5):
            row = np.asarray([_payload(i)[c] for c in COLS])
            self._post_binary(srv, row)
        after = [_post_raw(srv.url, _payload(i)) for i in range(5)]
        assert before == after  # JSON clients never see the upgrade

    def test_protocol_mix_counted(self, hot_server):
        srv = hot_server
        base = dict(srv.protocol_counts())
        hits0 = srv.hot_path.decoder.binary_hits
        row = np.asarray([_payload(2)[c] for c in COLS])
        for _ in range(3):
            self._post_binary(srv, row)
        _post_raw(srv.url, _payload(2))
        counts = srv.protocol_counts()
        assert counts["binary"] >= base.get("binary", 0) + 3
        assert counts["json"] >= base.get("json", 0) + 1
        assert srv.hot_path.decoder.binary_hits >= hits0 + 3

    def test_bad_frame_is_an_http_error_not_a_dropped_socket(
            self, hot_server):
        from mmlspark_tpu.io_http import wire

        srv = hot_server
        req = urllib.request.Request(
            srv.url, data=b"MSWRgarbage-not-a-frame",
            headers={"Content-Type": wire.WIRE_CONTENT_TYPE})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code >= 400
        # the server (and schema cache) survive: a JSON request right
        # after scores normally
        out = json.loads(_post_raw(srv.url, _payload(4)))
        assert "prediction" in out
