"""Device-resident SAR serving (ISSUE 11): byte-identity, stickiness, soak.

`serve_model(sar_model)` delegates to `serve_recommender`, which pins the
item-item similarity and user-affinity on device once and scores live
request batches through a fused gather -> matmul -> seen-mask -> top_k
program per bucket rung, counted under the `sar_resident` route label.
The contract mirrors the GBDT hot path: reply bytes NEVER depend on the
route, at any ladder size including ragged tails and users with fewer
than k unseen items; the gateway's hash-by-user routing keeps a user on
one replica through kill/respawn; and a mixed GBDT+SAR fleet behind one
gateway survives replica surgery with zero client-visible errors and
monotone counters.
"""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core.dataplane import cache_stats, reset_cache_stats
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.io_http.gateway import ServingGateway
from mmlspark_tpu.io_http.schema import HTTPRequestData
from mmlspark_tpu.io_http.serving import ServingFleet, serve_model
from mmlspark_tpu.recommendation import SAR, serve_recommender
from mmlspark_tpu.recommendation.serving import SARHotPath

K = 10


def _interactions(n_users=30, n_items=20, per_user=6, seed=11) -> Table:
    rng = np.random.default_rng(seed)
    rows = [(float(u), float(i), 1.0)
            for u in range(n_users)
            for i in rng.choice(n_items, size=per_user, replace=False)]
    arr = np.asarray(rows, np.float64)
    return Table({"user": arr[:, 0], "item": arr[:, 1], "rating": arr[:, 2]})


def _train_sar(**kw):
    return SAR(support_threshold=1).fit(_interactions(**kw))


def _requests(n: int, n_users: int = 30):
    return [HTTPRequestData.from_json("/", {"user": i % n_users})
            for i in range(n)]


def _post_raw(url: str, payload: dict, headers=None, timeout=30) -> bytes:
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers=hdrs)
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


def _oracle_bodies(model, k=K, remove_seen=True) -> "list[bytes]":
    """The offline answer: recommend_for_all_users rendered exactly as
    topk_reply renders a serving reply — one JSON body per user id."""
    recs = model.recommend_for_all_users(k=k, remove_seen=remove_seen)
    ids = np.asarray(recs["recommendations"]).tolist()
    ratings = np.asarray(recs["ratings"]).tolist()
    return [json.dumps({"recommendations": i, "ratings": r}).encode()
            for i, r in zip(ids, ratings)]


@pytest.fixture(scope="module")
def sar_server():
    """One warmed SAR server shared by the identity tests, reached through
    the generic `serve_model` entry point to prove the delegation —
    max_batch_size=256 so the ladder covers every rung the batcher can
    mint."""
    model = _train_sar()
    srv = serve_model(model, max_batch_size=256)
    _wait_ready(srv)
    yield model, srv
    srv.stop()


# every ladder rung of the 256 ladder at its full size plus a ragged
# tail that pads INTO it (3->4, 13->16, 100->128, 200->256, ...)
_SIZES = [1, 2, 3, 4, 5, 8, 13, 16, 31, 32, 64, 100, 128, 200, 255, 256]


class TestResidentByteIdentity:
    def test_serve_model_delegates_to_sar_hot_path(self, sar_server):
        _, srv = sar_server
        assert isinstance(srv.hot_path, SARHotPath)
        snap = srv.hot_path.snapshot()
        assert snap["enabled"] and snap["resident_label"] == "sar_resident"

    @pytest.mark.parametrize("n", _SIZES)
    def test_resident_matches_host_and_oracle_at_every_rung(
            self, sar_server, n):
        """Handler path vs device-resident executor at every ladder rung
        and ragged tail, both padded to that rung: identical reply ENTITY
        BYTES, request for request. The offline recommend_for_all_users
        answer scores all 30 users as one block, another shape than the
        rung's, so against it the replies are parsed: the same items in
        the same order, each rating (a float32) within 4 units in its
        last place."""
        model, srv = sar_server
        hp = srv.hot_path
        assert hp is not None and hp.disabled is None, hp and hp.snapshot()
        reqs = _requests(n)
        target = srv.bucketer.bucket_for(n)

        padded = reqs + [reqs[-1]] * (target - n)
        host = [r.entity
                for r in srv.handler(Table({"request": padded}))["reply"]][:n]

        feats = hp.decoder.decode(reqs, target)
        assert feats is not None
        resident = [r.entity
                    for r in hp.replies_for(hp.resident_values(feats, n))]

        assert host == resident, f"resident diverges from host at n={n}"
        oracle = [json.loads(body) for body in _oracle_bodies(model)]
        for i, body in enumerate(host):
            got, want = json.loads(body), oracle[i % 30]
            assert got["recommendations"] == want["recommendations"], (n, i)
            np.testing.assert_array_max_ulp(
                np.float32(got["ratings"]), np.float32(want["ratings"]),
                maxulp=4)

    def test_routes_agree_over_http(self, sar_server):
        """The same identity observed by a real client: force each route
        in turn and compare raw response bodies."""
        _, srv = sar_server
        bodies = {}
        for path in ("host", "sar_resident"):
            srv.hot_path.force_path = path
            try:
                bodies[path] = [_post_raw(srv.url, {"user": i})
                                for i in range(7)]
            finally:
                srv.hot_path.force_path = None
        assert bodies["host"] == bodies["sar_resident"]
        snap = srv.hot_path.snapshot()
        assert snap["paths"]["sar_resident"] >= 7

    def test_warmup_learned_the_full_ladder(self, sar_server):
        """/readyz flips only after the fused top-k executable compiled
        and byte-verified on EVERY rung, timed under the SAR label."""
        _, srv = sar_server
        snap = srv.hot_path.snapshot()
        assert snap["enabled"], snap
        ladder = [str(b) for b in srv.bucketer.ladder]
        assert sorted(snap["crossover"], key=int) == ladder
        for rung, t in snap["timings_ms"].items():
            assert "sar_resident" in t and t["sar_resident"] > 0, (rung, t)
        info = _get(srv.url)
        assert info["hot_path"]["enabled"]
        assert info["hot_path"]["resident_label"] == "sar_resident"

    def test_out_of_range_users_answer_invalid_rows(self, sar_server):
        """Unknown and non-integral user ids answer all-(-1) rows —
        byte-identically on both routes, never a 500."""
        _, srv = sar_server
        for payload in ({"user": 999}, {"user": 2.5}, {"user": -1}):
            got = {}
            for path in ("host", "sar_resident"):
                srv.hot_path.force_path = path
                try:
                    got[path] = json.loads(_post_raw(srv.url, payload))
                finally:
                    srv.hot_path.force_path = None
            assert got["host"] == got["sar_resident"]
            assert got["host"]["recommendations"] == [-1] * K
            assert got["host"]["ratings"] == [0.0] * K


class TestFewerThanKUnseen:
    def test_remove_seen_pads_with_invalid_slots(self):
        """A user who has seen all but one of 5 items asks for k=5: the
        single unseen item leads the reply and the exhausted slots carry
        the -1/0.0 sentinel — identical on both routes and equal to the
        offline answer."""
        rows = [(0.0, float(i), 1.0) for i in range(4)]       # user 0: 4/5
        rows += [(float(u), float(i), 1.0)
                 for u in (1, 2, 3) for i in (u, u + 1, 4)]
        arr = np.asarray(rows, np.float64)
        model = SAR(support_threshold=1).fit(Table(
            {"user": arr[:, 0], "item": arr[:, 1], "rating": arr[:, 2]}))
        srv = serve_recommender(model, k=5, max_batch_size=8)
        try:
            _wait_ready(srv)
            assert srv.hot_path is not None and srv.hot_path.disabled is None
            bodies = {}
            for path in ("host", "sar_resident"):
                srv.hot_path.force_path = path
                try:
                    bodies[path] = [_post_raw(srv.url, {"user": u})
                                    for u in range(4)]
                finally:
                    srv.hot_path.force_path = None
            assert bodies["host"] == bodies["sar_resident"]
            oracle = _oracle_bodies(model, k=5)
            assert bodies["host"] == oracle[:4]
            user0 = json.loads(bodies["host"][0])
            assert user0["recommendations"][0] == 4
            assert user0["recommendations"][1:] == [-1] * 4
            assert user0["ratings"][1:] == [0.0] * 4
        finally:
            srv.stop()


class TestSteadyStateSoak:
    def test_concurrent_soak_zero_recompiles(self):
        """8 clients x 30 requests on a warm SAR server, everything
        forced resident: zero executable recompiles, one upload+readback
        round trip per batch, sar_resident counter exact."""
        srv = serve_recommender(_train_sar(), max_batch_size=32)
        try:
            _wait_ready(srv)
            hp = srv.hot_path
            assert hp is not None and hp.disabled is None
            hp.force_path = "sar_resident"
            reset_cache_stats()
            results, errors = [], []

            def client(k: int):
                try:
                    for i in range(30):
                        body = json.loads(_post_raw(srv.url, {"user": i % 30}))
                        results.append((i % 30, json.dumps(body)))
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
            by_u = {}
            for u, v in results:
                by_u.setdefault(u, set()).add(v)
            assert all(len(vs) == 1 for vs in by_u.values())

            exe = cache_stats()
            assert exe["recompiles"] == 0, exe
            snap = hp.snapshot()
            assert snap["paths"]["sar_resident"] == 240, snap
            assert 0 < snap["round_trips_per_resident_request"] <= 1.0, snap
        finally:
            srv.stop()


class TestGatewayStickiness:
    def test_hash_by_user_sticks_through_kill_and_respawn(self):
        """x-routing-key=user pins each user to one replica; killing a
        replica only moves ITS users (consistent hashing), answers stay
        byte-identical throughout (same model everywhere), and a respawn
        re-enters rotation without disturbing stickiness."""
        model = _train_sar()
        a = serve_recommender(model, max_batch_size=8)
        b = serve_recommender(model, max_batch_size=8)
        gw = None
        c = None
        oracle = _oracle_bodies(model)
        try:
            _wait_ready(a)
            _wait_ready(b)
            gw = ServingGateway(urls=[a.url, b.url]).start()

            def home_of(key: str, servers, n=3) -> "tuple[object, list]":
                before = {s.url: s.requests_seen for s in servers}
                bodies = [_post_raw(gw.url, {"user": int(key)},
                                    {"x-routing-key": f"user-{key}"})
                          for _ in range(n)]
                grew = [s for s in servers
                        if s.requests_seen == before[s.url] + n]
                assert len(grew) == 1, "key split across replicas"
                return grew[0], bodies

            keys = [str(u) for u in range(16)]
            homes = {}
            for key in keys:
                srv, bodies = home_of(key, (a, b))
                homes[key] = srv
                assert bodies == [oracle[int(key)]] * 3
            assert {a, b} == set(homes.values()), \
                "want keys spread over both replicas"

            # kill replica a: its users move, b's users stay home
            gw.remove(a.url)
            a.stop()
            for key in keys:
                srv, bodies = home_of(key, (b,))
                assert srv is b
                if homes[key] is b:
                    pass  # survivor's users never moved
                assert bodies == [oracle[int(key)]] * 3

            # respawn: a fresh warmed replica re-enters rotation; every
            # key is sticky again and bytes still match the oracle
            c = serve_recommender(model, max_batch_size=8)
            _wait_ready(c)
            gw.admit(c.url)
            rehome = {}
            for key in keys:
                srv, bodies = home_of(key, (b, c))
                rehome[key] = srv
                assert bodies == [oracle[int(key)]] * 3
            for key in keys:  # sticky: a second pass repeats the mapping
                srv, _ = home_of(key, (b, c))
                assert srv is rehome[key]

            routes = gw.routes()
            assert routes["strategy_requests"]["hash"] >= len(keys) * 9
        finally:
            if gw is not None:
                gw.stop()
            for srv in (a, b, c):
                if srv is None:
                    continue
                try:
                    srv.stop()
                except Exception:  # noqa: BLE001 — already stopped
                    pass

    def test_mixed_gbdt_and_sar_replicas_behind_one_gateway(self):
        """One gateway fronting a GBDT replica and two SAR replicas:
        sticky keys discovered per workload keep every request on a
        replica speaking its schema; killing + respawning the idle SAR
        replica never surfaces to a client; per-route counters
        (resident/native/host vs sar_resident) stay monotone."""
        from mmlspark_tpu.gbdt.estimators import GBDTRegressor

        rng = np.random.default_rng(7)
        X = rng.normal(size=(128, 4)).astype(np.float32).astype(np.float64)
        y = X @ np.asarray([1.0, -2.0, 0.5, 3.0])
        cols = ["x0", "x1", "x2", "x3"]
        gb_model = GBDTRegressor(num_iterations=3, num_leaves=7).fit(
            Table({"features": X, "label": y}))
        sar_model = _train_sar()
        gb_payload = {c: float(np.float32(0.25 + 0.125 * j))
                      for j, c in enumerate(cols)}

        gb = serve_model(gb_model, cols, max_batch_size=8,
                         warmup_request=HTTPRequestData.from_json(
                             "/", gb_payload))
        s1 = serve_recommender(sar_model, max_batch_size=8)
        s2 = serve_recommender(sar_model, max_batch_size=8)
        gw = None
        s3 = None
        try:
            for srv in (gb, s1, s2):
                _wait_ready(srv)
            gw = ServingGateway(urls=[gb.url, s1.url, s2.url]).start()

            def find_key(payload: dict, want: set) -> str:
                """Probe sticky keys until one lands on a replica that
                answers this payload's schema (wrong-schema probes 500,
                which is exactly why production keys are per-workload)."""
                for i in range(64):
                    key = f"probe-{i}"
                    try:
                        body = json.loads(_post_raw(
                            gw.url, payload, {"x-routing-key": key}))
                    except urllib.error.HTTPError:
                        continue
                    if set(body) >= want:
                        return key
                raise AssertionError("no key mapped to a matching replica")

            key_gb = find_key(gb_payload, {"prediction"})
            key_sar = find_key({"user": 0}, {"recommendations"})
            ref_gb = _post_raw(gw.url, gb_payload,
                               {"x-routing-key": key_gb})
            ref_sar = _post_raw(gw.url, {"user": 0},
                                {"x-routing-key": key_sar})
            assert ref_sar == _oracle_bodies(sar_model)[0]

            def paths_snapshot():
                out = {}
                for name, srv in (("gb", gb), ("s1", s1), ("s2", s2)):
                    if srv.hot_path is not None:
                        out[name] = dict(srv.hot_path.snapshot()["paths"])
                return out

            statuses, bodies = [], []

            def drive(n: int):
                for i in range(n):
                    if i % 2 == 0:
                        bodies.append(("gb", _post_raw(
                            gw.url, gb_payload, {"x-routing-key": key_gb})))
                    else:
                        bodies.append(("sar", _post_raw(
                            gw.url, {"user": 0},
                            {"x-routing-key": key_sar})))
                    statuses.append(200)

            seen_before = {s.url: s.requests_seen for s in (s1, s2)}
            drive(20)
            mid = paths_snapshot()

            # surgery on the SAR replica NOT homing key_sar: remove,
            # stop, respawn, readmit — the sticky streams never notice
            sar_home = s1 if s1.requests_seen > seen_before[s1.url] else s2
            victim = s2 if sar_home is s1 else s1
            gw.remove(victim.url)
            victim.stop()
            drive(20)
            s3 = serve_recommender(sar_model, max_batch_size=8)
            _wait_ready(s3)
            gw.admit(s3.url)
            drive(20)

            assert statuses == [200] * 60
            for kind, body in bodies:
                assert body == (ref_gb if kind == "gb" else ref_sar)
            end = paths_snapshot()
            for name, mid_paths in mid.items():
                if name in end:
                    for path, n in mid_paths.items():
                        assert n <= end[name][path], (name, path)
            # both workloads flowed: the GBDT replica scored through its
            # routes, the SAR home through sar_resident/host
            assert sum(end["gb"].values()) >= 30
            sar_name = "s1" if sar_home is s1 else "s2"
            assert sum(end[sar_name].values()) >= 30
        finally:
            if gw is not None:
                gw.stop()
            for srv in (gb, s1, s2, s3):
                if srv is None:
                    continue
                try:
                    srv.stop()
                except Exception:  # noqa: BLE001 — already stopped
                    pass


# module-level factory: fleet workers use the spawn context, so the
# factory must be importable from this file. Children rebuild both
# models deterministically — every replica answers BOTH schemas, which
# is what lets hash routing spread mixed traffic over the whole fleet.

def _mixed_fleet_factory():
    from mmlspark_tpu.gbdt.estimators import GBDTRegressor
    from mmlspark_tpu.io_http.schema import make_reply, parse_request
    from mmlspark_tpu.recommendation import SAR, SARTopKScorer
    from mmlspark_tpu.recommendation.serving import topk_reply

    rng = np.random.default_rng(7)
    X = rng.normal(size=(128, 4)).astype(np.float32).astype(np.float64)
    y = X @ np.asarray([1.0, -2.0, 0.5, 3.0])
    gbdt = GBDTRegressor(num_iterations=3, num_leaves=7).fit(
        Table({"features": X, "label": y}))
    scorer = SARTopKScorer.from_model(
        SAR(support_threshold=1).fit(_interactions()), k=5)

    def handler(table: Table) -> Table:
        first = json.loads(table["request"][0].entity)
        if "user" in first:
            t = parse_request(table)
            t = t.with_column("features", np.asarray(
                t["user"], np.float64).reshape(-1, 1))
            return topk_reply(scorer.transform(t))
        t = parse_request(table)
        feats = np.stack([np.asarray(t[c], np.float64)
                          for c in ("x0", "x1", "x2", "x3")], axis=1)
        scored = gbdt.transform(t.with_column("features", feats))
        return make_reply(scored, "prediction")

    return handler


class TestMixedFleetSoak:
    def test_fleet_kill_respawn_zero_client_errors(self):
        """Real-process fleet serving BOTH workloads behind one gateway:
        mixed GBDT+SAR traffic with hash-by-user stickiness, a hard
        mid-soak kill + self-heal respawn — zero client-visible errors,
        byte-stable answers per user, monotone fleet counters, and a
        journal-dense gateway."""
        fleet = ServingFleet(_mixed_fleet_factory, n_hosts=2,
                             max_batch_size=1).start()
        gw = ServingGateway(strategy="round_robin")
        gw.attach_fleet(fleet)
        gw.start()
        rv = fleet.rendezvous
        seen_name = "mmlspark_tpu_serving_requests_seen_total"
        statuses = []

        def post(payload: dict, user: str) -> bytes:
            resp = _post_raw(gw.url, payload, {"x-routing-key": user},
                             timeout=60)
            statuses.append(200)
            return resp

        gb_payload = {c: float(np.float32(0.25 + 0.125 * j))
                      for j, c in enumerate(("x0", "x1", "x2", "x3"))}
        try:
            refs = {}
            for u in range(4):
                refs[("sar", u)] = post({"user": u}, f"u{u}")
                refs[("gb", u)] = post(gb_payload, f"g{u}")

            def drive(n: int):
                for i in range(n):
                    u = i % 4
                    assert post({"user": u}, f"u{u}") == refs[("sar", u)]
                    assert post(gb_payload, f"g{u}") == refs[("gb", u)]

            drive(10)
            rv.aggregator.scrape()
            seen_mid = rv.aggregator.total(seen_name)
            assert seen_mid > 0

            # hard kill one replica; the gateway hedge covers the corpse
            fleet.kill(0)
            drive(10)
            assert gw.routes()["n_live"] == 1
            assert fleet.dead_slots() == [0]
            fleet.respawn(0)
            assert fleet.dead_slots() == []
            drive(10)
            assert gw.routes()["n_live"] == 2

            rv.aggregator.scrape()
            assert rv.aggregator.total(seen_name) >= seen_mid
            assert statuses == [200] * len(statuses)
            assert len(statuses) == 68
            assert gw.routes()["strategy_requests"]["hash"] == 68
        finally:
            gw.stop()
            fleet.stop()
