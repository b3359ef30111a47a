"""Service-layer tests: /report behavior parity with SURVEY.md §3.1.

The reference's tests POST canned traces to a running service and assert the
reported segments (SURVEY.md §4); these do the same through the WSGI
interface (no sockets), plus unit tests of the cache and report builder.
"""

import io
import json

import numpy as np
import pytest

from reporter_tpu.config import CompilerParams, Config, ServiceConfig
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.tiles.compiler import compile_network
from reporter_tpu.netgen.traces import synthesize_probe
from reporter_tpu.service.app import make_app
from reporter_tpu.service.cache import PartialTraceCache
from reporter_tpu.service.reports import Report, build_reports
from reporter_tpu.matcher.segments import SegmentRecord


def wsgi_call(app, method, path, payload=None):
    body = json.dumps(payload).encode() if payload is not None else b""
    environ = {
        "REQUEST_METHOD": method,
        "PATH_INFO": path,
        "CONTENT_LENGTH": str(len(body)),
        "wsgi.input": io.BytesIO(body),
    }
    captured = {}

    def start_response(status, headers):
        captured["status"] = int(status.split()[0])

    chunks = app(environ, start_response)
    data = b"".join(chunks)
    return captured["status"], (json.loads(data) if data else None)


@pytest.fixture(scope="module")
def svc_tiles():
    """Short OSMLR segments (~200 m): full traversals are common, so the
    fully-traversed-only report filter has something to let through."""
    return compile_network(
        generate_city("tiny"),
        CompilerParams(reach_radius=500.0, osmlr_max_length=200.0))


@pytest.fixture(scope="module")
def app(svc_tiles):
    published = []

    def transport(url, body):
        published.append(json.loads(body))
        return 200

    cfg = Config(service=ServiceConfig(datastore_url="http://datastore.test/"))
    a = make_app(svc_tiles, cfg, transport=transport)
    a.test_published = published
    return a


def _probe_payload(ts, seed=5, num_points=120):
    return synthesize_probe(ts, seed=seed, num_points=num_points,
                            gps_sigma=3.0).to_report_json()


class TestEndpoints:
    def test_health(self, app):
        status, body = wsgi_call(app, "GET", "/health")
        assert status == 200
        assert body["status"] == "ok"
        assert body["edges"] == app.matcher.ts.num_edges

    def test_stats_endpoint(self, app, svc_tiles):
        payload = _probe_payload(svc_tiles, seed=23)
        wsgi_call(app, "POST", "/report", payload)
        status, body = wsgi_call(app, "GET", "/stats")
        assert status == 200
        assert body["probes"] >= len(payload["trace"])
        assert body["match_seconds_count"] >= 1
        assert body["match_seconds_p50"] > 0
        assert "uptime_seconds" in body

    def test_report_roundtrip(self, app, svc_tiles):
        payload = _probe_payload(svc_tiles, seed=11)
        status, body = wsgi_call(app, "POST", "/report", payload)
        assert status == 200
        assert body["mode"] == "auto"
        assert len(body["segments"]) > 0
        assert len(body["reports"]) > 0
        for r in body["reports"]:
            assert r["t1"] > r["t0"]
            assert r["length"] > 0
            assert r["id"] >= 0

    def test_reports_published_to_datastore(self, app, svc_tiles):
        before = app.publisher.published
        payload = _probe_payload(svc_tiles, seed=12)
        _, body = wsgi_call(app, "POST", "/report", payload)
        assert app.publisher.published == before + len(body["reports"])
        last = app.test_published[-1]
        assert last["mode"] == "auto"
        assert {"id", "next_id", "t0", "t1", "length", "queue_length"} <= set(
            last["reports"][0])

    def test_publish_json_failures_counted(self):
        from reporter_tpu.service.datastore import DatastorePublisher

        def bad_transport(url, body):
            raise OSError("connection refused")

        pub = DatastorePublisher(url="http://ds.test/",
                                 transport=bad_transport)
        assert pub.publish_json({"histograms": []}) is False
        assert pub.json_failures == 1
        pub2 = DatastorePublisher(url="http://ds.test/",
                                  transport=lambda u, b: 503)
        assert pub2.publish_json({"histograms": []}) is False
        assert pub2.json_failures == 1
        pub3 = DatastorePublisher(url="http://ds.test/",
                                  transport=lambda u, b: 200)
        assert pub3.publish_json({"histograms": []}) is True
        assert pub3.json_failures == 0

    def test_next_segment_chaining(self, app, svc_tiles):
        payload = _probe_payload(svc_tiles, seed=13, num_points=200)
        _, body = wsgi_call(app, "POST", "/report", payload)
        reports = body["reports"]
        if len(reports) >= 2:
            # At least one consecutive pair should be chained.
            assert any(r["next_id"] is not None for r in reports[:-1])
            for a, b in zip(reports, reports[1:]):
                if a["next_id"] is not None:
                    assert a["next_id"] == b["id"]

    def test_report_many_batches(self, app, svc_tiles):
        payloads = [_probe_payload(svc_tiles, seed=20 + i) for i in range(3)]
        status, body = wsgi_call(app, "POST", "/report_many",
                                 {"traces": payloads})
        assert status == 200
        assert len(body["results"]) == 3
        assert all(len(r["segments"]) > 0 for r in body["results"])

    @pytest.mark.parametrize("method,path,payload,want", [
        ("POST", "/report", None, 400),                       # empty body
        ("POST", "/report", {"trace": [{"lat": 0, "lon": 0}]}, 400),  # no uuid
        ("POST", "/report", {"uuid": "v", "trace": []}, 400),  # empty trace
        ("POST", "/report", {"uuid": "v", "trace": [{"lat": 1}]}, 400),
        ("POST", "/report", {"uuid": "v", "trace": [
            {"lat": 1, "lon": 1, "accuracy": "25m"}]}, 400),  # non-numeric
        ("POST", "/report", {"uuid": "v", "trace": [
            {"lat": 1, "lon": 1, "accuracy": float("nan")}]}, 400),  # NaN
        ("POST", "/report", {"uuid": "v", "trace": [
            {"lat": 1, "lon": 1, "accuracy": -3.0}]}, 400),   # negative
        ("GET", "/report", None, 405),
        ("POST", "/nope", {"x": 1}, 404),
    ])
    def test_bad_requests(self, app, method, path, payload, want):
        status, _ = wsgi_call(app, method, path, payload)
        assert status == want


class TestCacheContinuation:
    def test_split_trace_completes_segments(self, svc_tiles):
        """A traversal split across two /report calls is completed by the
        per-uuid cache (the reference's partial-trace behavior)."""
        cfg = Config()
        app_split = make_app(svc_tiles, cfg)
        app_whole = make_app(svc_tiles, cfg)

        payload = _probe_payload(svc_tiles, seed=31, num_points=160)
        pts = payload["trace"]
        half = len(pts) // 2

        whole = wsgi_call(app_whole, "POST", "/report", payload)[1]
        first = wsgi_call(app_split, "POST", "/report",
                          {"uuid": "v", "trace": pts[:half]})[1]
        second = wsgi_call(app_split, "POST", "/report",
                           {"uuid": "v", "trace": pts[half:]})[1]

        ids_whole = [r["id"] for r in whole["reports"]]
        ids_split = [r["id"] for r in first["reports"]] + [
            r["id"] for r in second["reports"]]
        # The split run must recover the segments a whole-trace run reports
        # (duplicates possible at the seam; missing segments are the failure).
        assert set(ids_whole) <= set(ids_split)

    def test_duplicate_uuid_in_one_batch(self, svc_tiles):
        """Two halves of one trace under the same uuid inside a single
        /report_many batch behave as if they arrived sequentially."""
        app = make_app(svc_tiles, Config())
        payload = _probe_payload(svc_tiles, seed=31, num_points=160)
        pts = payload["trace"]
        half = len(pts) // 2
        whole = app.report_one(payload)
        app2 = make_app(svc_tiles, Config())
        results = app2.report_many([
            {"uuid": "v", "trace": pts[:half]},
            {"uuid": "v", "trace": pts[half:]},
        ])
        ids_whole = {r["id"] for r in whole["reports"]}
        ids_batch = {r["id"] for res in results for r in res["reports"]}
        assert ids_whole <= ids_batch

    def test_cache_is_dropped_after_completion(self, svc_tiles):
        app = make_app(svc_tiles, Config())
        payload = _probe_payload(svc_tiles, seed=32)
        wsgi_call(app, "POST", "/report", payload)
        # Tail at or after the last complete segment is retained, bounded.
        assert len(app.cache) <= 1


class TestPartialTraceCache:
    def test_merge_dedupes_and_sorts(self):
        c = PartialTraceCache(ttl=60)
        c.retain("v", [{"lat": 0, "lon": 0, "time": 1.0},
                       {"lat": 0, "lon": 0, "time": 2.0}], from_time=0.0)
        merged = c.merge("v", [{"lat": 0, "lon": 0, "time": 2.0},
                               {"lat": 0, "lon": 0, "time": 3.0}])
        assert [p["time"] for p in merged] == [1.0, 2.0, 3.0]

    def test_ttl_eviction_with_fake_clock(self):
        now = [0.0]
        c = PartialTraceCache(ttl=10.0, clock=lambda: now[0])
        c.retain("v", [{"lat": 0, "lon": 0, "time": 1.0}], from_time=0.0)
        assert len(c) == 1
        now[0] = 11.0
        assert c.merge("v", []) == []          # evicted on access
        assert len(c) == 0

    def test_lru_bound(self):
        c = PartialTraceCache(ttl=1e9, max_uuids=2)
        for i in range(4):
            c.retain(f"v{i}", [{"lat": 0, "lon": 0, "time": 1.0}], 0.0)
        assert len(c) == 2
        assert c.merge("v3", []) != []
        assert c.merge("v0", []) == []


class TestReportBuilder:
    def _rec(self, sid, t0, t1, internal=False, length=100.0):
        return SegmentRecord(segment_id=sid, way_ids=[1], start_time=t0,
                             end_time=t1, length=length, internal=internal)

    def test_filters_partial_and_internal(self):
        recs = [
            self._rec(1, 0.0, 10.0),
            self._rec(2, 10.0, -1.0),          # exit unobserved → dropped
            self._rec(-1, 3.0, 4.0, internal=True),
            self._rec(3, -1.0, 20.0),          # entry unobserved → dropped
        ]
        reports = build_reports(recs)
        assert [r.segment_id for r in reports] == [1]

    def test_min_length(self):
        recs = [self._rec(1, 0.0, 10.0, length=5.0)]
        assert build_reports(recs, min_length=10.0) == []
        assert len(build_reports(recs, min_length=1.0)) == 1

    def test_chaining_across_internal_connector(self):
        """Internal connector edges must NOT break the segment pair — that is
        what the internal flag exists for (turn channels between segments)."""
        recs = [self._rec(1, 0.0, 10.0),
                self._rec(-1, 10.0, 12.0, internal=True),
                self._rec(2, 12.0, 20.0)]
        reports = build_reports(recs)
        assert reports[0].next_segment_id == 2

    def test_partial_record_breaks_chain(self):
        recs = [self._rec(1, 0.0, 10.0),
                self._rec(2, 10.0, -1.0),          # in-progress, unobserved exit
                self._rec(3, 10.0, 20.0)]
        reports = build_reports(recs)
        assert reports[0].next_segment_id is None

    def test_chaining_requires_contiguity(self):
        recs = [self._rec(1, 0.0, 10.0), self._rec(2, 10.0, 20.0),
                self._rec(3, 25.0, 30.0)]     # gap 20→25 breaks the chain
        reports = build_reports(recs)
        assert reports[0].next_segment_id == 2
        assert reports[1].next_segment_id is None
        assert reports[2].next_segment_id is None


class TestRequestCombining:
    def test_concurrent_requests_combine_and_stay_scoped(self, svc_tiles):
        import threading

        cfg = Config(matcher_backend="jax")
        a = make_app(svc_tiles, cfg, transport=lambda u, b: 200)
        n = 12
        payloads = [_probe_payload(svc_tiles, seed=40 + i, num_points=40)
                    for i in range(n)]
        for i, p in enumerate(payloads):
            p["uuid"] = f"veh-{i}"
        solo_app = make_app(svc_tiles, cfg, transport=lambda u, b: 200)
        expected = [solo_app.report_one(p) for p in payloads]

        results: list = [None] * n
        errors: list = []

        def worker(i):
            try:
                results[i] = a.report_one(payloads[i])
            except Exception as e:     # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors
        for i in range(n):
            got = [s["segment_id"] for s in results[i]["segments"]]
            want = [s["segment_id"] for s in expected[i]["segments"]]
            assert got == want, f"request {i}"
        # at least some combining happened (n submissions, fewer batches)
        assert a.stats["batched_submissions"] == n
        assert 1 <= a.stats["batches"] <= n

    def test_bad_payload_rejected_without_poisoning_batch(self, svc_tiles):
        a = make_app(svc_tiles, Config(matcher_backend="jax"),
                     transport=lambda u, b: 200)
        import pytest as _pytest

        from reporter_tpu.service.app import BadRequest

        with _pytest.raises(BadRequest):
            a.report_one({"uuid": "x", "trace": "nope"})
        # service still healthy afterwards
        ok = a.report_one(_probe_payload(svc_tiles, seed=77, num_points=30))
        assert "segments" in ok


class TestMetroRouter:
    @pytest.fixture(scope="class")
    def router(self):
        from reporter_tpu.service.router import make_router

        # two tiny metros at well-separated centers
        a = compile_network(generate_city("tiny"),
                            CompilerParams(reach_radius=500.0,
                                           osmlr_max_length=200.0))
        b_net = generate_city("nyc", nx=8, ny=8)
        b = compile_network(b_net, CompilerParams(reach_radius=500.0,
                                                  osmlr_max_length=200.0))
        r = make_router([a, b], Config(matcher_backend="jax"),
                        transport=lambda u, body: 200)
        r.test_tiles = {"a": a, "b": b}
        return r

    def test_routes_by_location(self, router):
        a, b = router.test_tiles["a"], router.test_tiles["b"]
        pa = _probe_payload(a, seed=5)
        pb = _probe_payload(b, seed=6)
        out_a = router.report_one(pa)
        out_b = router.report_one(pb)
        assert out_a["metro"] == a.name
        assert out_b["metro"] == b.name
        assert out_a["segments"] or out_b["segments"]

    def test_explicit_metro_field_and_batch(self, router):
        a, b = router.test_tiles["a"], router.test_tiles["b"]
        pa = _probe_payload(a, seed=7)
        pa["metro"] = a.name
        pb = _probe_payload(b, seed=8)
        outs = router.report_many([pb, pa, pb])
        assert [o["metro"] for o in outs] == [b.name, a.name, b.name]

    def test_unroutable_and_unknown(self, router):
        from reporter_tpu.service.app import BadRequest

        with pytest.raises(BadRequest):
            router.report_one({"uuid": "x", "trace": [
                {"lat": -45.0, "lon": 100.0}]})
        with pytest.raises(BadRequest):
            router.report_one({"uuid": "x", "metro": "atlantis",
                               "trace": [{"lat": 0, "lon": 0}]})

    def test_wsgi_endpoints(self, router):
        a = router.test_tiles["a"]
        status, body = wsgi_call(router, "GET", "/health")
        assert status == 200 and set(body["metros"]) == set(router.apps)
        status, body = wsgi_call(router, "POST", "/report",
                                 _probe_payload(a, seed=9))
        assert status == 200 and body["metro"] == a.name
        status, body = wsgi_call(router, "GET", "/stats")
        assert status == 200 and set(body) == set(router.apps)


def test_router_nested_metros_route_most_specific():
    """Overlapping/nested bboxes must route to the smallest containing
    metro, independent of tileset list order."""
    from reporter_tpu.service.router import make_router

    # big: 16x16 city; small: 6x6 city at the same center → nested bboxes
    big = compile_network(generate_city("tiny", nx=16, ny=16, seed=2),
                          CompilerParams(reach_radius=400.0))
    big.name = "big"
    small = compile_network(generate_city("tiny", nx=6, ny=6, seed=3),
                            CompilerParams(reach_radius=400.0))
    small.name = "small"

    probe = synthesize_probe(small, seed=4, num_points=20, gps_sigma=3.0)
    payload = probe.to_report_json()

    for order in ([big, small], [small, big]):
        r = make_router(order, Config(matcher_backend="jax"),
                        transport=lambda u, b: 200)
        assert r.route(payload) == "small", [ts.name for ts in order]


def test_config_json_roundtrip_all_fields():
    from reporter_tpu.config import (Config, MatcherParams, ServiceConfig,
                                     StreamingConfig)

    c = Config(
        matcher=MatcherParams(candidate_backend="grid", search_radius=42.0,
                              max_candidates=6),
        service=ServiceConfig(datastore_url="http://x/", mode="bike"),
        streaming=StreamingConfig(hist_flush_interval=7.0,
                                  num_partitions=3),
        matcher_backend="reference_cpu")
    assert Config.from_json(c.to_json()) == c


def test_server_backlog_holds_a_fleet_of_connects():
    """128 clients connecting at once, none served yet (nothing accepts):
    every connect completes from the listen queue, none waits on the
    kernel's 1 s SYN retransmit. socketserver's default backlog of 5
    would leave all but six of them unanswered."""
    import select
    import socket
    import time

    from reporter_tpu.service.server import serve

    def wsgi(environ, start_response):          # never reached: no accept
        start_response("200 OK", [])
        return [b""]

    server = serve(wsgi, "127.0.0.1", 0)
    socks = []
    try:
        assert server.request_queue_size >= 128
        addr = server.server_address
        for _ in range(128):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setblocking(False)
            s.connect_ex(addr)
            socks.append(s)
        pending = {s.fileno(): s for s in socks}
        done = 0
        deadline = time.monotonic() + 0.9       # under the 1 s retransmit
        while pending and time.monotonic() < deadline:
            _, ready, _ = select.select([], list(pending), [],
                                        max(0.0, deadline - time.monotonic()))
            for fd in ready:
                s = pending.pop(fd)
                assert s.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR) == 0
                done += 1
        assert done == 128, f"{done} of 128 connects completed"
    finally:
        for s in socks:
            s.close()
        server.server_close()
