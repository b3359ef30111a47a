"""Open-loop HTTP load generator and datastore sink, run as its own process.

    python benchmark/traffic/loadgen.py <graph.npz> <seed> <seconds> <mix json>

It never imports JAX, so it runs beside the process that holds the chip,
and it makes its requests itself from the seed (``schedule``), while that
process loads and warms up. Protocol, one JSON object per line:

  -> {"sink_port": p}                  the datastore sink listens
  -> {"ready": true}                   requests built
  <- {"url": ..., "sample": [i, ...]}  where to send, which answers to keep
  -> {"done": true, ...results}        after the last answer or the drain

The sink keeps every report row it receives from the start of the window;
``publish_mismatch`` counts the rows by which the sink and the answered
requests' ``reports`` differ (the app publishes synchronously before it
answers, so every answered report is at the sink by then).

Each request is sent when it is due, by a dispatcher thread that hands it
to a pool of client threads, and is timed from its DUE time: a generator
that falls behind shows as latency and as ``sent - due`` lateness, never
as a quiet server.
"""

from __future__ import annotations

import http.client
import json
from collections import Counter
import os
import queue
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlsplit

import numpy as np


class Sink:
    """Datastore stand-in: accepts report POSTs and keeps their rows."""

    def __init__(self):
        self.posts = 0
        self.rows: Counter = Counter()
        self._lock = threading.Lock()
        sink = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                raw = self.rfile.read(n)
                try:
                    rows = json.loads(raw or b"{}").get("reports", ())
                except (ValueError, AttributeError):
                    rows = [{"unparsed": raw.decode(errors="replace")}]
                with sink._lock:
                    sink.posts += 1
                    sink.rows.update(canonical(r) for r in rows)
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *args):
                pass

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.server.daemon_threads = True
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()

    @property
    def port(self) -> int:
        return self.server.server_address[1]

    def reset(self) -> None:
        with self._lock:
            self.posts = 0
            self.rows = Counter()

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()


def canonical(row) -> str:
    return json.dumps(row, sort_keys=True)


def publish_mismatch(sink_rows: Counter, answers: dict) -> int:
    """Rows by which the sink and the answered requests' reports differ."""
    want: Counter = Counter()
    for raw in answers.values():
        want.update(canonical(r) for r in json.loads(raw).get("reports", ()))
    return sum(((sink_rows - want) + (want - sink_rows)).values())


def run_open_loop(url: str, dues: list, bodies: list, sample: set,
                  threads: int, drain_s: float, clock=time.perf_counter,
                  post=None) -> dict:
    """Send ``bodies[i]`` at ``start + dues[i]``; returns per-request
    ``due``, ``sent``, ``done`` (seconds after start; ``done`` None for a
    request with no answer), ``status`` and every answered body
    (``answers``; ``sample`` marks those the caller will compare)."""
    n = len(bodies)
    sent = [None] * n
    done = [None] * n
    status = [0] * n
    answers: dict = {}
    work: queue.Queue = queue.Queue()
    parts = urlsplit(url)

    def default_post(body: bytes):
        conn = http.client.HTTPConnection(parts.hostname, parts.port,
                                          timeout=drain_s + 60.0)
        try:
            conn.request("POST", parts.path or "/", body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, r.read()
        finally:
            conn.close()

    send = post or default_post

    def client():
        while True:
            i = work.get()
            if i is None:
                return
            sent[i] = clock() - start
            try:
                st, raw = send(bodies[i])
            except OSError:
                st, raw = -1, b""
            status[i] = st
            done[i] = clock() - start
            if st == 200:
                answers[i] = raw

    pool = [threading.Thread(target=client, daemon=True)
            for _ in range(threads)]
    for t in pool:
        t.start()
    start = clock()
    for i in range(n):
        wait = start + dues[i] - clock()
        if wait > 0:
            time.sleep(wait)
        work.put(i)
    for _ in pool:
        work.put(None)
    deadline = time.monotonic() + drain_s
    for t in pool:
        t.join(max(0.0, deadline - time.monotonic()))
    answers = {i: raw.decode() for i, raw in answers.items()}
    return {"due": list(dues), "sent": sent, "done": done,
            "status": status, "answers": answers,
            "sampled": {i: answers[i] for i in sample if i in answers},
            "clients_alive": sum(t.is_alive() for t in pool)}


def schedule(g, mix: dict, seed: int, seconds: float):
    """Poisson arrivals at ``rate_per_s`` over ``seconds``, one vehicle's
    trace per request. Every seed gets the same request lengths (evenly
    over [min_points, max_points]) and the same inter-arrival gaps (the
    exponential's quantiles), in an order of its own, so the seed changes
    the routes and the order, not the amount of work.
    Returns (dues [n] s, xy [n, T, 2], lengths [n])."""
    from benchmark.traffic import fleet

    rng = np.random.default_rng([seed, 2])
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    lengths = np.round(np.linspace(mix["min_points"], mix["max_points"],
                                   n)).astype(np.int64)
    rng.shuffle(lengths)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    xy, lengths = fleet.synthesize(
        g, seed, n, min_points=mix["min_points"],
        max_points=mix["max_points"], lengths=lengths, **mix["probes"])
    return dues, xy, lengths


def body(uuid: str, lonlat, times) -> bytes:
    pts = ",".join('{"lat":%r,"lon":%r,"time":%r}' % (la, lo, t)
                   for (lo, la), t in zip(lonlat.tolist(), times.tolist()))
    return ('{"uuid":"%s","trace":[%s]}' % (uuid, pts)).encode()


def main(argv) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.traffic.city import xy_to_lonlat
    from benchmark.traffic.graph import Graph

    graph_path, seed, seconds, mix = argv
    seed, seconds, mix = int(seed), float(seconds), json.loads(mix)
    sink = Sink()
    print(json.dumps({"sink_port": sink.port}), flush=True)
    g = Graph.load(graph_path)
    dues, xy, lengths = schedule(g, mix, seed, seconds)
    dt = mix["probes"].get("dt", 1.0)
    bodies = [body(f"{seed}-{i}", xy_to_lonlat(xy[i, :n], g.origin),
                   np.arange(n) * dt) for i, n in enumerate(lengths)]
    print(json.dumps({"ready": True}), flush=True)
    cmd = json.loads(sys.stdin.readline())
    sink.reset()               # the warm-up's reports are not the window's
    res = run_open_loop(cmd["url"], dues.tolist(), bodies,
                        set(cmd["sample"]), mix["client_threads"],
                        mix["drain_s"])
    time.sleep(0.5)            # a POST still in flight for a late answer
    with sink._lock:
        rows, res["sink_posts"] = Counter(sink.rows), sink.posts
    sink.close()
    answers = res.pop("answers")
    res["sink_rows"] = sum(rows.values())
    res["publish_mismatch"] = publish_mismatch(rows, answers)
    res["answers"] = res.pop("sampled")
    print(json.dumps({"done": True, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
