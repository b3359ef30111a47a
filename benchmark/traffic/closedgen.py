"""Closed-loop HTTP load generator and datastore sink, run as its own process.

    python benchmark/traffic/closedgen.py <graph.npz> <seed> <seconds> <mix json>

A fixed fleet of ``clients`` threads, each posting one vehicle's trace to
``/report``, waiting for the answer and then posting the next, for
``seconds``: the served capacity at that concurrency, by Little's law
clients x mean points per request / mean latency. Sends draw their trace
from a pool of ``pool_traces`` distinct vehicles (``pool``) in an order
drawn from the seed, and every send carries a uuid of its own, so no
cached tail merges two unrelated routes.

Like ``loadgen.py`` it never imports JAX, builds its requests itself from
the seed while the serving process loads, and hosts the datastore sink.
Protocol, one JSON object per line:

  -> {"sink_port": p}                  the datastore sink listens
  -> {"ready": true}                   requests built
  <- {"url": ..., "sample": [i, ...]}  where to send, whose answers to keep
  -> {"done": true, ...results}        after the last answer or the drain

A request in flight when the window closes is drained (answered, checked,
published) but is not the window's work; ``tally`` counts only what was
answered inside the window.
"""

from __future__ import annotations

import http.client
import itertools
import json
import os
import sys
import threading
import time
from collections import Counter
from urllib.parse import urlsplit

import numpy as np

HEAD = b'{"uuid":"'


def pool(g, mix: dict, seed: int):
    """``pool_traces`` vehicles' traces, one per request, with lengths
    evenly over [min_points, max_points]: the same lengths for every
    seed, on routes and in a send order of the seed's own. Returns (xy
    [n, T, 2], lengths [n], order [n]: send k draws ``order[k % n]``)."""
    from benchmark.traffic import fleet

    rng = np.random.default_rng([seed, 3])
    n = int(mix["pool_traces"])
    lengths = np.round(np.linspace(mix["min_points"], mix["max_points"],
                                   n)).astype(np.int64)
    rng.shuffle(lengths)
    xy, lengths = fleet.synthesize(
        g, seed, n, min_points=mix["min_points"],
        max_points=mix["max_points"], lengths=lengths, **mix["probes"])
    return xy, lengths, rng.permutation(n)


def body_tail(raw: bytes) -> bytes:
    """``loadgen.body("", ...)`` without its head: a send's body is
    ``HEAD + uuid + tail``."""
    if not raw.startswith(HEAD + b'"'):
        raise ValueError("a body starts with its uuid")
    return raw[len(HEAD):]


def run_closed_loop(url: str, tails: list, order, clients: int,
                    seconds: float, drain_s: float, prefix: str,
                    clock=time.perf_counter, post=None) -> dict:
    """``clients`` threads send back to back until ``seconds`` after the
    start; send k posts pool item ``order[k % len(order)]`` as uuid
    ``<prefix>-<k>``. Returns, per send in order of k: ``item``, ``sent``
    and ``done`` (seconds after the start; ``done`` None for a send with
    no answer by the end of the drain) and ``status``; and every answered
    body by k (``answers``)."""
    n = len(order)
    records: dict = {}
    answers: dict = {}
    serial = itertools.count()
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
            t = clock() - start
            if t >= seconds:
                return
            k = next(serial)
            item = int(order[k % n])
            rec = records[k] = [item, t, None, 0]
            try:
                st, raw = send(HEAD + f"{prefix}-{k}".encode() + tails[item])
            except OSError:
                st, raw = -1, b""
            rec[2], rec[3] = clock() - start, st
            if st == 200:
                answers[k] = raw

    pool_threads = [threading.Thread(target=client, daemon=True)
                    for _ in range(clients)]
    start = clock()
    for t in pool_threads:
        t.start()
    deadline = time.monotonic() + seconds + drain_s
    for t in pool_threads:
        t.join(max(0.0, deadline - time.monotonic()))
    recs = [records[k] for k in sorted(records)]
    return {"item": [r[0] for r in recs], "sent": [r[1] for r in recs],
            "done": [r[2] for r in recs], "status": [r[3] for r in recs],
            "answers": {k: answers[k] for k in sorted(answers)},
            "clients_alive": sum(t.is_alive() for t in pool_threads)}


def tally(res: dict, lengths, seconds: float) -> dict:
    """The window's work: probes of the sends answered 200 inside
    ``seconds``; sends answered later (drained) are checked but not
    counted; a send with any other status or no answer failed."""
    status = np.asarray(res["status"])
    done = np.asarray([np.inf if d is None else d for d in res["done"]])
    items = np.asarray(res["item"], dtype=np.int64)
    ok = status == 200
    inside = ok & (done <= seconds)
    return {"sends": int(len(status)), "answered": int(ok.sum()),
            "answered_in_window": int(inside.sum()),
            "drained": int((ok & ~inside).sum()),
            "failed": int((~ok).sum()),
            "probes": int(np.asarray(lengths)[items[inside]].sum())}


def main(argv) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.traffic import loadgen
    from benchmark.traffic.city import xy_to_lonlat
    from benchmark.traffic.graph import Graph

    graph_path, seed, seconds, mix = argv
    seed, seconds, mix = int(seed), float(seconds), json.loads(mix)
    sink = loadgen.Sink()
    print(json.dumps({"sink_port": sink.port}), flush=True)
    g = Graph.load(graph_path)
    xy, lengths, order = pool(g, mix, seed)
    dt = mix["probes"].get("dt", 1.0)
    tails = [body_tail(loadgen.body("", xy_to_lonlat(xy[i, :n], g.origin),
                                    np.arange(n) * dt))
             for i, n in enumerate(lengths)]
    print(json.dumps({"ready": True}), flush=True)
    cmd = json.loads(sys.stdin.readline())
    sink.reset()               # the warm-up's reports are not the window's
    res = run_closed_loop(cmd["url"], tails, order, mix["clients"], seconds,
                          mix["drain_s"], str(seed))
    time.sleep(0.5)            # a POST still in flight for a late answer
    with sink._lock:
        rows, res["sink_posts"] = Counter(sink.rows), sink.posts
    sink.close()
    answers = {k: raw.decode() for k, raw in res.pop("answers").items()}
    res["sink_rows"] = sum(rows.values())
    res["publish_mismatch"] = loadgen.publish_mismatch(rows, answers)
    # the first answer of each sampled pool item, for the comparison
    sample, first = set(cmd["sample"]), {}
    for k, raw in answers.items():
        item = res["item"][k]
        if item in sample and item not in first:
            first[item] = raw
    res["answers"] = first
    print(json.dumps({"done": True, **res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
