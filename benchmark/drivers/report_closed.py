"""Closed-loop ``POST /report`` driver: a fixed fleet of clients against the
product's threaded WSGI server (``service/server.py``) on loopback, each
sending its next request when the last is answered. The clients run in a
load generator of their own (``traffic/closedgen.py``) that never imports
JAX and hosts the datastore sink, so the publish path runs.

Set-up, shape warm-up, the sample of compared answers and shutdown are
the open-loop driver's (``drivers/report.py``); ``probes_per_s`` is the
probes of the requests answered 200 inside the window over its seconds."""

from __future__ import annotations

import atexit
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import build
from benchmark.drivers import report
from benchmark.traffic import closedgen


# the registry's per-stage series along a request's path, read into the
# window's diagnostics: a request's wall time inside the app (WSGI entry
# to response), its parse, its queue age, and per batch the match, the
# report build and the publish
SPLIT = ("request_seconds", "http_parse_seconds", "sched_queue_age_seconds",
         "batch_match_seconds", "report_build_seconds", "publish_seconds")


def split(before: dict, after: dict) -> dict:
    """Count and mean milliseconds of each ``SPLIT`` series over the
    window (None where the program has no such series)."""
    out = {}
    for name in SPLIT:
        new, old = after["counters"], before["counters"]
        n = new.get(name + "_count", 0.0) - old.get(name + "_count", 0.0)
        s = new.get(name + "_total", 0.0) - old.get(name + "_total", 0.0)
        out[name] = [int(n), 1000.0 * s / n if n else None]
    return out


def sample(cell, g, seed: int, seconds: float) -> list:
    """What a run of this cell compares when every pool item is answered:
    (xy in the graph's frame, times) of each picked request."""
    tr = cell.traffic
    mix = {**tr, "probes": cell.config["probes"]}
    xy, lens, _ = closedgen.pool(g, mix, seed)
    cand = report.candidates(seed, lens, tr["sample_traces"])
    return [(xy[i, :lens[i]], np.arange(lens[i], dtype=np.float64))
            for i in report.pick(cand, set(cand), tr["sample_traces"])]


class Run(report.Run):
    def __init__(self, cell, seed: int, devs, timer, seconds: float):
        from reporter_tpu.matcher.api import Trace
        from reporter_tpu.service.app import make_app
        from reporter_tpu.service.server import serve

        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.mix = {**tr, "probes": cfg["probes"]}
        with timer("tile"):
            self.city = build.city(cfg)
            g = build.graph(cfg, self.city)
            graph_path = build.graph_path(cfg, self.city)
        # the generator builds its requests while this process loads
        self.gen = subprocess.Popen(
            [sys.executable, os.path.join(build.BENCH, "traffic",
                                          "closedgen.py"),
             graph_path, str(seed), repr(seconds), json.dumps(self.mix)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        atexit.register(self._reap)
        sink_port = json.loads(self.gen.stdout.readline())["sink_port"]
        with timer("tile"):
            ts = build.tileset(cfg, self.city)
        with timer("traffic"):
            self.xy, self.lens, _ = closedgen.pool(g, self.mix, seed)
        with timer("staging+autotune"):
            self.app = make_app(ts, build.matcher_config(
                cfg, datastore_url=f"http://127.0.0.1:{sink_port}/",
                **tr["service"]))
        with timer("warmup"):
            self._warm(Trace)
        self.server = serve(self.app, "127.0.0.1", 0)
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}/report"
        with timer("warmup"):
            self._warm_http()
        with timer("traffic"):
            if json.loads(self.gen.stdout.readline()) != {"ready": True}:
                raise RuntimeError("load generator failed")
        self.metrics = self.app.matcher.metrics

    def window(self, seconds: float, tracer) -> dict:
        tr = self.cell.traffic
        self.cand = report.candidates(self.seed, self.lens,
                                      tr["sample_traces"])
        before = self.metrics.export()
        t0 = time.perf_counter()
        self.gen.stdin.write(json.dumps({"url": self.url,
                                         "sample": self.cand}) + "\n")
        self.gen.stdin.flush()
        tracer.start()
        time.sleep(min(tracer.SECONDS, seconds) if tracer.dir else 0.0)
        tracer.stop()
        res = json.loads(self.gen.stdout.readline())
        elapsed = time.perf_counter() - t0
        self.gen.wait(timeout=30)
        after = self.metrics.export()
        self.res = res
        count = closedgen.tally(res, self.lens, seconds)
        status = np.asarray(res["status"])
        ok = status == 200
        done = np.asarray([np.nan if d is None else d for d in res["done"]])
        lat = 1000.0 * (done[ok] - np.asarray(res["sent"])[ok])
        codes, counts = np.unique(status, return_counts=True)
        diag = {**count,
                "requests_per_s": count["answered_in_window"] / seconds,
                "p50_ms": float(np.percentile(lat, 50)) if lat.size else None,
                "p95_ms": float(np.percentile(lat, 95)) if lat.size else None,
                "status": dict(zip(codes.tolist(), counts.tolist())),
                "clients_alive": res["clients_alive"],
                "split_count_ms": split(before, after),
                "sink_rows": res["sink_rows"],
                "publish_mismatch": res["publish_mismatch"],
                "elapsed_s": elapsed}
        return {
            "attempted": count["sends"], "failed": count["failed"],
            "seconds": seconds, "elapsed": elapsed,
            "end_to_end": {"probes_per_s": count["probes"] / seconds},
            "diag": diag,
            "registry_before": before, "registry_after": after,
        }
