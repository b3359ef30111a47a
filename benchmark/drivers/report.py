"""Open-loop ``POST /report`` driver: the product's threaded WSGI server
(``service/server.py``) on loopback, fed by the load generator
(``traffic/loadgen.py``) in a process of its own that never imports JAX
and also hosts the datastore sink, so the publish path runs.

Latency is from each request's due time to its answer; a request that
failed (an error status, a refused or reset connection), was shed or
never answered counts as the window plus the drain limit."""

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
from benchmark.traffic import loadgen


def candidates(seed: int, lens, sample_traces: int) -> list:
    """The requests whose answers a run compares, drawn from the seed
    before the window: twice the sample, and the longest request last."""
    rng = np.random.default_rng([seed, 1])
    n = len(lens)
    cand = rng.choice(n, size=min(n, 2 * sample_traces),
                      replace=False).tolist()
    return cand + [int(np.argmax(lens))]


def pick(cand: list, answered, sample_traces: int) -> list:
    """Up to ``sample_traces`` answered candidates, the longest first."""
    longest = cand[-1]
    keep = [i for i in cand[:-1] if i in answered and i != longest]
    return ([longest] if longest in answered else []) + keep[
        :sample_traces - 1]


def sample(cell, g, seed: int, seconds: float) -> list:
    """What a run of this cell compares when every request is answered:
    (xy in the graph's frame, times) of each picked request."""
    tr = cell.traffic
    mix = {**tr, "probes": cell.config["probes"]}
    _, xy, lens = loadgen.schedule(g, mix, seed, seconds)
    cand = candidates(seed, lens, tr["sample_traces"])
    return [(xy[i, :lens[i]], np.arange(lens[i], dtype=np.float64))
            for i in pick(cand, set(cand), tr["sample_traces"])]


class Run:
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
                                          "loadgen.py"),
             graph_path, str(seed), repr(seconds), json.dumps(self.mix)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        atexit.register(self._reap)
        sink_port = json.loads(self.gen.stdout.readline())["sink_port"]
        with timer("tile"):
            ts = build.tileset(cfg, self.city)
        with timer("traffic"):
            self.dues, self.xy, self.lens = loadgen.schedule(
                g, self.mix, seed, seconds)
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

    def _warm(self, Trace) -> None:
        """Compile every shape the traffic can reach: the scheduler pads
        each point bucket's group to a rung (asked of the scheduler
        itself), and a slice's infeed is i8 deltas unless some step is
        longer than the i8 range, then i16. Lengths at both ends of the
        mix and on each side of every power of two between them meet
        every bucket the mix can fill."""
        tr = self.cell.traffic
        m, sched = self.app.matcher, self.app.scheduler
        lo, hi = tr["min_points"], tr["max_points"]
        lengths = {lo, hi} | {n for k in range(16) for n in (2**k, 2**k + 1)
                              if lo <= n <= hi}
        base = self.xy[int(np.argmax(self.lens))]
        for n in sorted(lengths):
            for far in (False, True):
                xy = (base[:n] - base[0]) * 0.05 + base[0]
                if far:
                    xy[n // 2:] += 40.0
                t = Trace(uuid="warm", xy=xy.astype(np.float32),
                          times=np.arange(n, dtype=np.float64))
                rungs = {len(sched.pad_traces([t] * k)) for k in
                         range(1, tr["service"]["max_batch_traces"] + 1)}
                for r in sorted(rungs):
                    m.match_many([t] * r)

    def _warm_http(self) -> None:
        import urllib.request

        for i in range(4):
            n = int(self.lens[i])
            raw = loadgen.body(
                f"warm-{i}", build.xy_to_lonlat(self.xy[i, :n], self.city),
                np.arange(n, dtype=np.float64))
            req = urllib.request.Request(
                self.url, data=raw,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                r.read()

    def window(self, seconds: float, tracer) -> dict:
        tr = self.cell.traffic
        n = len(self.dues)
        cand = candidates(self.seed, self.lens, tr["sample_traces"])
        self.cand = cand
        before = self.metrics.export()
        t0 = time.perf_counter()
        self.gen.stdin.write(json.dumps({"url": self.url,
                                         "sample": cand}) + "\n")
        self.gen.stdin.flush()
        tracer.start()
        time.sleep(min(tracer.SECONDS, seconds) if tracer.dir else 0.0)
        tracer.stop()
        res = json.loads(self.gen.stdout.readline())
        elapsed = time.perf_counter() - t0
        self.gen.wait(timeout=30)
        after = self.metrics.export()
        self.res = res
        status = np.asarray(res["status"])
        ok = status == 200
        drain_ms = 1000.0 * (seconds + tr["drain_s"])
        lat = np.full(n, drain_ms)
        done = np.asarray([d if d is not None else np.nan
                           for d in res["done"]])
        lat[ok] = 1000.0 * (done[ok] - np.asarray(res["due"])[ok])
        sent = np.asarray([s if s is not None else np.nan
                           for s in res["sent"]])
        lag = 1000.0 * (sent - np.asarray(res["due"]))
        probes = int(self.lens[ok].sum())
        codes, counts = np.unique(status, return_counts=True)
        diag = {"p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "p99_ms": float(np.percentile(lat, 99)),
                "over_1s": int((lat > 1000.0).sum()),
                "lag_p95_ms": float(np.nanpercentile(lag, 95)),
                "lag_max_ms": float(np.nanmax(lag)),
                "status": dict(zip(codes.tolist(), counts.tolist())),
                "sink_rows": res["sink_rows"],
                "publish_mismatch": res["publish_mismatch"],
                "elapsed_s": elapsed}
        return {
            "attempted": n, "failed": int((~ok).sum()),
            "seconds": seconds, "elapsed": elapsed,
            "end_to_end": {"probes_per_s": probes / seconds,
                           "report_p50_ms": float(np.percentile(lat, 50)),
                           "report_p95_ms": float(np.percentile(lat, 95))},
            "gen_lag_ms": lag[~np.isnan(lag)].tolist(),
            "diag": diag,
            "registry_before": before, "registry_after": after,
        }

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=30)
        self.app.close()
        self.app = None
        self._reap()

    def _reap(self) -> None:
        """The generator never outlives this process, on any exit path."""
        if self.gen.poll() is None:
            self.gen.kill()
        self.gen.wait(timeout=30)

    def answers(self, seed: int):
        """Up to ``sample_traces`` of the answered requests, drawn from the
        seed before the window, the longest request among them; and the
        counts that must be 0: requests that failed (an error status, a
        shed, a refused or reset connection, or no answer by the end of
        the drain) and report rows the sink and the answers disagree on."""
        got = self.res["answers"]
        keep = pick(self.cand, {int(k) for k in got},
                    self.cell.traffic["sample_traces"])
        out = []
        for i in keep:
            segs = json.loads(got[str(i)])["segments"]
            n = int(self.lens[i])
            out.append((self.xy[i, :n], np.arange(n, dtype=np.float64),
                        [(s["segment_id"], s["length"]) for s in segs]))
        failed = int(np.sum(np.asarray(self.res["status"]) != 200))
        return out, {"failed_requests": failed,
                     "publish_mismatch": int(self.res["publish_mismatch"])}
