"""Closed-loop batch driver: back-to-back ``SegmentMatcher.match_many``
calls of full device batches, as a historical reprocessing job makes them.

One caller, one call at a time. On ``chips`` > 1 the matcher is the
data-parallel mesh matcher (``SegmentMatcher(ts, mesh=...)``). The calls
cycle through a pool of distinct batches drawn from the seed (the matcher
keeps no state between ``match_many`` calls, so a repeated batch costs
what a fresh one does); the rate counts every probe of every call that
finished in the window, over the window's whole length.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import build
from benchmark.traffic import fleet


class Run:
    def __init__(self, cell, seed: int, devs, timer, seconds: float):
        import jax

        from reporter_tpu.matcher.api import SegmentMatcher, Trace

        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        with timer("tile"):
            self.city = build.city(cfg)
            g = build.graph(cfg, self.city)
            ts = build.tileset(cfg, self.city)
        if not np.allclose(ts.meta.origin_lonlat, g.origin, rtol=0,
                           atol=1e-12):
            raise RuntimeError("the tile's frame is not the graph's")
        B, P = tr["batch_traces"], tr["pool_batches"]
        with timer("traffic"):
            xy, lens = fleet.synthesize(
                g, seed, B * (P + 1), min_points=tr["min_points"],
                max_points=tr["max_points"], **cfg["probes"])
            times = np.arange(tr["max_points"], dtype=np.float64)
            self.xy, self.lens, self.times = xy, lens, times
            traces = [Trace(uuid=f"{seed}-{i}",
                            xy=xy[i, :lens[i]].astype(np.float32),
                            times=times[:lens[i]]) for i in range(len(xy))]
            self.batches = [traces[k * B:(k + 1) * B] for k in range(P + 1)]
        self.B = B
        mesh = None
        if len(devs) > 1:
            from reporter_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(dp=len(devs), devices=list(devs))
        with timer("staging+autotune"):
            self.matcher = SegmentMatcher(ts, build.matcher_config(cfg),
                                          mesh=mesh)
        with timer("warmup"):
            # the pool's extra batch: compiles the one shape the window
            # uses, twice so that lazy first-call work is done too
            for _ in range(2):
                self.matcher.match_many(self.batches[P])
        self.metrics = self.matcher.metrics
        self.calls: list = []
        self._jax = jax

    def window(self, seconds: float, tracer) -> dict:
        m, P = self.matcher, len(self.batches) - 1
        before = self.metrics.export()["counters"]
        annotate = self._jax.profiler.TraceAnnotation
        probes = 0
        tracer.start()
        t0 = time.perf_counter()
        k = 0
        while True:
            batch = self.batches[k % P]
            with annotate("match_many"):
                out = m.match_many(batch)
            self.calls.append((k % P, out))
            probes += sum(len(t.xy) for t in batch)
            k += 1
            elapsed = time.perf_counter() - t0
            if elapsed >= tracer.SECONDS:
                tracer.stop()
            if elapsed >= seconds:
                break
        after = self.metrics.export()["counters"]
        return {"attempted": k * len(self.batches[0]), "failed": 0,
                "calls": k, "seconds": elapsed,
                "end_to_end": {"probes_per_s": probes / elapsed},
                "counters": {key: after.get(key, 0.0) - before.get(key, 0.0)
                             for key in after}}

    def close(self) -> None:
        """Drop the program's state before the reference runs."""
        self.matcher = self.batches = None
        gc.collect()

    def answers(self, seed: int):
        """A sample, drawn from the seed, of the traces the window
        matched: (xy in the graph's frame, times, records as the program
        returned them); no count here can fail but by an exception."""
        out = []
        for c, i in picks(seed, len(self.calls), self.B,
                          self.cell.traffic["sample_traces"]):
            k, batch = self.calls[c]
            if len(batch) != self.B:
                raise RuntimeError(f"{len(batch)} answers for {self.B} traces")
            j = k * self.B + i
            got = [(r.segment_id, r.length) for r in batch[i]]
            out.append((self.xy[j, :self.lens[j]],
                        self.times[:self.lens[j]], got))
        return out, {}


def picks(seed: int, calls: int, B: int, n: int) -> list:
    """(call, row) of the sampled traces, drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    p = rng.choice(calls * B, size=min(n, calls * B), replace=False)
    return [divmod(int(x), B) for x in sorted(p)]


def sample(cell, g, seed: int, seconds: float) -> list:
    """What a run of this cell compares, taking one pass over the pool of
    batches for the window's calls: (xy in the graph's frame, times)."""
    tr, cfg = cell.traffic, cell.config
    B, P = tr["batch_traces"], tr["pool_batches"]
    xy, lens = fleet.synthesize(
        g, seed, B * (P + 1), min_points=tr["min_points"],
        max_points=tr["max_points"], **cfg["probes"])
    return [(xy[j, :lens[j]], np.arange(lens[j], dtype=np.float64))
            for j in (c * B + i for c, i in picks(seed, P, B,
                                                  tr["sample_traces"]))]
