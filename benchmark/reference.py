"""Plain reference map matcher and the comparison that decides ``correct``.

An HMM matcher written from the published model (Newson & Krumm 2009, as
Valhalla's Meili parameterises it), over the benchmark's own graph
(``traffic/graph.py``). It imports nothing of the program and takes
nothing the program made:

- candidates: every edge within ``search_radius`` of a point, its closest
  projection, the ``max_candidates`` nearest (KD-tree over segment
  midpoints, exact point-segment distance);
- emission ``d^2 / (2 sigma_z^2)``; transition ``|route - gc| / beta``,
  where ``route`` is the exact shortest path (Dijkstra) and a route longer
  than ``max_route_distance_factor * gc + 10`` is not allowed; points
  farther apart than ``breakage_distance`` break the chain; a point within
  ``interpolation_distance`` of the last kept point does not vote and
  rides the matched path; a backward move on one edge within
  ``backward_slack`` costs nothing;
- records: the matched chain expanded along shortest paths, one record per
  maximal run of edges on one OSMLR segment, clipped to the observed span;
  ``length`` is the meters of the segment that the run covers.

``dtype`` is the precision of every coordinate, length and distance: the
reference runs in float64; the control runs it in bfloat16.

The comparison is the BASELINE fidelity measure: per trace, the share of
record meters whose segment id the two sides do not both report,
averaged over the compared traces.
"""

from __future__ import annotations

import heapq
from collections import Counter

import numpy as np
from scipy.spatial import cKDTree

from benchmark.traffic.graph import Graph

INF = float("inf")
MIN_RECORD_SPAN = 0.25        # meters: spans below this are not records


class Reference:
    def __init__(self, g: Graph, matcher: dict, dtype=np.float64):
        self.g = g
        self.p = matcher
        self.dtype = np.dtype(dtype)
        a, b, e, off = g.segments()
        self.a, self.b = self._q(a), self._q(b)
        self.seg_edge, self.seg_off = e, self._q(off)
        self.edge_len = self._q(g.edge_len)
        self.tree = cKDTree((a + b) / 2.0)
        self.reach = float(matcher["search_radius"]
                           + np.linalg.norm(b - a, axis=1).max() / 2.0 + 1.0)

    def _q(self, x):
        """Round to the reference's precision (a no-op in float64)."""
        x = np.asarray(x, np.float64)
        if self.dtype == np.float64:
            return x
        return x.astype(self.dtype).astype(np.float64)

    def _qs(self, x: float) -> float:
        return float(self._q(np.float64(x)))

    # ---- candidates -----------------------------------------------------

    def candidates(self, xy: np.ndarray):
        p = self.p
        out = []
        near = self.tree.query_ball_point(xy, self.reach)
        for t, ids in enumerate(near):
            ids = np.asarray(ids, np.int64)
            if not len(ids):
                out.append([])
                continue
            a, b = self.a[ids], self.b[ids]
            ab = b - a
            den = np.maximum((ab * ab).sum(1), 1e-12)
            s = np.clip(((xy[t] - a) * ab).sum(1) / den, 0.0, 1.0)
            d = self._q(np.linalg.norm(a + s[:, None] * ab - xy[t], axis=1))
            off = self._q(self.seg_off[ids] + s * np.sqrt(den))
            best: dict = {}
            for i in np.lexsort((self.seg_edge[ids], d)):
                if d[i] > p["search_radius"]:
                    break
                e = int(self.seg_edge[ids[i]])
                if e not in best:
                    best[e] = (float(d[i]), float(off[i]))
                    if len(best) >= p["max_candidates"]:
                        break
            out.append([(e, o, dd) for e, (dd, o) in best.items()])
        return out

    # ---- routing --------------------------------------------------------

    def dijkstra(self, e_from: int, bound: float) -> dict:
        """{edge: (distance from the end of e_from to its start, previous
        edge or -1)} for every edge whose start lies within ``bound``."""
        g = self.g
        u0 = int(g.edge_dst[e_from])
        dist = {u0: 0.0}
        prev = {u0: -1}
        out: dict = {}
        pq = [(0.0, u0)]
        while pq:
            d, u = heapq.heappop(pq)
            if d > dist[u]:
                continue
            for e in g.out_edge[g.out_ptr[u]:g.out_ptr[u + 1]]:
                e = int(e)
                if e not in out:
                    out[e] = (d, prev[u])
                nd = self._qs(d + self.edge_len[e])
                w = int(g.edge_dst[e])
                if nd <= bound and nd < dist.get(w, INF):
                    dist[w] = nd
                    prev[w] = e
                    heapq.heappush(pq, (nd, w))
        return out

    @staticmethod
    def _chain_back(reached: dict, e2: int) -> list:
        chain = []
        e = e2
        while True:
            pe = reached[e][1]
            if pe < 0:
                break
            chain.append(pe)
            e = pe
        chain.reverse()
        return chain

    # ---- HMM ------------------------------------------------------------

    def match_points(self, xy: np.ndarray):
        """Per point (edge, offset, chain_start); edge -1 = unmatched."""
        p = self.p
        xy = self._q(xy)
        T = len(xy)
        cands = self.candidates(xy)
        keep = [True] * T
        last = None
        for t in range(T):
            if last is not None and (np.linalg.norm(xy[t] - xy[last])
                                     < p["interpolation_distance"]):
                keep[t] = False
            else:
                last = t
        res = [(-1, 0.0, False)] * T
        act = [t for t in range(T) if keep[t] and cands[t]]
        if not act:
            return res
        sig2 = 2.0 * p["sigma_z"] ** 2

        def emit(c):
            return self._qs(c[2] ** 2 / sig2)

        scores, bps, starts = {}, {}, {}
        prev_t = -1
        for t in act:
            if prev_t >= 0:
                gc = self._qs(np.linalg.norm(xy[t] - xy[prev_t]))
                limit = p["max_route_distance_factor"] * gc + 10.0
                ns = [INF] * len(cands[t])
                bp = [-1] * len(cands[t])
                if gc <= p["breakage_distance"]:
                    for j, (ej, oj, _) in enumerate(cands[prev_t]):
                        if scores[prev_t][j] == INF:
                            continue
                        reached = None
                        for k, (ek, ok, _) in enumerate(cands[t]):
                            if ej == ek and ok >= oj - p["backward_slack"]:
                                route = max(ok - oj, 0.0)
                            else:
                                if reached is None:
                                    reached = self.dijkstra(ej, limit)
                                if ek not in reached:
                                    continue
                                route = self._qs(self.edge_len[ej] - oj
                                                 + reached[ek][0] + ok)
                            if route > limit:
                                continue
                            cost = self._qs(scores[prev_t][j]
                                            + abs(route - gc) / p["beta"])
                            if cost < ns[k]:
                                ns[k], bp[k] = cost, j
            if prev_t < 0 or all(s == INF for s in ns):
                scores[t] = [emit(c) for c in cands[t]]
                bps[t] = [-1] * len(cands[t])
                starts[t] = True
            else:
                scores[t] = [s + emit(c) if s < INF else INF
                             for s, c in zip(ns, cands[t])]
                bps[t] = bp
                starts[t] = False
            prev_t = t
        i = len(act) - 1
        while i >= 0:
            s = i
            while not starts[act[s]]:
                s -= 1
            chain = act[s:i + 1]
            k = int(np.argmin(scores[chain[-1]]))
            for tt in reversed(chain):
                e, o, _ = cands[tt][k]
                res[tt] = (e, o, tt == chain[0])
                k = bps[tt][k]
                if k < 0:
                    break
            i = s - 1
        last = None
        for t in range(T):
            if res[t][0] >= 0:
                last = res[t]
            elif not keep[t] and last is not None:
                res[t] = (last[0], last[1], False)
        return res

    # ---- records --------------------------------------------------------

    def route(self, e1: int, e2: int):
        if e1 == e2:
            return []
        reached = self.dijkstra(e1, 2000.0)
        if e2 not in reached:
            return None
        return self._chain_back(reached, e2)

    def match(self, xy: np.ndarray, times: np.ndarray) -> list:
        """Records (segment_id, length, start_time, end_time) in drive
        order; -1 times mark a partial traversal."""
        pts = self.match_points(xy)
        chains, cur = [], None
        for t, (e, off, start) in enumerate(pts):
            if e < 0:
                continue
            if cur is None or start:
                cur = []
                chains.append(cur)
            cur.append((e, off, float(times[t])))
        records = []
        slack = self.p["backward_slack"]
        for chain in chains:
            for path, dts in self._paths(chain, slack):
                records.extend(self._records(path, dts))
        return records

    def _paths(self, chain, slack):
        out = []
        path, cum = [chain[0][0]], [0.0]
        dts = [(chain[0][1], chain[0][2])]
        for i in range(1, len(chain)):
            (ep, op, _), (ec, oc, t) = chain[i - 1], chain[i]
            if ec == ep and oc >= op - slack:
                dts.append((cum[-1] + max(oc, dts[-1][0] - cum[-1]), t))
                continue
            mid = self.route(ep, ec)
            if mid is None:
                out.append((path, dts))
                path, cum, dts = [ec], [0.0], [(oc, t)]
                continue
            for m in [*mid, ec]:
                cum.append(cum[-1] + self.edge_len[path[-1]])
                path.append(m)
            dts.append((cum[-1] + oc, t))
        out.append((path, dts))
        return out

    def _records(self, path, dts):
        g = self.g
        L = self.edge_len[path]
        cum = np.concatenate([[0.0], np.cumsum(L)])
        lo_obs, hi_obs = dts[0][0], dts[-1][0]
        ds = np.asarray([d for d, _ in dts])
        ts = np.asarray([t for _, t in dts])

        def time_at(d):
            if d < ds[0] - 1e-6 or d > ds[-1] + 1e-6:
                return -1.0
            i = int(np.clip(np.searchsorted(ds, d), 1, len(ds) - 1))
            if ds[i] <= ds[i - 1] + 1e-9:
                return float(ts[i - 1])
            w = (d - ds[i - 1]) / (ds[i] - ds[i - 1])
            return float(ts[i - 1] + w * (ts[i] - ts[i - 1]))

        out = []
        i = 0
        while i < len(path):
            row = int(g.edge_osmlr[path[i]])
            j = i
            while (j + 1 < len(path) and int(g.edge_osmlr[path[j + 1]]) == row
                   and (row < 0 or abs(g.edge_osmlr_off[path[j + 1]]
                                       - g.edge_osmlr_off[path[j]]
                                       - L[j]) < 1.0)):
                j += 1
            d_lo, d_hi = cum[i], cum[j + 1]
            c_lo, c_hi = max(d_lo, lo_obs), min(d_hi, hi_obs)
            if c_hi > c_lo + MIN_RECORD_SPAN:
                if row < 0:
                    out.append((-1, c_hi - c_lo, time_at(c_lo),
                                time_at(c_hi)))
                else:
                    o = g.edge_osmlr_off[path[i]]
                    seg_len = g.osmlr_len[row]
                    lo = o + (c_lo - d_lo)
                    hi = o + (c_hi - d_lo)
                    out.append((int(g.osmlr_id[row]), hi - lo,
                                time_at(c_lo) if lo <= 1.0 else -1.0,
                                time_at(c_hi) if hi >= seg_len - 1.0
                                else -1.0))
            i = j + 1
        return out


def disagreement(got: list, want: list) -> float:
    """Length-weighted segment-id disagreement of one trace, in [0, 1]:
    1 - (meters per id that both report) / (the larger side's meters).
    ``got``/``want`` are (segment_id, length, ...) records; both empty is
    full agreement."""
    a, b = Counter(), Counter()
    for r in got:
        a[r[0]] += r[1]
    for r in want:
        b[r[0]] += r[1]
    if not a and not b:
        return 0.0
    total = max(sum(a.values()), sum(b.values()), 1.0)
    return 1.0 - sum(min(a[k], b[k]) for k in a.keys() & b.keys()) / total
