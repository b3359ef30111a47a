"""The benchmark's own directed road graph, built from a ``City``.

Both the probe synthesizer and the plain reference matcher read this, and
nothing here reads the program: the edges, their shapes, lengths and the
OSMLR segments are worked out from the network by the rules the program
documents (one directed edge per leg and direction, ways' legs in order;
OSMLR chains of <= ``osmlr_max_length`` meters that continue within a way
and across a node of degree two; ids ``way_id << 20 | dir << 19 | chunk``).
Cached as npz per configuration under ``benchmark/.cache``.
"""

from __future__ import annotations

import os
import zlib

import numpy as np

from benchmark.traffic.city import City, lonlat_to_xy

GRAPH_VERSION = 1


class Graph:
    """Flat arrays; edge polylines padded to 3 points (a leg has at most
    one shape point in the synthetic generator)."""

    FIELDS = ("origin", "edge_src", "edge_dst", "edge_way", "edge_opp",
              "edge_poly", "edge_npts", "edge_len", "out_ptr", "out_edge",
              "edge_rank", "edge_osmlr", "edge_osmlr_off", "osmlr_id",
              "osmlr_len")

    def __init__(self, **arrays):
        for k in self.FIELDS:
            setattr(self, k, arrays[k])

    @property
    def num_edges(self) -> int:
        return len(self.edge_src)

    def save(self, path: str) -> None:
        tmp = path + ".tmp.npz"
        np.savez(tmp, **{k: getattr(self, k) for k in self.FIELDS})
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "Graph":
        with np.load(path) as z:
            return cls(**{k: z[k] for k in cls.FIELDS})

    def segments(self):
        """Line segments (a, b, edge, offset along edge) of every polyline."""
        P = self.edge_poly
        a = np.concatenate([P[:, 0], P[:, 1]])
        b = np.concatenate([P[:, 1], P[:, 2]])
        e = np.concatenate([np.arange(self.num_edges)] * 2)
        l0 = np.linalg.norm(P[:, 1] - P[:, 0], axis=1)
        off = np.concatenate([np.zeros(self.num_edges), l0])
        keep = np.linalg.norm(b - a, axis=1) > 1e-6
        keep[self.num_edges:] &= self.edge_npts == 3
        return a[keep], b[keep], e[keep], off[keep]


def city_fingerprint(city: City, osmlr_max_length: float) -> int:
    crc = zlib.crc32(np.ascontiguousarray(city.node_lonlat).tobytes())
    words = [GRAPH_VERSION, int(osmlr_max_length * 100)]
    for w in city.ways:
        words.extend((w.way_id, len(w.nodes), int(w.oneway),
                      int(w.speed_mps * 100), *w.nodes, *sorted(w.geometry)))
    return zlib.crc32(np.asarray(words, np.int64).tobytes(), crc)


def build_graph(city: City, osmlr_max_length: float) -> Graph:
    origin = city.origin()
    node_xy = lonlat_to_xy(city.node_lonlat, origin)
    src, dst, way, opp, polys, legs = [], [], [], [], [], []
    for wi, w in enumerate(city.ways):
        for leg in range(len(w.nodes) - 1):
            a, b = w.nodes[leg], w.nodes[leg + 1]
            mid = w.geometry.get(leg)
            pts = [node_xy[a]]
            if mid is not None:
                pts.extend(lonlat_to_xy(mid, origin))
            pts.append(node_xy[b])
            poly = np.asarray(pts)
            fwd = len(src)
            src.append(a); dst.append(b); way.append(w.way_id)
            polys.append(poly); legs.append((wi, leg, 0)); opp.append(-1)
            if not w.oneway:
                src.append(b); dst.append(a); way.append(w.way_id)
                polys.append(poly[::-1]); legs.append((wi, leg, 1))
                opp.append(fwd)
                opp[fwd] = fwd + 1
    E = len(src)
    npts = np.asarray([len(p) for p in polys], np.int8)
    P = np.zeros((E, 3, 2))
    for e, p in enumerate(polys):
        P[e, :len(p)] = p
        P[e, len(p):] = p[-1]
    seg = np.linalg.norm(np.diff(P, axis=1), axis=2)
    edge_len = np.where(seg > 1e-6, seg, 0.0).sum(axis=1)
    edge_src = np.asarray(src, np.int64)
    order = np.argsort(edge_src, kind="stable")
    n_nodes = len(city.node_lonlat)
    out_ptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(edge_src, minlength=n_nodes), out=out_ptr[1:])
    rank = np.empty(E, np.int64)
    rank[order] = np.arange(E) - out_ptr[edge_src[order]]
    g = dict(origin=origin, edge_src=edge_src,
             edge_dst=np.asarray(dst, np.int64),
             edge_way=np.asarray(way, np.int64),
             edge_opp=np.asarray(opp, np.int64), edge_poly=P,
             edge_npts=npts, edge_len=edge_len, out_ptr=out_ptr,
             out_edge=order.astype(np.int64), edge_rank=rank)
    g.update(_osmlr(city, g, legs, osmlr_max_length))
    return Graph(**g)


def _osmlr(city: City, g: dict, legs: list, max_len: float) -> dict:
    """OSMLR chains: a chain follows its way's next leg; at a way's end it
    continues only through a node of degree two (exactly two undirected
    legs meet there) onto the single onward edge that is not the U-turn.
    Chains start at edges no other edge continues into, in edge order,
    then whatever is left (pure cycles). Each chain is cut greedily into
    pieces of at most ``max_len`` meters."""
    E = len(legs)
    src, dst, opp = g["edge_src"], g["edge_dst"], g["edge_opp"]
    out_ptr, out_edge = g["out_ptr"], g["out_edge"]
    edge_len = g["edge_len"]
    by_leg = {leg: e for e, leg in enumerate(legs)}
    degree = np.zeros(len(city.node_lonlat), np.int64)
    for e, (_, _, d) in enumerate(legs):
        if d == 0:
            degree[src[e]] += 1
            degree[dst[e]] += 1

    def onward(e: int):
        wi, leg, d = legs[e]
        nxt = by_leg.get((wi, leg + 1, 0) if d == 0 else (wi, leg - 1, 1))
        if nxt is not None:
            return nxt
        u = dst[e]
        if degree[u] != 2:
            return None
        outs = [int(x) for x in out_edge[out_ptr[u]:out_ptr[u + 1]]
                if x != e and x != opp[e]]
        return outs[0] if len(outs) == 1 else None

    nxt = [onward(e) for e in range(E)]
    has_pred = np.zeros(E, bool)
    for s in nxt:
        if s is not None:
            has_pred[s] = True
    seen = np.zeros(E, bool)
    chains = []
    for heads_only in (True, False):
        for e in range(E):
            if seen[e] or (heads_only and has_pred[e]):
                continue
            chain = []
            while e is not None and not seen[e]:
                seen[e] = True
                chain.append(e)
                e = nxt[e]
            chains.append(chain)

    edge_osmlr = np.full(E, -1, np.int64)
    edge_off = np.zeros(E)
    ids, lens, count = [], [], {}
    for chain in chains:
        wi, _, d = legs[chain[0]]
        base = (city.ways[wi].way_id, d)
        pieces, cur, cur_len = [], [], 0.0
        for e in chain:
            if cur and cur_len + edge_len[e] > max_len:
                pieces.append(cur)
                cur, cur_len = [], 0.0
            cur.append(e)
            cur_len += edge_len[e]
        pieces.append(cur)
        for piece in pieces:
            k = count.get(base, 0)
            count[base] = k + 1
            row = len(ids)
            ids.append((base[0] << 20) | (base[1] << 19) | k)
            off = 0.0
            for e in piece:
                edge_osmlr[e] = row
                edge_off[e] = off
                off += edge_len[e]
            lens.append(off)
    return dict(edge_osmlr=edge_osmlr, edge_osmlr_off=edge_off,
                osmlr_id=np.asarray(ids, np.int64),
                osmlr_len=np.asarray(lens))
