"""Probe synthesizer: the yardstick's own copy of ``netgen.traces``.

Same model as the program's ``synthesize_fleet``: each vehicle drives a
random walk over the graph (never an immediate U-turn where another exit
exists) at a speed drawn from U(7, 16) m/s, sampled every ``dt`` seconds
with Gaussian GPS noise. Vectorised over vehicles, so a pool of several
hundred thousand traces takes seconds, and drawn only from ``seed``:
the same seed gives the same traces, whatever their number.
"""

from __future__ import annotations

import numpy as np

from benchmark.traffic.graph import Graph


def synthesize(g: Graph, seed: int, n: int, *, min_points: int,
               max_points: int, dt: float = 1.0, gps_sigma: float = 5.0,
               speed_range=(7.0, 16.0), lengths=None):
    """Returns (xy [n, max_points, 2] float64 in the graph's frame, lengths
    [n] int). Points past a trace's length are zero. ``lengths`` fixes
    each trace's length; by default they are drawn uniformly."""
    rng = np.random.default_rng(seed)
    if lengths is None:
        lengths = rng.integers(min_points, max_points + 1, size=n)
    speed = rng.uniform(*speed_range, size=n)
    need = speed * dt * (lengths + 2)
    cur = rng.integers(g.num_edges, size=n)
    steps = [cur]
    total = g.edge_len[cur].copy()
    active = total < need
    while active.any():
        idx = np.nonzero(active)[0]
        e = cur[idx]
        u = g.edge_dst[e]
        lo, deg = g.out_ptr[u], g.out_ptr[u + 1] - g.out_ptr[u]
        opp = g.edge_opp[e]
        opp_rank = np.where(opp >= 0, g.edge_rank[np.maximum(opp, 0)], -1)
        opp_rank = np.where(deg > 1, opp_rank, -1)   # only exit: U-turn ok
        n_opt = deg - (opp_rank >= 0)
        pick = np.floor(rng.random(len(idx)) * np.maximum(n_opt, 1))
        pick = pick.astype(np.int64)
        pick += (opp_rank >= 0) & (pick >= opp_rank)
        nxt = np.where(deg > 0, g.out_edge[lo + np.minimum(pick, deg - 1)],
                       -1)
        stuck = deg == 0
        new = np.full(n, -1, np.int64)
        new[idx[~stuck]] = nxt[~stuck]
        cur = np.where(new >= 0, new, cur)
        steps.append(new)
        total[idx[~stuck]] += g.edge_len[nxt[~stuck]]
        active[idx[stuck]] = False
        active &= total < need
    path = np.stack(steps, axis=1)                      # [n, S], -1 pad
    plen = np.where(path >= 0, g.edge_len[np.maximum(path, 0)], 0.0)
    cum = np.concatenate([np.zeros((n, 1)), np.cumsum(plen, axis=1)], 1)
    T = max_points
    s = np.arange(T)[None, :] * dt * speed[:, None]
    s = np.minimum(s, cum[:, -1:] - 1e-3)
    # per-row searchsorted via row offsets
    big = cum[:, -1].max() + 10.0
    rows = np.arange(n)[:, None] * big
    k = np.searchsorted((cum[:, 1:] + rows).ravel(), (s + rows).ravel(),
                        side="right").reshape(n, T) - np.arange(n)[:, None] * path.shape[1]
    k = np.clip(k, 0, path.shape[1] - 1)
    e = np.take_along_axis(path, k, axis=1)
    e = np.where(e >= 0, e, path[:, :1])
    off = s - np.take_along_axis(cum, k, axis=1)
    P = g.edge_poly[e]                                  # [n, T, 3, 2]
    l0 = np.linalg.norm(P[..., 1, :] - P[..., 0, :], axis=-1)
    l1 = np.linalg.norm(P[..., 2, :] - P[..., 1, :], axis=-1)
    first = off <= l0
    t0 = np.clip(off / np.maximum(l0, 1e-6), 0.0, 1.0)[..., None]
    t1 = np.clip((off - l0) / np.maximum(l1, 1e-6), 0.0, 1.0)[..., None]
    true = np.where(first[..., None],
                    P[..., 0, :] + t0 * (P[..., 1, :] - P[..., 0, :]),
                    P[..., 1, :] + t1 * (P[..., 2, :] - P[..., 1, :]))
    xy = true + rng.normal(0.0, gps_sigma, size=true.shape)
    xy[np.arange(T)[None, :] >= lengths[:, None]] = 0.0
    return xy, lengths
