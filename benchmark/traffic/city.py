"""The yardstick's own copy of the synthetic road-network generator.

Copied from the program's ``netgen/synthetic.generate_city`` (grid
branch) so that a later PR may change ``reporter_tpu/netgen`` without
moving the benchmark's data. It draws the same random numbers in the same
order, so a preset here gives the same network the program's preset of
that name gives today. The output is plain data: node (lon, lat) and a
list of ways; ``graph.py`` builds the benchmark's own directed graph from
it, and the drivers hand it to the program's tile compiler.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

EARTH_RADIUS_M = 6_371_008.8


@dataclass
class Way:
    way_id: int
    nodes: list
    oneway: bool
    name: str
    speed_mps: float
    geometry: dict = field(default_factory=dict)   # leg -> [k, 2] lonlat


@dataclass
class City:
    name: str
    node_lonlat: np.ndarray          # [N, 2] float64
    ways: list

    def origin(self) -> np.ndarray:
        """Centre of the node bbox: the frame the compiler projects into."""
        return (self.node_lonlat.min(axis=0) + self.node_lonlat.max(axis=0)) / 2.0


def lonlat_to_xy(lonlat, origin) -> np.ndarray:
    """Equirectangular projection around ``origin`` (meters)."""
    lonlat = np.asarray(lonlat, np.float64)
    origin = np.asarray(origin, np.float64)
    k = np.pi / 180.0 * EARTH_RADIUS_M
    x = (lonlat[..., 0] - origin[0]) * k * np.cos(np.deg2rad(origin[1]))
    y = (lonlat[..., 1] - origin[1]) * k
    return np.stack([x, y], axis=-1)


def xy_to_lonlat(xy, origin) -> np.ndarray:
    xy = np.asarray(xy, np.float64)
    origin = np.asarray(origin, np.float64)
    k = np.pi / 180.0 * EARTH_RADIUS_M
    lon = xy[..., 0] / (k * np.cos(np.deg2rad(origin[1]))) + origin[0]
    lat = xy[..., 1] / k + origin[1]
    return np.stack([lon, lat], axis=-1)


def generate_grid_city(name: str, *, seed: int, nx: int, ny: int,
                       center: tuple, spacing: float = 120.0,
                       jitter: float = 12.0, p_missing_block: float = 0.06,
                       p_oneway: float = 0.25, p_curved: float = 0.25) -> City:
    """Jittered street grid with one-ways, missing blocks, curved legs and
    two diagonal boulevards (the program's generator, draw for draw)."""
    rng = np.random.default_rng(seed)
    lon0, lat0 = center
    xs = (np.arange(nx) - (nx - 1) / 2.0) * spacing
    ys = (np.arange(ny) - (ny - 1) / 2.0) * spacing
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    xy = np.stack([gx, gy], axis=-1)
    xy = xy + rng.normal(0.0, jitter, size=xy.shape)
    node_lonlat = xy_to_lonlat(xy.reshape(-1, 2), np.array([lon0, lat0]))
    node_index = np.arange(nx * ny).reshape(nx, ny)
    removed = rng.random((nx, ny, 2)) < p_missing_block

    ways: list = []
    way_id = 1

    def add_chain(chain, oneway, name_, speed):
        nonlocal way_id
        if len(chain) < 2:
            return
        w = Way(way_id=way_id, nodes=chain, oneway=oneway, name=name_,
                speed_mps=speed)
        for i in range(len(chain) - 1):
            if rng.random() < p_curved:
                a = node_lonlat[chain[i]]
                b = node_lonlat[chain[i + 1]]
                mid = (a + b) / 2.0
                d = b - a
                perp = np.array([-d[1], d[0]])
                if np.linalg.norm(perp) > 0:
                    mid = mid + perp * rng.uniform(0.05, 0.12)
                    w.geometry[i] = mid[None, :]
        ways.append(w)
        way_id += 1

    for j in range(ny):
        chain = [int(node_index[0, j])]
        for i in range(nx - 1):
            if removed[i, j, 0]:
                add_chain(chain, rng.random() < p_oneway, f"st_{j}", 13.4)
                chain = [int(node_index[i + 1, j])]
            else:
                chain.append(int(node_index[i + 1, j]))
        add_chain(chain, rng.random() < p_oneway, f"st_{j}", 13.4)
    for i in range(nx):
        chain = [int(node_index[i, 0])]
        for j in range(ny - 1):
            if removed[i, j, 1]:
                add_chain(chain, rng.random() < p_oneway, f"av_{i}", 13.4)
                chain = [int(node_index[i, j + 1])]
            else:
                chain.append(int(node_index[i, j + 1]))
        add_chain(chain, rng.random() < p_oneway, f"av_{i}", 13.4)
    k = min(nx, ny)
    add_chain([int(node_index[t, t]) for t in range(k)], False, "diag_ne",
              17.9)
    add_chain([int(node_index[t, ny - 1 - t]) for t in range(k)], False,
              "diag_se", 17.9)
    return City(name=name, node_lonlat=node_lonlat, ways=ways)
