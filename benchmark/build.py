"""Set-up shared by the drivers: the city, the benchmark's own graph, and
the program's tile set, each cached under ``benchmark/.cache`` so that
only a checkout's first run builds them."""

from __future__ import annotations

import os
import time

from benchmark.traffic.city import City, generate_grid_city, xy_to_lonlat as _to_lonlat
from benchmark.traffic.graph import Graph, build_graph, city_fingerprint

BENCH = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(BENCH, ".cache")


def city(config: dict) -> City:
    c = config["city"]
    return generate_grid_city(config["name"], **c)


def graph_path(config: dict, the_city: City) -> str:
    fp = city_fingerprint(the_city, config["osmlr_max_length"])
    return os.path.join(CACHE, f"graph-{config['name']}-{fp:08x}.npz")


def graph(config: dict, the_city: City) -> Graph:
    osmlr = config["osmlr_max_length"]
    path = graph_path(config, the_city)
    if os.path.exists(path):
        return Graph.load(path)
    g = build_graph(the_city, osmlr)
    os.makedirs(CACHE, exist_ok=True)
    g.save(path)
    return g


def tileset(config: dict, the_city: City):
    """The program's compiled tile set for this city (the system under
    test's own offline step: ``tiles.compiler.compile_network``)."""
    from reporter_tpu.config import CompilerParams
    from reporter_tpu.netgen.network import RoadNetwork, Way
    from reporter_tpu.tiles.compiler import compile_network
    from reporter_tpu.tiles.tileset import TileSet

    fp = city_fingerprint(the_city, config["osmlr_max_length"])
    path = os.path.join(CACHE, f"tiles-{config['name']}-{fp:08x}.npz")
    if os.path.exists(path):
        return TileSet.load(path)
    net = RoadNetwork(
        node_lonlat=the_city.node_lonlat, name=config["name"],
        ways=[Way(way_id=w.way_id, nodes=list(w.nodes), oneway=w.oneway,
                  name=w.name, speed_mps=w.speed_mps,
                  geometry=dict(w.geometry)) for w in the_city.ways])
    ts = compile_network(net, CompilerParams(
        osmlr_max_length=config["osmlr_max_length"]))
    os.makedirs(CACHE, exist_ok=True)
    tmp = path + ".tmp.npz"
    ts.save(tmp)
    os.replace(tmp, path)
    return ts


def xy_to_lonlat(xy, the_city: City):
    return _to_lonlat(xy, the_city.origin())


def matcher_config(config: dict, **service):
    """The program's Config with the configuration's HMM model pinned;
    kernel and batching knobs stay the program's defaults."""
    from reporter_tpu.config import Config, MatcherParams, ServiceConfig

    cfg = Config(matcher=MatcherParams(**config["matcher"]),
                 service=ServiceConfig(**service))
    return cfg


class Timer:
    """Set-up split: seconds per named step, printed on stderr."""

    def __init__(self):
        self.split: dict = {}

    def __call__(self, name: str):
        timer = self

        class _T:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *exc):
                timer.split[name] = (timer.split.get(name, 0.0)
                                     + time.perf_counter() - self.t0)

        return _T()
