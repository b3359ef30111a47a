"""Rehearsal without the chip: compile each cell's warmed shapes for a
described TPU v5e and print the device memory the compiler plans.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_v5e.py [cell ...]

The programs are the product's wire entries (``ops.match.wire_from_q16``
/ ``q8``) at the cell's batch shapes, on one described chip, and for the
four-chip cell the ``shard_map`` mesh entry (``parallel.dp_e2e``) over a
described 2x2 v5e. Nothing runs: it shows what the compiler refuses and
what it plans to hold, not a time.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import build  # noqa: E402

SHAPES = {
    # cell: (config, [(rows, points, infeed)], chips)
    "xl-backfill": ("bayarea-xl", [(4096, 128, "q16")], 1),
    "xl-backfill-x4": ("bayarea-xl", [(4096, 128, "q16")], 4),
    "bayarea-report": ("bayarea", [(r, b, k) for b in (64, 128)
                                   for r in (1, 16, 256)
                                   for k in ("q8", "q16")], 1),
}


def main(cells) -> int:
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from reporter_tpu.ops import dense_candidates as dc
    from reporter_tpu.ops import match as om
    from reporter_tpu.parallel.dp_e2e import mesh_wire_fn

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    tiles = {}
    for cell in cells:
        config_name, shapes, chips = SHAPES[cell]
        with open(os.path.join(BENCH, "configs", f"{config_name}.json")) as f:
            cfg = json.load(f)
        if config_name not in tiles:
            tiles[config_name] = build.tileset(cfg, build.city(cfg))
        ts = tiles[config_name]
        params = build.matcher_config(cfg).matcher.replace(
            candidate_backend="dense", sweep_autotune=False)
        spec = om.wire_spec(ts.num_edges, float(ts.edge_len.max()))
        host = ts.host_tables("dense")
        if chips == 1:
            where = SingleDeviceSharding(topo.devices[0])
            rows_where = where
        else:
            mesh = Mesh(topo.devices[:chips], ("dp",))
            where = NamedSharding(mesh, P())
            rows_where = NamedSharding(mesh, P("dp"))
        tabs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=where)
                for k, v in host.items()}
        for rows, T, kind in shapes:
            dt = jnp.int16 if kind == "q16" else jnp.int8
            args = (jax.ShapeDtypeStruct((rows, T, 2), dt,
                                         sharding=rows_where),
                    jax.ShapeDtypeStruct((rows, 2), jnp.float32,
                                         sharding=rows_where),
                    jax.ShapeDtypeStruct((rows,), jnp.int32,
                                         sharding=rows_where))
            with dc.pallas_trace_override():
                if chips == 1:
                    fn = (om.match_batch_wire_q if kind == "q16"
                          else om.match_batch_wire_q8)
                    low = fn.lower(*args, tabs, ts.meta, params, None,
                                   spec=spec)
                else:
                    fn = mesh_wire_fn(mesh, kind, ts.meta, params, spec,
                                      tabs, False)
                    low = fn.lower(*args, tabs)
            comp = low.compile()
            ma = comp.memory_analysis()
            print(json.dumps({
                "cell": cell, "rows": rows, "points": T, "infeed": kind,
                "chips": chips,
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "kernel": "tpu_custom_call" in comp.as_text()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or list(SHAPES)))
