"""The match path compiled by the TPU compiler for a described v5e chip.

Interpret mode accepts kernels that Mosaic refuses (a (1, 16) block of a
(13, 16) array passed every interpret test and failed to lower for the
chip), so these cases compile the real ``match_traces`` program for a
v5e device that is described, not attached: no chip time, nothing runs.
The topology is described only inside the fixture below — never at
import — so every xdist worker collects the same tests and only the
worker given this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reporter_tpu.config import CompilerParams, MatcherParams
from reporter_tpu.matcher.autotune import TunedPlan
from reporter_tpu.netgen.synthetic import generate_city
from reporter_tpu.ops import dense_candidates as dc
from reporter_tpu.ops.match import match_traces
from reporter_tpu.tiles.compiler import compile_network

ARMS = {
    "block": TunedPlan(arm="block"),
    "subcull": TunedPlan(),
    "mxu": TunedPlan(arm="mxu"),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    can never be read back without a chip — keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def tiles():
    """city -> TileSet, built on first use and shared by the cases."""
    return {}


def _compile(ts, one_chip, B, T, params, plan=TunedPlan()):
    host = ts.host_tables("dense")
    tabs = {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                    sharding=one_chip)
            for k, v in host.items()}
    pts = jax.ShapeDtypeStruct((B, T, 2), jnp.float32, sharding=one_chip)
    val = jax.ShapeDtypeStruct((B, T), jnp.bool_, sharding=one_chip)

    def fn(p, v, t):
        return match_traces(p, v, t, ts.meta, params, plan=plan)

    # the CPU test host answers default_backend() == "cpu"; the override
    # traces the Pallas program the chip would run
    with dc.pallas_trace_override():
        lowered = jax.jit(fn).lower(pts, val, tabs)
    return lowered.compile()


def _loop_gathers(hlo: str) -> list[tuple[str, str]]:
    """(operand shape, slice sizes) of every gather that a ``while`` body
    of the compiled module reaches, directly or through the computations
    it calls (fusions, reducers, inner loops)."""
    comps, types, name = {}, {}, None
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.endswith("{"):
            name = line.split()[line.startswith("ENTRY")].lstrip("%")
            comps[name] = []
        elif name and (m := re.match(r"\s+(?:ROOT )?%(\S+) = (\S+) ", line)):
            types[m.group(1)] = m.group(2).split("{")[0]
            comps[name].append(line)
    todo, seen, found = re.findall(r"\bbody=%([\w.-]+)", hlo), set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.-]+)",
                               line)
            m = re.search(r" gather\(%([\w.-]+),.*slice_sizes=\{([\d,]+)\}",
                          line)
            if m:
                found.append((types[m.group(1)], m.group(2)))
    return found


@pytest.mark.parametrize("city,B,arm", [
    ("sf", 512, "block"),
    ("sf", 512, "subcull"),
    ("sf", 512, "mxu"),
    ("bayarea", 4096, "block"),
])
def test_match_traces_compiles_for_v5e(city, B, arm, one_chip,
                                       no_persistent_cache, tiles):
    if city not in tiles:
        tiles[city] = compile_network(generate_city(city), CompilerParams())
    params = MatcherParams(candidate_backend="dense", sweep_autotune=False)
    ts = tiles[city]
    text = _compile(ts, one_chip, B, 120, params, ARMS[arm]).as_text()
    assert "tpu_custom_call" in text
    # the Viterbi scan looks the previous candidates' per-edge attributes
    # up by row gathers from the [ceil(E / 64), 128] table, never by a
    # per-element gather from an [E] table
    E = ts.num_edges
    gathers = _loop_gathers(text)
    assert not [g for g in gathers if g[0].endswith(f"[{E}]")], gathers
    assert (f"s32[{-(-E // 64)},128]", "1,128") in gathers, gathers
