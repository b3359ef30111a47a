"""Mesh-aware wire backend — the multi-device PRODUCT path (configs 3–4).

Round 4 proved the sharded *kernel step* (parallel/dp, multimetro,
sharded_candidates); this module carries the sharding into the deployable
pipeline: ``SegmentMatcher(ts, mesh=mesh)`` routes every device dispatch in
``_submit_many`` through a :class:`DpWireMatcher`, whose jitted programs are
``shard_map`` wrappings of the SAME undecorated wire bodies
(ops.match.wire_from_*) the single-device path jits — wire packing included.
Everything downstream (harvest, unpack, native C++ walk, columnar
MatchBatch, report build, service layers) is byte-stream work on the SAME
wire format, so the sharded product is bit-identical to single-device by
construction (test-asserted in tests/test_parallel.py).

Sharding layout (SURVEY.md §2.3 DP row): batch rows sharded over every mesh
axis flattened into one data axis; tile tables replicated (read-only,
staged once at construction). Zero cross-device communication per dispatch
— the forward match is embarrassingly data-parallel, which is why DP is the
first-choice scaling axis for this workload. shard_map rather than jit
in_shardings because the dense candidate backend is a pallas custom call
GSPMD cannot partition (see parallel/dp.py).

Batches whose row count is not a device-count multiple are padded with
zero-length (all-invalid) rows on submit; the harvest side slices wires
back to the real row count, so callers never see the padding.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reporter_tpu.config import MatcherParams
from reporter_tpu.matcher.autotune import TunedPlan
from reporter_tpu.ops.match import wire_from_f32, wire_from_q8, wire_from_q16
from reporter_tpu.parallel.compat import shard_map
from reporter_tpu.tiles.tileset import TileSet

_IMPLS = {"f32": wire_from_f32, "q16": wire_from_q16, "q8": wire_from_q8}
_NARGS = {"f32": 2, "q16": 3, "q8": 3}


def data_pspec(mesh: Mesh) -> P:
    """THE data-parallel PartitionSpec: leading dim sharded over every
    mesh axis flattened into one logical data axis. One spelling, every
    mesh consumer (wire dispatch below, the backfill aggregate scatter in
    ops/aggregate.py) — two spellings would let a placement drift."""
    return P(tuple(mesh.axis_names))


def flat_device_count(mesh: Mesh) -> int:
    """Total devices under the flattened data axis (== rows per padded
    dispatch block)."""
    return int(np.prod(tuple(mesh.shape.values())))


def mesh_wire_fn(mesh: Mesh, kind: str, meta, params: MatcherParams,
                 spec: "tuple | None", tables_pytree, has_acc: bool,
                 plan: TunedPlan = TunedPlan()):
    """``jit(shard_map(wire_from_<kind>))`` over ``mesh`` — THE product-
    path program builder. One spelling, two callers: DpWireMatcher's
    dispatch cache below, and the device-contract audit
    (analysis/device_contract.py), which abstractly traces the same
    callable so the audited mesh program can never drift from the served
    one. ``tables_pytree`` only shapes the replicated in_specs tree —
    ShapeDtypeStructs work as well as placed arrays. ``plan``: the
    sweep's kernel plan (a jit static like ``params``)."""
    impl = _IMPLS[kind]
    nargs = _NARGS[kind]
    data = data_pspec(mesh)                  # rows over ALL mesh axes
    tbl_specs = jax.tree.map(lambda _: P(), tables_pytree)

    if has_acc:
        def local(*a):
            *ins, acc, tbl = a
            return impl(*ins, tbl, meta, params, acc, spec, plan)
        in_specs = (data,) * nargs + (data, tbl_specs)
    else:
        def local(*a):
            *ins, tbl = a
            return impl(*ins, tbl, meta, params, None, spec, plan)
        in_specs = (data,) * nargs + (tbl_specs,)

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=data,
        check_vma=False))   # same constant-carry caveat as parallel/dp


class DpWireMatcher:
    """Duck-type of matcher.api._LocalWire: f32/q16/q8 entries taking host
    numpy arrays, returning an inflight device wire array (padded rows
    possible — harvest slices to the caller's row count). The mesh path
    never tunes, so its kernel plan is the static default."""

    def __init__(self, mesh: Mesh, ts: TileSet, params: MatcherParams,
                 spec: "tuple | None"):
        self.mesh = mesh
        self.ndev = flat_device_count(mesh)
        self.meta = ts.meta
        self.params = params
        self.spec = spec
        self.plan = TunedPlan()
        # replicated once; stage only the resolved backend's layout (the
        # unused index is the largest table at metro scale)
        self.tables = jax.device_put(
            ts.device_tables(params.candidate_backend),
            NamedSharding(mesh, P()))
        self._fns: dict = {}

    # ---- public entries (same shapes as the single-device jits) ---------

    def f32(self, pts, lens, acc):
        return self._dispatch("f32", (pts, lens), acc)

    def q16(self, pts_q, origins, lens, acc):
        return self._dispatch("q16", (pts_q, origins, lens), acc)

    def q8(self, deltas_q, origins, lens, acc):
        return self._dispatch("q8", (deltas_q, origins, lens), acc)

    # ---- internals -------------------------------------------------------

    def _dispatch(self, kind: str, arrays, acc):
        B = arrays[0].shape[0]
        pad = (-B) % self.ndev
        if pad:
            arrays = tuple(
                np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
                for a in arrays)
            if acc is not None:
                acc = np.concatenate(
                    [acc, np.ones((pad,) + acc.shape[1:], acc.dtype)])
        fn = self._fn(kind, acc is not None)
        args = [jnp.asarray(a) for a in arrays]
        if acc is not None:
            args.append(jnp.asarray(acc))
        return fn(*args, self.tables)

    def _fn(self, kind: str, has_acc: bool):
        """Cached mesh_wire_fn — one program per (entry kind, accuracy
        presence); shapes recompile inside the jit cache."""
        key = (kind, has_acc)
        cached = self._fns.get(key)
        if cached is None:
            cached = self._fns[key] = mesh_wire_fn(
                self.mesh, kind, self.meta, self.params, self.spec,
                self.tables, has_acc, self.plan)
        return cached
