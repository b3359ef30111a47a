"""Pin the BENCH_DETAIL.json roofline / culling-stats schema (round-8
satellite): the utilization evidence (%-of-peak, two-level culling
counts, kernel tag, block-level "before" flops) must survive future
kernel changes — a refactor that silently drops a field would erase the
capture's before/after story. Pure-host checks: the culling replication
is numpy, the roofline runs against a fake matcher object."""

import importlib.util
import os
from types import SimpleNamespace

import numpy as np

_BENCH = os.path.join(os.path.dirname(__file__), os.pardir, "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench_module", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_pack():
    """A few segments through build_seg_pack — the real layout builder,
    so the stats replication is exercised against real quads."""
    from reporter_tpu.ops.dense_candidates import build_seg_pack

    rng = np.random.default_rng(5)
    n = 40
    a = rng.uniform(0, 900.0, (n, 2)).astype(np.float32)
    b = (a + rng.uniform(-80.0, 80.0, (n, 2))).astype(np.float32)
    seg_len = np.linalg.norm(b - a, axis=1).astype(np.float32)
    return build_seg_pack(a, b, np.arange(n, dtype=np.int32),
                          np.zeros(n, np.float32), seg_len)


CULLING_KEYS = {
    "blocks_total", "block_visits_per_dispatch", "mean_blocks_per_chunk",
    "culled_fraction", "sub_slices_per_block", "sub_visits_per_dispatch",
    "sub_fraction_of_block_cols",
}

ROOFLINE_KEYS = CULLING_KEYS | {
    "kernel", "hbm_bytes_swept", "pair_flops", "pair_flops_block_level",
    "mxu_flops", "select_flops_ceiling",
    "topk_width", "achieved_GBps", "achieved_Gflops",
    "pct_of_v5e_hbm_peak", "pct_of_v5e_vpu_f32_peak",
    "pct_vpu_block_level", "pct_of_v5e_mxu_bf16_peak", "note",
}


def test_culling_stats_schema_and_invariants():
    bench = _load_bench()
    sp = _tiny_pack()
    rng = np.random.default_rng(6)
    pts = rng.uniform(0, 900.0, (300, 2))

    stats = bench._sweep_culling_stats(sp.bbox, sp.sub, pts, 50.0)
    assert CULLING_KEYS <= set(stats)
    nsub = stats["sub_slices_per_block"]
    assert nsub >= 1
    # level 2 can only SHRINK level 1's work, never exceed it
    assert (stats["sub_visits_per_dispatch"]
            <= stats["block_visits_per_dispatch"] * nsub)
    assert 0.0 <= stats["sub_fraction_of_block_cols"] <= 1.0
    assert 0.0 <= stats["culled_fraction"] <= 1.0

    # without sub quads the stats degrade to block-level identities
    flat = bench._sweep_culling_stats(sp.bbox, None, pts, 50.0)
    assert flat["sub_slices_per_block"] == 1
    assert (flat["sub_visits_per_dispatch"]
            == flat["block_visits_per_dispatch"])


def test_roofline_schema_all_kernels():
    import jax.numpy as jnp

    from reporter_tpu.config import MatcherParams
    from reporter_tpu.matcher.autotune import TunedPlan

    bench = _load_bench()
    sp = _tiny_pack()
    tables = {"seg_pack": jnp.asarray(sp.pack),
              "seg_bbox": jnp.asarray(sp.bbox),
              "seg_sub": jnp.asarray(sp.sub),
              "seg_feat": jnp.asarray(sp.feat)}
    pts = np.random.default_rng(7).uniform(0, 900.0, (256, 2)
                                           ).astype(np.float32)
    for plan in (TunedPlan(),
                 TunedPlan(arm="block"),
                 TunedPlan(lowp="bf16"),
                 TunedPlan(arm="mxu", lowp="bf16")):
        m = SimpleNamespace(_tables=tables, params=MatcherParams(),
                            tuned_plan=plan)
        out = bench._sweep_roofline(m, pts, per_dispatch_s=0.1)
        assert ROOFLINE_KEYS <= set(out), plan
        assert out["pair_flops"] <= out["pair_flops_block_level"]
        assert out["select_flops_ceiling"] > 0
        if plan.arm != "block":
            assert out["kernel"].startswith("subcull")
        else:
            assert out["kernel"] == "block"
        if plan.lowp == "bf16":
            assert out["kernel"].endswith("+bf16")
        if plan.arm == "mxu":
            # third work level: the matmul coarse pass is counted and
            # compared against the MXU peak, and the feature-row DMA
            # rides the swept bytes
            assert "+mxu" in out["kernel"]
            assert out["mxu_flops"] > 0
            assert out["pct_of_v5e_mxu_bf16_peak"] is not None
        else:
            assert out["mxu_flops"] == 0
            assert out["pct_of_v5e_mxu_bf16_peak"] is None


def test_summary_line_carries_roofline_era_fields():
    """The compact driver line must keep the round-8 fields — per-tile
    co-located table, sweep A/B, overload boundary — with the r13 mxu
    arm in the third sweep slot (the promoted home of the r8 bf16
    lever) plus the dedicated mxu acceptance token."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "colocated_e2e": {"sf": 3000000.0, "bayarea-xl": 1800000.0},
               "sweep_ab": {
                   "subcull": {"device_probes_per_sec": 3500000.0},
                   "block": {"device_probes_per_sec": 3000000.0},
                   "mxu": {"device_probes_per_sec": 3700000.0},
                   "wires_bit_identical": True,
                   "wires_identical_after_paging": True,
                   "mxu_compared": True},
               "xl": {"sweep_ab": {
                   "mxu": {"device_probes_per_sec": 2900000.0},
                   "wires_bit_identical": True,
                   "wires_identical_after_paging": True,
                   "mxu_compared": True}},
               "service_overload_boundary": {"clients": 512},
           }}
    line = bench._summary_line(doc)
    assert line["coe2e_kpps"][0] == 3000    # sf first, fixed order
    assert line["coe2e_kpps"][3] == 1800    # bayarea-xl fourth
    assert line["sweep_kpps"] == [3500, 3000, 3700, 1]
    assert line["mxu"] == [3.7, 2.9, 1]
    # r20 compaction: the overload boundary rides the svc array's LAST
    # slot (the dedicated svc_edge key paid for the bf token)
    assert line["svc"][-1] == 512
    # one False identity bit anywhere → the acceptance slot reads 0
    doc["detail"]["xl"]["sweep_ab"]["wires_identical_after_paging"] = False
    assert bench._summary_line(doc)["mxu"] == [3.7, 2.9, 0]
    # a tile where the mxu arm FAILED to run must not contribute its
    # legacy-arm identity bits to the mxu acceptance slot (a lowering
    # failure on chip must read "not exercised", never vacuous green)
    for tile in (doc["detail"]["sweep_ab"], doc["detail"]["xl"]["sweep_ab"]):
        tile["mxu_compared"] = False
        tile.pop("mxu")
    line3 = bench._summary_line(doc)
    assert line3["mxu"] == [None, None, None]
    # nothing recorded → None slots, never KeyError
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["mxu"] == [None] * 3


def test_coverage_diff_matches_traversals_not_bytes():
    """detail.recovery's lost/duplicated accounting: a replayed wave may
    legally shift a report's interpolated t0/t1 by a few samples — the
    at-least-once bound is coverage of the traversal, and deliveries
    beyond one per traversal are the counted replay tax."""
    from collections import Counter

    bench = _load_bench()
    a = Counter({(7, -1, 10.0, 20.0): 1, (7, -1, 70.0, 80.0): 1,
                 (9, 7, 15.0, 25.0): 1})
    # same traversals, one boundary-shifted, one delivered twice, plus a
    # replay-only extra the reference never saw
    b = Counter({(7, -1, 12.5, 21.0): 1, (7, -1, 70.0, 80.0): 2,
                 (9, 7, 15.0, 25.0): 1, (11, -1, 0.0, 5.0): 1})
    lost, dup = bench._coverage_diff(a, b)
    assert lost == 0                 # every reference traversal covered
    assert dup == 2                  # one double delivery + one extra
    # a genuinely missing traversal counts as lost
    lost2, _ = bench._coverage_diff(a, Counter({(7, -1, 70.0, 80.0): 1}))
    assert lost2 == 2


def test_summary_line_carries_chaos_fields():
    """The rec token: [recovery s, duplicated, LOST (must be 0),
    dead-letter rows pending at outage end (must be 0), 2v1 speedup]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "recovery": {"recovery_seconds": 12.3,
                            "duplicated_reports": 456,
                            "lost_reports": 0},
               "publish_outage": {"dead_letter_pending_end": 0},
               "streaming_soak_mp": {"speedup_2v1": 0.91},
           }}
    line = bench._summary_line(doc)
    assert line["rec"] == [12.3, 456, 0, 0, 0.91]
    # sparse runs degrade to None slots, never KeyError
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["rec"] == [None] * 5


def test_recovery_leg_schema_keys():
    """Pin the detail.recovery keys the docs/README cite — a refactor
    that drops one erases the capture's recovery story. Checked against
    the leg's early-return-free result shape (source-level pin: the keys
    must appear in the function body)."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._recovery_bench)
    for key in ("recovery_seconds", "duplicated_reports", "lost_reports",
                "lost_segments", "at_least_once_ok", "reports_at_kill",
                "committed_at_restart", "broker_probes"):
        assert f'"{key}"' in src, key
    src_o = inspect.getsource(bench._publish_outage_soak)
    for key in ("publish_retried", "dead_lettered", "dead_letter_replayed",
                "dead_letter_pending_end", "spool_drained",
                "rss_max_delta_mb"):
        assert f'"{key}"' in src_o, key


ATTRIBUTION_STAGES = ("broker_dwell", "prepare", "device_match",
                      "report_build")

ATTRIBUTION_KEYS = {
    "samples", "stages", "e2e_p50_ms", "e2e_p99_ms", "stage_sum_p50_ms",
    "stage_sum_over_e2e_p50", "reconciles_within_15pct",
}


def test_latency_attribution_schema_and_reconciliation():
    """Pin the detail.latency_attribution stage decomposition (ISSUE 5):
    stage names, the reconciliation field, and the telescoping invariant
    — per-probe stage components that sum exactly to e2e must reconcile
    at the p50 level within the acceptance bound."""
    bench = _load_bench()
    rng = np.random.default_rng(8)
    n = 500
    parts = {
        "broker_dwell": rng.uniform(0.05, 0.8, n),
        "prepare": rng.uniform(0.001, 0.01, n),
        "device_match": rng.uniform(0.02, 0.3, n),
        "report_build": rng.uniform(0.001, 0.02, n),
    }
    samples = dict(parts, e2e=sum(parts.values()),
                   publish=rng.uniform(0.01, 0.1, 40))
    out = bench._attribution_from_samples(samples)
    assert ATTRIBUTION_KEYS <= set(out)
    assert set(out["stages"]) == set(ATTRIBUTION_STAGES) | {"publish"}
    for name in ATTRIBUTION_STAGES:
        st = out["stages"][name]
        assert st["p50_ms"] >= 0 and st["p99_ms"] >= 0
    assert out["samples"] == n
    # the components are CONDITIONAL on the e2e quantile window (what
    # the median probe's time was spent on), so the telescoping
    # partition makes their sum track the e2e p50 with only
    # window-mean-vs-percentile slack — reconciliation is structural,
    # not a property of these particular magnitudes
    assert out["reconciles_within_15pct"] is True
    assert abs(out["stage_sum_over_e2e_p50"] - 1.0) <= 0.05
    # the p99 decomposition tracks the e2e p99 the same way
    sum_p99 = sum(out["stages"][k]["p99_ms"] for k in ATTRIBUTION_STAGES)
    assert abs(sum_p99 / out["e2e_p99_ms"] - 1.0) <= 0.05
    # publish is reported but EXCLUDED from the reconciling sum (it
    # completes after the probe→report cut)
    s = sum(out["stages"][k]["p50_ms"] for k in ATTRIBUTION_STAGES)
    assert abs(s - out["stage_sum_p50_ms"]) < 0.02

    empty = bench._attribution_from_samples(None)
    assert ATTRIBUTION_KEYS <= set(empty)
    assert empty["samples"] == 0
    assert empty["reconciles_within_15pct"] is None


def test_latency_attribution_leg_records_overhead_ab():
    """The tracing-overhead A/B (traced vs untraced soak at the same
    offer) must stay a recorded field in every capture — regressions in
    the off-path cost must be visible run over run."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._latency_attribution)
    for key in ("sustained_pps_traced", "sustained_pps_untraced",
                "tracing_overhead_pct", "offered_pps", "service_face"):
        assert f'"{key}"' in src, key


PREPARE_BENCH_KEYS = (
    "rows", "bucket", "python_krows_per_s", "native_krows_per_s",
    "speedup", "bytes_identical", "native_available",
)


def test_prepare_bench_schema_keys():
    """Pin detail.prepare_bench (ISSUE 7 satellite): the host-prepare
    native-vs-Python A/B and its byte-identity re-proof must stay
    recorded fields on every composite — extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._prepare_bench)
    for key in PREPARE_BENCH_KEYS:
        assert f'"{key}"' in src, key


def test_summary_line_carries_prep_token():
    """prep = [native krows/s, speedup vs numpy, bytes identical]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "prepare_bench": {"native_krows_per_s": 54321.0,
                                 "speedup": 11.5,
                                 "bytes_identical": True},
           }}
    line = bench._summary_line(doc)
    assert line["prep"] == [54321.0, 11.5, 1]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["prep"] == [None] * 3


def test_summary_line_carries_lattr_token():
    """lattr = [e2e p50 WHOLE ms (r18 compaction), stage-sum/e2e ratio,
    tracing overhead %]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "latency_attribution": {
                   "e2e_p50_ms": 2481.5,
                   "stage_sum_over_e2e_p50": 1.0312,
                   "tracing_overhead_pct": 1.27},
           }}
    line = bench._summary_line(doc)
    assert line["lattr"] == [2481, 1.0312, 1.27]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["lattr"] == [None] * 3


AUTOTUNE_PROBE_KEYS = (
    "plan", "source", "candidates", "calibration_seconds",
    "calibration_dispatches", "cache_hit", "tuned", "default",
    "tuned_vs_default_speedup", "dispatch_shape",
)

AUTOTUNE_VALIDATE_KEYS = (
    "cpu_short_circuit", "deterministic", "cache_hit",
    "plan_from_cache_identical", "v2_refused_at_construction",
    "v2_refused_at_restage", "mechanism_ok",
)


def test_autotune_leg_schema_keys():
    """Pin detail.autotune (round 17): the chosen plan, per-candidate
    timings, tuned-vs-default A/B (chip) and the mechanism bits (CPU
    validation) must stay recorded fields — extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._autotune_probe)
    for key in AUTOTUNE_PROBE_KEYS:
        assert f'"{key}"' in src, key
    src_v = inspect.getsource(bench._autotune_cpu_validate)
    for key in AUTOTUNE_VALIDATE_KEYS:
        assert f'"{key}"' in src_v, key


def test_summary_line_carries_tune_token():
    """tune = [chosen plan label, tuned-vs-default speedup, source,
    mechanism bit (CPU validation; None on chip)]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "autotune": {
                   "plan": {"arm": "mxu", "lowp": "bf16", "nj_cap": 128,
                            "source": "measured",
                            "label": "mxu+bf16@128"},
                   "tuned_vs_default_speedup": 1.183,
                   "source": "measured",
               },
           }}
    line = bench._summary_line(doc)
    assert line["tune"] == ["mxu+bf16@128", 1.183, "measured", None]
    # the CPU-validation composite carries the mechanism bit instead
    doc["detail"]["autotune"] = {
        "plan": {"label": "mxu+bf16@256"}, "source": "cpu-validate",
        "mechanism_ok": True}
    assert bench._summary_line(doc)["tune"] == [
        "mxu+bf16@256", None, "cpu-validate", 1]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["tune"] == [None] * 4


QUALITY_PROBE_KEYS = (
    "signals", "audit", "audit_overhead", "drift", "disagreement_rate",
    "audited_batches", "audit_timeouts", "audit_seconds",
    "drift_events", "window_waves",
)

QUALITY_VALIDATE_KEYS = QUALITY_PROBE_KEYS + (
    "signals_recorded", "sampler_deterministic", "audit_ran",
    "one_event_one_dump", "clean_twin_ok", "mechanism_ok",
)

QUALITY_OVERHEAD_KEYS = (
    "off_pps", "on_pps", "audit_rate", "audit_s_per_batch",
    "min_interval_s", "duty_pct_cap", "direct_overhead_pct",
    "uncapped_overhead_pct", "audit_overhead_pct", "meets_2pct_bar",
)


def test_quality_leg_schema_keys():
    """Pin detail.quality (round 18): the signal window, the shadow-
    audit record, the overhead A/B (the <2% acceptance number), and the
    CPU-validation mechanism bits must stay recorded fields on every
    composite — extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._quality_probe)
    for key in QUALITY_PROBE_KEYS:
        assert f'"{key}"' in src, key
    src_v = inspect.getsource(bench._quality_cpu_validate)
    for key in QUALITY_VALIDATE_KEYS:
        assert f'"{key}"' in src_v, key
    src_o = inspect.getsource(bench._quality_overhead_ab)
    for key in QUALITY_OVERHEAD_KEYS:
        assert f'"{key}"' in src_o, key


def test_summary_line_carries_qual_token():
    """qual = [empty-match bp, violation bp, audit disagreement bp,
    audit overhead %, drift events, mechanism bit (None on chip)]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "quality": {
                   "signals": {"empty_match_rate": 0.0123,
                               "violation_rate": 0.002},
                   "audit": {"disagreement_rate": 0.0077},
                   "audit_overhead": {"audit_overhead_pct": 0.41},
                   "drift": {"drift_events": 0},
                   "mechanism_ok": True,
               },
           }}
    line = bench._summary_line(doc)
    assert line["qual"] == [123, 20, 77, 0.41, 0, 1]
    # chip probes carry no mechanism bit — None, never vacuous green
    del doc["detail"]["quality"]["mechanism_ok"]
    assert bench._summary_line(doc)["qual"][-1] is None
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["qual"] == [None] * 6


def test_fleet_leg_schema_keys():
    """Pin detail.fleet's occupancy/paging block (ISSUE 6): the
    capture's fleet story — metros served, mixed kpps, promotion
    latency, paging counts, the bit-identity bit — must survive future
    refactors. Extend these key sets, never drop from them."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._fleet_bench)
    for key in ("n_metros", "build_seconds", "staged_bytes_total",
                "probes_per_sec", "per_metro_kpps", "capacity_bytes",
                "touches", "promote_p50_ms", "promote_p99_ms",
                "promote_to_first_report_p50_ms", "occupancy",
                "wires_bit_identical", "wires_identical_to_dedicated",
                "wires_identical_after_paging", "per_metro",
                "tuned_plan"):
        assert f'"{key}"' in src, key
    # the occupancy report itself (fleet/residency.py) feeds /health and
    # the bench artifact — same extend-don't-drop discipline
    from reporter_tpu.fleet.residency import FleetResidency

    src_o = inspect.getsource(FleetResidency.occupancy)
    for key in ("capacity_bytes", "evict_watermark", "resident_bytes",
                "occupancy_frac", "resident_metros", "registered_metros",
                "promotions", "demotions", "metros", "tuned_plan"):
        assert f'"{key}"' in src_o, key


def test_summary_line_carries_fleet_token():
    """fleet = [metros served, mixed-traffic kpps, storm promotion p50
    ms, promotions, demotions, wires bit-identical through paging]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "fleet": {
                   "n_metros": 8,
                   "mixed": {"probes_per_sec": 456789.1},
                   "storm": {"promote_p50_ms": 42.51},
                   "occupancy": {"promotions": 24, "demotions": 20},
                   "fidelity": {"wires_bit_identical": True},
               },
           }}
    line = bench._summary_line(doc)
    assert line["fleet"] == [8, 456, 42, 24, 20, 1]   # p50 whole ms (r18)
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["fleet"] == [None] * 6


TOPOLOGY_KEYS = (
    "workers", "broker_probes", "stamped_records", "soak",
    "probes_per_sec_wall", "deaths", "restarts", "reports_at_kill",
    "lag_at_kill", "detect_seconds", "recovery_seconds", "lost_records",
    "zero_lost_ok", "aggregation", "counters_checked", "buckets_checked",
    "fidelity_ok", "exposition_ok", "event_counts", "exit_reports",
    "worker_exit_reports_ok", "stitch",
)


def test_topology_leg_schema_keys():
    """Pin detail.topology (round 19): the supervised-soak story —
    death/restart/recovery, zero-lost accounting, aggregation fidelity,
    the stitched cross-pid trace — must stay recorded fields on every
    composite. Extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._topology_bench)
    for key in TOPOLOGY_KEYS:
        assert f'"{key}"' in src, key
    # the leg's worker subprocesses are CPU-pinned on EVERY composite
    # (a chip run must not donate its device to two startup compiles)
    assert '"JAX_PLATFORMS": "cpu"' in src


def test_summary_line_carries_topo_token():
    """topo = [workers, aggregate probes/s (int), deaths (main + lease
    arms summed), restarts, recovery seconds (1 decimal), lost records
    (both arms), lease kill→reacquire seconds (None when the arm didn't
    run), folded identity bit (fidelity/stitch + the lease arm's
    zero-lost/zero-dup/fenced/fault-surfaced when recorded)]."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "topology": {
                   "workers": 2,
                   "soak": {"probes_per_sec_wall": 163.2},
                   "deaths": 1, "restarts": 1,
                   "recovery_seconds": 2.36,
                   "lost_records": 0,
                   "aggregation": {"fidelity_ok": True},
                   "stitch": {"ok": True},
               },
           }}
    line = bench._summary_line(doc)
    # no lease arm recorded: its timing slot is None and the fold
    # covers only the two main-arm bits — never vacuous green
    assert line["topo"] == [2, 163, 1, 1, 2.4, 0, None, 1]
    doc["detail"]["topology"]["lease"] = {
        "deaths": 2, "lost_records": 0,
        "kill_to_reacquire_seconds": 2.38,
        "zero_lost_ok": True, "zero_dup_ok": True,
        "stale_commit_rejected": True, "fault_stats_surfaced": False,
    }
    line = bench._summary_line(doc)
    assert line["topo"] == [2, 163, 3, 1, 2.4, 0, 2.4, 0]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["topo"] == [None] * 8


BACKFILL_KEYS = (
    "records", "open_loop", "krows_per_s", "seconds", "waves", "chunks",
    "reports", "replay_tax_records", "kept_segments", "kanon_dropped",
    "agg_identical", "closed_loop", "posts", "vs_soak_x",
    "open_ge_closed_ok",
    # r21 mesh arm: device count, mesh/single throughput ratio, and the
    # two mesh-only identity bits (aggregate grids equal the single
    # arm's bit-for-bit; prepared-seam wire bytes identical)
    "mesh", "devices", "vs_single_x", "agg_equal_single",
    "wire_bytes_identical",
)


def test_backfill_leg_schema_keys():
    """Pin detail.backfill (round 20; mesh arm round 21): open-loop
    engine vs closed-loop drain of the SAME spool, device-vs-shadow
    aggregate identity, the counted k-anonymity cutoff, the (zero on a
    clean run) replay tax, and the data-parallel mesh arm with its
    identity bits. Extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._backfill_bench)
    for key in BACKFILL_KEYS:
        assert f'"{key}"' in src, key


def test_summary_line_carries_bf_token():
    """bf = [open-loop krows/s (1 decimal), open/closed-loop speedup
    (2 decimals), folded identity bit, k-anonymity-withheld segment
    count, mesh-arm krows/s (1 decimal; None on 1-device composites)].
    The identity slot folds every RECORDED bit (mxu-token style): a
    single-device composite folds the one shadow bit, a mesh composite
    folds all four — one recorded False reads 0."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "backfill": {
                   "open_loop": {"krows_per_s": 84.237,
                                 "agg_identical": True,
                                 "kanon_dropped": 27},
                   "vs_soak_x": 2.504,
               },
           }}
    line = bench._summary_line(doc)
    assert line["bf"] == [84.2, 2.5, 1, 27, None]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["bf"] == [None] * 5

    # mesh arm recorded: slot 4 carries its krows/s and slot 2 folds
    # the mesh bits — one False anywhere reads 0
    doc["detail"]["backfill"]["mesh"] = {
        "devices": 8, "krows_per_s": 412.561, "vs_single_x": 4.9,
        "agg_identical": True, "agg_equal_single": True,
        "wire_bytes_identical": True}
    line = bench._summary_line(doc)
    assert line["bf"] == [84.2, 2.5, 1, 27, 412.6]
    doc["detail"]["backfill"]["mesh"]["agg_equal_single"] = False
    assert bench._summary_line(doc)["bf"][2] == 0


def test_service_ab_records_draw_spread():
    """Round-19 satellite: the closed-loop service A/B records the
    client-thread count and per-draw req/s spread, so the r18
    bimodality class ("120-484 req/s across draws") is diagnosable
    FROM the capture. Source pin on the ab-block builder."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._service_saturation_curve)
    assert '"round_rps"' in src
    # the ab block (built in main's _leg_service) carries the per-draw
    # fields; _summary_line is untouched (detail-only satellite)
    src_main = inspect.getsource(bench.main)
    for key in ("client_threads", "scheduler_draw_rps",
                "legacy_draw_rps", "scheduler_draw_spread_pct",
                "legacy_draw_spread_pct"):
        assert f'"{key}"' in src_main, key


def test_service_overload_boundary_rules():
    bench = _load_bench()

    def lvl(clients, p99, rps, errors=0):
        return {"clients": clients,
                "scheduler": {"p99_ms": p99, "req_per_sec": rps,
                              "errors": errors}}

    held = [lvl(16, 100.0, 100.0), lvl(64, 150.0, 300.0),
            lvl(256, 300.0, 900.0), lvl(512, 600.0, 1500.0)]
    out = bench._service_overload_boundary(held)
    assert out["clients"] is None and "512" in out["reason"]

    blow = held[:3] + [lvl(512, 3000.0, 1500.0)]
    assert bench._service_overload_boundary(blow) == {
        "clients": 512, "reason": "p99_blowup"}

    regress = held[:3] + [lvl(512, 500.0, 300.0)]
    assert bench._service_overload_boundary(regress) == {
        "clients": 512, "reason": "rps_regression"}

    errs = held[:2] + [lvl(256, 300.0, 900.0, errors=3)]
    assert bench._service_overload_boundary(errs) == {
        "clients": 256, "reason": "errors"}


# ---------------------------------------------------------------------------
# Round 15: capture journal + link-health + regression sentinel pins
# (extend these sets, never drop — the resumability and attribution
# stories live in these keys)


def test_journal_entry_schema_keys():
    """Every journaled leg must carry result + provenance (wall time,
    capture timestamp, link window); the composite's journal block must
    name what was resumed and what was truncated."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench.BenchJournal.leg)
    for key in ("leg", "seconds", "captured_at", "link", "result"):
        assert f'"{key}"' in src, key
    src_j = inspect.getsource(bench.BenchJournal.to_json)
    for key in ("resumed_legs", "truncated_lines", "legs"):
        assert f'"{key}"' in src_j, key
    # the journal's write path is the r9 atomic discipline
    src_w = inspect.getsource(bench.BenchJournal._write_all)
    assert "fsync" in src_w and "os.replace" in src_w


def test_link_window_schema_keys():
    from reporter_tpu.utils.linkhealth import LinkHealthSampler
    import inspect

    src = inspect.getsource(LinkHealthSampler.window)
    for key in ("rtt_ms", "mbps", "mood", "samples"):
        assert f'"{key}"' in src, key
    bench = _load_bench()
    src_m = inspect.getsource(bench.main)
    for key in ("probe_duty_pct", "dead_probes", "link_health"):
        assert f'"{key}"' in src_m, key


def test_delta_report_schema_keys():
    import inspect

    from reporter_tpu.analysis import bench_delta

    src = inspect.getsource(bench_delta.compare)
    for key in ("regressions", "link_attributable", "compared", "flat",
                "improved", "only_old_keys", "only_new_keys",
                "threshold_pct"):
        assert f'"{key}"' in src, key
    src_c = inspect.getsource(bench_delta.compact)
    for key in ("regressions_total", "link_attributable_total"):
        assert f'"{key}"' in src_c, key


def test_summary_line_carries_link_token():
    """link = [rtt_ms (int), mbps, mood]; the CPU path records
    mood="cpu" — the token is never omitted (r15 satellite)."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "link_health": {"rtt_ms": 130.25, "mbps": 25.13,
                               "mood": "healthy", "samples": 40},
           }}
    assert bench._summary_line(doc)["link"] == [130, 25.1, "healthy"]
    doc["detail"]["link_health"] = {"rtt_ms": None, "mbps": None,
                                    "mood": "cpu", "samples": 2}
    assert bench._summary_line(doc)["link"] == [None, None, "cpu"]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["link"] == [None, None, None]


def test_summary_line_carries_delta_token():
    """delta = [regressions, link-attributable, worst regression %] vs
    the committed same-flavor capture."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "bench_delta": {
                   "regressions_total": 3,
                   "link_attributable_total": 5,
                   "regressions": [
                       {"path": "detail.xl.probes_per_sec_e2e",
                        "delta_pct": -42.7},
                       {"path": "detail.streaming.probes_per_sec",
                        "delta_pct": -12.0}]},
           }}
    assert bench._summary_line(doc)["delta"] == [3, 5, -42.7]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["delta"] == [None] * 3


# ---------------------------------------------------------------------------
# SLO burn-rate leg (round 24)

SLO_KEYS = (
    "specs", "ticks", "clean_alerts", "clean_active", "chaos_alerts",
    "publish_fired", "publish_resolved", "latency_fired",
    "latency_resolved", "tp_match", "post_mortems", "one_pm_per_fire",
    "ledger_entries", "ledger_ok", "merge_commute", "seconds",
)


def test_slo_leg_schema_keys():
    """Pin detail.slo (round 24): the clean/chaos arm tallies, the
    matching-spec + one-post-mortem-per-fire + ledger contracts, and the
    topology merge-commute property bit. Extend, never drop."""
    import inspect

    bench = _load_bench()
    src = inspect.getsource(bench._slo_bench)
    for key in SLO_KEYS:
        assert f'"{key}"' in src, key


def test_summary_line_carries_slo_token():
    """slo = [clean-arm alerts (must be 0), chaos-arm alerts (2 = both
    fault classes fired), folded contract bit]. The fold takes every
    RECORDED bit (mxu-token style): one recorded False reads 0, nothing
    recorded reads None — never vacuous green."""
    bench = _load_bench()
    doc = {"metric": "probes_per_sec_e2e", "value": 1000000.0,
           "unit": "probes/s", "vs_baseline": 1.0,
           "detail": {
               "slo": {"clean_alerts": 0, "chaos_alerts": 2,
                       "tp_match": True, "one_pm_per_fire": True,
                       "ledger_ok": True, "merge_commute": True},
           }}
    assert bench._summary_line(doc)["slo"] == [0, 2, 1]
    # one recorded False anywhere → the fold reads 0
    doc["detail"]["slo"]["one_pm_per_fire"] = False
    assert bench._summary_line(doc)["slo"] == [0, 2, 0]
    # partially recorded (clean arm only): absent bits are excluded
    # from the fold, present ones still gate
    doc["detail"]["slo"] = {"clean_alerts": 0, "merge_commute": True}
    assert bench._summary_line(doc)["slo"] == [0, None, 1]
    empty = bench._summary_line({"metric": "m", "value": 1.0, "unit": "u",
                                 "vs_baseline": 1.0, "detail": {}})
    assert empty["slo"] == [None] * 3
