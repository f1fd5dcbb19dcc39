"""bench_compare — turn the BENCH_r*.json pile into a gated signal.

Each bench round drops a ``BENCH_r<NN>.json`` at the repo root; until
now the perf trajectory lived in the reviewer's memory. This tool
diffs the two most recent rounds and exits nonzero when a headline
files/s throughput regressed by more than the threshold (default 15%),
so ``make bench-check`` (and CI) observes the trajectory instead of
trusting it.

Comparison rules:

- only *same-named* metrics compare — when the headline metric was
  renamed between rounds (e.g. ``cas_id_blake3_throughput`` →
  ``cas_id_e2e_throughput`` at the PR 3 rig change), the pair is
  reported as incomparable, not as a 98% regression;
- every throughput-shaped series is gated: the headline ``parsed
  .value`` plus any numeric ``extras`` entry whose name marks a rate
  (``*_files_per_s``, ``*_thumbs_per_s``, ``*_per_s``, ``*throughput*``,
  ``*_gbps``) — cas_id and thumbnail rates ride the same rule.

BENCH_E2E leg: when ``BENCH_E2E_prev.json`` and ``BENCH_E2E.json`` both
exist (bench_e2e.py archives the replaced artifact), the per-config
rate series (``config1.device_files_per_s``, …,
``config_warm.warm_files_per_s``, ``config_mesh.mesh2_files_per_s`` +
the warm journal hit rate and mesh scaling_efficiency) gate with the
same threshold.

BENCH_AUTOTUNE leg: when ``BENCH_AUTOTUNE.json`` exists (``make
bench-autotune``), the adaptive series gates ABSOLUTELY rather than
against a previous round: adaptive must be ≥1.3× static on the
fault-plane-throttled link and ≥0.95× static on the clean link — a
controller that loses to the config it replaced is a regression by
definition, no history needed.

BENCH_PROCS leg: when ``BENCH_PROCS.json`` exists (``make
bench-procs``), the multi-process A/B's bit-identity bar gates on
every rig; the scaling bars (pool ≥1.3× single, attribution
gap+gil_wait share shrinking) gate only on recordings taken with ≥2
cores and ≥2 workers — a 1-core recording is an honest floor, not the
design's scaling (the config_mesh precedent).

BENCH_SEMANTIC leg: when ``BENCH_SEMANTIC.json`` exists (``make
bench-semantic``), the semantic plane's correctness bars gate on every
rig: the warm pass must embed ZERO files (the journal vouch), the
planted near-duplicate must rank first among non-self hits, and the
warm media pass must beat cold by the recorded floor. Query latencies
ride the artifact ungated — absolute milliseconds on an unknown CI box
measure the box, not the index.

BENCH_SCALE leg: when ``BENCH_SCALE.json`` exists (``make bench-scale``
or ``make soak-smoke``), the churn-soak bars gate on every rig: zero
trend-SLO breaches, zero protected-class sheds, bounded fd/RSS drift,
and warm-pass throughput flatness — the gate re-derives the verdict
from the recorded figures rather than trusting the artifact's own. The
``--history`` leg additionally gates a least-squares growth slope over
the continuous ``resource_rss_mb``/``resource_fds`` history series.

Usage:
    python tools/bench_compare.py [--dir .] [--threshold 0.15] [old new]
Exit codes: 0 ok / nothing to compare, 1 regression, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from statistics import median
from typing import Any

DEFAULT_THRESHOLD = 0.15

# extras whose name marks a higher-is-better rate
_RATE_NAME = re.compile(
    r"(_files_per_s|_thumbs_per_s|_clips_per_s|_per_s|throughput|_gbps)$"
)


def _series(doc: dict[str, Any]) -> dict[str, float]:
    """Comparable {name: value} rates from one BENCH_r JSON."""
    parsed = doc.get("parsed") or {}
    out: dict[str, float] = {}
    metric, value = parsed.get("metric"), parsed.get("value")
    if isinstance(metric, str) and isinstance(value, (int, float)):
        out[metric] = float(value)
    for k, v in (parsed.get("extras") or {}).items():
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and _RATE_NAME.search(k):
            out[f"extras.{k}"] = float(v)
    return out


def compare(old: dict[str, Any], new: dict[str, Any],
            threshold: float = DEFAULT_THRESHOLD) -> dict[str, Any]:
    """Diff two bench documents. Returns {checked, regressions,
    skipped} where regressions is a list of {name, old, new, delta}."""
    old_s, new_s = _series(old), _series(new)
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    for name in sorted(old_s):
        if name not in new_s:
            skipped.append(f"{name}: absent in newer run")
            continue
        ov, nv = old_s[name], new_s[name]
        if ov <= 0:
            skipped.append(f"{name}: non-positive baseline {ov}")
            continue
        delta = (nv - ov) / ov
        rec = {"name": name, "old": ov, "new": nv,
               "delta_pct": round(delta * 100, 2)}
        checked.append(rec)
        if delta < -threshold:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


_E2E_CONFIGS = ("config1", "config3", "config4", "config5", "config_warm",
                "config_mesh", "config_mesh_procs", "config_continuum")
# higher-is-better ratio series gated alongside the rates
_E2E_RATIOS = ("journal_hit_rate", "warm_speedup_vs_cold", "scaling",
               "scaling_efficiency")
# parallelism ratios that only mean something on a multi-core rig: on
# one core N in-process nodes / pool workers time-slice a single GIL
# and the recorded ratio measures plane overhead, not the design's
# scaling — such recordings are honest floors, never gate material
_SCALING_KEYS = ("scaling", "scaling_efficiency", "pool_vs_single",
                 "per_worker_efficiency")


def _rig_cores(sec: dict[str, Any]) -> int:
    """Core count a config section was recorded on (rig_stamp's
    cpu_count, falling back to the older host_cores stamp). 0 when the
    artifact predates both stamps — treated as unknown, not single."""
    for key in ("cpu_count", "host_cores"):
        v = sec.get(key)
        if isinstance(v, int) and not isinstance(v, bool):
            return v
    return 0


def e2e_series(doc: dict[str, Any]) -> dict[str, float]:
    """Comparable {config.metric: value} rates from a BENCH_E2E doc."""
    out: dict[str, float] = {}
    for cfg in _E2E_CONFIGS:
        sec = doc.get(cfg)
        if not isinstance(sec, dict):
            continue
        for k, v in sec.items():
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            if _RATE_NAME.search(k) or k in _E2E_RATIOS:
                out[f"{cfg}.{k}"] = float(v)
    return out


# attribution buckets (bench_e2e attrib_summary: seconds per 1000
# items, LOWER is better). Buckets under the floor are noise — a 15%
# swing on 10 ms/kfile is measurement jitter, not a regression — but a
# bucket growing from under the floor to twice it still fails.
ATTRIB_MIN_S_PER_KFILE = 0.5
_ATTRIB_KEYS = ("device_s_per_kfile", "host_cpu_s_per_kfile",
                "link_s_per_kfile", "queue_wait_s_per_kfile",
                "gap_s_per_kfile")


def _compare_attrib(cfg: str, old_cfg: dict[str, Any],
                    new_cfg: dict[str, Any], threshold: float,
                    checked: list, regressions: list,
                    skipped: list) -> None:
    """Gate one config's attribution bucket split (lower-is-better
    seconds; a bucket absorbing >threshold more time per file fails
    like any rate regression)."""
    old_a, new_a = old_cfg.get("attrib"), new_cfg.get("attrib")
    if not isinstance(old_a, dict) or not isinstance(new_a, dict):
        return
    # fixed bucket keys plus whatever gap_<group>_s_per_kfile frame
    # groups the host profiler decomposed. Dynamic keys gate only when
    # BOTH runs recorded them: attrib_summary keeps a top-5, so a group
    # hovering around rank 5 (or a run with profiling off) is absent on
    # one side for reasons that are not perf — the total gap bucket
    # still gates unconditionally, so a real regression cannot hide in
    # a dropped group. `gap_other` is exempt entirely: growth there is
    # a classifier-coverage problem the profile-smoke gate owns (the
    # same policy as the history-share gate below).
    gap_keys = {
        k for k in old_a
        if k in new_a and k.startswith("gap_")
        and k.endswith("_s_per_kfile") and k != "gap_other_s_per_kfile"
    }
    for key in sorted(set(_ATTRIB_KEYS) | gap_keys):
        ov, nv = old_a.get(key), new_a.get(key)
        if not isinstance(ov, (int, float)) \
                or not isinstance(nv, (int, float)):
            continue
        name = f"{cfg}.attrib.{key}"
        if max(ov, nv) < ATTRIB_MIN_S_PER_KFILE:
            continue  # sub-floor noise either side
        if ov < ATTRIB_MIN_S_PER_KFILE:
            # a bucket appearing from (near) nothing: gate absolutely
            bad = nv >= 2 * ATTRIB_MIN_S_PER_KFILE
            rec = {"name": name, "old": ov, "new": nv,
                   "delta_pct": float("inf") if ov == 0
                   else round((nv - ov) / ov * 100, 2)}
            checked.append(rec)
            if bad:
                regressions.append(rec)
            continue
        delta = (nv - ov) / ov
        rec = {"name": name, "old": ov, "new": nv,
               "delta_pct": round(delta * 100, 2)}
        checked.append(rec)
        if delta > threshold:
            regressions.append(rec)


def compare_e2e(old: dict[str, Any], new: dict[str, Any],
                threshold: float = DEFAULT_THRESHOLD) -> dict[str, Any]:
    """Diff two BENCH_E2E documents (same result shape as compare())."""
    old_s, new_s = e2e_series(old), e2e_series(new)
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    for cfg in _E2E_CONFIGS:
        old_cfg, new_cfg = old.get(cfg), new.get(cfg)
        if isinstance(old_cfg, dict) and isinstance(new_cfg, dict):
            _compare_attrib(cfg, old_cfg, new_cfg, threshold,
                            checked, regressions, skipped)
    for name in sorted(old_s):
        cfg, _, key = name.partition(".")
        if name not in new_s:
            skipped.append(f"{name}: absent in newer run")
            continue
        if key in _SCALING_KEYS:
            oc = _rig_cores(old.get(cfg) or {})
            nc = _rig_cores(new.get(cfg) or {})
            if 0 < min(oc or 99, nc or 99) < 2:
                skipped.append(
                    f"{name}: recorded on a single-core rig — "
                    "honest-floor recording, scaling ratios ungated "
                    "(config_mesh precedent)"
                )
                continue
        ov, nv = old_s[name], new_s[name]
        if ov <= 0:
            skipped.append(f"{name}: non-positive baseline {ov}")
            continue
        delta = (nv - ov) / ov
        rec = {"name": name, "old": ov, "new": nv,
               "delta_pct": round(delta * 100, 2)}
        checked.append(rec)
        if delta < -threshold:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# the autotune A/B's absolute bars (mirrored in bench_e2e.py — the
# recorder stamps its own verdict, this gate re-derives it from the
# recorded rates so a hand-edited verdict cannot sneak past)
AUTOTUNE_THROTTLED_MIN = 1.3
AUTOTUNE_CLEAN_MIN = 0.95

# bench_serve.py's graceful-degradation bars (mirrored there; this gate
# re-derives every figure from the recorded arm rates)
SERVE_P99_RATIO_MAX = 5.0
SERVE_GOODPUT_MIN = 0.7
SERVE_SHED_P99_MAX_S = 1.0
# the multi-tenant leg's bars (telemetry/tenants.py acceptance): the
# serve sketch's resident top-K must recall ≥ this fraction of the
# exact client-side oracle, protected classes must not shed during the
# arm, and the SD_TENANT_OBS=0 replay must digest bit-identical bodies
SERVE_TENANT_RECALL_MIN = 0.9


def check_serve(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_SERVE document (same result shape as compare()).
    Lower-is-better bars (p99 ratio, shed p99) record delta as the
    margin below the bar; higher-is-better (goodput) as margin above."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    for leg_name in ("clean", "throttled"):
        leg = doc.get(leg_name)
        if not isinstance(leg, dict):
            skipped.append(f"serve.{leg_name}: leg missing")
            continue
        unloaded = (leg.get("unloaded") or {}).get("admitted_p99_ms")
        over = (leg.get("overload") or {}).get("admitted_p99_ms")
        cap = (leg.get("capacity") or {}).get("admitted_rps")
        good = (leg.get("overload") or {}).get("admitted_rps")
        bars = [
            # (name, value, bar, higher_is_better)
            ("p99_ratio",
             (over / unloaded) if unloaded and over is not None else None,
             SERVE_P99_RATIO_MAX, False),
            ("goodput_ratio",
             (good / cap) if cap and good is not None else None,
             SERVE_GOODPUT_MIN, True),
            ("shed_p99_s", leg.get("shed_p99_s"),
             SERVE_SHED_P99_MAX_S, False),
        ]
        for name, value, bar, higher in bars:
            full = f"serve.{leg_name}.{name}"
            if not isinstance(value, (int, float)) or isinstance(value, bool):
                skipped.append(f"{full}: not recorded")
                continue
            margin = (value - bar) if higher else (bar - value)
            rec = {"name": full, "old": bar, "new": round(float(value), 3),
                   "delta_pct": round(margin * 100, 2)}
            checked.append(rec)
            if margin < 0:
                regressions.append(rec)
        protected = (leg.get("overload") or {})
        answered = protected.get("health_answered")
        total = protected.get("health_total")
        bad = (
            protected.get("control_shed", 0) or protected.get("sync_shed", 0)
            or (total is not None and answered != total)
        )
        rec = {"name": f"serve.{leg_name}.protected_classes",
               "old": 0, "new": 1 if bad else 0,
               "delta_pct": -100.0 if bad else 0.0}
        checked.append(rec)
        if bad:
            regressions.append(rec)
    _check_serve_tenants(doc, checked, regressions, skipped)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


def _check_serve_tenants(doc: dict[str, Any], checked: list,
                         regressions: list, skipped: list) -> None:
    """Gate the multi-tenant leg of a BENCH_SERVE document; recordings
    that predate the leg skip it (nothing to gate, not a failure)."""
    ten = doc.get("tenants")
    if not isinstance(ten, dict):
        skipped.append("serve.tenants: leg not recorded (older artifact)")
        return

    recall = ten.get("topk_recall")
    if not isinstance(recall, (int, float)) or isinstance(recall, bool):
        skipped.append("serve.tenants.topk_recall: not recorded")
    else:
        rec = {"name": "serve.tenants.topk_recall",
               "old": SERVE_TENANT_RECALL_MIN,
               "new": round(float(recall), 3),
               "delta_pct": round(
                   (float(recall) - SERVE_TENANT_RECALL_MIN) * 100, 2)}
        checked.append(rec)
        if recall < SERVE_TENANT_RECALL_MIN:
            regressions.append(rec)

    bad = bool(ten.get("control_shed", 0) or ten.get("sync_shed", 0))
    rec = {"name": "serve.tenants.protected_classes", "old": 0,
           "new": 1 if bad else 0, "delta_pct": -100.0 if bad else 0.0}
    checked.append(rec)
    if bad:
        regressions.append(rec)

    identical = ten.get("obs_off_identical")
    if not isinstance(identical, bool):
        skipped.append("serve.tenants.obs_off_identical: not recorded")
    else:
        rec = {"name": "serve.tenants.obs_off_identical", "old": 1,
               "new": 1 if identical else 0,
               "delta_pct": 0.0 if identical else -100.0}
        checked.append(rec)
        if not identical:
            regressions.append(rec)


def check_autotune(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_AUTOTUNE document (same result shape as compare():
    {checked, regressions, skipped})."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    for leg, floor in (("throttled", AUTOTUNE_THROTTLED_MIN),
                       ("clean", AUTOTUNE_CLEAN_MIN)):
        # the recorded figure is the median of per-pair ratios (each
        # pair ran back-to-back, so the box's load drift cancels)
        ratio = doc.get(f"{leg}_adaptive_vs_static")
        if not isinstance(ratio, (int, float)) or isinstance(ratio, bool):
            skipped.append(f"autotune.{leg}: ratio missing")
            continue
        rec = {"name": f"autotune.{leg}_adaptive_vs_static",
               "old": floor, "new": round(float(ratio), 3),
               "delta_pct": round((float(ratio) - floor) * 100, 2)}
        checked.append(rec)
        if ratio < floor:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# bench_e2e config_procs' absolute bar (mirrored there; this gate
# re-derives the verdict from the recorded figures). The ratio and the
# gap/gil-shrink bars gate only on recordings taken on a >=2-core rig
# with >=2 workers — on a 1-core box N workers + the owner time-slice
# one core, so the recording is an honest floor, not the design's
# scaling (the config_mesh precedent). Bit-identity gates EVERYWHERE:
# a pool that changes pass output is a correctness regression no
# matter how many cores recorded it.
PROCS_RATIO_MIN = 1.3


def check_procs(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_PROCS document (same result shape as compare())."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    identical = doc.get("identical")
    rec = {"name": "procs.identical", "old": 1,
           "new": 1 if identical else 0,
           "delta_pct": 0.0 if identical else -100.0}
    checked.append(rec)
    if not identical:
        regressions.append(rec)
    cores = doc.get("host_cores") or 0
    workers = doc.get("workers") or 0
    ratio = doc.get("pool_vs_single")
    if cores < 2 or workers < 2:
        skipped.append(
            f"procs.pool_vs_single: recorded on a {cores}-core rig with "
            f"{workers} worker(s) — honest-floor recording, scaling "
            "bars ungated (config_mesh precedent)"
        )
        return {"checked": checked, "regressions": regressions,
                "skipped": skipped}
    if not isinstance(ratio, (int, float)) or isinstance(ratio, bool):
        skipped.append("procs.pool_vs_single: ratio missing")
        return {"checked": checked, "regressions": regressions,
                "skipped": skipped}
    rec = {"name": "procs.pool_vs_single", "old": PROCS_RATIO_MIN,
           "new": round(float(ratio), 3),
           "delta_pct": round((float(ratio) - PROCS_RATIO_MIN) * 100, 2)}
    checked.append(rec)
    if ratio < PROCS_RATIO_MIN:
        regressions.append(rec)
    shares_s = [doc.get("gap_share_single"), doc.get("gil_share_single")]
    shares_p = [doc.get("gap_share_pool"), doc.get("gil_share_pool")]
    if all(not isinstance(v, (int, float)) for v in shares_s):
        skipped.append("procs.gap_gil_share: not recorded (profiler off)")
    else:
        tot_s = sum(v for v in shares_s if isinstance(v, (int, float)))
        tot_p = sum(v for v in shares_p if isinstance(v, (int, float)))
        rec = {"name": "procs.gap_gil_share", "old": round(tot_s, 4),
               "new": round(tot_p, 4),
               "delta_pct": round((tot_p - tot_s) * 100, 2)}
        checked.append(rec)
        # the plane's whole thesis: the pool must SHRINK the
        # unattributed-gap + gil_wait share, not just the wall clock
        if tot_p >= tot_s:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# bench_e2e config_continuum's absolute bars (mirrored there; this
# gate re-derives the verdict from the recorded figures). Bit-identity
# (webp bytes + embedding vectors across every arm of every repeat)
# gates on EVERY rig: distribution that changes stage output is a
# correctness regression regardless of core count. The efficiency
# floor and the gap+gil-shrink bar gate only on >=2-core recordings
# (the config_mesh / config_procs precedent). The floor is
# config_mesh_procs' recorded scaling_efficiency: the unified
# scheduler must beat the plane it fused.
CONTINUUM_EFF_MIN = 0.302


def check_continuum(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_CONTINUUM document (same result shape as
    compare())."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    identical = doc.get("identical")
    rec = {"name": "continuum.identical", "old": 1,
           "new": 1 if identical else 0,
           "delta_pct": 0.0 if identical else -100.0}
    checked.append(rec)
    if not identical:
        regressions.append(rec)
    cores = _rig_cores(doc)
    if cores < 2:
        skipped.append(
            f"continuum.scaling_efficiency: recorded on a {cores}-core "
            "rig — honest-floor recording, scaling bars ungated "
            "(config_mesh precedent)"
        )
        return {"checked": checked, "regressions": regressions,
                "skipped": skipped}
    eff = doc.get("scaling_efficiency")
    if not isinstance(eff, (int, float)) or isinstance(eff, bool):
        skipped.append("continuum.scaling_efficiency: missing")
    else:
        rec = {"name": "continuum.scaling_efficiency",
               "old": CONTINUUM_EFF_MIN, "new": round(float(eff), 3),
               "delta_pct": round((float(eff) - CONTINUUM_EFF_MIN) * 100,
                                  2)}
        checked.append(rec)
        if eff <= CONTINUUM_EFF_MIN:
            regressions.append(rec)
    shares_l = [doc.get("gap_share_local"), doc.get("gil_share_local")]
    shares_m = [doc.get("gap_share_mesh"), doc.get("gil_share_mesh")]
    if all(not isinstance(v, (int, float)) for v in shares_l):
        skipped.append(
            "continuum.gap_gil_share: not recorded (profiler off)")
    else:
        tot_l = sum(v for v in shares_l if isinstance(v, (int, float)))
        tot_m = sum(v for v in shares_m if isinstance(v, (int, float)))
        rec = {"name": "continuum.gap_gil_share", "old": round(tot_l, 4),
               "new": round(tot_m, 4),
               "delta_pct": round((tot_m - tot_l) * 100, 2)}
        checked.append(rec)
        # the continuum's thesis: distributing the stage legs must
        # SHRINK the unattributed-gap + gil_wait share, not just move
        # wall clock around
        if tot_m >= tot_l:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# bench_e2e config_semantic's absolute bars (mirrored there; this gate
# re-derives the verdict from the recorded figures). All three bars are
# correctness-shaped, so they gate on every rig: a warm pass that
# embeds ANY unchanged file broke the journal vouch, a planted
# near-duplicate that isn't the top non-self hit broke the
# embed→index→score chain, and a warm media pass slower than the floor
# means the skip path stopped skipping. Query latencies are recorded,
# not gated — absolute milliseconds on an unknown rig measure the rig.
SEMANTIC_WARM_SPEEDUP_MIN = 1.2


def check_semantic(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_SEMANTIC document (same result shape as compare())."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []

    warm = doc.get("files_embedded_warm")
    if not isinstance(warm, int) or isinstance(warm, bool):
        skipped.append("semantic.warm_zero_embeds: count missing")
    else:
        rec = {"name": "semantic.files_embedded_warm", "old": 0,
               "new": warm, "delta_pct": 0.0 if warm == 0 else -100.0}
        checked.append(rec)
        if warm != 0:
            regressions.append(rec)

    rank1 = doc.get("neardup_rank1")
    if not isinstance(rank1, bool):
        skipped.append("semantic.neardup_rank1: verdict missing")
    else:
        rec = {"name": "semantic.neardup_rank1", "old": 1,
               "new": 1 if rank1 else 0,
               "delta_pct": 0.0 if rank1 else -100.0}
        checked.append(rec)
        if not rank1:
            regressions.append(rec)

    speedup = doc.get("warm_media_speedup")
    if not isinstance(speedup, (int, float)) or isinstance(speedup, bool):
        skipped.append("semantic.warm_media_speedup: ratio missing")
    else:
        rec = {"name": "semantic.warm_media_speedup",
               "old": SEMANTIC_WARM_SPEEDUP_MIN,
               "new": round(float(speedup), 2),
               "delta_pct": round(
                   (float(speedup) - SEMANTIC_WARM_SPEEDUP_MIN) * 100, 2)}
        checked.append(rec)
        if speedup < SEMANTIC_WARM_SPEEDUP_MIN:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# bench-scale absolute bars — mirrored in bench_scale.py. The artifact
# records its own verdict, but the gate re-derives it from the recorded
# figures so a bench_scale.py bug can't silently wave a bad run through.
SCALE_FD_DELTA_MAX = 32
SCALE_RSS_DELTA_MAX_MB = 512.0
SCALE_FLATNESS_MIN = 0.5


def check_scale(doc: dict[str, Any]) -> dict[str, Any]:
    """Gate a BENCH_SCALE document (same result shape as compare()).
    Re-derives the soak verdict: zero trend-SLO breaches, zero
    protected-class sheds, bounded fd/RSS drift over the run, and
    warm-pass throughput flatness above the floor."""
    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    res = doc.get("resources") or {}

    breaches = (doc.get("slo") or {}).get("breaches")
    if not isinstance(breaches, list):
        skipped.append("scale.slo_breaches: not recorded")
    else:
        rec = {"name": "scale.slo_breaches", "old": 0, "new": len(breaches),
               "delta_pct": -100.0 if breaches else 0.0}
        checked.append(rec)
        if breaches:
            regressions.append(rec)

    sheds = doc.get("protected_sheds")
    if not isinstance(sheds, int) or isinstance(sheds, bool):
        skipped.append("scale.protected_sheds: not recorded")
    else:
        rec = {"name": "scale.protected_sheds", "old": 0, "new": sheds,
               "delta_pct": -100.0 if sheds else 0.0}
        checked.append(rec)
        if sheds:
            regressions.append(rec)

    bars = [
        # (name, value, bar, higher_is_better)
        ("fd_delta", res.get("fd_delta"), SCALE_FD_DELTA_MAX, False),
        ("rss_delta_mb", res.get("rss_delta_mb"),
         SCALE_RSS_DELTA_MAX_MB, False),
        ("flatness", (doc.get("throughput") or {}).get("flatness"),
         SCALE_FLATNESS_MIN, True),
    ]
    for name, value, bar, higher in bars:
        full = f"scale.{name}"
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            skipped.append(f"{full}: not recorded")
            continue
        value = abs(float(value)) if name == "fd_delta" else float(value)
        margin = (value - bar) if higher else (bar - value)
        rec = {"name": full, "old": bar, "new": round(value, 3),
               "delta_pct": round(margin * 100, 2)}
        checked.append(rec)
        if margin < 0:
            regressions.append(rec)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# --- telemetry-history leg (telemetry/history.py segment store) ------------

#: history series gated as higher-is-better rates; idle (0) samples are
#: excluded — a node that stopped indexing is quiet, not slow
_HISTORY_RATE_SERIES = ("files_per_s",)
#: recent window = the trailing fraction of the series compared against
#: the median of everything before it
HISTORY_RECENT_FRACTION = 0.2
HISTORY_MIN_SAMPLES = 10


def check_history(directory: str,
                  threshold: float = DEFAULT_THRESHOLD) -> dict[str, Any]:
    """Gate a node's persistent telemetry history (the
    ``<data-dir>/telemetry_history/`` segment store): the recent
    window's median throughput must not sit more than ``threshold``
    below the long-baseline median. Unlike the artifact diffs, this
    reads the *continuous* series — restarts included — so a
    regression that landed between two bench rounds still fails."""
    # the history store is plain JSONL; the reader lives with the
    # writer so the two formats cannot drift apart. Script invocation
    # puts tools/ (not the repo root) on sys.path — fix that up.
    try:
        from spacedrive_tpu.telemetry import history as _history
    except ImportError:
        sys.path.insert(
            0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        from spacedrive_tpu.telemetry import history as _history

    checked: list[dict[str, Any]] = []
    regressions: list[dict[str, Any]] = []
    skipped: list[str] = []
    for name in _HISTORY_RATE_SERIES:
        samples = [v for _, v in _history.series(directory, name) if v > 0]
        full = f"history.{name}"
        if len(samples) < HISTORY_MIN_SAMPLES:
            skipped.append(
                f"{full}: {len(samples)} non-idle samples "
                f"(< {HISTORY_MIN_SAMPLES}) — nothing to gate"
            )
            continue
        cut = max(1, int(len(samples) * (1 - HISTORY_RECENT_FRACTION)))
        baseline, recent = samples[:cut], samples[cut:]
        if not recent:
            skipped.append(f"{full}: no recent window")
            continue
        ov, nv = median(baseline), median(recent)
        if ov <= 0:
            skipped.append(f"{full}: non-positive baseline {ov}")
            continue
        delta = (nv - ov) / ov
        rec = {"name": full, "old": round(ov, 2), "new": round(nv, 2),
               "delta_pct": round(delta * 100, 2)}
        checked.append(rec)
        if delta < -threshold:
            regressions.append(rec)
    _check_history_profile_shares(_history, directory, checked,
                                  regressions, skipped)
    _check_history_growth(_history, directory, checked,
                          regressions, skipped)
    return {"checked": checked, "regressions": regressions,
            "skipped": skipped}


# resource-growth series (telemetry/resources.py sampler → history):
# gated as a bounded least-squares slope over the CONTINUOUS record,
# mirroring the trend-SLO bars (SD_SLO_RSS_MB_PER_H / SD_SLO_FD_PER_H
# defaults) — a leak that lands between bench rounds still fails here.
_HISTORY_GROWTH_SERIES = (
    ("resource_rss_mb", 64.0),  # MB per hour
    ("resource_fds", 50.0),     # descriptors per hour
)


def _check_history_growth(_history, directory: str,
                          checked: list, regressions: list,
                          skipped: list) -> None:
    from spacedrive_tpu.telemetry.slo import _slope_per_h

    for name, bar in _HISTORY_GROWTH_SERIES:
        pts = _history.series(directory, name)
        full = f"history.{name}.slope_per_h"
        if len(pts) < HISTORY_MIN_SAMPLES:
            skipped.append(
                f"{full}: {len(pts)} samples "
                f"(< {HISTORY_MIN_SAMPLES}) — nothing to gate"
            )
            continue
        span_h = (pts[-1][0] - pts[0][0]) / 3600.0
        if span_h < 0.25:
            # a slope extrapolated from a few minutes of warmup is
            # noise, not a leak — the trend SLO's warmup exclusion,
            # applied to the offline record
            skipped.append(
                f"{full}: {span_h * 60:.1f} min span (< 15 min) — "
                f"too short to extrapolate a per-hour slope"
            )
            continue
        slope = _slope_per_h(pts)
        rec = {"name": full, "old": bar, "new": round(slope, 3),
               "delta_pct": round((bar - slope) / bar * 100, 2)}
        checked.append(rec)
        if slope > bar:
            regressions.append(rec)


# host-profiler frame-group shares (history `profile_share_*` series,
# 0..1): attribution drift against the CONTINUOUS record. Shares are
# ratios, so the gate is an absolute delta — a group absorbing 15
# percentage points more of all samples than its baseline is a code
# path that got hot between bench rounds, restarts included.
PROFILE_SHARE_MAX_DELTA = 0.15


def _check_history_profile_shares(_history, directory: str,
                                  checked: list, regressions: list,
                                  skipped: list) -> None:
    names = sorted({
        n for rec in _history.read(directory)
        for n in (rec.get("v") or {})
        if n.startswith("profile_share_") and not n.endswith(
            ("__min", "__max"))
    })
    for name in names:
        if name.endswith("_other"):
            # the honesty bucket: growth there is a classifier-coverage
            # problem the profile-smoke gate owns, not a perf series
            continue
        # zero-valued samples are profiler-off (SD_PROFILE=0) or
        # pre-first-tick periods, not "this group vanished" — the same
        # idle-exclusion the throughput gate above applies
        samples = [v for _, v in _history.series(directory, name) if v > 0]
        full = f"history.{name}"
        if len(samples) < HISTORY_MIN_SAMPLES:
            skipped.append(
                f"{full}: {len(samples)} samples "
                f"(< {HISTORY_MIN_SAMPLES}) — nothing to gate"
            )
            continue
        cut = max(1, int(len(samples) * (1 - HISTORY_RECENT_FRACTION)))
        baseline, recent = samples[:cut], samples[cut:]
        if not recent:
            skipped.append(f"{full}: no recent window")
            continue
        ov, nv = median(baseline), median(recent)
        rec = {"name": full, "old": round(ov, 4), "new": round(nv, 4),
               "delta_pct": round((nv - ov) * 100, 2)}
        checked.append(rec)
        if nv - ov > PROFILE_SHARE_MAX_DELTA:
            regressions.append(rec)


def latest_pair(bench_dir: str) -> tuple[str, str] | None:
    files = sorted(glob.glob(os.path.join(bench_dir, "BENCH_r*.json")))
    if len(files) < 2:
        return None
    return files[-2], files[-1]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("files", nargs="*",
                    help="explicit OLD NEW pair (default: two most recent "
                         "BENCH_r*.json in --dir)")
    ap.add_argument("--dir", default=".",
                    help="where BENCH_r*.json live (default: cwd)")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="fractional regression that fails the gate "
                         "(default 0.15 = 15%%)")
    ap.add_argument("--history", metavar="DIR", default=None,
                    help="additionally gate a node's persistent telemetry "
                         "history (<data-dir>/telemetry_history): recent "
                         "median throughput vs the long baseline — "
                         "regressions that landed between bench rounds "
                         "still fail")
    args = ap.parse_args(argv)

    if args.files and len(args.files) != 2:
        print("bench-compare: pass exactly two files (old new), or none",
              file=sys.stderr)
        return 2
    def render(label: str, result: dict[str, Any]) -> None:
        print(f"bench-compare: {label}  (gate: -{args.threshold:.0%})")
        for rec in result["checked"]:
            mark = "REGRESSION" if rec in result["regressions"] else "ok"
            print(f"  {mark:>10}  {rec['name']}: {rec['old']:g} -> "
                  f"{rec['new']:g}  ({rec['delta_pct']:+.1f}%)")
        for note in result["skipped"]:
            print(f"     skipped  {note}")
        if not result["checked"]:
            print("  no comparable series (metric renamed between rounds?)")

    total_regressions = 0

    if args.files:
        pairs: list[tuple[str, str]] = [tuple(args.files)]
    else:
        pair = latest_pair(args.dir)
        pairs = [pair] if pair else []
        if not pairs:
            print("bench-compare: fewer than two BENCH_r*.json rounds — "
                  "nothing to gate")

    for old_path, new_path in pairs:
        try:
            with open(old_path) as f:
                old = json.load(f)
            with open(new_path) as f:
                new = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench-compare: cannot read bench JSON: {e}",
                  file=sys.stderr)
            return 2
        result = compare(old, new, args.threshold)
        render(f"{os.path.basename(old_path)} -> "
               f"{os.path.basename(new_path)}", result)
        total_regressions += len(result["regressions"])

    # BENCH_E2E leg (only in --dir mode; explicit pairs stay BENCH_r)
    if not args.files:
        e2e_prev = os.path.join(args.dir, "BENCH_E2E_prev.json")
        e2e_cur = os.path.join(args.dir, "BENCH_E2E.json")
        if os.path.exists(e2e_prev) and os.path.exists(e2e_cur):
            try:
                with open(e2e_prev) as f:
                    old = json.load(f)
                with open(e2e_cur) as f:
                    new = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_E2E JSON: {e}",
                      file=sys.stderr)
                return 2
            result = compare_e2e(old, new, args.threshold)
            render("BENCH_E2E_prev.json -> BENCH_E2E.json", result)
            total_regressions += len(result["regressions"])
        at_path = os.path.join(args.dir, "BENCH_AUTOTUNE.json")
        if os.path.exists(at_path):
            try:
                with open(at_path) as f:
                    at_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_AUTOTUNE JSON: {e}",
                      file=sys.stderr)
                return 2
            result = check_autotune(at_doc)
            render("BENCH_AUTOTUNE.json (absolute adaptive-vs-static bars)",
                   result)
            total_regressions += len(result["regressions"])
        pr_path = os.path.join(args.dir, "BENCH_PROCS.json")
        if os.path.exists(pr_path):
            try:
                with open(pr_path) as f:
                    pr_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_PROCS JSON: {e}",
                      file=sys.stderr)
                return 2
            result = check_procs(pr_doc)
            render("BENCH_PROCS.json (absolute pool-vs-single bars)",
                   result)
            total_regressions += len(result["regressions"])
        ct_path = os.path.join(args.dir, "BENCH_CONTINUUM.json")
        if os.path.exists(ct_path):
            try:
                with open(ct_path) as f:
                    ct_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_CONTINUUM "
                      f"JSON: {e}", file=sys.stderr)
                return 2
            result = check_continuum(ct_doc)
            render("BENCH_CONTINUUM.json (absolute stage-continuum bars)",
                   result)
            total_regressions += len(result["regressions"])
        sm_path = os.path.join(args.dir, "BENCH_SEMANTIC.json")
        if os.path.exists(sm_path):
            try:
                with open(sm_path) as f:
                    sm_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_SEMANTIC JSON: {e}",
                      file=sys.stderr)
                return 2
            result = check_semantic(sm_doc)
            render("BENCH_SEMANTIC.json (absolute semantic-plane bars)",
                   result)
            total_regressions += len(result["regressions"])
        sv_path = os.path.join(args.dir, "BENCH_SERVE.json")
        if os.path.exists(sv_path):
            try:
                with open(sv_path) as f:
                    sv_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_SERVE JSON: {e}",
                      file=sys.stderr)
                return 2
            result = check_serve(sv_doc)
            render("BENCH_SERVE.json (absolute graceful-degradation bars)",
                   result)
            total_regressions += len(result["regressions"])
        sc_path = os.path.join(args.dir, "BENCH_SCALE.json")
        if os.path.exists(sc_path):
            try:
                with open(sc_path) as f:
                    sc_doc = json.load(f)
            except (OSError, ValueError) as e:
                print(f"bench-compare: cannot read BENCH_SCALE JSON: {e}",
                      file=sys.stderr)
                return 2
            result = check_scale(sc_doc)
            render("BENCH_SCALE.json (absolute resource-growth bars)",
                   result)
            total_regressions += len(result["regressions"])

    if args.history:
        result = check_history(args.history, args.threshold)
        render(f"telemetry history ({args.history})", result)
        total_regressions += len(result["regressions"])

    if total_regressions:
        print(f"bench-compare: {total_regressions} series regressed "
              f"past the {args.threshold:.0%} gate", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
