"""Bench-history regression gate over the ``BENCH_r*.json`` trajectory.

A capture history is a series of ``BENCH_rNN.json`` files in one
directory (the repo root carries none on the current installation). This
module compares the newest capture against a trailing
window of prior captures, per metric, and answers one question: *did we
just get meaningfully worse at anything we already did better?*

Gate semantics (deliberately asymmetric — improvements always pass):

- per metric, the baseline is the **best** value in the trailing window
  (max for higher-is-better, min for lower-is-better). Comparing against
  the best — not the mean — means a regression can't hide behind a weak
  early capture while the trajectory was still climbing;
- a regression is a relative move past the metric's ``tolerance``
  (default 20%): ``newest < best × (1 - tol)`` for higher-is-better,
  ``newest > best × (1 + tol)`` for lower-is-better;
- metrics absent from a capture are skipped for that capture (r01 carries
  only img/s — history grows monotonically richer, the gate never
  requires retro-fitting old files);
- a metric may declare a ``guard`` path: only window captures whose guard
  value equals the newest capture's are comparable (``compile_s`` is
  guarded on ``phases.compile_cache_hit`` — a cold compile after a warm
  one is a cache state change, not a compiler regression).

Per-metric tolerances are meant to encode run-to-run noise: wide for
``h2d_gbps``, tight for img/s at best-of-5-reps. None has been measured on
the current installation.

Consumers: ``benchmarks/compare.py`` (standalone CLI + ``--self-test``
fixture run, wired into tier-1) and ``bench.py`` (embeds the verdict as a
``regressions`` block in each new capture, so BENCH_r06+ files carry
their own gate result).
"""

from __future__ import annotations

import glob as _glob
import json
import os
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

_BENCH_RE = re.compile(r"BENCH_r(\d+)\.json$")


@dataclass(frozen=True)
class MetricSpec:
    """One gated metric: a dotted ``path`` into the capture's parsed JSON,
    a direction, and an optional noise tolerance / comparability guard.
    ``fallback`` names a second path tried when ``path`` is absent — the
    continuity mechanism for renamed keys (``mfu_formula`` reads old
    captures' ``mfu``, so the r01-r05 trajectory keeps gating the formula
    series across the headline-MFU switch)."""

    name: str
    path: str
    higher_is_better: bool = True
    tolerance: Optional[float] = None  # None -> the gate's default
    guard: Optional[str] = None        # dotted path; must match to compare
    fallback: Optional[str] = None     # alternate path for older captures
    # absolute slack added to the relative band. Essential for
    # lower-is-better metrics that legitimately record 0.0 (zero SLO
    # minutes, an un-delayed reaction): best=0 collapses the relative
    # band to nothing and every later nonzero capture would flag
    # REGRESSED forever
    atol: float = 0.0


# The ISSUE-mandated gate set: img/s, MFU, h2d bandwidth, compile wall,
# int8 serving, and the router-tier headlines (BENCH_SERVE=1 `serving.
# router` block). Tolerances per the noise notes in the module docstring;
# `availability` during the kill-a-replica soak is a correctness-adjacent
# number, so its tolerance is tight.
DEFAULT_METRICS: Sequence[MetricSpec] = (
    MetricSpec("img_per_sec", "value"),
    # headline-MFU switch (this release): `mfu` is now the XLA
    # cost-analysis figure, so the continuous formula series moved to
    # `mfu_formula` — gated with an `mfu` fallback so r01-r05 captures
    # (which only carry `mfu` = the formula value) stay in the window;
    # the analytic series gates separately and only against captures
    # that measured it.
    MetricSpec("mfu_formula", "mfu_formula", fallback="mfu"),
    MetricSpec("mfu_analytic", "mfu_analytic"),
    MetricSpec("h2d_gbps", "h2d_gbps", tolerance=0.75),
    MetricSpec("compile_s", "phases.compile_s", higher_is_better=False,
               tolerance=0.5, guard="phases.compile_cache_hit"),
    MetricSpec("serve_int8_img_per_sec", "infer_int8_img_per_sec"),
    MetricSpec("serve_router_capacity_img_per_sec",
               "serving.router.capacity_img_per_sec",
               guard="serving.router.replicas"),
    MetricSpec("serve_router_capacity_scaling",
               "serving.router.capacity_scaling_x",
               guard="serving.router.replicas"),
    MetricSpec("serve_router_kill_availability",
               "serving.router.kill_soak.availability", tolerance=0.05),
    # the autoscaler's diurnal soak (BENCH_AUTOSCALE=1, PR 11):
    # availability through kill + canary + every fleet resize is
    # correctness-adjacent like the kill soak, so its tolerance is
    # tight; pre-PR-11 captures simply lack the `autoscale` block and
    # are skipped, not lied about (the gate's absent-metric semantics).
    MetricSpec("autoscale.availability", "autoscale.availability",
               tolerance=0.01),
    # atol: a clean capture records exactly 0.0 minutes/seconds (zero
    # breach, un-delayed first reaction), and the soak gates both at the
    # ~1-minute / one-cooldown budget — values inside the budget are
    # operating-as-designed, not a regression against a perfect window
    MetricSpec("autoscale.slo_violation_minutes",
               "autoscale.slo_violation_minutes", higher_is_better=False,
               tolerance=0.5, atol=1.0),
    # reaction time is budgeted by the configured cooldown — comparing
    # across different budgets would be a config change masquerading as
    # a regression, so the guard pins the knob
    MetricSpec("autoscale.scale_up_reaction_s",
               "autoscale.scale_up_reaction_s", higher_is_better=False,
               tolerance=0.5, guard="autoscale.up_cooldown_s", atol=5.0),
    # the pipeline kill-a-stage probe (BENCH_FAULTS=1, ISSUE 13):
    # detection + repartition-and-resume walls are loopback sub-second
    # numbers with scheduler noise, hence the atol slack; batches_lost is
    # correctness-adjacent (the journal contract says 0), so ANY increase
    # flags. Guarded on the probe's stage count — a topology change is
    # config, not regression. Pre-PR-13 captures lack the block and are
    # skipped, not lied about.
    MetricSpec("pipeline.detection_s", "resilience.pipeline.detection_s",
               higher_is_better=False, tolerance=1.0, atol=0.5,
               guard="resilience.pipeline.stages"),
    MetricSpec("pipeline.repartition_wall_s",
               "resilience.pipeline.repartition_wall_s",
               higher_is_better=False, tolerance=1.0, atol=2.0,
               guard="resilience.pipeline.stages"),
    MetricSpec("pipeline.batches_lost", "resilience.pipeline.batches_lost",
               higher_is_better=False, tolerance=0.0,
               guard="resilience.pipeline.stages"),
    # the uint8-first feed wire (ISSUE 16): bytes actually shipped
    # host-to-device per image is a design invariant of the wire contract
    # (uint8 + int labels — regrowing toward 4x/fp32 would be a feed-path
    # regression, not noise), so its tolerance is tight. Pre-r06 captures
    # lack the key and are skipped, not lied about.
    MetricSpec("wire_bytes_per_image",
               "streaming_timeline.wire_bytes_per_image",
               higher_is_better=False, tolerance=0.05),
    # streaming throughput is only comparable between captures that
    # shipped the same bytes per image — a wire-dtype change re-baselines
    # the feed, so the guard pins it; pre-r06 captures have no guard
    # value and are skipped (skip-not-lie), exactly like the autoscale
    # block's absent-metric semantics
    MetricSpec("streaming_img_per_sec", "streaming_img_per_sec",
               tolerance=0.3,
               guard="streaming_timeline.wire_bytes_per_image"),
    # goodput plane (ISSUE 18): the fraction of the capture's wall the
    # ledger attributes to compute. Only BENCH_OBS=1 r06+ captures carry
    # the block — earlier captures are skipped, not lied about.
    MetricSpec("goodput_fraction",
               "telemetry_essentials.goodput.goodput_fraction",
               tolerance=0.25),
    # gray-failure probes (BENCH_FAULTS=1 `resilience.gray` block,
    # ISSUE 19): detection + eviction walls for the stalled elastic peer
    # (50 ms absolute stall per step, ~10x its healthy compute wall)
    # are loopback sub-second numbers with scheduler noise (atol slack,
    # like the pipeline kill probe above); hedged-serving p99 is gated as
    # the with-hedge/without-hedge ratio so machine speed divides out.
    # Guards pin the probe's topology knobs — pre-r19 captures lack the
    # block and are skipped, not lied about.
    MetricSpec("gray.detection_s", "resilience.gray.detection_s",
               higher_is_better=False, tolerance=1.0, atol=1.0,
               guard="resilience.gray.peers"),
    MetricSpec("gray.evict_wall_s", "resilience.gray.evict_wall_s",
               higher_is_better=False, tolerance=1.0, atol=1.0,
               guard="resilience.gray.peers"),
    MetricSpec("gray.hedge_p99_ratio", "resilience.gray.hedge_p99_ratio",
               higher_is_better=False, tolerance=0.5, atol=0.5,
               guard="resilience.gray.hedge_replicas"),
    # continuous-batching decode (BENCH_DECODE=1 `decode` block,
    # ISSUE 20): generated tokens/s and mean slot occupancy for the
    # continuous batcher on the synthetic length mix; TTFT p99 is a
    # loopback sub-10ms wall, so wide relative tolerance + atol slack
    # (scheduler noise dominates). Guards pin the probe's slot count —
    # pre-r20 captures lack the block and are skipped, not lied about.
    MetricSpec("decode.tokens_per_sec", "decode.tokens_per_sec",
               tolerance=0.3, guard="decode.max_slots"),
    MetricSpec("decode.ttft_p99_ms", "decode.ttft_p99_ms",
               higher_is_better=False, tolerance=1.0, atol=10.0,
               guard="decode.max_slots"),
    MetricSpec("decode.slot_occupancy", "decode.slot_occupancy",
               tolerance=0.25, guard="decode.max_slots"),
)

DEFAULT_TOLERANCE = 0.2
DEFAULT_WINDOW = 4


def get_path(d: Any, path: str) -> Optional[Any]:
    """Resolve a dotted path into nested dicts; None on any miss."""
    cur = d
    for part in path.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur


def load_capture(path: str) -> Optional[Dict[str, Any]]:
    """One BENCH file → its parsed-metrics dict, or None when unreadable.
    Driver captures wrap the bench JSON under ``"parsed"``; a bare bench
    JSON (a local ``python bench.py > out.json``) is accepted as-is."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        return data["parsed"]
    if isinstance(data, dict) and "metric" in data:
        return data
    return None


def find_bench_files(root: str) -> List[str]:
    """``BENCH_r*.json`` under ``root``, oldest → newest by capture
    number (NOT mtime — a re-checkout resets mtimes, numbers don't)."""
    hits = []
    for p in _glob.glob(os.path.join(root, "BENCH_r*.json")):
        m = _BENCH_RE.search(os.path.basename(p))
        if m:
            hits.append((int(m.group(1)), p))
    return [p for _, p in sorted(hits)]


def compare(history: Sequence[Dict[str, Any]], *,
            metrics: Sequence[MetricSpec] = DEFAULT_METRICS,
            tolerance: float = DEFAULT_TOLERANCE,
            window: int = DEFAULT_WINDOW) -> Dict[str, Any]:
    """Gate the LAST entry of ``history`` against the trailing window of
    earlier entries. Returns the report dict (see keys below); raises
    ``ValueError`` on an empty history or nonsensical knobs."""
    if not history:
        raise ValueError("empty bench history: nothing to compare")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if not 0 < tolerance < 1:
        raise ValueError(f"tolerance must be in (0, 1), got {tolerance}")
    newest, prior = history[-1], list(history[:-1])
    rows: List[Dict[str, Any]] = []
    regressions: List[str] = []

    def resolve(entry, spec):
        v = get_path(entry, spec.path)
        if v is None and spec.fallback:
            v = get_path(entry, spec.fallback)
        return v

    for spec in metrics:
        tol = spec.tolerance if spec.tolerance is not None else tolerance
        cur = resolve(newest, spec)
        row: Dict[str, Any] = {
            "metric": spec.name, "path": spec.path,
            "higher_is_better": spec.higher_is_better,
            "tolerance": tol, "newest": cur,
        }
        if not isinstance(cur, (int, float)):
            row["verdict"] = "skipped: metric absent from newest capture"
            rows.append(row)
            continue
        guard_val = get_path(newest, spec.guard) if spec.guard else None
        vals: List[float] = []
        for entry in reversed(prior):  # newest-first until the window fills
            v = resolve(entry, spec)
            if not isinstance(v, (int, float)):
                continue
            if spec.guard and get_path(entry, spec.guard) != guard_val:
                continue  # different regime (e.g. cache warmth) — not
                # comparable, and saying so beats a false alarm
            vals.append(float(v))
            if len(vals) >= window:
                break
        if not vals:
            row["verdict"] = "skipped: no comparable prior capture"
            rows.append(row)
            continue
        best = max(vals) if spec.higher_is_better else min(vals)
        ratio = (float(cur) / best) if best else None
        if spec.higher_is_better:
            regressed = float(cur) < best * (1.0 - tol) - spec.atol
        else:
            regressed = float(cur) > best * (1.0 + tol) + spec.atol
        row.update({"window": list(reversed(vals)), "best": best,
                    "ratio": round(ratio, 4) if ratio is not None else None,
                    "verdict": "REGRESSED" if regressed else "ok"})
        rows.append(row)
        if regressed:
            regressions.append(spec.name)
    return {"metrics": rows, "regressions": regressions,
            "ok": not regressions, "window": window,
            "default_tolerance": tolerance}


def compare_files(paths: Sequence[str], **kw) -> Dict[str, Any]:
    """:func:`compare` over capture FILES (oldest → newest). Unreadable
    files are reported, never silently dropped."""
    history, skipped = [], []
    used = []
    for p in paths:
        cap = load_capture(p)
        if cap is None:
            skipped.append(p)
            continue
        history.append(cap)
        used.append(p)
    report = compare(history, **kw)
    report["files"] = used
    report["unparseable_files"] = skipped
    return report


def gate_current(current: Dict[str, Any], root: str, **kw
                 ) -> Optional[Dict[str, Any]]:
    """Gate an in-flight bench result (``bench.py``'s ``out`` dict)
    against the ``BENCH_r*.json`` history under ``root``. ``None`` when
    there is no history (first capture — nothing to regress against);
    never raises: the gate is a passenger on the bench run, not a way to
    crash it."""
    try:
        files = find_bench_files(root)
        history = [c for c in (load_capture(p) for p in files)
                   if c is not None]
        if not history:
            return None
        report = compare(history + [current], **kw)
        report["baseline_files"] = files
        return report
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}


def format_report(report: Dict[str, Any]) -> str:
    """Human-readable table for the CLI."""
    lines = []
    for row in report["metrics"]:
        if "best" not in row:
            lines.append(f"  {row['metric']:<24} {row['verdict']}")
            continue
        arrow = "↑" if row["higher_is_better"] else "↓"
        lines.append(
            f"  {row['metric']:<24} {arrow} newest {row['newest']:g} "
            f"vs best-of-{len(row['window'])} {row['best']:g} "
            f"(ratio {row['ratio']}, tol {row['tolerance']:.0%}) "
            f"-> {row['verdict']}")
    verdict = ("OK: no regressions" if report["ok"] else
               f"REGRESSED: {', '.join(report['regressions'])}")
    return "\n".join(lines + [verdict])
