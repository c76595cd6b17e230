"""Bench-regression gate: diff fresh BENCH_*.json against committed baselines.

CI's bench jobs (`benchmarks-smoke`, `matmat-smoke`, `solve-smoke`,
`decode-smoke`, `chaos-smoke`) run `python -m benchmarks.run --smoke|
--matmat|--solve|--decode|--chaos`, which writes BENCH_smoke.json /
BENCH_matmat.json / BENCH_solve.json / BENCH_decode.json /
BENCH_chaos.json into the working directory. This script compares the higher-is-better metrics in those files
against the baselines committed under ``benchmarks/baselines/`` and exits
nonzero when any metric drops more than its tolerance — the perf trajectory
becomes a merge gate instead of an artifact someone has to remember to read.

Two metric classes, two tolerances:

  * **model** metrics (perf-model mem_util / traffic ratios, the packed-plan
    metadata reduction) are deterministic functions of the plan — any drop
    beyond ``--model-tol`` (default 10%) is a real modeling/plan regression.
  * **measured** metrics (solver iters/s, retry cheapness) carry shared
    CI-runner jitter, so they get the looser ``--measured-tol`` (default
    50%) *and* a jitter floor: a drop only fails once it also clears
    ``--jitter-floor`` (default 0.10) in absolute terms, so near-zero
    baselines can't fail on noise-sized wiggles. Real regressions — a lost
    kernel fusion, a broken plan cache — blow well past both.

Usage:
  python tools/bench_compare.py                # compare whatever files exist
  python tools/bench_compare.py --require smoke    # that file must exist
  python tools/bench_compare.py --update           # regenerate baselines
  python tools/bench_compare.py --summary out.md   # markdown gate table
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

BASELINE_DIR = os.path.join("benchmarks", "baselines")
BENCH_FILES = {
    "smoke": "BENCH_smoke.json",
    "matmat": "BENCH_matmat.json",
    "solve": "BENCH_solve.json",
    "decode": "BENCH_decode.json",
    "chaos": "BENCH_chaos.json",
}
MODEL_TOL = 0.10
MEASURED_TOL = 0.50
JITTER_FLOOR = 0.10


def _parse_derived(derived: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for part in derived.split(";"):
        if "=" in part:
            key, val = part.split("=", 1)
            out[key] = val
    return out


def _fig5_metrics(payload: dict) -> List[Tuple[str, float, str]]:
    """Model-side mem_util + traffic_ratio per fig5 (matrix, system) row.
    Timings (us_per_call) are deliberately not compared — absolute CPU
    timings don't survive the trip between a dev box and a CI runner."""
    metrics: List[Tuple[str, float, str]] = []
    for row in payload.get("rows", []):
        name = row.get("name", "")
        if not name.startswith("fig5/"):
            continue
        derived = _parse_derived(row.get("derived", ""))
        for key in ("mem_util", "traffic_ratio"):
            if key in derived:
                metrics.append(
                    (f"{name}/{key}", float(derived[key]), "model")
                )
    return metrics


def extract_metrics(kind: str, payload: dict) -> List[Tuple[str, float, str]]:
    """Flatten one BENCH payload into (metric, value, class) rows; every
    value is higher-is-better."""
    metrics: List[Tuple[str, float, str]] = []
    if kind == "smoke":
        metrics += _fig5_metrics(payload)
        for name, row in (payload.get("packed_plans") or {}).items():
            # mem_util is reported but not gated: achieved bandwidth drops
            # legitimately when traffic shrinks in a compute-bound regime
            metrics.append((
                f"packed/{name}/traffic_reduction",
                float(row["traffic_reduction"]), "model",
            ))
        part = payload.get("sharded_partition") or {}
        strategies = part.get("strategies") or {}
        if "even" in strategies and "cost" in strategies:
            # imbalance is lower-is-better; the tracked metric is the
            # cost partitioner's gain over the even split (deterministic
            # model output, not a timing)
            metrics.append((
                "sharded/partition/imbalance_gain",
                float(strategies["even"]["imbalance"])
                / float(strategies["cost"]["imbalance"]), "model",
            ))
        for name, row in (payload.get("value_dtypes") or {}).items():
            metrics.append((
                f"values/{name}/traffic_reduction",
                float(row["traffic_reduction"]), "model",
            ))
    elif kind == "matmat":
        # Parity only, gated by `benchmarks.run --matmat` itself: nothing
        # higher-is-better to compare, so the file need only exist.
        pass
    elif kind == "solve":
        solve = payload.get("solve") or {}
        for solver in ("cg", "pagerank"):
            for name, row in (solve.get(solver) or {}).items():
                metrics.append((
                    f"solve/{solver}/{name}/iters_per_s",
                    float(row["iters_per_s"]), "measured",
                ))
    elif kind == "decode":
        decode = payload.get("decode") or {}
        sp = decode.get("shared_prefix") or {}
        # plan-structural metrics are deterministic functions of the stream
        for key in ("dedup_ratio", "model_speedup_shared"):
            if key in sp:
                metrics.append((
                    f"decode/shared_prefix/{key}", float(sp[key]), "model"
                ))
        plan = decode.get("plan") or {}
        if "coalesce_rate" in plan:
            metrics.append((
                "decode/plan/coalesce_rate",
                float(plan["coalesce_rate"]), "model",
            ))
        if "tokens_per_s" in decode:
            metrics.append((
                "decode/tokens_per_s", float(decode["tokens_per_s"]),
                "measured",
            ))
    elif kind == "chaos":
        chaos = payload.get("chaos") or {}
        totals = chaos.get("totals") or {}
        # recovery accounting is deterministic under the seeded fault plan:
        # any drop is a healing-path regression, not runner jitter
        if "recovery_rate" in totals:
            metrics.append((
                "chaos/totals/recovery_rate",
                float(totals["recovery_rate"]), "model",
            ))
        if "injected" in totals:
            # injected count dropping means a fault site went dark — the
            # drill stopped exercising a healing path it used to cover
            metrics.append((
                "chaos/totals/injected", float(totals["injected"]), "model",
            ))
        sr = chaos.get("store_read") or {}
        for key in ("quarantined", "rebuilds", "rebuilt_disk_hits"):
            if key in sr:
                metrics.append((
                    f"chaos/store_read/{key}", float(sr[key]), "model",
                ))
        stream = chaos.get("stream_retry") or {}
        if "retry_overhead" in stream:
            # lower-is-better, so gate its inverse (retry cheapness): a
            # ballooning retry path shows up as this metric dropping
            metrics.append((
                "chaos/stream/retry_cheapness",
                1.0 / float(stream["retry_overhead"]), "measured",
            ))
    else:
        raise ValueError(f"unknown bench kind {kind!r}")
    return metrics


def compare(
    baseline: List[Tuple[str, float, str]],
    current: List[Tuple[str, float, str]],
    *,
    model_tol: float,
    measured_tol: float,
    jitter_floor: float,
) -> List[dict]:
    """Pair metrics by name and flag regressions. Metrics new in `current`
    pass (no baseline to regress from); metrics that vanished fail — a
    silently dropped gate is itself a regression."""
    base_by_name = {name: (val, cls) for name, val, cls in baseline}
    cur_by_name = {name: (val, cls) for name, val, cls in current}
    rows: List[dict] = []
    for name, (b_val, cls) in base_by_name.items():
        if name not in cur_by_name:
            rows.append({
                "metric": name, "baseline": b_val, "current": None,
                "class": cls, "status": "MISSING",
            })
            continue
        c_val = cur_by_name[name][0]
        tol = model_tol if cls == "model" else measured_tol
        drop = b_val - c_val
        rel_drop = drop / b_val if b_val else 0.0
        failed = rel_drop > tol
        if cls == "measured" and failed:
            # jitter floor: a relative drop on a near-zero baseline must
            # also be a real absolute move before it can fail the gate
            failed = drop > jitter_floor
        rows.append({
            "metric": name, "baseline": b_val, "current": c_val,
            "class": cls, "rel_drop": rel_drop,
            "status": "FAIL" if failed else "ok",
        })
    for name, (c_val, cls) in cur_by_name.items():
        if name not in base_by_name:
            rows.append({
                "metric": name, "baseline": None, "current": c_val,
                "class": cls, "status": "new",
            })
    return rows


def _fmt(val: Optional[float]) -> str:
    return "-" if val is None else f"{val:.4g}"


def write_summary(path: str, kind: str, rows: List[dict]) -> None:
    """Append one gate table in GitHub-flavored markdown (bench jobs point
    this at $GITHUB_STEP_SUMMARY)."""
    lines = [
        f"### bench-compare: {kind}",
        "",
        "| metric | class | baseline | current | drop | status |",
        "|---|---|---|---|---|---|",
    ]
    for r in sorted(rows, key=lambda r: (r["status"] == "ok", r["metric"])):
        drop = r.get("rel_drop")
        lines.append(
            f"| `{r['metric']}` | {r['class']} | {_fmt(r['baseline'])} | "
            f"{_fmt(r['current'])} | "
            f"{'-' if drop is None else f'{drop * 100:.1f}%'} | "
            f"{'❌ ' + r['status'] if r['status'] in ('FAIL', 'MISSING') else r['status']} |"
        )
    lines.append("")
    with open(path, "a") as f:
        f.write("\n".join(lines) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser(
        description="gate fresh BENCH_*.json files against committed "
        "baselines (benchmarks/baselines/)",
    )
    ap.add_argument("--bench-dir", default=".",
                    help="directory holding the fresh BENCH_*.json files")
    ap.add_argument("--baseline-dir", default=BASELINE_DIR)
    ap.add_argument(
        "--require", action="append", choices=sorted(BENCH_FILES),
        default=None,
        help="fail unless this bench file exists and is compared (default: "
        "compare whichever files exist); repeatable",
    )
    ap.add_argument("--model-tol", type=float, default=MODEL_TOL)
    ap.add_argument("--measured-tol", type=float, default=MEASURED_TOL)
    ap.add_argument("--jitter-floor", type=float, default=JITTER_FLOOR)
    ap.add_argument(
        "--update", action="store_true",
        help="copy the fresh files into the baseline dir instead of "
        "comparing (commit the result)",
    )
    ap.add_argument(
        "--summary", default=os.environ.get("GITHUB_STEP_SUMMARY"),
        help="append a markdown gate table to this file (defaults to "
        "$GITHUB_STEP_SUMMARY when set)",
    )
    args = ap.parse_args()

    kinds = args.require or sorted(BENCH_FILES)
    failed = False
    compared = 0
    for kind in kinds:
        fresh_path = os.path.join(args.bench_dir, BENCH_FILES[kind])
        base_path = os.path.join(args.baseline_dir, BENCH_FILES[kind])
        if not os.path.exists(fresh_path):
            if args.require:
                print(f"bench-compare: required {fresh_path} is missing "
                      f"(run benchmarks.run --{kind} first)",
                      file=sys.stderr)
                failed = True
            continue
        with open(fresh_path) as f:
            payload = json.load(f)
        if args.update:
            os.makedirs(args.baseline_dir, exist_ok=True)
            with open(base_path, "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
            print(f"bench-compare: baseline {base_path} updated from "
                  f"{fresh_path}")
            continue
        if not os.path.exists(base_path):
            print(f"bench-compare: no baseline {base_path} — run "
                  f"`python tools/bench_compare.py --update` and commit it",
                  file=sys.stderr)
            failed = True
            continue
        with open(base_path) as f:
            base_payload = json.load(f)
        rows = compare(
            extract_metrics(kind, base_payload),
            extract_metrics(kind, payload),
            model_tol=args.model_tol,
            measured_tol=args.measured_tol,
            jitter_floor=args.jitter_floor,
        )
        compared += 1
        bad = [r for r in rows if r["status"] in ("FAIL", "MISSING")]
        ok = len(rows) - len(bad)
        print(f"bench-compare: {kind}: {ok}/{len(rows)} metrics ok")
        for r in bad:
            print(
                f"  REGRESSION {r['metric']} ({r['class']}): baseline "
                f"{_fmt(r['baseline'])} -> current {_fmt(r['current'])}",
                file=sys.stderr,
            )
        if args.summary:
            write_summary(args.summary, kind, rows)
        failed = failed or bool(bad)
    if not args.update and compared == 0 and not failed:
        print("bench-compare: nothing to compare (no BENCH_*.json found)",
              file=sys.stderr)
        return 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
