"""Benchmark harness: one entry per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV at the end (per harness contract).

Bench modules are imported lazily, one entry at a time; an entry whose
module fails to import stops the run, and a bench that imports but
*fails to run* is reported and fails the harness at the end.

Every completed bench run is appended to ``BENCH_<name>.json`` at the
repo root via :func:`record_bench` — an append-mode trajectory of
``{timestamp, commit, metrics}`` entries, so bench numbers are tracked
across commits instead of asserted ad hoc.  Benches may also call
:func:`record_bench` themselves with richer metrics (set module attr
``RECORDS_OWN = True`` to suppress the harness's automatic entry).

    PYTHONPATH=src python -m benchmarks.run [--only fig4]
"""
from __future__ import annotations

import argparse
import importlib
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

BENCHES = [
    ("smoke", "benchmarks.smoke"),
    ("table1", "benchmarks.table1_latency_grid"),
    ("fig1", "benchmarks.fig1_dynamic_slo"),
    ("fig3", "benchmarks.fig3_perf_model"),
    ("fig4", "benchmarks.fig4_e2e"),
    ("solver", "benchmarks.solver_bench"),
    ("predictive", "benchmarks.predictive_bench"),
    ("ablation", "benchmarks.ablation_bench"),
    # control-plane throughput: the 1M-request scenario through the fast
    # engine vs the pre-refactor loop (see benchmarks/throughput_bench.py)
    ("throughput", "benchmarks.throughput_bench"),
    # autoregressive serving: 100k-request continuous batching + the
    # real-kernel TokenJaxBackend slice (benchmarks/token_serving_bench.py)
    ("token", "benchmarks.token_serving_bench"),
    # fleet serving: 500k requests across >=8 replicas, joint (n, c, b)
    # scaling vs a static fleet (benchmarks/fleet_bench.py)
    ("fleet", "benchmarks.fleet_bench"),
    # online sessions: 100k+ requests with mid-flight SLO renegotiation
    # and cancel storms via the session API (benchmarks/session_bench.py)
    ("session", "benchmarks.session_bench"),
    # multi-tenant pool: >=200k requests over 3 heterogeneous tenants on
    # a 128-core pool vs static partitions (benchmarks/tenant_bench.py)
    ("tenant", "benchmarks.tenant_bench"),
    # distribution-aware admission: quantile planning + cancel-on-overrun
    # vs the deterministic-cost scaler on heavy-tailed decode lengths
    # (benchmarks/uncertainty_bench.py)
    ("uncertainty", "benchmarks.uncertainty_bench"),
    # accuracy degradation: the (m, n, c, b) planner vs fixed-model
    # fleets on the degrade-under-pressure family
    # (benchmarks/degrade_bench.py)
    ("degrade", "benchmarks.degrade_bench"),
]


def _git_commit() -> str:
    """Best-effort short commit hash for trajectory entries."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:                                # pragma: no cover
        return "unknown"


def record_bench(name: str, metrics, *, path: Path = None) -> Path:
    """Append one ``{timestamp, commit, metrics}`` entry to
    ``BENCH_<name>.json`` (created as a JSON list on first use).

    ``metrics`` is any JSON-serializable value — the harness passes the
    CSV rows; benches with richer results (e.g. ``tenant_bench``'s
    pooled-vs-static comparison) pass their own dict.  Returns the file
    path.  The file stays a valid JSON array across appends so the
    trajectory is trivially loadable.
    """
    out = path or REPO_ROOT / f"BENCH_{name}.json"
    entries = []
    if out.exists():
        try:
            entries = json.loads(out.read_text())
            if not isinstance(entries, list):        # pragma: no cover
                entries = [entries]
        except Exception:                            # pragma: no cover
            entries = []
    entries.append({"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "unix_time": round(time.time(), 3),
                    "commit": _git_commit(), "metrics": metrics})
    out.write_text(json.dumps(entries, indent=1, default=float) + "\n")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--no-record", action="store_true",
                    help="skip the BENCH_<name>.json trajectory append")
    args = ap.parse_args(argv)
    rows = []
    failed = []
    for name, modpath in BENCHES:
        if args.only and args.only != name:
            continue
        mod = importlib.import_module(modpath)
        try:
            bench_rows = list(mod.run())
        except Exception as e:
            traceback.print_exc()
            failed.append((name, repr(e)))
            continue
        rows.extend(bench_rows)
        if not args.no_record and not getattr(mod, "RECORDS_OWN", False):
            record_bench(name, [list(r) for r in bench_rows])
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
