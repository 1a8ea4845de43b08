#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json``'s ``workloads``) is one model configuration
(``bench/configs/<config>.json``) under one traffic mix
(``bench/traffic/<mix>.json``).  The run loads and warms the program,
drives a lead-in and then ``--seconds`` of open-loop traffic on the wall
clock, drains, and checks what the window served against the plain
reference.  With ``--trace 0`` it reports the cell's end-to-end metrics;
with ``--trace 1`` the per-layer ones (``bench/metrics/<metric>.py``),
read from a profiler trace of the window's last seconds and from the
harness's wall stamps.

Information goes to earlier lines; the last line of standard output is one
JSON object, and the compared numbers with their limits are the last lines
of standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def _num(x):
    return None if x is None or not math.isfinite(x) else float(x)


def per_layer(run, cell, peaks) -> dict:
    from bench import harness
    from bench.metrics import context
    ctx = context.Context(run, peaks)
    out = {}
    for m in cell.per_layer:
        v = harness.load_reader(m["name"])(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(tr: dict) -> dict:
    ops = sorted(tr.get("ops", {}).items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(tr.get("idle", {}).items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    from bench.stats import pct
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; platform {dev.platform}, device_kind "
          f"{dev.device_kind}, count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from bench.peaks import peaks as peak_table
    peaks = peak_table(dev.device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = harness.CompileMeter.get()
    print(f"compilation cache: {cache_dir}", flush=True)

    run, mem = harness.drive(cell, args.seed, args.seconds, t_proc=T_PROC,
                             trace=bool(args.trace))
    loop, busy = harness.lateness(run)
    rows = run.window_rows()
    print(f"set-up {run.setup_s:.3f} s; backend compiles {meter.count} "
          f"({meter.seconds:.2f} s), persistent-cache hits "
          f"{meter.cache_hits}; compiles inside the window "
          f"{run.compiles_in_window}")
    print(f"generator lateness over the window's requests (s): p50 "
          f"{pct(loop[rows], 50):.6f}, max {np.nanmax(loop[rows]):.6f}; "
          f"runner still busy when due: p50 {pct(busy[rows], 50):.6f}, "
          f"max {np.nanmax(busy[rows]):.6f}")
    print("gaps between tokens by decode bucket (ms): "
          + json.dumps(harness.gap_profile(run)))
    if args.trace:
        print(f"profiler start {run.trace_costs[0]:.3f} s, stop "
              f"{run.trace_costs[1]:.3f} s (outside the analysed slice)")
    res = harness.check(run)
    checks = res["checks"]
    print(f"check readings: {json.dumps(res['readings'])}")

    e2e = harness.end_to_end(run)
    if args.trace:
        metrics = per_layer(run, cell, peaks)
    else:
        metrics = {m["name"]: {"value": _num(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
        print("other end-to-end numbers: " + json.dumps(
            {k: _num(v) for k, v in e2e.items()}))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": harness.correct(checks), "attempted": int(rows.size),
           "failed": int(checks["unserved"]["value"]), "metrics": metrics,
           "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = breakdown(run.trace)
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
