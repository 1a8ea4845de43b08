#!/usr/bin/env python3
"""Readings that a cell's logit-gap limit is set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,...,12 --seconds 10

In one process, for each seed: set the cell up, drive a short window at
the cell's own rate, and compare what it served with the plain reference
(the program's reading) and with the float8 (e4m3 W8A8) reference on the
same prompts and tokens (the control's reading).  Prints one JSON line per seed, with whether the program
and the control come out correct under the cell's limits file, and, for
the compared number, the lower reading (largest over the program's
seeds), the upper reading (smallest over the control's) and their ratio.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    print(f"platform {dev.platform}, device_kind {dev.device_kind}",
          flush=True)
    if dev.platform != "tpu":
        return 1
    from bench import harness  # puts the program's src/ on the path
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run, _ = harness.drive(cell, seed, args.seconds,
                               t_proc=time.perf_counter(),
                               log=lambda *_: None)
        res = harness.check(run, control=True, log=lambda *_: None)
        rows.append(res["readings"])
        print(json.dumps({"seed": seed, **res["readings"],
                          "unserved": res["checks"]["unserved"]["value"],
                          "wrong_tokens":
                              res["checks"]["wrong_tokens"]["value"],
                          "correct": harness.correct(res["checks"]),
                          "control_correct":
                              harness.correct(res["control_checks"])}),
              flush=True)
    for name in harness.COMPARED:
        lower = max(r[name] for r in rows)
        upper = min(r[f"control_{name}"] for r in rows)
        print(json.dumps({"workload": args.workload, "number": name,
                          "seeds": len(rows), "lower": lower,
                          "upper": upper,
                          "ratio": upper / lower if lower else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
