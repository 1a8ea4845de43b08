#!/usr/bin/env python3
"""Find a (configuration, mix) pair's knee by a sweep of fixed rates.

    python3 bench/sweep.py --config smollm-135m --traffic chat-r18.4 \
        --rates 14,18,22 --seeds 1,2,3 --seconds 30

One process; for each rate and seed the program is set up again (compiled
programs come from the cache) and one window of the mix, at that rate, is
run.  Each run prints one JSON line: the end-to-end numbers, the share of
requests sent that met both limits, and whether a backlog grew through
the window (median TTFT of the window's last quarter against its first).
The knee is the highest rate at which every seed keeps at least 90%
attainment with no growing backlog (a ratio under 1.5); a cell below it
runs at about 0.8 x knee, one above it where the tokens completed per
second no longer follow the offered load.
"""
from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def backlog_ratio(run) -> float:
    """Median TTFT of the window's last quarter over its first quarter."""
    from bench import stats
    tr = run.traffic
    rows = run.window_rows()
    w0, w1 = tr.window
    q = (w1 - w0) / 4
    send = run.t0 + tr.send[rows]
    ttft = stats.ttft(send, [run.rec.stamps.get(run.ids[i]) for i in rows])
    first = ttft[tr.send[rows] < w0 + q]
    last = ttft[tr.send[rows] >= w1 - q]
    return float(np.median(last) / max(np.median(first), 1e-9))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", default="1")
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

    from bench import gen
    cfg = json.loads((ROOT / "bench" / "configs" /
                      f"{args.config}.json").read_text())
    seeds = [int(x) for x in args.seeds.split(",")]
    for rate in (float(r) for r in args.rates.split(",")):
        mix = gen.load_mix(args.traffic)
        mix["rate_rps"] = rate
        cell = harness.Cell(name=f"{args.config}.{args.traffic}@{rate}",
                            cfg=cfg, mix=mix, end_to_end=[], per_layer=[],
                            limits={})
        for seed in seeds:
            t = time.perf_counter()
            run, mem = harness.drive(cell, seed, args.seconds, t_proc=t,
                                     log=lambda *_: None)
            e2e = harness.end_to_end(run)
            line = {"rate_rps": rate, "seed": seed, **e2e,
                    "backlog_ratio": backlog_ratio(run),
                    "attempted": int(run.window_rows().size),
                    "compiles_in_window": run.compiles_in_window,
                    "memory_peak_bytes": mem}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
