"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The analysed window is the host span ``bench.slice`` that the harness opens
and closes on the profiler's own clock, so host and device times need no
alignment.  From the device planes (``/device:...``) it takes the line of
XLA operations, and from the host plane the harness's spans
(``bench.*``).  Reading the trace needs nothing but JAX.

Outputs, all clipped to the window:

* ``busy_s``: union of the intervals in which an operation ran, averaged
  over the devices;
* ``ops``: seconds per operation (its HLO name; while and conditional
  ops are left out, since their bodies' ops are counted);
* ``kernel_s``: seconds of Pallas kernel operations per program, keyed by
  the program (XLA module) they ran in, since both kernels are one
  function name;
* ``idle``: seconds of device idle time per host activity, each idle gap
  named by the innermost ``bench.*`` span open at its midpoint;
* ``spans``: count of each host span that starts in the window.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

SLICE = "bench.slice"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _is_container(name: str) -> bool:
    """A while or conditional op: its body's ops are events of their own."""
    return " while(" in name or " conditional(" in name


def _is_kernel(name: str, stats: dict) -> bool:
    """A Pallas kernel: an XLA custom call to ``tpu_custom_call``."""
    if "tpu_custom_call" in name:
        return True
    for v in stats.values():
        if isinstance(v, str) and "tpu_custom_call" in v:
            return True
    return False


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:
        return {}


def reduce_trace(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = next((lines[n] for n in OPS_LINES if n in lines), None)
            if ops is None:
                continue
            mods = next((lines[n] for n in MODULE_LINES if n in lines), None)
            devices.append((plane.name, ops, mods))
    win = [s for s in host_spans if s[2] == SLICE]
    if not win or not devices:
        return {}
    w0, w1 = win[0][0], win[0][1]
    window_s = (w1 - w0) * 1e-9
    spans = sorted(s for s in host_spans if s[2] != SLICE)
    by_name = _by_name(spans)

    busy, ops_s, kernel_s = [], defaultdict(float), defaultdict(float)
    idle = defaultdict(float)
    for _, ops, mods in devices:
        modules = []
        if mods is not None:
            modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                             for ev in mods.events
                             if ev.end_ns > w0 and ev.start_ns < w1)
        mstarts = [m[0] for m in modules]
        ivs = []
        for ev in ops.events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            if _is_container(ev.name):
                continue
            dur = (e - s) * 1e-9
            ops_s[_op_name(ev.name)] += dur
            if _is_kernel(ev.name, _stats(ev)):
                i = bisect.bisect_right(mstarts, ev.start_ns) - 1
                mod = modules[i][2] if i >= 0 and \
                    modules[i][1] >= ev.start_ns else "?"
                kernel_s[mod] += dur
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                idle[_activity(by_name, (prev + s) / 2)] += (s - prev) * 1e-9
            prev = max(prev, e)
    n = len(devices)
    counts = defaultdict(int)
    for s, _, name in spans:
        if w0 <= s < w1:
            counts[name] += 1
    return {"window_s": window_s, "busy_s": sum(busy) / n,
            "ops": {k: v / n for k, v in ops_s.items()},
            "kernel_s": {k: v / n for k, v in kernel_s.items()},
            "idle": {k: v / n for k, v in idle.items()},
            "spans": dict(counts), "devices": n}


def _by_name(spans) -> dict:
    out = defaultdict(list)
    for s, e, name in spans:
        out[name].append((s, e))
    return {k: (v, [a for a, _ in v]) for k, v in out.items()}


def _activity(by_name, t) -> str:
    """Name of the innermost ``bench.*`` span open at time t."""
    best, best_len = "none", None
    for name, (ivs, starts) in by_name.items():
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and ivs[i][1] > t:
            if best_len is None or ivs[i][1] - ivs[i][0] < best_len:
                best, best_len = name[len("bench."):], ivs[i][1] - ivs[i][0]
    return best
