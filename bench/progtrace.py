"""Reduce the serving program's own spans in a profiler trace
(``.xplane.pb``) against the device's XLA modules and operations.

The program opens a host span at each layer boundary (``repro.utils.trace``):
``runner.*``, ``control.*``, ``backend.*`` and ``model.*``.  They share the
profiler's clock with the device, so each device-idle instant can be put
down to what the host was doing then.  The analysed slice is the harness's
``bench.slice`` span, as in ``devtrace``; the harness's ``bench.wait`` span
marks where its loop sleeps until the next arrival.

Outputs, all over the slice (device numbers averaged over the devices):

* ``window_s``, ``busy_s``: the slice, and the union of device-operation
  intervals in it;
* ``idle_by_span``: seconds of device idle time by the innermost program
  span open at the time; ``bench.wait`` where no program span is open but
  the harness waits, ``none`` where neither is open;
* ``host_by_span``: seconds of the slice by the innermost program span
  (``bench.wait``, ``none`` as above), on the host's clock alone;
* ``decode``: the ``model.decode`` spans that start in the slice (``calls``),
  how many hold exactly one start of a ``jit_decode`` module execution on
  the device (``one_start``, the clock check) and how many hold it whole
  (``one_inside``), for the latter the host launch (module start minus
  span start, ``launch_s``), and the device time of every ``jit_decode``
  execution that starts in the slice (``device_s``), from the first
  device.  Where the device's clock is out of step with the host's,
  ``one_inside`` falls short of ``calls``, and so does the idle
  attribution's accuracy;
* ``device_by_scope``: seconds of device operations by the outermost of
  the program's named scopes they ran in (``embed``, ``attention``,
  ``mlp``, ``lm_head``, ``sample``), else ``layers``, ``params`` or
  ``other`` (``scope_of``), read from the ``tf_op`` of each operation's
  metadata;
* ``spans``: the count of each program span that starts in the slice.

The per-layer readers ``bench/metrics/model.decode_launch_ms.py``,
``model.decode_device_ms.py``, ``backend.device_idle_share.py`` and
``queue.device_idle_share.py`` read this under ``ctx.trace["program"]``.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

from bench import devtrace

PROGRAM = ("runner.", "control.", "backend.", "model.")
WAIT = "bench.wait"
DECODE_SPAN, DECODE_MODULE = "model.decode", "jit_decode"
# the named scopes of the program's step (models/transformer.py,
# models/api.py, serving/token_backend.py)
SCOPES = frozenset({"embed", "attention", "mlp", "moe", "mamba2", "rwkv6",
                    "rwkv_cm", "lm_head", "sample"})


# ---------------------------------------------------------------------------
# pure parts: checked on synthetic intervals by the CPU tests
# ---------------------------------------------------------------------------
def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def attribute_idle(spans, busy, window) -> tuple:
    """Device idle time in ``window`` by what the host was doing then.

    ``spans``: ``(start, end, name)`` host spans; ``busy``: ``(start,
    end)`` intervals in which the device ran an operation (any order, may
    overlap); ``window``: ``(w0, w1)``.  Each idle instant goes to the
    innermost program span open then (the one that opened last, the
    shorter on a tie); where none is open, to ``bench.wait`` if the
    harness waits, else to ``none``.  Other spans are ignored.  Returns
    ``({name: idle}, busy)`` in the units of the input: idle and busy add
    up to the window."""
    w0, w1 = window
    merged = devtrace._union((max(s, w0), min(e, w1)) for s, e in busy
                             if min(e, w1) > max(s, w0))
    busy_total = sum(e - s for s, e in merged)
    kept = [(max(s, w0), min(e, w1), n) for s, e, n in spans
            if (is_program(n) or n == WAIT) and min(e, w1) > max(s, w0)]
    opens = sorted(range(len(kept)), key=lambda i: kept[i][0])
    closes = sorted(range(len(kept)), key=lambda i: kept[i][1])
    cuts = sorted({w0, w1, *(t for s, e, _ in kept for t in (s, e)),
                   *(t for s, e in merged for t in (s, e))})
    out = defaultdict(float)
    open_, oi, ci, bi = set(), 0, 0, 0
    for a, b in zip(cuts, cuts[1:]):
        while ci < len(closes) and kept[closes[ci]][1] <= a:
            open_.discard(closes[ci])
            ci += 1
        while oi < len(opens) and kept[opens[oi]][0] <= a:
            if kept[opens[oi]][1] > a:
                open_.add(opens[oi])
            oi += 1
        while bi < len(merged) and merged[bi][1] <= a:
            bi += 1
        if bi < len(merged) and merged[bi][0] <= a:
            continue                        # the device is busy here
        out[_innermost(kept, open_)] += b - a
    return dict(out), busy_total


def _innermost(kept, open_) -> str:
    prog = [kept[i] for i in open_ if is_program(kept[i][2])]
    if prog:
        return max(prog, key=lambda s: (s[0], s[0] - s[1]))[2]
    return WAIT if open_ else "none"


def match_decode(spans, modules, window) -> dict:
    """Each ``model.decode`` span that starts in ``window`` against the
    ``jit_decode`` module executions (``(start, end, name)``).

    ``one_start`` counts the spans inside which exactly one execution
    starts; ``one_inside`` those that also hold it whole, as they must:
    the span ends after ``block_until_ready``, so a module that ends after
    it shows the device clock out of step with the host's there.  Only
    those give a launch (module start minus span start).  ``device`` is
    the duration of every execution that starts in the window, which
    needs no matching."""
    w0, w1 = window
    mods = sorted((s, e) for s, e, n in modules
                  if n.startswith(DECODE_MODULE))
    starts = [s for s, _ in mods]
    calls = one_start = one_inside = 0
    launch = []
    for s, e, n in spans:
        if n != DECODE_SPAN or not w0 <= s < w1:
            continue
        calls += 1
        i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        if j - i != 1:
            continue
        one_start += 1
        if mods[i][1] <= e:
            one_inside += 1
            launch.append(mods[i][0] - s)
    device = [e - s for s, e in mods if w0 <= s < w1]
    return {"calls": calls, "one_start": one_start,
            "one_inside": one_inside, "launch": launch, "device": device}


def scope_of(tf_op: str) -> str:
    """Outermost of the program's named scopes (``SCOPES``) in an
    operation's ``tf_op`` name path
    (``jit(decode)/while/body/attention/dot_general:`` -> ``attention``).
    Outside them: ``layers`` for operations in the scan over layers that
    carry no one scope (its slicing of each layer's weights and cache out
    of the stacked arrays, and fusions that cross a scope's edge, which
    XLA names by the part of the path their operations share),
    ``params`` for copies of a parameter (``params['embed']:``), else
    ``other``."""
    parts = tf_op.split(":", 1)[0].split("/")
    for part in parts:
        if part in SCOPES:
            return part
    if "while" in parts:
        return "layers"
    return "params" if tf_op.startswith("params[") else "other"


def median(xs):
    return statistics.median(xs) if xs else None


# ---------------------------------------------------------------------------
# the trace file
# ---------------------------------------------------------------------------
def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message; a
    length-delimited value is a slice of ``buf``."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, v


def op_paths(path: str) -> dict:
    """Per device plane, each operation's ``tf_op`` name path keyed by its
    event name, read from the planes' event metadata (which
    ``ProfileData`` does not expose).  Only the few XSpace fields needed
    are decoded: XSpace.planes (1); XPlane.name (2), event_metadata (4),
    stat_metadata (5); map entries key (1), value (2); XEventMetadata.name
    (2), stats (5); XStat.metadata_id (1), str_value (5), ref_value (7);
    XStatMetadata.name (2)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, emeta, smeta = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                emeta.append(v)
            elif g == 5:
                entry = dict(_fields(v))
                smeta[entry.get(1, 0)] = bytes(
                    dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_op = next((k for k, v in smeta.items() if v == "tf_op"), None)
        paths = {}
        for entry in emeta:
            ev = {}
            stats = []
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 5:
                    stats.append(v)
                else:
                    ev[g] = v
            for st in stats:
                st = dict(_fields(st))
                if st.get(1) != tf_op:
                    continue
                if 5 in st:
                    paths[bytes(ev.get(2, b"")).decode()] = \
                        bytes(st[5]).decode()
                elif 7 in st:
                    paths[bytes(ev.get(2, b"")).decode()] = smeta.get(
                        st[7], "")
        out[name] = paths
    return out


def reduce_program(path: str) -> dict:
    """The reduction described in the module docstring; ``{}`` when the
    trace has no ``bench.slice`` span or no device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if is_program(ev.name) or ev.name.startswith("bench."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = next((lines[n] for n in devtrace.OPS_LINES if n in lines),
                       None)
            if ops is None:
                continue
            mods = next((lines[n] for n in devtrace.MODULE_LINES
                         if n in lines), None)
            devices.append((plane.name, ops, mods))
    win = [s for s in spans if s[2] == devtrace.SLICE]
    if not win or not devices:
        return {}
    w0, w1 = win[0][0], win[0][1]
    paths = op_paths(path)
    idle, busy, scope = defaultdict(float), 0.0, defaultdict(float)
    decode = None
    for name, ops, mods in sorted(devices, key=lambda d: d[0]):
        ivs, by_path = [], paths.get(name, {})
        for ev in ops.events:
            s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            if not devtrace._is_container(ev.name):
                scope[scope_of(by_path.get(ev.name, ""))] += (e - s) * 1e-9
        got, b = attribute_idle(spans, ivs, (w0, w1))
        for k, v in got.items():
            idle[k] += v * 1e-9
        busy += b * 1e-9
        if decode is None:
            modules = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in (mods.events if mods is not None else ())]
            decode = match_decode(spans, modules, (w0, w1))
    n = len(devices)
    host, _ = attribute_idle(spans, [], (w0, w1))
    counts = defaultdict(int)
    for s, _, name in spans:
        if is_program(name) and w0 <= s < w1:
            counts[name] += 1
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy / n,
            "idle_by_span": {k: v / n for k, v in idle.items()},
            "host_by_span": {k: v * 1e-9 for k, v in host.items()},
            "device_by_scope": {k: v / n for k, v in scope.items()},
            "decode": {"calls": decode["calls"],
                       "one_start": decode["one_start"],
                       "one_inside": decode["one_inside"],
                       "launch_s": [x * 1e-9 for x in decode["launch"]],
                       "device_s": [x * 1e-9 for x in decode["device"]]},
            "spans": dict(counts), "devices": n}


def clock_in_step(program: dict, share: float = 0.9) -> bool:
    """At least ``share`` of the slice's decode calls hold their device
    execution whole: the host and device clocks agree there."""
    d = (program or {}).get("decode") or {}
    return d.get("calls", 0) > 0 and d["one_inside"] >= share * d["calls"]


def idle_share(program: dict, layer: str):
    """Percent of the slice in which the device was idle while the
    innermost program span was one of ``layer``'s (``"backend."``)."""
    if not program or program.get("window_s", 0) <= 0:
        return None
    idle = sum(v for k, v in program["idle_by_span"].items()
               if k.startswith(layer))
    return 100.0 * idle / program["window_s"]

