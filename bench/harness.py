"""One run of one cell: set-up, a wall-clock open-loop window, the check.

The system under test is the program's own real-kernel token stack, built
from its constructors: ``ScenarioRunner`` + ``TokenSpongeScaler`` +
``TokenJaxBackend`` over ``build_token_step_fns`` with both Pallas kernels
on, warmed and calibrated by ``warmup_token_fns`` / ``calibrate_token_fns``
as a deployment would be, and driven through its online session
(``runner.session()``).

The window is driven in real time.  Every request is submitted up front
with its server arrival (send time plus uplink latency); the harness then
calls ``step_until(wall - t0)`` in a loop, so nothing is processed before
its wall time.  Each token is stamped on the wall clock when it reaches
the backend's ``generated`` lists, that is when the server hands it out,
whatever device call made it.  The table entries the backend calls are
wrapped too, and stamp each device call's start and return.  Every
end-to-end time comes from those stamps, never from the runner's virtual
clock.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import gen, stats  # noqa: E402

OUT_DIR = ROOT / ".bench_out"       # scratch inside the checkout
TRACE_S = 2.0                       # length of the traced slice
TRACE_LEAD_S = 0.5                  # profiler start before the slice
POLL_S = 0.001                      # longest sleep while nothing is due
FAULTS = ("state_unchanged", "half_batch", "token_altered")
# the number compared with the reference; its limit is in the cell's
# limits file, bench/limits/<cell>.json
COMPARED = ("logit_gap",)


# ---------------------------------------------------------------------------
# the benchmark's data files
# ---------------------------------------------------------------------------
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclass
class Cell:
    name: str
    cfg: dict                 # the configuration file
    mix: dict                 # the traffic mix (with its rate)
    end_to_end: list          # this cell's end-to-end metric entries
    per_layer: list           # this cell's per-layer metric entries
    limits: dict              # the compared numbers' limits
    chips: int = 1


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    mix = gen.load_mix(w["traffic"], root / "bench" / "traffic")
    lim_path = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.exists() else {}
    return Cell(name=name, cfg=cfg, mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                limits=limits, chips=int(w["chips"]))


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------
class CompileMeter:
    """Backend-compile seconds and count, and persistent-cache hits
    (copied from ``chip_smoke.py``)."""

    _installed = None

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._installed is None:
            cls._installed = cls()
        return cls._installed

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclass
class Recorder:
    """Wall stamps of everything the harness wraps (``perf_counter``)."""
    annotate: bool = False
    calls: list = field(default_factory=list)   # (kind, b, t_call, t_ret)
    decides: list = field(default_factory=list)  # (t_start, seconds)
    steps: list = field(default_factory=list)   # (wall, target, wall end)
    stamps: dict = field(default_factory=dict)  # request id -> [wall]
    via: dict = field(default_factory=dict)     # request id -> [call index]
    tracer: "TraceWindow | None" = None         # polled before each call

    def span(self, name: str, **kw):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name, **kw)

    def delivered(self, rid, k: int, fresh: bool = False) -> None:
        """``k`` tokens of request ``rid`` were handed out just now, made
        by the latest device call that returned."""
        if fresh:
            self.stamps[rid], self.via[rid] = [], []
        t = time.perf_counter()
        self.stamps.setdefault(rid, []).extend([t] * k)
        self.via.setdefault(rid, []).extend([len(self.calls) - 1] * k)


class _Tokens(list):
    """A request's generated tokens, stamped as each one is added."""

    def __init__(self, rec: Recorder, rid, toks):
        super().__init__(toks)
        self.rec, self.rid = rec, rid
        rec.delivered(rid, len(self), fresh=True)

    def append(self, tok):
        super().append(tok)
        self.rec.delivered(self.rid, 1)

    def extend(self, toks):
        toks = list(toks)
        super().extend(toks)
        self.rec.delivered(self.rid, len(toks))

    def __iadd__(self, toks):
        self.extend(toks)
        return self


class StampedTokens(dict):
    """Stands in for the backend's ``generated`` dict (request id -> token
    ids): every list put in it stamps its tokens as they arrive."""

    def __init__(self, rec: Recorder):
        super().__init__()
        self.rec = rec

    def __setitem__(self, rid, toks):
        if toks is not self.get(rid):        # ``+=`` stores the same list
            toks = _Tokens(self.rec, rid, toks)
        super().__setitem__(rid, toks)

    def setdefault(self, rid, toks=()):
        if rid not in self:
            self[rid] = toks
        return self[rid]


class Stamped:
    """A table entry that blocks on its result and stamps its return.

    ``fault`` breaks the timed path on purpose (the harness's own tests)."""

    def __init__(self, fn, kind: str, b: int, rec: Recorder, vocab: int,
                 fault: str | None = None):
        self.fn, self.kind, self.b, self.rec = fn, kind, b, rec
        self.vocab, self.fault = vocab, fault

    def __call__(self, *args):
        import jax
        if self.rec.tracer is not None:
            self.rec.tracer.poll()
        t = time.perf_counter()
        with self.rec.span(f"bench.{self.kind}", b=self.b):
            out = jax.block_until_ready(self.fn(*args))
        tok, cache = out
        if self.fault == "token_altered" and self.kind == "decode":
            tok = tok.at[0].set((tok[0] + 1) % self.vocab)
        elif self.fault == "state_unchanged" and self.kind == "decode":
            cache = args[0]
        elif self.fault == "half_batch" and self.kind == "prefill" \
                and self.b > 1:
            h = self.b // 2
            tok = tok.at[h:].set(tok[:self.b - h])
        self.rec.calls.append((self.kind, self.b, t, time.perf_counter()))
        return tok, cache


class TraceWindow:
    """The profiler and the analysed slice, at fixed wall times after
    ``t0``: the profiler starts at ``start``, the ``bench.slice`` span
    covers ``[s0, s1)`` and the profiler stops at ``s1``.

    It is polled by the harness's loop and before every device call, so a
    runner step that runs gang after gang for seconds cannot move or
    stretch the slice.  Stopping the profiler blocks for as long as the
    trace takes to collect; that falls after the window."""

    def __init__(self, rec: Recorder, t0: float, start: float, s0: float,
                 s1: float, trace_dir: Path):
        self.rec, self.t0, self.trace_dir = rec, t0, trace_dir
        self.times = (start, s0, s1)
        self.state = 0      # 0: before, 1: tracing, 2: in the slice, 3: done
        self.costs = [0.0, 0.0]     # seconds to start and to stop
        self.wall = [0.0, 0.0]      # the slice on the wall clock
        self._span = None

    def next_due(self) -> float:
        return self.times[self.state] if self.state < 3 else math.inf

    def poll(self) -> None:
        import jax.profiler
        now = time.perf_counter() - self.t0
        if self.state == 0 and now >= self.times[0]:
            a = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no Python call events
            opts.host_tracer_level = 1      # the bench.* spans only
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self.costs[0] = time.perf_counter() - a
            self.state = 1
        if self.state == 1 and now >= self.times[1]:
            self._span = self.rec.span("bench.slice")
            self._span.__enter__()
            self.wall[0] = time.perf_counter()
            self.state = 2
        if self.state == 2 and now >= self.times[2]:
            self.wall[1] = time.perf_counter()
            self._span.__exit__(None, None, None)
            a = time.perf_counter()
            jax.profiler.stop_trace()
            self.costs[1] = time.perf_counter() - a
            self.state = 3


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def program_config(cfg: dict):
    """The program's ModelConfig at the configuration file's sizes, with
    both Pallas kernel routes on."""
    import dataclasses
    from repro.configs import get_config
    prog = cfg["program"]
    layers = cfg["num_hidden_layers"]
    return dataclasses.replace(
        get_config(prog["arch"]), num_layers=layers,
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        blocks=(prog["block"],) * layers,
        window_size=int(cfg.get("sliding_window") or 0),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"], use_pallas_prefill=True,
        use_pallas_decode=True)


def kernel_guard(pre_fns: dict, dec_fns: dict, prompt_len: int,
                 expect: list) -> list:
    """Names of the expected kernels missing from the lowered entries.

    Each entry is lowered (not run) at its own shapes; a Pallas kernel
    lowers to a ``tpu_custom_call``, and an einsum fallback through the
    model's shape gates has none."""
    import jax
    import jax.numpy as jnp
    missing = []
    for (c, b), pf in sorted(pre_fns.items()):
        tokens = jax.ShapeDtypeStruct((b, prompt_len), jnp.int32)
        if "prefill" in expect:
            text = pf.func.lower(*pf.args, tokens).as_text()
            if "tpu_custom_call" not in text:
                missing.append(f"prefill b={b}")
        if "decode" in expect:
            first, cache = jax.eval_shape(pf, tokens)
            df = dec_fns[(c, b)]
            text = df.func.lower(*df.args, cache, first).as_text()
            if "tpu_custom_call" not in text:
                missing.append(f"decode b={b}")
    return missing


@dataclass
class Run:
    """What one run leaves for the metrics and the check."""
    cell: Cell
    seed: int
    seconds: float
    traffic: gen.Traffic
    rec: Recorder
    ids: list                 # request id per traffic row
    t0: float                 # wall time of the lead-in's start
    setup_s: float
    served: dict              # request id -> generated token ids
    bucket_log: list
    compiles_in_window: int
    trace: dict | None = None
    trace_costs: tuple = (0.0, 0.0)
    slice_wall: tuple = (0.0, 0.0)   # analysed slice, wall clock

    def wall_window(self) -> tuple:
        w0, w1 = self.traffic.window
        return self.t0 + w0, self.t0 + w1

    def window_rows(self) -> np.ndarray:
        return np.nonzero(self.traffic.phase == gen.WINDOW)[0]

    def dispatched(self, rid):
        """Wall start of the device call that made the request's first
        token (None if it has none)."""
        via = self.rec.via.get(rid)
        return self.rec.calls[via[0]][2] if via and via[0] >= 0 else None


def drive(cell: Cell, seed: int, seconds: float, *, t_proc: float,
          trace: bool = False, fault: str | None = None,
          require_tpu: bool = True, log=print) -> tuple:
    """Set up the program, drive the window, drain.  Returns ``(run,
    memory_peak_bytes, device_info)``; the program's state is freed."""
    import jax
    from repro.core.scaler import TokenSpongeScaler
    from repro.core.slo import Request
    from repro.models import build_model
    from repro.serving.api import ScenarioRunner
    from repro.serving.token_backend import (TokenJaxBackend,
                                             build_token_step_fns,
                                             calibrate_token_fns,
                                             warmup_token_fns)

    from bench import weights
    meter = CompileMeter.get()
    cfg, mix = cell.cfg, cell.mix
    prog = cfg["program"]
    table = mix["table"]
    prompt_len, max_decode = int(table["prompt_len"]), int(
        table["max_decode"])
    pcfg = program_config(cfg)
    model = build_model(pcfg)
    expected = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.program_params(seed, cfg, expected)
    c_set, b_set = tuple(prog["c_set"]), tuple(prog["b_set"])
    pre_fns, dec_fns = build_token_step_fns(model, params, c_set, b_set,
                                            prompt_len, max_decode)
    if require_tpu:
        missing = kernel_guard(pre_fns, dec_fns, prompt_len,
                               prog["kernels"])
        if missing:
            raise RuntimeError(f"kernel missing from compiled entries: "
                               f"{missing}")
        log(f"kernel guard: {prog['kernels']} present in every entry "
            f"(b in {list(b_set)})")
    warmup_token_fns(pre_fns, dec_fns, prompt_len)
    traffic = gen.generate(mix, seed, seconds, cfg["vocab_size"])
    cost = calibrate_token_fns(pre_fns, dec_fns, prompt_len,
                               mean_decode=float(traffic.decode.mean()))
    rec = Recorder(annotate=trace)
    vocab = cfg["vocab_size"]
    wrap = {}
    pre_w = {k: wrap.setdefault(("p", id(f), k[1]), Stamped(
        f, "prefill", k[1], rec, vocab, fault)) for k, f in pre_fns.items()}
    dec_w = {k: wrap.setdefault(("d", id(f), k[1]), Stamped(
        f, "decode", k[1], rec, vocab, fault)) for k, f in dec_fns.items()}
    # one call of each wrapped entry, so the wrappers' own ops are warm
    for k in sorted(pre_w):
        first, cache = pre_w[k](np.ones((k[1], prompt_len), np.int32))
        dec_w[k](cache, first)
    del first, cache
    rec.calls.clear()
    tick = float(mix["tick_s"])
    scaler = TokenSpongeScaler(cost, c_set=c_set, b_set=b_set,
                               adaptation_interval=tick)
    backend = TokenJaxBackend(pre_w, dec_w, cost, prompt_len,
                              max_decode=max_decode)
    backend.generated = StampedTokens(rec)
    runner = ScenarioRunner(scaler, backend, tick=tick)
    runner.monitor.rate.prior_rps = float(mix["rate_rps"])

    decide = scaler.decide

    def timed_decide(*a, **k):
        t = time.perf_counter()
        with rec.span("bench.decide"):
            d = decide(*a, **k)
        rec.decides.append((t, time.perf_counter() - t))
        return d

    scaler.decide = timed_decide
    sess = runner.session()
    ids, reqs = [], []
    for i in range(len(traffic)):
        req = Request.make(
            arrival=float(traffic.send[i] + traffic.comm[i]),
            comm_latency=float(traffic.comm[i]), slo=float(traffic.slo[i]),
            size_kb=float(traffic.size_kb[i]),
            prompt_tokens=int(traffic.prompt_len[i]),
            decode_tokens=int(min(traffic.decode[i], max_decode)),
            tbt_slo=float(traffic.tbt[i]))
        sess.submit(req, payload=traffic.prompts[i])
        ids.append(req.id)
        if traffic.phase[i] == gen.WINDOW:
            reqs.append(req)
    arrivals = np.sort(traffic.send + traffic.comm)

    w0, w1 = traffic.window
    end_arrivals = w1 + float(mix["tail_s"])
    hard_stop = end_arrivals + float(mix["drain_limit_s"])
    trace_dir = OUT_DIR / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()
    gc.disable()
    compiles0 = meter.count
    t0 = time.perf_counter()
    setup_s = t0 - t_proc
    if trace:
        s0 = max(w0, w1 - TRACE_S)
        rec.tracer = TraceWindow(rec, t0, s0 - TRACE_LEAD_S, s0, w1,
                                 trace_dir)
    tracer = rec.tracer
    try:
        while True:
            now = time.perf_counter() - t0
            if tracer is not None:
                tracer.poll()
            done = tracer is None or tracer.state == 3
            if now >= end_arrivals and done and all(
                    r.finish is not None for r in reqs):
                break
            # the drain's limit does not count the profiler's own cost
            if done and now >= hard_stop + (sum(tracer.costs) if tracer
                                            else 0.0):
                break
            a = time.perf_counter()
            with rec.span("bench.step"):
                sess.step_until(now)
            rec.steps.append((a, now, time.perf_counter()))
            # the next arrival or tick, or a poll for the runner's own
            # wake-ups, whichever comes first
            j = np.searchsorted(arrivals, now, side="right")
            nxt = min(arrivals[j] if j < arrivals.size else math.inf,
                      (math.floor(now / tick) + 1) * tick,
                      tracer.next_due() if tracer is not None else math.inf)
            wait = min(nxt - (time.perf_counter() - t0), POLL_S)
            if wait > 0.0002:
                with rec.span("bench.wait"):
                    time.sleep(wait)
    finally:
        gc.enable()
    compiles = meter.count - compiles0
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    served = {rid: list(map(int, toks))
              for rid, toks in backend.generated.items()}
    for r in reqs:
        if r.finish is None:
            served.pop(r.id, None)
    run = Run(cell=cell, seed=seed, seconds=seconds, traffic=traffic,
              rec=rec, ids=ids, t0=t0, setup_s=setup_s, served=served,
              bucket_log=list(runner.bucket_log),
              compiles_in_window=compiles,
              trace_costs=tuple(tracer.costs) if tracer else (0.0, 0.0),
              slice_wall=tuple(tracer.wall) if tracer else (0.0, 0.0))
    rec.tracer = None
    del sess, runner, backend, scaler, pre_w, dec_w, wrap, pre_fns, dec_fns
    del params, model, reqs
    gc.collect()
    if trace:
        from bench import devtrace
        a = time.perf_counter()
        run.trace = devtrace.reduce_trace(devtrace.find_xplane(
            str(trace_dir)))
        log(f"trace reduced in {time.perf_counter() - a:.2f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
    return run, int(mem)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def end_to_end(run: Run) -> dict:
    """Every end-to-end number of a run, from the wall stamps."""
    tr = run.traffic
    rows = run.window_rows()
    send = run.t0 + tr.send[rows]
    st = [run.rec.stamps.get(run.ids[i]) for i in rows]
    a, b = run.wall_window()
    return {
        "ttft_p95_s": stats.pct(stats.ttft(send, st), 95),
        "slo_attainment": stats.attainment(send, st, tr.slo[rows],
                                           tr.tbt[rows]),
        "tbt_p95_ms": stats.pct(stats.gaps(st), 95) * 1e3,
        "tokens_per_s": stats.tokens_in(run.rec.stamps.values(), a, b)
        / run.seconds,
        "setup_s": run.setup_s,
    }


def gap_profile(run: Run) -> dict:
    """The window's gaps between tokens by the bucket ``b`` of the call
    that made the later token: per ``b`` the count, median and p95 (ms),
    and how many gaps held another call besides that one (a prefill, or
    another gang's decode).  Also which ``b`` the overall p95 gap came
    from.  Information only; it explains where the TBT tail sits."""
    calls = run.rec.calls
    rows = run.window_rows()
    by_b, other, allg = {}, {}, []
    for i in rows:
        rid = run.ids[i]
        st, via = run.rec.stamps.get(rid), run.rec.via.get(rid)
        if not st or len(st) < 2:
            continue
        for k in range(1, len(st)):
            g = st[k] - st[k - 1]
            j = via[k]
            b = calls[j][1] if j >= 0 else 0
            by_b.setdefault(b, []).append(g)
            allg.append((g, b))
            if j - via[k - 1] > 1:
                other[b] = other.get(b, 0) + 1
    out = {}
    for b in sorted(by_b):
        g = np.asarray(by_b[b]) * 1e3
        out[f"b{b}"] = {"n": int(g.size), "p50": float(np.median(g)),
                        "p95": stats.pct(g, 95),
                        "with_other_calls": int(other.get(b, 0))}
    if allg:
        allg.sort()
        k = max(int(math.ceil(0.95 * len(allg))) - 1, 0)
        out["p95_from_b"] = int(allg[k][1])
    return out


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def lateness(run: Run) -> tuple:
    """Per request: how late the harness's loop handed it to the runner,
    and how long the runner was still busy when it was due.

    A request is handed over by the first ``step_until`` whose target
    reached its server arrival.  The loop's own lateness is that call's
    wall start minus the later of the arrival and the end of the call
    before it (sleep overshoot, bookkeeping); the runner's busy wait is
    the end of the call before it minus the arrival, when positive (a
    gang was still running, so the request could not be taken earlier)."""
    steps = run.rec.steps
    starts = np.array([w for w, _, _ in steps]) - run.t0
    targets = np.array([t for _, t, _ in steps])
    ends = np.array([e for _, _, e in steps]) - run.t0
    arr = run.traffic.send + run.traffic.comm
    idx = np.searchsorted(targets, arr, side="left")
    loop = np.full(arr.size, np.nan)
    busy = np.full(arr.size, np.nan)
    ok = idx < targets.size
    prev_end = np.where(idx > 0, ends[np.maximum(idx - 1, 0)], -np.inf)
    loop[ok] = starts[idx[ok]] - np.maximum(arr[ok], prev_end[ok])
    busy[ok] = np.maximum(prev_end[ok] - arr[ok], 0.0)
    return loop, busy


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def check(run: Run, *, control: bool = False, log=print) -> dict:
    """Compare what the window served with the plain reference.

    Returns the compared numbers, each with its limit, and the readings.
    With ``control`` also ``control_checks``: the same numbers as the
    float8 reference reads them in the program's place."""
    from bench import check as chk
    cell, tr = run.cell, run.traffic
    rows = run.window_rows()
    max_decode = int(cell.mix["table"]["max_decode"])
    unserved = wrong_len = 0
    finished = []
    for i in rows:
        toks = run.served.get(run.ids[i])
        if not toks:
            unserved += 1
            continue
        if len(toks) != 1 + min(int(tr.decode[i]), max_decode) or \
                min(toks) < 0 or max(toks) >= cell.cfg["vocab_size"]:
            wrong_len += 1
            continue
        finished.append(i)
    sample = chk.sample(finished, [len(run.served[run.ids[i]])
                                   for i in finished], run.seed,
                        int(cell.mix["sample"]["min_served_tokens"]),
                        int(cell.mix["sample"]["max_requests"]))
    t = time.perf_counter()
    res = chk.compare(cell.cfg, run.seed,
                      [tr.prompts[i] for i in sample],
                      [run.served[run.ids[i]] for i in sample],
                      int(cell.mix["decode"]["hi"]), control=control)
    log(f"reference over {len(sample)} requests, {res['tokens']} served "
        f"tokens, in {time.perf_counter() - t:.2f} s")

    def checks_of(prefix: str) -> dict:
        out = {"unserved": {"value": unserved, "limit": 0},
               "wrong_tokens": {"value": wrong_len, "limit": 0}}
        # a cell without a limits file has no limit, so it is never correct
        for name in COMPARED:
            v = res[prefix + name]
            out[name] = {"value": v if math.isfinite(v) else None,
                         "limit": cell.limits.get(name, {}).get("limit")}
        return out

    out = {"checks": checks_of(""), "readings": res}
    if control:
        out["control_checks"] = checks_of("control_")
    return out


def correct(checks: dict) -> bool:
    """Every compared number is present and within its limit (a number
    with no limit, or a run with nothing to compare, is not correct)."""
    return all(c["value"] is not None and c["limit"] is not None
               and c["value"] <= c["limit"] for c in checks.values())
