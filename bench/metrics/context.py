"""What a per-layer reader sees of one run (``bench/metrics/<name>.py``).

Host numbers come from the harness's wall stamps over the measured window;
device numbers from the reduced profiler trace of the analysed slice (the
window's last seconds) and the calls that fell inside that slice.
"""
from __future__ import annotations

import numpy as np

from bench import stats
from bench.counts import kernels, model


class Context:
    def __init__(self, run, peaks: dict):
        self.run = run
        self.cfg = run.cell.cfg
        self.mix = run.cell.mix
        self.peaks = peaks
        self.trace = run.trace or {}
        self.window = run.wall_window()
        self.prompt_len = int(self.mix["table"]["prompt_len"])

    # -- host stamps ------------------------------------------------------
    def _per_call(self):
        """Per device call: tokens it made, and the highest position of
        a token it made within its request (1 for a first decode step)."""
        if not hasattr(self, "_made"):
            made, pos = {}, {}
            for via in self.run.rec.via.values():
                for j, ci in enumerate(via):
                    made[ci] = made.get(ci, 0) + 1
                    pos[ci] = max(pos.get(ci, 0), j)
            self._made, self._pos = made, pos
        return self._made, self._pos

    def calls(self, kind: str, span=None):
        """(b, t_call, t_ret, step, made) of each ``kind`` call inside
        ``span`` (default: the window): ``step`` is the highest token
        position the call made in a request (the decode step), ``made``
        how many tokens it handed out."""
        a, b = span or self.window
        made, pos = self._per_call()
        return [(b_, tc, tr, pos.get(i, 0), made.get(i, 0))
                for i, (k, b_, tc, tr) in enumerate(self.run.rec.calls)
                if k == kind and tc >= a and tr <= b]

    def window_requests(self):
        """(request id, wall send, wall server arrival) per window row."""
        run = self.run
        tr = run.traffic
        rows = run.window_rows()
        return [(run.ids[i], run.t0 + tr.send[i],
                 run.t0 + tr.send[i] + tr.comm[i]) for i in rows]

    def window_stamps(self):
        return [self.run.rec.stamps.get(rid)
                for rid, _, _ in self.window_requests()]

    # -- trace ------------------------------------------------------------
    def slice_span(self):
        return self.run.slice_wall

    def kernel_seconds(self, program: str) -> float:
        return sum(v for k, v in self.trace.get("kernel_s", {}).items()
                   if program in k)

    def roofline(self, flops: float, nbytes: float, seconds: float):
        """Percent of the chip's roofline, and which bound sets it."""
        if seconds <= 0 or flops <= 0:
            return None, None
        tf = flops / self.peaks["bf16_flops_per_s"]
        tb = nbytes / self.peaks["hbm_bytes_per_s"]
        return 100.0 * max(tf, tb) / seconds, ("compute" if tf >= tb
                                               else "memory")

    # -- counts -----------------------------------------------------------
    def heads(self):
        c = self.cfg
        return (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], c["num_hidden_layers"])

    def decode_attention_work(self, span):
        h, kv, hd, layers = self.heads()
        f = by = 0.0
        for b, _, _, step, _ in self.calls("decode", span):
            ff, bb = kernels.decode_attention(b, h, kv, hd,
                                              self.prompt_len + step)
            f += ff * layers
            by += bb * layers
        return f, by

    def swa_prefill_work(self, span):
        h, kv, hd, layers = self.heads()
        w = int(self.cfg.get("sliding_window") or 0)
        f = by = 0.0
        for b, _, _, _, _ in self.calls("prefill", span):
            ff, bb = kernels.swa_prefill(b, h, kv, hd, self.prompt_len, w)
            f += ff * layers
            by += bb * layers
        return f, by

    def served_model_flops(self) -> float:
        """Model FLOPs of the tokens served inside the window."""
        a, b = self.window
        p = self.prompt_len
        total = 0.0
        for s in self.run.rec.stamps.values():
            if not s:
                continue
            for j, t in enumerate(s):
                if a <= t < b:
                    total += (model.prefill_flops(self.cfg, p) if j == 0
                              else model.decode_flops(self.cfg, p + j - 1))
        return total

    pct = staticmethod(stats.pct)
    np = np
