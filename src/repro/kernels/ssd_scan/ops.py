"""Jitted public wrapper for the SSD kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.platform import interpret_mode
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, b, c, h0, chunk: int = 128):
    """Mamba2 SSD scan.  x: (B,T,H,P); dt: (B,T,H); a_log: (H,);
    b,c: (B,T,N); h0: (B,H,P,N).  Returns (y, h_final)."""
    return ssd_scan_pallas(x, dt, a_log, b, c, h0, chunk=chunk,
                           interpret=interpret_mode())
