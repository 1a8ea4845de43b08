"""Pallas TPU kernels for the serving hot spots.

Each kernel ships three files: the pl.pallas_call implementation with
explicit BlockSpec VMEM tiling, ``ops.py`` (the jitted public wrapper:
compiled by Mosaic on a TPU, ``interpret=True`` on the CPU backend, an
error anywhere else — see ``platform.interpret_mode``), and ``ref.py``
(the pure-jnp oracle used by the shape/dtype sweep tests).
"""
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.swa_prefill.ops import swa_prefill_attention
