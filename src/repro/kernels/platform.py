"""Where the Pallas kernels run: compiled by Mosaic on a TPU, or in the
Pallas interpreter on the CPU (tests and CPU rehearsals)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True on the CPU backend, False on a TPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas path for backend {backend!r}: kernels "
                       "compile for 'tpu' and interpret on 'cpu'")
