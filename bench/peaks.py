"""Peak rates of a chip, keyed by JAX's ``device_kind`` (``peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """``{"bf16_flops_per_s", "hbm_bytes_per_s"}`` of ``device_kind``.

    A kind that is not in the table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{path.name}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]
