"""The benchmark's own CPU tests: its arithmetic, and a tiny run of the
harness with the timed path broken underneath."""
import copy
import json
import os
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]

# A tiny decoder of Danube's shape (untied head, windowed layers), small
# enough for the Pallas interpreter; its window is wider than any sequence.
# At 60 requests/s on the CPU its gangs hold several requests.
TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256)


def tiny_cell(rate=60.0, limit=None):
    from bench import gen, harness
    cfg = json.loads((BENCH / "configs" / "h2o-danube-1.8b.json").read_text())
    cfg.update(TINY)
    cfg["program"] = dict(cfg["program"], b_set=[1, 2, 4, 8])
    mix = gen.load_mix("chat-r18.4", BENCH / "traffic")
    mix.update(rate_rps=rate, lead_in_s=0.5, tail_s=0.5, prompt_tokens=16,
               decode={"median": 4, "sigma": 0.6, "lo": 1, "hi": 8},
               table={"prompt_len": 16, "max_decode": 8},
               sample={"min_served_tokens": 200, "max_requests": 64})
    limits = {} if limit is None else {"logit_gap": {"limit": limit}}
    return harness.Cell(name="tiny", cfg=cfg, mix=mix, end_to_end=[],
                        per_layer=[], limits=limits)


# Widest logit gap allowed at this size: sound runs read 0.008-0.037 (six
# seeds), the float8 control 0.32-0.74 (the same six), the planted faults
# 4.3-5.2 (CPU).
TINY_LIMIT = 0.1


def tiny_run(seed, fault=None, control=False, seconds=1.5, limit=TINY_LIMIT):
    """Drive the tiny cell on the CPU and check it, as a run would."""
    from bench import harness
    t = time.perf_counter()
    run, _ = harness.drive(tiny_cell(limit=limit), seed, seconds, t_proc=t,
                           fault=fault, require_tpu=False,
                           log=lambda *_: None)
    return run, harness.check(run, control=control, log=lambda *_: None)


@pytest.fixture
def tiny():
    return copy.deepcopy(TINY)
