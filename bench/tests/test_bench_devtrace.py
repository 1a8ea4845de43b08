"""The trace reduction, on a 40 ms slice of a smollm135m-chat run recorded
on a TPU v5e (one prefill and twelve decode calls; trimmed to the device's
XLA ops and modules and the harness's spans)."""
from pathlib import Path

import pytest

from bench import devtrace

TRACE = Path(__file__).resolve().parent / "data" / \
    "smollm135m-chat.slice.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return devtrace.reduce_trace(str(TRACE))


def test_window_busy_and_idle_add_up(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.040, abs=1e-6)
    assert reduced["busy_s"] == pytest.approx(0.015897435, rel=1e-6)
    idle = sum(reduced["idle"].values())
    assert reduced["busy_s"] + idle == pytest.approx(reduced["window_s"],
                                                     rel=1e-6)


def test_kernels_found_by_their_program(reduced):
    k = reduced["kernel_s"]
    prefill = [v for (m, n), v in k.items() if "prefill" in m]
    decode = [v for (m, n), v in k.items() if "decode" in m]
    assert len(prefill) == len(decode) == 1
    # each keyed by its kernel too: the op's name without its suffix
    assert sorted(n for _, n in k) == ["decode_attention",
                                       "swa_prefill_attention"]
    assert prefill[0] == pytest.approx(0.00062108, rel=1e-4)
    assert decode[0] == pytest.approx(0.002177836, rel=1e-4)
    # the decode kernel is also the op of that name in the breakdown
    assert reduced["ops"]["decode_attention.6"] == pytest.approx(decode[0])


def test_idle_named_by_host_span(reduced):
    idle = reduced["idle"]
    assert set(idle) <= {"decide", "prefill", "decode", "gang", "step",
                         "wait", "none"}
    assert max(idle, key=idle.get) == "decode"
    assert reduced["spans"]["bench.decode"] == 12
    assert reduced["spans"]["bench.prefill"] == 1


def test_container_ops_left_out(reduced):
    assert not any(name.startswith("while") for name in reduced["ops"])
    assert all(" = " not in name for name in reduced["ops"])
