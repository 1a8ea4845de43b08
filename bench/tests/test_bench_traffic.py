"""Traffic mixes repeat exactly for one seed; across seeds they keep the
same counts and lengths per phase and differ in when requests come."""
import numpy as np
import pytest

from bench import gen, harness


def _mix(rate, name="chat-r18.4"):
    m = gen.load_mix(name)
    m["rate_rps"] = rate
    return m


def _cell_mixes():
    return sorted({w["traffic"] for w in harness.load_benchmark()["workloads"]})


@pytest.mark.parametrize("name", _cell_mixes())
def test_same_seed_same_traffic(name):
    a = gen.generate(gen.load_mix(name), 2 ** 31 + 11, 10.0, 1000)
    b = gen.generate(gen.load_mix(name), 2 ** 31 + 11, 10.0, 1000)
    for f in ("send", "phase", "decode", "comm", "slo", "tbt"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_every_cell_mix_is_one_file_with_a_rate():
    for name in _cell_mixes():
        m = gen.load_mix(name)
        assert m["rate_rps"] > 0 and "extends" not in m, name


def test_seeds_change_times_not_load():
    m = _mix(20.0)
    a = gen.generate(m, 1, 10.0, 1000)
    b = gen.generate(m, 2, 10.0, 1000)
    assert not np.array_equal(a.send, b.send)
    assert not np.array_equal(a.decode, b.decode)
    for ph, n in ((gen.LEAD_IN, 60), (gen.WINDOW, 200), (gen.TAIL, 60)):
        sa, sb = a.phase == ph, b.phase == ph
        assert sa.sum() == sb.sum() == n
        assert np.array_equal(np.sort(a.decode[sa]), np.sort(b.decode[sb]))
    w = a.phase == gen.WINDOW
    assert a.window == (3.0, 13.0)
    assert a.send[w].min() >= 3.0 and a.send[w].max() < 13.0
    assert (np.diff(a.send) >= 0).all()


def test_window_arrivals_are_poisson():
    """Gaps are exponential (coefficient of variation near 1) and the
    counts in 1 s bins spread like a Poisson count (variance near mean)."""
    t = gen.generate(_mix(20.0), 7, 500.0, 1000)
    s = t.send[t.phase == gen.WINDOW]
    g = np.diff(s)
    assert 0.9 < g.std() / g.mean() < 1.1
    counts = np.histogram(s, bins=np.arange(3.0, 503.0, 1.0))[0]
    assert 0.8 < counts.var() / counts.mean() < 1.2


def test_lengths_follow_the_mix():
    t = gen.generate(_mix(40.0), 5, 30.0, 49152)
    assert (t.prompt_len == 128).all()
    assert 1 <= t.decode.min() and t.decode.max() <= 128
    assert 20 <= np.median(t.decode) <= 28
    assert (t.comm >= 0.02).all() and (t.comm < 0.1).all()
    assert all(p.shape == (128,) and p.max() < 49152 for p in t.prompts)
