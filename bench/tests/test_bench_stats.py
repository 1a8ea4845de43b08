"""Percentiles and attainment from wall stamps; a request never served
counts as a miss and as infinitely late."""
import math

import numpy as np

from bench import stats


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert stats.pct(v, 95) == 95
    assert stats.pct(v, 50) == 50
    assert stats.pct([3.0], 95) == 3.0
    assert stats.pct([1, 2, math.inf], 50) == 2
    assert stats.pct([1, math.inf], 95) == math.inf
    assert math.isnan(stats.pct([], 95))


def test_ttft_and_gaps_from_stamps():
    send = [0.0, 1.0, 2.0]
    st = [[0.5, 0.6, 0.8], None, [2.1]]
    t = stats.ttft(send, st)
    assert t[0] == 0.5 and math.isinf(t[1]) and abs(t[2] - 0.1) < 1e-12
    assert np.allclose(stats.gaps(st), [0.1, 0.2])


def test_attainment_counts_unserved_as_miss():
    send = [0.0, 0.0, 0.0, 0.0]
    st = [[0.5, 0.55],      # meets both
          [1.5],            # first token late
          [0.2, 0.4],       # a gap over the TBT limit
          None]             # never served
    slo, tbt = [1.0] * 4, [0.1] * 4
    assert stats.attainment(send, st, slo, tbt) == 25.0


def test_tokens_in_window():
    st = [[0.5, 1.0, 1.5], None, [0.9, 2.0]]
    assert stats.tokens_in(st, 1.0, 2.0) == 2


def test_tokens_are_stamped_as_they_are_handed_out():
    """The backend's ``generated`` lists stamp each token when it is added,
    with the device call that made it, whatever the gang's shape."""
    from bench.harness import Recorder, StampedTokens
    rec = Recorder()
    gen = StampedTokens(rec)
    rec.calls.append(("prefill", 2, 0.0, 1.0))
    gen[7] = [5]
    gen[8] = [6]
    rec.calls.append(("decode", 2, 1.0, 2.0))
    gen[7].append(9)
    gen[8] += [1, 2]                 # two tokens from one call
    assert rec.via == {7: [0, 1], 8: [0, 1, 1]}
    assert len(rec.stamps[8]) == 3 and rec.stamps[8] == sorted(rec.stamps[8])
    gen[7] = [3]                     # a fresh list starts the request again
    assert rec.via[7] == [1] and len(rec.stamps[7]) == 1
    assert gen[8] == [6, 1, 2]
