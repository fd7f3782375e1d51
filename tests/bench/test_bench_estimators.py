"""The estimators on synthetic event streams."""

import math
import statistics

import pytest

from benchmark import estimators as est

ROUND_S, ROWS = 0.012, 21


def rounds(t_first, seconds, chunk_every=25, k=4):
    """A 12 ms round of 21 rows, one token a row, each row's event some
    tens of microseconds after the last; every ``chunk_every``-th dispatch
    is a chunk of ``k`` steps, whose tokens arrive together after ``k``
    rounds' time: the stream pauses 48 ms now and then and its rate is
    the same throughout."""
    stream, t, i = [], t_first, 0
    while t < t_first + seconds:
        steps = k if i % chunk_every == chunk_every - 1 else 1
        t += steps * ROUND_S
        stream += [(t + 0.00002 * r, steps, r) for r in range(ROWS)]
        i += 1
    return stream


@pytest.mark.parametrize("phase", [0.0, 0.0031, 0.0077, 0.013, 0.019, 0.0479, 0.5, 1.234])
def test_plain_rate_of_a_short_round_is_the_true_rate_whatever_the_windows_phase(phase):
    stream = rounds(100.0, 60.0)
    t0 = 110.0 + phase
    # an edge cuts one chunk's 84 tokens of 78,750 at the most
    assert est.plain_rate(stream, t0, t0 + 45.0) == pytest.approx(ROWS / ROUND_S, rel=2e-3)


def test_plain_rate_counts_a_stall_as_the_time_it_took():
    calm = rounds(100.0, 60.0)
    stalled = [(t + 2.0 if t > 130.0 else t, n, r) for t, n, r in calm]
    assert est.plain_rate(stalled, 110.0, 155.0) == pytest.approx(
        est.plain_rate(calm, 110.0, 155.0) * 43.0 / 45.0, rel=2e-3
    )
    assert est.plain_rate([], 110.0, 155.0) == 0.0


def test_too_few_slices_with_a_reading_give_nothing():
    stream = [(100.0 + 0.05 * i, 1, 0) for i in range(40)]  # two seconds of 45
    assert est.slice_tpot(stream, 100.0, 145.0, 10) is None
    assert est.median_of_slices([1.0, None, 3.0, 5.0]) == 3.0


def test_pooled_tpot_weights_by_tokens_not_by_requests():
    # request 0: 10 tokens 100 ms apart; request 1: 2 tokens 1 s apart
    stream = sorted(
        [(0.1 * i, 1, 0) for i in range(10)] + [(0.0, 1, 1), (1.0, 1, 1)]
    )
    assert est.pooled_tpot(stream, 0.0, 2.0) == pytest.approx((0.9 + 1.0) / (9 + 1))
    # an event that carries three tokens counts three
    burst = [(0.0, 1, 0), (0.8, 8, 0)]
    assert est.pooled_tpot(burst, 0.0, 1.0) == pytest.approx(0.1)
    assert est.pooled_tpot([(0.0, 1, 0)], 0.0, 1.0) is None


def test_slice_tpot_on_chunked_arrivals():
    # 8-token chunks every 616 ms: 77 ms a token whatever the slicing
    stream = [(0.616 * i, 8, r) for i in range(200) for r in range(4)]
    assert est.slice_tpot(sorted(stream), 10.0, 55.0, 10) == pytest.approx(0.077, rel=1e-6)


def test_token_gaps_pool_per_request():
    stream = [(0.0, 1, 0), (0.05, 1, 1), (0.1, 1, 0), (0.25, 1, 1)]
    assert sorted(est.token_gaps(stream, 0.0, 1.0)) == pytest.approx([0.1, 0.2])


def test_quantile_interpolates_and_failures_rank_above_every_sample():
    assert est.quantile(range(1, 101), 0.9) == pytest.approx(90.1)
    assert est.quantile([5.0], 0.9) == 5.0
    assert est.quantile([], 0.9) is None
    # ten failures above ninety good samples: the P90 sits at the edge of
    # the good ones and everything beyond is the largest seen
    values = list(range(1, 91))
    assert est.quantile_with_failures(values, 10, 0.9) == pytest.approx(90.0, abs=1.0)
    assert est.quantile_with_failures(values, 10, 0.95) == 90.0


def test_failures_count_as_the_largest_or_the_time_limit():
    assert est.quantile_with_failures([100.0, 200.0], 2, 0.9, at_least=120000.0) == 120000.0
    assert est.quantile_with_failures([], 3, 0.9, at_least=7.0) == 7.0
    assert est.quantile_with_failures([], 0, 0.9) is None


@pytest.mark.parametrize("text,tokens", [
    ("abc", 3), ("é", 2), ("€", 3), ("\U0001f600", 4), ("�", 1),
    ("a�b", 3), ("", 0),
])
def test_tokens_in_text(text, tokens):
    assert est.tokens_in_text(text) == tokens


def test_tokens_in_text_never_overcounts_the_decoder():
    import codecs
    import random

    rng = random.Random(4)
    for _ in range(200):
        data = bytes(rng.randrange(256) for _ in range(40))
        dec = codecs.getincrementaldecoder("utf-8")("replace")
        seen = sum(est.tokens_in_text(dec.decode(bytes([b]))) for b in data)
        seen += est.tokens_in_text(dec.decode(b"", final=True))
        assert seen <= len(data)
        assert seen >= 0.7 * len(data)


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert est.spread(values) == pytest.approx((q3 - q1) / statistics.median(values))
    assert est.spread([1.0]) is None


def test_sync_readings_ignore_the_window():
    stamps = [10.0 + 2.232 * i for i in range(20)]
    readings = est.sync_readings(stamps, 8 * 32 * 1024)
    assert len(readings) == 19
    assert statistics.median(readings) == pytest.approx(8 * 32 * 1024 / 2.232)
    stamps[7:] = [s + 3.0 for s in stamps[7:]]  # one stall
    assert statistics.median(est.sync_readings(stamps, 8 * 32 * 1024)) == pytest.approx(
        8 * 32 * 1024 / 2.232
    )
    assert math.isclose(min(est.sync_readings(stamps, 262144)), 262144 / 5.232)


def test_longest_pause_finds_a_stall_and_where_it_began():
    stream = [(100.0 + 0.25 * i, 24, i % 24) for i in range(40)]  # up to 109.75
    stalled = [e for e in stream if not 103.0 < e[0] < 106.0]
    assert est.longest_pause(stream, 100.0, 110.0)[0] == pytest.approx(0.25)
    assert est.longest_pause(stalled, 100.0, 110.0) == pytest.approx((3.0, 3.0))
    assert est.longest_pause([], 100.0, 110.0) == pytest.approx((10.0, 0.0))
