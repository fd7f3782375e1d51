"""Estimators over client-side event streams and trainer sync stamps.

A token stream is a list of ``(t, n_tokens, request)`` sorted by ``t``:
one entry per SSE event that carried text. ``plain_rate`` is the tokens
that arrived inside the window over its seconds, all the work and all the
time of the window (``serve_tok_s``); ``pooled_tpot`` pools every token
after a request's first over every request seen in the window, stalls
included (``tpot_ms``); a trainer's rate is read between its own syncs.

``slice_tpot`` is read by no metric. Every serving run prints it beside
the pooled reading (ISSUE 23 asked for the comparison; PERF.md section 2
has what it showed: a median of slices drops the slices in which prefill
stalls decoding, so it reads low in time and spreads more).
"""

from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Stream = Sequence[Tuple[float, int, int]]


def tokens_in_text(text: str) -> int:
    """Tokens the byte tokenizer spent on ``text`` as the front door's
    incremental UTF-8 decoder emitted it: one per byte of every decoded
    character, and one for each U+FFFD (an undecodable byte; a broken
    multi-byte sequence can hide one or two more, which the caller adds
    to the request's last event from ``usage``)."""
    n = 0
    for ch in text:
        cp = ord(ch)
        if cp < 0x80 or cp == 0xFFFD:
            n += 1
        elif cp < 0x800:
            n += 2
        elif cp < 0x10000:
            n += 3
        else:
            n += 4
    return n


def quantile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile (the 'inclusive' method); None when
    there is nothing to read."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def quantile_with_failures(values: Sequence[float], n_failed: int,
                           q: float, at_least: float = 0.0) -> Optional[float]:
    """Quantile in which each failed sample ranks as the largest: it
    takes the largest value seen (or ``at_least``, say the time limit,
    if that is larger)."""
    if not values and not n_failed:
        return None
    worst = max([at_least, *values])
    return quantile([*values, *([worst] * n_failed)], q)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the bounds are set from."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def _in(stream: Stream, a: float, b: float) -> List[Tuple[float, int, int]]:
    return [e for e in stream if a <= e[0] < b]


def slices(t0: float, t1: float, n: int) -> List[Tuple[float, float]]:
    w = (t1 - t0) / n
    return [(t0 + i * w, t0 + (i + 1) * w) for i in range(n)]


def median_of_slices(readings: Sequence[Optional[float]]) -> Optional[float]:
    """Median of the slices that gave a reading; nothing unless at least
    half of them did."""
    got = [r for r in readings if r is not None]
    if not got or 2 * len(got) < len(readings):
        return None
    return float(statistics.median(got))


def plain_rate(stream: Stream, t0: float, t1: float) -> float:
    """Tokens that arrived in [t0, t1) over its seconds: everything the
    window saw, over all of its time."""
    return sum(n for _, n, _ in _in(stream, t0, t1)) / (t1 - t0)


def pooled_tpot(stream: Stream, a: float, b: float) -> Optional[float]:
    """Seconds per output token after the first, pooled and weighted by
    tokens: over every request with two or more events in [a, b), the
    sum of (last - first arrival) over the sum of tokens that arrived
    after the first."""
    first: Dict[int, float] = {}
    last: Dict[int, float] = {}
    toks: Dict[int, int] = {}
    for t, n, r in _in(stream, a, b):
        if r not in first:
            first[r] = t
            toks[r] = 0
        else:
            toks[r] += n
        last[r] = t
    num = sum(last[r] - first[r] for r in first if toks[r] > 0)
    den = sum(toks[r] for r in first if toks[r] > 0)
    return num / den if den else None


def slice_tpot(stream: Stream, t0: float, t1: float,
               n_slices: int) -> Optional[float]:
    return median_of_slices(
        [pooled_tpot(stream, a, b) for a, b in slices(t0, t1, n_slices)]
    )


def token_gaps(stream: Stream, t0: float, t1: float) -> List[float]:
    """Gaps between consecutive text events of the same request."""
    prev: Dict[int, float] = {}
    gaps: List[float] = []
    for t, _, r in _in(stream, t0, t1):
        if r in prev:
            gaps.append(t - prev[r])
        prev[r] = t
    return gaps


def longest_pause(stream: Stream, t0: float, t1: float) -> Tuple[float, float]:
    """The longest time in [t0, t1) without a token arriving, the
    window's own ends counting as arrivals: (seconds, offset of its start
    from t0). A stalled engine shows here and in no median."""
    times = [t0, *(t for t, _, _ in _in(stream, t0, t1)), t1]
    pause, start = max((b - a, a) for a, b in zip(times, times[1:]))
    return pause, start - t0


def sync_readings(stamps: Sequence[float], tokens_per_sync: float) -> List[float]:
    """Tokens per second between each pair of consecutive sync stamps."""
    return [
        tokens_per_sync / (b - a) for a, b in zip(stamps, stamps[1:]) if b > a
    ]


def stream_of(records: Sequence[Dict]) -> List[Tuple[float, int, int]]:
    """The token stream of a run's request records, in order of arrival."""
    return sorted(
        (t, n, rec["i"]) for rec in records for t, n in rec["events"]
    )
