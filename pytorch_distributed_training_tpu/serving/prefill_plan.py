"""How one tick's fresh admissions are grouped into prefill calls.

A prefill call runs on the compiled grid ``batch_buckets x seq_buckets``: its
rows are padded to the smallest batch bucket that holds them and to the
smallest sequence bucket that holds the longest.  One call over every row of
a tick pays for the padding of both at once (two rows under ``batch_buckets``
[1, 8, 32] run eight rows at the longer row's bucket); one call a row pays a
call's fixed time once a row.  Which is cheaper depends on the model, so the
choice is made from what a call is ESTIMATED to cost and nothing else.

:func:`plan_calls` is pure: suffix lengths, the two bucket lists and a
``cost(batch_bucket, seq_bucket)`` go in, groups of row indices come out.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Sequence

__all__ = ["LinearCost", "bucket_for", "plan_calls"]


def bucket_for(n: int, buckets: Sequence[int], kind: str) -> int:
    """The smallest of the (ascending) ``buckets`` that holds ``n``."""
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{kind} {n} exceeds largest bucket {buckets[-1]}")


class LinearCost(NamedTuple):
    """``fixed_ms + ms_per_ktoken x padded tokens / 1000``: a call's time as
    two timed programs of the warm-up put it (``serving/engine.py``)."""

    fixed_ms: float
    ms_per_ktoken: float

    def __call__(self, batch_bucket: int, seq_bucket: int) -> float:
        return self.fixed_ms + self.ms_per_ktoken * batch_bucket * seq_bucket / 1e3

    @classmethod
    def through(cls, small, large) -> "LinearCost":
        """The line through two ``(padded tokens, measured ms)`` points,
        neither part below zero: a timing's jitter must not make padding or
        a call look like a gain."""
        (n0, ms0), (n1, ms1) = small, large
        slope = max((ms1 - ms0) / (n1 - n0), 0.0)
        return cls(max(ms0 - slope * n0, 0.0), 1e3 * slope)


def plan_calls(
    suffix: Sequence[int],
    batch_buckets: Sequence[int],
    seq_buckets: Sequence[int],
    cost: Callable[[int, int], float],
) -> List[List[int]]:
    """Partition rows ``0..len(suffix)-1`` into the calls whose summed
    ``cost`` is least.

    Rows are taken in order of suffix length and a call is a run of that
    order: with a cost that does not fall as a call's sequence bucket grows,
    some cheapest partition has this form (hand each call, longest first,
    the longest rows left: no call's longest row grows).  A dynamic
    programme over the run's end finds it in ``O(rows x largest batch
    bucket)`` evaluations.  The one call over every row is among the
    candidates and wins a tie, so the result never costs more than it.

    Returns the calls in the order of their earliest row, each a list of
    row indices in ascending order.  More rows than the largest batch bucket
    holds are refused (``bucket_for``'s ``ValueError``).
    """
    n = len(suffix)
    order = sorted(range(n), key=lambda i: (suffix[i], i))
    seq = [bucket_for(suffix[i], seq_buckets, "prefill suffix") for i in order]
    # best[j] = (cost, calls) of the cheapest way to run the j shortest rows;
    # fewer calls win a tie
    best = [(0.0, 0)] + [None] * n
    cut = [0] * (n + 1)
    for j in range(1, n + 1):
        for i in range(j):
            bb = bucket_for(j - i, batch_buckets, "admitted rows")
            cand = (best[i][0] + cost(bb, seq[j - 1]), best[i][1] + 1)
            if best[j] is None or cand < best[j]:
                best[j], cut[j] = cand, i
    calls = []
    j = n
    while j:
        calls.append(sorted(order[cut[j]:j]))
        j = cut[j]
    return sorted(calls, key=lambda rows: rows[0])
