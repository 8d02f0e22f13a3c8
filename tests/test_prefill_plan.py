"""The partition of one tick's fresh admissions into prefill calls
(serving/prefill_plan.py), as a pure function: no model, no device."""
import numpy as np
import pytest

from pytorch_distributed_training_tpu.serving.prefill_plan import (
    LinearCost,
    bucket_for,
    plan_calls,
)

GRIDS = {
    "b1-8": ([1, 8], [256, 1024]),
    "b1-8-32": ([1, 8, 32], [1024, 4096]),
    "one-seq-bucket": ([1, 8, 32], [512]),
}
COSTS = {
    # a call is mostly its dispatch: a batch is nearly free
    "batch-nearly-free": LinearCost(10.0, 0.01),
    # attention and the scan walk a call's rows: time follows padded tokens
    "by-padded-tokens": LinearCost(0.0, 35.0),
    # the fit PERF.md's readings give in long32
    "long32-fit": LinearCost(24.0, 35.0),
}


def _cost_of(calls, suffix, grid, cost):
    batch, seq = grid
    return sum(
        cost(bucket_for(len(rows), batch, "rows"),
             bucket_for(max(suffix[i] for i in rows), seq, "suffix"))
        for rows in calls
    )


def _set_partitions(items):
    if not items:
        yield []
        return
    first, rest = items[0], items[1:]
    for part in _set_partitions(rest):
        yield [[first]] + part
        for k in range(len(part)):
            yield part[:k] + [[first] + part[k]] + part[k + 1:]


def _suffixes(n, longest, seed):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(1, longest + 1, n)]


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize(
    "grid, n",
    # a tick admits at most the largest batch bucket
    [(g, n) for g in sorted(GRIDS) for n in (1, 2, 3, 8, 9, 32)
     if n <= GRIDS[g][0][-1]],
)
def test_every_row_runs_once_on_the_grid_and_never_dearer_than_one_call(
    grid, cost, n
):
    batch, seq = GRIDS[grid]
    for seed in range(5):
        suffix = _suffixes(n, seq[-1], seed)
        calls = plan_calls(suffix, batch, seq, COSTS[cost])
        assert sorted(i for rows in calls for i in rows) == list(range(n))
        assert all(len(rows) <= batch[-1] for rows in calls)
        # the calls in the order of their earliest row, rows ascending
        assert [rows[0] for rows in calls] == sorted(rows[0] for rows in calls)
        assert all(rows == sorted(rows) for rows in calls)
        one_call = _cost_of([list(range(n))], suffix, GRIDS[grid], COSTS[cost])
        assert _cost_of(calls, suffix, GRIDS[grid], COSTS[cost]) <= one_call


@pytest.mark.parametrize("cost", sorted(COSTS))
@pytest.mark.parametrize("grid", sorted(GRIDS))
@pytest.mark.parametrize("n", [2, 3, 4, 5, 6])
def test_as_cheap_as_the_cheapest_of_all_partitions(grid, cost, n):
    batch, seq = GRIDS[grid]
    for seed in range(3):
        suffix = _suffixes(n, seq[-1], 100 + seed)
        cheapest = min(
            _cost_of(part, suffix, GRIDS[grid], COSTS[cost])
            for part in _set_partitions(list(range(n)))
        )
        planned = _cost_of(
            plan_calls(suffix, batch, seq, COSTS[cost]),
            suffix, GRIDS[grid], COSTS[cost],
        )
        assert planned == pytest.approx(cheapest, rel=1e-12)


@pytest.mark.parametrize("grid", sorted(GRIDS))
@pytest.mark.parametrize("n", [2, 3, 7])
def test_a_batch_that_is_nearly_free_is_kept(grid, n):
    batch, seq = GRIDS[grid]
    suffix = _suffixes(n, seq[0], 7)
    assert plan_calls(suffix, batch, seq, COSTS["batch-nearly-free"]) == [
        list(range(n))
    ]


@pytest.mark.parametrize("grid", ["b1-8", "b1-8-32"])
@pytest.mark.parametrize("cost", ["by-padded-tokens", "long32-fit"])
def test_two_rows_split_where_time_follows_padded_tokens(grid, cost):
    batch, seq = GRIDS[grid]
    # the later arrival is the shorter: the calls keep the order of arrival
    assert plan_calls([seq[-1], seq[0] // 2], batch, seq, COSTS[cost]) == [
        [0], [1]
    ]


def test_full_rows_of_one_bucket_share_a_call():
    """Eight rows of one sequence bucket fill the batch bucket of 8: nothing
    is padding, and eight calls would pay the fixed part eight times."""
    batch, seq = GRIDS["b1-8-32"]
    assert plan_calls([900] * 8, batch, seq, COSTS["long32-fit"]) == [
        list(range(8))
    ]
    # a ninth, long row gets a call of its own and not a bucket of 32
    calls = plan_calls([900] * 8 + [4000], batch, seq, COSTS["long32-fit"])
    assert calls == [list(range(8)), [8]]


def test_more_rows_than_the_largest_batch_bucket_is_refused():
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        plan_calls([1] * 9, [1, 8], [16], COSTS["long32-fit"])


@pytest.mark.parametrize(
    "small, large, want",
    [
        # PERF.md's long32 readings: 1 x 1,024 in 60 ms, 1 x 4,096 in 167
        ((1024, 60.0), (4096, 167.0), (24.33, 34.83)),
        # a longer program timed FASTER (jitter): no negative slope
        ((256, 5.0), (1024, 4.0), (5.0, 0.0)),
        # a line that would cross below zero at no tokens: no negative call
        ((256, 1.0), (1024, 8.0), (0.0, 9.11)),
    ],
)
def test_the_line_through_two_timed_programs(small, large, want):
    fit = LinearCost.through(small, large)
    assert tuple(fit) == pytest.approx(want, abs=0.01)
    assert fit.fixed_ms >= 0.0 and fit.ms_per_ktoken >= 0.0
    assert fit(8, 4096) == pytest.approx(
        fit.fixed_ms + fit.ms_per_ktoken * 32.768
    )
