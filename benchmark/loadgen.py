"""Traffic for the serving cells: one general generator that reads a traffic
file's parameters, and the open-loop client that stamps every token as the
client receives it.

The generator is a copy of ``serving/workload.py::TraceGenerator``'s rate
model and length draws (see PERF.md, Open questions) with two changes the
benchmark needs:

- the arrivals are fixed by the traffic file's ``mix_seed``: the same
  (due time, prompt length, output length) in every run.  ``--seed`` makes
  the weights and fills in the token ids.  Measured on the chip (PERF.md,
  PR 23): with the seed ordering the same multiset, even only by entering
  one cycle of arrivals at another point, the 95th percentile of time to
  first token spread 44% between seeds, because which burst meets which
  long request IS the tail; a bound of at most 10% can then tell nothing;
- exactly ``round(rate x seconds)`` requests are due in the window, their
  gaps scaled so that the last is due as the window closes.

Stdlib and numpy only; nothing of the program is imported.
"""
from __future__ import annotations

import heapq
import math
import threading
import time
from dataclasses import dataclass, field
from random import Random
from typing import Callable, List, Optional

import numpy as np


@dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float          # offset from the window's opening; < 0 = lead-in
    prompt_len: int
    gen_len: int
    group: Optional[int]  # shared-prefix group, None = a prompt of its own
    counted: bool         # due inside the window


def _tail_len(rng: Random, lo: int, hi: int, alpha: float) -> int:
    """Pareto-tailed whole length in [lo, hi] (TraceGenerator._tail_len)."""
    return min(hi, max(lo, int(lo * rng.paretovariate(alpha))))


def rate_factor(t: float, traffic: dict, flash_starts: List[float]) -> float:
    """The instantaneous rate at window offset ``t`` as a multiple of
    ``rate_rps``: one diurnal sine (trough at 0) and flash crowds
    (TraceGenerator.rate_at)."""
    factor = 1.0
    amp = float(traffic.get("diurnal_amplitude", 0.0))
    if amp:
        period = float(traffic["diurnal_period_s"])
        factor *= 1.0 - amp * math.cos(2.0 * math.pi * t / period)
    dur = float(traffic.get("flash_duration_s", 0.0))
    for start in flash_starts:
        if start <= t < start + dur:
            factor *= float(traffic["flash_multiplier"])
            break
    return factor


def make_trace(traffic: dict, seconds: float) -> List[Arrival]:
    """The arrivals of one run: a lead-in (sent, not counted, so that the
    window opens on a system already in its steady state) and the window."""
    mix = Random(int(traffic.get("mix_seed", 0)))
    rate = float(traffic["rate_rps"])
    lead = float(traffic.get("lead_in_s", 0.0))
    alpha = float(traffic.get("tail_alpha", 1.8))
    groups = int(traffic.get("prefix_groups", 0))
    share = float(traffic.get("prefix_fraction", 0.0))
    crowds = int(traffic.get("flash_crowds", 0))
    span = seconds / crowds if crowds else 0.0
    flash_starts = [
        i * span + mix.uniform(0.1 * span, max(
            0.1 * span, span - float(traffic["flash_duration_s"])))
        for i in range(crowds)
    ]

    def phase(n: int, length: float, offset: float, counted: bool):
        gaps, t = [], 0.0
        for _ in range(n):
            # non-homogeneous Poisson by the instantaneous-rate exponential
            gap = mix.expovariate(rate * rate_factor(t, traffic, flash_starts))
            gaps.append(gap)
            t += gap
        sizes = [
            (_tail_len(mix, int(traffic["prompt_min"]), int(traffic["prompt_max"]), alpha),
             _tail_len(mix, int(traffic["gen_min"]), int(traffic["gen_max"]), alpha),
             mix.randrange(groups) if groups and mix.random() < share else None)
            for _ in range(n)
        ]
        scale = length / sum(gaps) if gaps else 1.0
        out, t = [], 0.0
        for gap, (p, g, grp) in zip(gaps, sizes):
            t += gap * scale
            out.append((offset + t, p, g, grp, counted))
        return out

    rows = phase(round(rate * lead), lead, -lead, False)
    rows += phase(round(rate * seconds), seconds, 0.0, True)
    return [
        Arrival(i, due, p, g, grp, counted)
        for i, (due, p, g, grp, counted) in enumerate(rows)
    ]


def make_prompts(trace: List[Arrival], traffic: dict, vocab: int, seed: int
                 ) -> List[np.ndarray]:
    """Token ids from the seed.  Requests of one prefix group share the
    first ``prefix_share`` of the shorter prompt's length."""
    rng = np.random.default_rng(int(seed))
    prefix_share = float(traffic.get("prefix_share", 0.5))
    longest = max((a.prompt_len for a in trace), default=1)
    group_tokens = {}
    prompts = []
    for a in trace:
        tokens = rng.integers(0, vocab, a.prompt_len, dtype=np.int64)
        if a.group is not None:
            if a.group not in group_tokens:
                group_tokens[a.group] = rng.integers(0, vocab, longest)
            n = int(a.prompt_len * prefix_share)
            tokens[:n] = group_tokens[a.group][:n]
        prompts.append(tokens.astype(np.int32))
    return prompts


@dataclass
class Served:
    arrival: Arrival
    due: float = 0.0             # monotonic
    submitted: float = 0.0
    token_times: List[float] = field(default_factory=list)
    tokens: Optional[np.ndarray] = None
    finished: Optional[float] = None
    error: Optional[str] = None


class OpenLoopClient:
    """Sends each request when it is due, whatever the system is doing, from
    one thread; stamps each token on the client's side (``on_token``)."""

    def __init__(self, submit: Callable, trace: List[Arrival], prompts):
        self.submit, self.prompts = submit, prompts
        self.records = [Served(a) for a in trace]
        self.futures: List = [None] * len(trace)
        self._thread = threading.Thread(target=self._send, name="bench-client")
        self.t0 = 0.0

    def start(self, t0: float) -> None:
        """``t0``: the monotonic instant the window opens (lead-in before)."""
        self.t0 = t0
        self._thread.start()

    def _send(self) -> None:
        for rec, prompt in zip(self.records, self.prompts):
            rec.due = self.t0 + rec.arrival.due_s
            delay = rec.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            rec.submitted = time.monotonic()
            stamp = rec.token_times.append
            try:
                self.futures[rec.arrival.index] = self.submit(
                    prompt, max_new_tokens=rec.arrival.gen_len,
                    on_token=lambda _tok, stamp=stamp: stamp(time.monotonic()),
                )
            except Exception as e:  # refused at the door: a failed request
                rec.error = repr(e)

    def finish(self, deadline: float) -> None:
        """Wait for every request until the monotonic ``deadline``."""
        self._thread.join(max(0.0, deadline - time.monotonic()) + 5.0)
        for rec, future in zip(self.records, self.futures):
            if future is None:
                rec.error = rec.error or "never submitted"
                continue
            try:
                result = future.result(timeout=max(0.0, deadline - time.monotonic()))
                rec.tokens = np.asarray(result["tokens"])
                rec.finished = rec.token_times[-1] if rec.token_times else None
            except Exception as e:
                rec.error = repr(e) or type(e).__name__


# ------------------------------------------------------------- arithmetic

def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of ALL the values given (a failed request is
    given as ``inf``: it misses any limit)."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def counted(records: List[Served]) -> List[Served]:
    return [r for r in records if r.arrival.counted]


def failed(rec: Served) -> bool:
    return (
        rec.error is not None or rec.tokens is None
        or len(rec.tokens) != rec.arrival.gen_len
        or len(rec.token_times) != rec.arrival.gen_len
    )


def ttft_ms(records: List[Served]) -> List[float]:
    """Due time to the client's receipt of the first token."""
    return [
        math.inf if failed(r) else (r.token_times[0] - r.due) * 1e3
        for r in counted(records)
    ]


def gaps_ms(records: List[Served]) -> List[float]:
    """Every gap between consecutive tokens of one request, pooled."""
    out = []
    for r in counted(records):
        if not failed(r):
            out += [(b - a) * 1e3 for a, b in zip(r.token_times, r.token_times[1:])]
    return out


def lag_ms(records: List[Served]) -> List[float]:
    """How late after its due time each request was really submitted."""
    return [(r.submitted - r.due) * 1e3 for r in counted(records) if r.submitted]


def tokens_per_s(records: List[Served], t0: float, seconds: float) -> float:
    """Every token the client stamped inside the window, a second: the
    lead-in's requests' too.  NOT a manifest metric (PERF.md, PR 36): the
    lead-in offers another rate than the window, so an engine that carries
    less of its backlog into the window reads LOWER here.  ``sweep.py``
    reads capacity above the knee with it, and a run's ``notes`` keep it as
    ``tokens_stamped_per_s`` to be laid beside the records before PR 36."""
    inside = sum(
        1 for r in records for t in r.token_times if t0 <= t <= t0 + seconds
    )
    return inside / seconds


def goodput_tokens_per_s(records: List[Served], t0: float, seconds: float) -> float:
    """Output tokens of the window's OWN requests that the client received
    inside the window, a second: over the requests due in the window and not
    failed, each token by its own time stamp.  A lead-in request counts
    nothing; a request that was refused, errored or came back short counts
    nothing, not even what it did deliver; a request that ends in the drain
    counts what it delivered before the window closed.  Below the knee it
    rises with every speed-up (less of the window's work is left for the
    drain) and falls with every shed or stalled request."""
    own = [r for r in counted(records) if not failed(r)]
    return tokens_per_s(own, t0, seconds)


def replay(trace: List[Arrival], slots: int, gap_s: float) -> List[Served]:
    """The trace served by an engine of ``slots`` rows that gives every
    request in a row one token each ``gap_s``, first come first served: no
    chip, no prefill, no jitter; the window opens at 0.  What a shorter
    token gap alone does to a count (PERF.md section 6, PR 36)."""
    free = [-math.inf] * slots            # when each row is free again
    out = []
    for a in trace:
        start = max(a.due_s, heapq.heappop(free))
        times = [start + (k + 1) * gap_s for k in range(a.gen_len)]
        heapq.heappush(free, times[-1])
        out.append(Served(a, due=a.due_s, submitted=a.due_s, token_times=times,
                          tokens=np.arange(a.gen_len), finished=times[-1]))
    return out
