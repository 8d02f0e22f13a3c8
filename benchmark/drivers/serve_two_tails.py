"""Serving cells whose two lengths have a tail each: ``drivers/serve.py`` as
it is, under a generator that draws a prompt's length with
``prompt_tail_alpha`` and an answer's with ``gen_tail_alpha``.

``loadgen.make_trace`` reads ONE ``tail_alpha`` for both lengths, and a mix of
long-tailed prompts with short-tailed answers (files and diffs in, a patch
out) cannot be said with it.  A traffic file of ``"kind": "serve_two_tails"``
brings this driver, as ``run.py`` finds every driver, and no file that was
there is edited.

The generator that is there does the drawing.  A Pareto draw takes one uniform
number from ``mix_seed``'s stream whatever its ``alpha``
(``random.paretovariate``), so the stream, and with it every arrival, is the
same under any tail: the trace drawn under the prompts' tail gives the
prompts, the trace drawn under the answers' tail gives the answers, and with
both tails equal the result is ``loadgen.make_trace``'s own.

The sweep that fixes such a cell's rate, ``sweep.py`` under the same
generator:

    python3 -m benchmark.drivers.serve_two_tails --workload <cell> --seed 1 \\
        --seconds 20 --rates 2 2.5 3
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
from typing import List

from .. import loadgen
from . import serve


_one_tail = loadgen.make_trace  # the accepted generator, whatever stands in its place below


def make_trace(traffic: dict, seconds: float) -> List[loadgen.Arrival]:
    """``loadgen.make_trace``'s arrivals with each length under its own tail."""
    prompts = _one_tail(dict(traffic, tail_alpha=traffic["prompt_tail_alpha"]), seconds)
    answers = _one_tail(dict(traffic, tail_alpha=traffic["gen_tail_alpha"]), seconds)
    if [(a.due_s, a.group) for a in prompts] != [(a.due_s, a.group) for a in answers]:
        raise RuntimeError("the tail moved the arrivals: a draw takes more than one number")
    return [dataclasses.replace(p, gen_len=a.gen_len) for p, a in zip(prompts, answers)]


@contextlib.contextmanager
def generator():
    """``loadgen.make_trace`` is the one above inside the block: the serving
    driver and the sweep reach the generator through the module."""
    loadgen.make_trace = make_trace
    try:
        yield
    finally:
        loadgen.make_trace = _one_tail


def run(cell: dict, args, out_dir: str, ledger, t_start: float):
    with generator():
        return serve.run(cell, args, out_dir, ledger, t_start)


if __name__ == "__main__":
    from .. import sweep

    with generator():
        sys.exit(sweep.main())
