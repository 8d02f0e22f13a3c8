"""Training cells: ``train_distributed.main()`` -> ``Runner`` with the loader
running, as a user launches it.

Set-up builds ONE runner.  Its first steps go through the loop's own call
and feed; a probe around ``Runner.train_iter`` (this file's, the program is
not touched) copies out what the comparison needs (the rows fed, each loss,
the optimizer's first moment after one step, the parameters after the last
checked step) and the same runner then runs the timed window.  The plain
reference follows those steps after the program's state is freed.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import re
import time

import numpy as np

from .. import spans as span_math
from .. import trace as trace_math
from ..common import (
    Check, Run, load_module, merge, program_bytes, runtime_peak_bytes,
    worst_leaf_gap,
)

INSIDE, OUTSIDE = "runner.train_iter", "runner.loop_outside_train_iter"
_ITER_LINE = re.compile(r"Iter \[(\d+)/\d+\] .*? Loss: (\S+)")


class Probe:
    """What the probe around ``train_iter`` gathers, and the stop rule."""

    def __init__(self, traffic: dict, seconds: float, ledger, trace_dir=None):
        self.check_steps = int(traffic["check_steps"])
        self.warmup_steps = int(traffic["warmup_steps"])
        self.print_interval = int(traffic["print_interval"])
        self.trace_steps = int(traffic["trace_steps"])
        self.trace_dir, self.tracing, self.trace_s = trace_dir, False, 0.0
        self._outside = None
        self.seconds = float(seconds)
        self.ledger = ledger
        self.fed, self.losses, self.step_args = [], [], None
        self.first_moment = self.params_after = None
        self.check_s = 0.0
        self.t0 = self.mark = self.compiled_before = self.inside = None

    def install(self, train_distributed):
        probe = self

        class ProbedRunner(train_distributed.Runner):
            def train_iter(self, g_img, g_label):
                checked = self.iter < probe.check_steps
                if checked:
                    probe.before(self, g_img, g_label)
                with probe.annotate():
                    super().train_iter(g_img, g_label)
                if checked:
                    probe.after(self)
                probe.trace(self)
                probe.clock(self)

        self._module, self._orig = train_distributed, train_distributed.Runner
        train_distributed.Runner = ProbedRunner

    def uninstall(self):
        self._module.Runner = self._orig

    def before(self, runner, g_img, g_label):
        import jax

        t = time.monotonic()
        self.fed.append(
            (np.asarray(jax.device_get(g_img)), np.asarray(jax.device_get(g_label)))
        )
        step_fn = runner.train_step

        def tapped(*args):
            # the step's arguments as the program passes them (shapes, types,
            # shardings), for the compiler's account of that same program
            self.step_args = jax.tree.map(_struct, args)
            out = step_fn(*args)
            self.losses.append(out[1])
            return out

        self._step_fn, runner.train_step = step_fn, tapped
        self.check_s += time.monotonic() - t

    def after(self, runner):
        import jax

        t = time.monotonic()
        runner.train_step = self._step_fn
        self.losses[-1] = float(self.losses[-1])
        if runner.iter == 0:
            self.first_moment = jax.device_get(runner.state.opt_state[0])
        if runner.iter == self.check_steps - 1:
            self.params_after = jax.device_get(runner.state.params)
        self.check_s += time.monotonic() - t

    @contextlib.contextmanager
    def annotate(self):
        """In the traced steps the host's line of the trace says whether the
        loop was inside ``train_iter`` or outside it (waiting for the loader,
        putting the batch): the idle gaps are named by these two."""
        if not self.tracing:
            yield
            return
        from jax.profiler import TraceAnnotation

        if self._outside is not None:
            self._outside.__exit__(None, None, None)
        with TraceAnnotation(INSIDE):
            yield
        self._outside = TraceAnnotation(OUTSIDE)
        self._outside.__enter__()

    def trace(self, runner):
        """Trace ``trace_steps`` steady steps of the warm-up, BEFORE the
        window, so that the traced run's own rate (mfu_pct) is not the
        profiler's.  The benchmark starts the trace itself and not through
        ``training.profile``: that hook cannot turn the python tracer off,
        which under sixteen loader threads took 22 s to stop a 1.4 s trace
        on a quiet host and ran a checked run past its time limit."""
        if self.trace_dir is None:
            return
        import jax

        t = time.monotonic()
        if runner.iter == self.check_steps:
            jax.block_until_ready(runner.state)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.tracing = True
        elif self.tracing and runner.iter == self.check_steps + self.trace_steps:
            jax.block_until_ready(runner.state)
            if self._outside is not None:
                self._outside.__exit__(None, None, None)
                self._outside = None
            jax.profiler.stop_trace()
            self.tracing = False
        else:
            return
        self.trace_s += time.monotonic() - t

    def clock(self, runner):
        """Open the window at the first synced step after warm-up; once it
        has lasted ``seconds``, end the run at the next synced step."""
        if runner.iter % self.print_interval or runner.iter < self.warmup_steps:
            return
        now = time.monotonic()
        if self.t0 is None:
            self.t0, self.mark = now, self.ledger.mark()
            self.compiled_before = self.ledger.since()
        elif now - self.t0 >= self.seconds and self.inside is None:
            self.inside = self.ledger.since(self.mark)
            runner.global_cfg["training"]["train_iters"] = runner.iter + 1


def compare(got: dict, expect: dict):
    """The numbers compared: each step's loss, the first gradient's norm and
    the parameters' change norm by the worst leaf.  Yields ``(name, the key
    of its limit in the configuration, value, note)``."""
    for step, (a, b) in enumerate(zip(got["losses"], expect["losses"])):
        yield (f"loss_step{step}_rel_gap", "loss_rel_gap", abs(a - b) / abs(b),
               f"got {a} reference {b}")
    gap, leaf = worst_leaf_gap(got["grad_norms"], expect["grad_norms"])
    yield ("first_grad_norm_worst_leaf_gap", "grad_norm_leaf_gap", gap,
           f"worst leaf {leaf}")
    gap, leaf = worst_leaf_gap(got["change_norms"], expect["change_norms"])
    yield ("param_change_norm_worst_leaf_gap", "change_norm_leaf_gap", gap,
           f"worst leaf {leaf}")


def program_cfg(cell: dict, out_dir: str, data_cfg: dict, weights: str) -> dict:
    config, traffic = cell["config_file"], cell["traffic_file"]
    return merge(config["train"], {
        "dataset": data_cfg,
        "training": {
            "batch_size": traffic["batch_size"],
            "num_workers": traffic["num_workers"],
            "train_iters": traffic["max_iters"],
            "print_interval": traffic["print_interval"],
            "val_interval": 10 ** 9,
            "telemetry": {"dir": os.path.join(out_dir, "telemetry")},
        },
        "model": {"pretrained": weights},
    })


def _struct(x):
    import jax

    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)


def compiled_step_bytes(runner, step_args) -> int:
    """The compiler's account of the step program the run drove, lowered
    for the arguments the program passed it, so that it is read back from
    the cache and nothing is built or executed: the runtime's own peak
    leaves temporaries out."""
    return program_bytes(runner.train_step.lower(*step_args).compile())


def run(cell: dict, args, out_dir: str, ledger, t_start: float):
    import jax
    import torch
    import yaml

    config, traffic = cell["config_file"], cell["traffic_file"]
    ref = load_module("reference", config["reference"])
    sizes = ref.sizes_of(config)
    seed = int(args.seed)

    # inputs and weights, from the seed, by the benchmark
    t_weights = time.monotonic()
    data_cfg = ref.prepare_data(seed, config, traffic, os.path.join(out_dir, "data"))
    params0 = jax.device_get(ref.make_params(seed, sizes))
    weights = os.path.join(out_dir, "weights.pth")
    torch.save(
        {k: torch.from_numpy(np.ascontiguousarray(v))
         for k, v in ref.to_torch_state_dict(params0).items()},
        weights,
    )

    cfg = program_cfg(cell, out_dir, data_cfg, weights)
    cfg_path = os.path.join(out_dir, "program.yml")
    with open(cfg_path, "w") as fp:
        yaml.safe_dump(cfg, fp)

    import train_distributed

    t_program = time.monotonic()
    probe = Probe(traffic, args.seconds, ledger,
                  os.path.join(out_dir, "trace") if args.trace else None)
    probe.install(train_distributed)
    try:
        runner = train_distributed.main([
            "--num-nodes", "1", "--rank", "0", "--dist-backend", "tpu",
            "--seed", str(seed % 2147483647), "--log-dir", out_dir,
            "--file-name-cfg", "program", "--cfg-filepath", cfg_path,
        ])
    finally:
        probe.uninstall()
    t_done = time.monotonic()

    run_ = Run(cell=cell, kind="train", seconds=args.seconds,
               chips=cell["chips"], out_dir=out_dir)
    run_.samples_per_step = runner.global_batch
    run_.spans = span_math.read_spans(
        os.path.join(out_dir, "telemetry", "spans_rank0.jsonl")
    )
    run_.window = span_math.find_window(
        run_.spans, int(traffic["warmup_steps"]), float(args.seconds)
    )
    run_.compile_inside = probe.inside
    if run_.window:
        run_.setup_s = run_.window["t0"] - t_start - probe.check_s
    run_.compile_before = probe.compiled_before
    with open(os.path.join(out_dir, "program.log")) as fp:
        logged = [float(m.group(2)) for m in _ITER_LINE.finditer(fp.read())]

    peak = max(runtime_peak_bytes(), compiled_step_bytes(runner, probe.step_args))
    run_.notes.update(
        memory_peak_bytes=peak, steps_run=runner.iter, run_s=t_done - t_start,
        check_transfer_s=probe.check_s, logged_losses=len(logged),
        # where a run's wall time goes, beside its 360 s limit
        weights_s=t_program - t_weights, program_s=t_done - t_program,
        trace_start_stop_s=probe.trace_s,
        step_bytes_s=time.monotonic() - t_done,
    )

    # free the program's state, then follow its first steps in the reference
    runner.state = runner.train_step = runner.eval_step = None
    del runner
    gc.collect()

    t_ref = time.monotonic()
    batches = [ref.reference_batch(fed, data_cfg) for fed in probe.fed]
    expect = ref.train_reference(
        jax.tree.map(jax.numpy.asarray, params0), batches, sizes,
        config["optimizer"], mode="f32",
    )
    limits = config["limits"]["train"]
    check = Check()
    got = {
        "losses": probe.losses,
        "grad_norms": ref.leaf_norms(ref.first_gradient(
            ref.from_program_tree(probe.first_moment, sizes), params0,
            config["optimizer"],
        )),
    }
    after = ref.from_program_tree(probe.params_after, sizes)
    got["change_norms"] = ref.leaf_norms(
        {k: after[k] - np.asarray(params0[k]) for k in after if k in params0}
    )
    for name, limit, value, note in compare(got, expect):
        check.add(name, value, limits[limit], note)
    if args.control:
        # limit-setting only: the reference itself in the nearest precision
        # below the configuration's, held to the same comparison
        lower = ref.train_reference(
            jax.tree.map(jax.numpy.asarray, params0), batches, sizes,
            config["optimizer"], mode=config["control_mode"],
        )
        for name, _, value, note in compare(lower, expect):
            print("control " + json.dumps(
                {"compared": name, "value": value, "note": note}), flush=True)
        with open(os.path.join(out_dir, "compared.json"), "w") as fp:
            json.dump({"program": got, "reference": expect, "control": lower}, fp)
    check.require(
        "window_filled", run_.window is not None,
        "the run's steps must outlast the window",
    )
    check.require(
        "no_compile_inside_window",
        probe.inside is not None and probe.inside["programs"] == 0,
        f"programs built or read back inside the window: {probe.inside}",
    )
    check.require(
        "losses_finite", bool(logged) and all(math.isfinite(x) for x in logged),
        f"{len(logged)} losses logged",
    )
    run_.notes["reference_s"] = time.monotonic() - t_ref

    if args.trace:
        path = trace_math.find_xplane(os.path.join(out_dir, "trace"))
        if path:
            t_load = time.monotonic()
            planes = trace_math.load(path)
            t_reduce = time.monotonic()
            run_.trace = trace_math.reduce(planes)
            run_.notes.update(
                xplane=path, trace_load_s=t_reduce - t_load,
                trace_reduce_s=time.monotonic() - t_reduce,
                trace_events=sum(len(e) for ls in planes.values() for e in ls.values()),
            )
    steps = run_.window["steps"] if run_.window else 0
    return run_, check, steps, 0
