"""Does the system still start on the chip?  The quickest end-to-end proof.

    python chip_smoke.py             # one chip: three phases, one process
    python chip_smoke.py --chips 4   # four chips: the data-parallel paths only

With no arguments, on one TPU chip, it drives the main path once through the
entry points a user calls, at full width and depth, with weights made from
``--seed``:

  1. ResNet-50 training through ``train_distributed.main()`` -> ``Runner``:
     ``config/ResNet50.yml``'s model and optimizer, synthetic 224x224 data,
     batch 128, bf16, a few iterations, one validation pass, loader running;
  2. the 271M ``TransformerLM`` through the same ``Runner``
     (``config/TransformerLM-271m.yml``): the compiled step must hold the
     Pallas flash-attention and fused-CE kernels;
  3. the same LM served (``config/serve-lm-271m.yml``) through
     ``InferenceEngine.from_config`` with the continuous scheduler over the
     paged KV pool: every request completes, every token is in the
     vocabulary, and one prompt's first token equals the argmax of a plain
     no-cache forward of the same parameters.

``--chips 4`` runs only what exists across chips: ResNet-50 DP + SyncBN and
the 271M LM under ``training.zero: 3``, each compared step by step with the
same global batch and seed on ONE device of the same host.

A phase is judged by what ran (iterations reached, finite losses, futures
resolved), never by an exit code.  Each phase prints one JSON line; timings
in it are smoke timings, not benchmark numbers.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (or outside the checkout) it exits non-zero within seconds
and builds nothing.  One process holds the chip: nothing here starts a
child that needs it.
"""
from __future__ import annotations

import argparse
import collections
import copy
import gc
import json
import math
import os
import re
import shutil
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

N_ITERS = 6
# Overrides on the recipe files: a few iterations on synthetic data, a loss
# line every iteration, ONE validation pass (at the last iteration).
_SHORT_RUN = {
    "train_iters": N_ITERS, "print_interval": 1, "val_interval": N_ITERS,
}
RESNET_OVERRIDES = {
    "dataset": {"name": "synthetic", "root": "/none", "n_samples": 1024},
    "training": {**_SHORT_RUN, "batch_size": 128, "dtype": "bfloat16"},
}
LM_OVERRIDES = {
    "dataset": {"n_samples": 64},
    "training": dict(_SHORT_RUN),
}
# Four chips: `batch_size` is per host, so these are 64 and 2 per chip.
RESNET_DP4_OVERRIDES = {
    "dataset": RESNET_OVERRIDES["dataset"],
    "training": {**RESNET_OVERRIDES["training"], "batch_size": 256},
}
LM_ZERO3_OVERRIDES = {
    "dataset": LM_OVERRIDES["dataset"],
    "training": {**_SHORT_RUN, "zero": 3},
}
N_REQUESTS = 8
PROMPT_RANGE = (64, 1024)
# Top-2 logit gap under which a first-token mismatch counts as a tie: the
# paged and the plain forward round to bf16 after every layer in different
# tilings, which moves a unit-variance logit by about this much.
TIE_GAP = 0.05
# Four devices against one: the forward math is the same per sample, so the
# runs part only by the order gradients and BN statistics are summed in
# bf16-computed f32.  Loss per step, relative; the parameter UPDATE (final
# minus initial), relative in L2 — a mis-scaled or unreduced gradient moves
# either by order one.
LOSS_TOL = 2e-2
UPDATE_TOL = 0.25
# ... or this many times what one device shows against itself when a batch
# is summed in another order (see against_one_device)
FLOOR_FACTOR = 10.0

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileLedger:
    """Counts from JAX's own monitoring events: programs built or loaded,
    seconds in trace / lower / backend compile, persistent-cache requests,
    hits and misses (a miss is an entry compiled anew and written)."""

    def __init__(self):
        import jax

        self.totals = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event in _EVENTS:
            self.totals[_EVENTS[event]] += 1

    def _on_duration(self, event, duration, **_):
        if event in _DURATIONS:
            self.totals[_DURATIONS[event]] += duration
            if event.endswith("backend_compile_duration"):
                self.totals["programs"] += 1

    def mark(self):
        return collections.Counter(self.totals)

    def since(self, mark) -> dict:
        d = {k: self.totals[k] - mark[k] for k in self.totals}
        compile_s = sum(d.get(k, 0.0) for k in _DURATIONS.values())
        return {
            "compile_s": round(compile_s, 2),
            "backend_compile_s": round(d.get("backend_compile_s", 0.0), 2),
            "compile_count": int(d.get("programs", 0)),
            "cache_requests": int(d.get("cache_requests", 0)),
            "cache_hits": int(d.get("cache_hits", 0)),
            "cache_misses": int(d.get("cache_misses", 0)),
        }


def memory(devices) -> dict:
    """``peak_bytes_in_use`` is the process's high-water mark so far, not
    this phase's alone; ``bytes_in_use`` is what is resident now.  On the
    v5e runtime both count live buffers (state, batches, outputs) and not a
    running program's temporaries: ``compiled_step`` has the compiler's
    figure for those.  A list where there are several devices."""
    stats = [d.memory_stats() or {} for d in devices]
    out = {}
    for key in ("peak_bytes_in_use", "bytes_in_use"):
        vals = [s.get(key) for s in stats]
        if all(v is not None for v in vals):
            out[key] = vals[0] if len(vals) == 1 else vals
    return out


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


_PATH_LINE = re.compile(r"Execution path: (\S+)")
_ITER_LINE = re.compile(r"Iter \[(\d+)/\d+\] .*? Loss: (\S+)")
_EVAL_LINE = re.compile(r"Acc@1: (\S+), Acc@5: (\S+), Loss: (\S+)")
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)


def compiled_step(runner) -> dict:
    """Facts about the compiled train step, from shapes (nothing executes;
    with the persistent cache on, the executable is read back, not rebuilt):
    Mosaic calls, collectives, and the compiler's own account of the bytes
    one device needs — ``memory_stats()`` counts live buffers only, not the
    program's temporaries, so this is the number to hold against the HBM."""
    import jax

    def struct(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    rows = runner.global_batch
    if runner.is_lm:
        shape, dtype = (rows, runner.seq_len), "int32"
        lab_shape = shape
    else:
        size = runner.global_cfg["dataset"].get("image_size", 224)
        shape, dtype = (rows, size, size, 3), "float32"
        lab_shape = (rows,)
    img = jax.ShapeDtypeStruct(shape, dtype, sharding=runner._img_sharding)
    lab = jax.ShapeDtypeStruct(lab_shape, "int32", sharding=runner._label_sharding)
    state = jax.tree.map(struct, runner.state)
    compiled = runner.train_step.lower(state, img, lab).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "pallas_calls_in_step": text.count("tpu_custom_call"),
        "collectives_in_step": dict(
            collections.Counter(_COLLECTIVE.findall(text))
        ),
        "step_program_bytes_per_device": (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes
        ),
        "step_program_temp_bytes": mem.temp_size_in_bytes,
    }


def run_trainer(ctx, name: str, recipe: str, overrides: dict):
    """One short run through ``train_distributed.main()``.  Returns the
    finished Runner and the phase record read back from what the run wrote:
    the reference-format log (loss per iteration, validation line), the
    telemetry spans (where each step's wall time went), and the compiled
    step itself."""
    import yaml

    import train_distributed
    from pytorch_distributed_training_tpu.config_parsing import get_cfg

    cfg = _merge(get_cfg(os.path.join(ROOT, "config", recipe)), overrides)
    tel_dir = os.path.join(ctx.out, name, "telemetry")
    cfg["training"]["telemetry"] = {"dir": tel_dir}
    os.makedirs(os.path.join(ctx.out, name), exist_ok=True)
    cfg_path = os.path.join(ctx.out, name, f"{name}.yml")
    with open(cfg_path, "w") as fp:
        yaml.safe_dump(cfg, fp)

    mark = ctx.ledger.mark()
    t0 = time.monotonic()
    runner = train_distributed.main([
        "--num-nodes", "1", "--rank", "0", "--dist-backend", "tpu",
        "--seed", str(ctx.seed), "--log-dir", os.path.join(ctx.out, name),
        "--file-name-cfg", name, "--cfg-filepath", cfg_path,
    ])
    wall = time.monotonic() - t0

    with open(os.path.join(ctx.out, name, f"{name}.log")) as fp:
        log = fp.read()
    path = _PATH_LINE.search(log)
    losses = [float(m.group(2)) for m in _ITER_LINE.finditer(log)]
    evals = [tuple(map(float, m.groups())) for m in _EVAL_LINE.finditer(log)]
    per_step = collections.defaultdict(float)
    data_wait = collections.defaultdict(float)
    with open(os.path.join(tel_dir, "spans_rank0.jsonl")) as fp:
        for line in fp:
            span = json.loads(line)
            # device_block nests inside step_dispatch: not added again
            if span["kind"] in ("data_wait", "step_dispatch"):
                per_step[span["step"]] += span["ms"] / 1e3
            if span["kind"] == "data_wait":
                data_wait[span["step"]] += span["ms"] / 1e3
    warm = [s for s in sorted(per_step) if s >= 2]
    iters = cfg["training"]["train_iters"]
    record = {
        "phase": name,
        "path": path.group(1) if path else None,
        "steps": runner.iter,
        "global_batch": runner.global_batch,
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "losses": losses,
        "eval": [{"acc1": a, "acc5": b, "loss": c} for a, b, c in evals],
        "wall_s": round(wall, 2),
        "first_step_s": round(per_step.get(0, float("nan")), 3),
        "smoke_s_per_step_after_warmup": (
            round(statistics.median(per_step[s] for s in warm), 4) if warm else None
        ),
        "smoke_data_wait_s_per_step": (
            round(statistics.median(data_wait[s] for s in warm), 4) if warm else None
        ),
        **ctx.ledger.since(mark),
        **memory(ctx.devices),
        **compiled_step(runner),
    }
    problems = []
    if runner.iter != iters:
        problems.append(f"reached iteration {runner.iter} of {iters}")
    if len(losses) != iters or not all(math.isfinite(x) for x in losses):
        problems.append(f"want {iters} finite losses, log has {losses}")
    if len(evals) != 1 or not all(math.isfinite(x) for x in evals[0]):
        problems.append(f"want one finite validation line, log has {evals}")
    record["problems"] = problems
    return runner, record


def release(runner) -> None:
    """Drop the run's device state so the next phase starts with the chip's
    memory to itself."""
    runner.state = None
    runner.train_step = runner.eval_step = None
    gc.collect()


# ------------------------------------------------------------- one chip

def phase_resnet50(ctx) -> dict:
    runner, rec = run_trainer(
        ctx, "resnet50_train", "ResNet50.yml", RESNET_OVERRIDES
    )
    release(runner)
    return rec


def phase_lm_train(ctx) -> dict:
    runner, rec = run_trainer(
        ctx, "lm271m_train", "TransformerLM-271m.yml", LM_OVERRIDES
    )
    if rec["pallas_calls_in_step"] == 0:
        rec["problems"].append(
            "no tpu_custom_call in the compiled LM step: the Pallas flash "
            "and fused-CE kernels are not on the path"
        )
    release(runner)
    return rec


def phase_lm_serve(ctx) -> dict:
    import jax
    import numpy as np

    from pytorch_distributed_training_tpu.config_parsing import get_serve_cfg
    from pytorch_distributed_training_tpu.serving import InferenceEngine

    cfg = get_serve_cfg(os.path.join(ROOT, "config", "serve-lm-271m.yml"))
    cfg["serving"]["seed"] = ctx.seed
    vocab = cfg["dataset"]["n_classes"]
    max_new = cfg["serving"]["max_new_tokens"]
    rng = np.random.default_rng(ctx.seed)
    lengths = np.linspace(*PROMPT_RANGE, N_REQUESTS).astype(int)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]

    mark = ctx.ledger.mark()
    t0 = time.monotonic()
    with InferenceEngine.from_config(cfg) as engine:
        if engine.scheduler is None:
            raise RuntimeError("serve-lm-271m.yml did not enable the scheduler")
        warm = engine.warmup()  # every bucket program, compiled up front
        warm_mark = ctx.ledger.mark()
        t_warm = time.monotonic()
        futures = [engine.submit(p) for p in prompts]
        results = [f.result(timeout=600) for f in futures]
        serve_s = time.monotonic() - t_warm
        compiles_while_serving = ctx.ledger.since(warm_mark)["compile_count"]
        snap = engine.metrics.snapshot()
        compile_count = engine.compile_count()

        # the reference: a plain forward over the whole prompt, no cache, no
        # paging, same parameters, same chip
        probe = N_REQUESTS // 2
        model, params = engine.model, engine.params
        logits = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, prompts[probe][None]
        )
        last = np.asarray(logits[0, -1], np.float32)
        mem = memory(ctx.devices)
    top2 = np.sort(last)[-2:]
    served = int(results[probe]["tokens"][0])
    record = {
        "phase": "lm271m_serve",
        "requests": len(results),
        "prompt_lens": [int(n) for n in lengths],
        "gen_tokens": int(sum(r["gen_len"] for r in results)),
        "first_token_served": served,
        "first_token_reference": int(last.argmax()),
        "reference_top2_gap": float(top2[1] - top2[0]),
        "reference_shortfall": float(last.max() - last[served]),
        "wall_s": round(time.monotonic() - t0, 2),
        "warmup_s": round(warm["warmup_ms"] / 1e3, 2),
        "smoke_serve_s": round(serve_s, 3),
        "smoke_decode_tokens_per_s": snap.get("decode_tokens_per_sec"),
        "smoke_prefill_tokens_per_s": snap.get("prefill_tokens_per_sec"),
        "smoke_tick_host_ms_p50": snap.get("tick_host_ms_p50"),
        "smoke_latency_ms_p50": snap.get("latency_ms_p50"),
        "engine_programs": compile_count,
        **ctx.ledger.since(mark),
        "compile_count_while_serving": compiles_while_serving,
        **mem,
    }
    problems = []
    for i, r in enumerate(results):
        toks = np.asarray(r["tokens"])
        if r["gen_len"] != max_new or toks.shape != (max_new,):
            problems.append(f"request {i}: {r['gen_len']} of {max_new} tokens")
        if toks.min() < 0 or toks.max() >= vocab:
            problems.append(f"request {i}: token outside [0, {vocab})")
    if not np.isfinite(last).all():
        problems.append("reference logits are not finite")
    if served != int(last.argmax()) and record["reference_shortfall"] > TIE_GAP:
        problems.append(
            f"first token {served} is not the reference argmax "
            f"{int(last.argmax())} (short by {record['reference_shortfall']:.4f}"
            f" > tie gap {TIE_GAP})"
        )
    for counter in (
        "requests_poisoned", "engine_restarts", "failed_inflight", "timeouts",
    ):
        if snap.get(counter):
            problems.append(f"serving counter {counter} = {snap[counter]}")
    record["problems"] = problems
    gc.collect()
    return record


# ----------------------------------------------------------- four chips

def _tree_l2(tree) -> float:
    import jax
    import numpy as np

    return math.sqrt(sum(
        float(np.sum(np.square(np.asarray(x, np.float64))))
        for x in jax.tree.leaves(tree)
    ))


def _tree_sub(a, b):
    import jax
    import numpy as np

    return jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b
    )


def _loss_err(losses, ref) -> float:
    return max(
        (abs(a - b) / max(1.0, abs(b)) for a, b in zip(losses, ref)),
        default=float("nan"),
    )


def _step_through(runner, state, step, put, n_steps, reverse):
    """Feed ``step`` the batches the Runner's own loader yields from the
    start of epoch 0 — the batches the run across chips just consumed —
    each in loader order, or with its rows in reverse order."""
    import jax

    from pytorch_distributed_training_tpu.utils import make_iter_dataloader

    stream = make_iter_dataloader(runner.train_loader)
    losses = []
    for _ in range(n_steps):
        img, label = next(stream)
        if reverse:
            img, label = img[::-1], label[::-1]
        state, loss = step(state, *put(img, label))
        losses.append(float(loss))
    stream.close()
    return jax.device_get(state.params), losses


def against_one_device(ctx, name, recipe, overrides, reference) -> dict:
    """Run ``recipe`` through the Runner on every device, then ``reference``
    — the same model, optimizer, schedule, seed and batches, stepped on
    device 0 alone — and compare loss per step and the parameter update.

    The one-device run is made twice, the second time with every batch's
    rows reversed: the same mathematics summed in another order, which is
    all that spreading the batch over devices changes.  How far those two
    part is the floor under any comparison here (ResNet-50 at random init
    amplifies a rounding error in its input about 10^4 times into its
    gradient, and the trajectory is chaotic after that), so the tolerance is
    the bf16 bound or ``FLOOR_FACTOR`` times that floor, whichever is
    larger.  The checks that do not lean on the floor: the first loss, the
    size of the update, where the state lives, the collectives."""
    import jax

    runner, rec = run_trainer(ctx, name, recipe, overrides)
    final = jax.device_get(runner.state.params)
    release(runner)

    mark = ctx.ledger.mark()
    n_steps = len(rec["losses"])
    state0, step, put = reference(ctx, runner)
    init = jax.device_get(state0.params)
    sharding = jax.tree.map(lambda x: x.sharding, state0)
    state0 = jax.device_get(state0)  # the step donates its state
    runs = [
        _step_through(
            runner, jax.device_put(state0, sharding), step, put, n_steps, rev
        )
        for rev in (False, True)
    ]
    (ref_final, ref_losses), (alt_final, alt_losses) = runs
    rec["reference_compile_s"] = ctx.ledger.since(mark)["compile_s"]
    rec["reference_losses"] = ref_losses
    ref_update = _tree_l2(_tree_sub(ref_final, init))

    def within(what, err, floor, bound):
        tol = max(bound, FLOOR_FACTOR * floor)
        rec[f"{what}_rel_err"] = err
        rec[f"{what}_rel_err_floor"] = floor
        rec[f"{what}_tolerance"] = tol
        if not err <= tol:
            rec["problems"].append(f"{what} off by {err:.3g} > {tol:.3g}")

    within(  # per step, the largest
        "loss", _loss_err(rec["losses"], ref_losses),
        _loss_err(alt_losses, ref_losses), LOSS_TOL,
    )
    within(  # final minus initial parameters, relative in L2
        "update", _tree_l2(_tree_sub(final, ref_final)) / ref_update,
        _tree_l2(_tree_sub(alt_final, ref_final)) / ref_update, UPDATE_TOL,
    )
    rec["update_norm_ratio"] = _tree_l2(_tree_sub(final, init)) / ref_update
    # before any update both runs hold the same parameters: the first loss
    # answers to the bf16 bound alone, whatever the floor
    first = _loss_err(rec["losses"][:1], ref_losses[:1])
    if not first <= LOSS_TOL:
        rec["problems"].append(f"first loss off by {first:.3g} > {LOSS_TOL}")
    if not 0.5 <= rec["update_norm_ratio"] <= 2.0:
        rec["problems"].append(
            f"update is {rec['update_norm_ratio']:.3g}x the one-device one"
        )
    # read while the Runner's state was still placed (run_trainer's record)
    used = rec.get("bytes_in_use")
    if isinstance(used, list) and min(used) < 0.5 * max(used):
        rec["problems"].append(f"state is not spread over the devices: {used}")
    if not rec["collectives_in_step"]:
        rec["problems"].append("no collective in the compiled step")
    return rec


def reference_resnet(ctx, runner):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.engine import (
        build_train_step,
        init_train_state,
    )
    from pytorch_distributed_training_tpu.parallel import (
        batch_sharding,
        make_mesh,
        replicated_sharding,
    )

    mesh = make_mesh(devices=ctx.devices[:1])
    sample, _ = runner.train_loader.dataset[0]
    state = init_train_state(
        runner.model, runner.optimizer, jax.random.PRNGKey(ctx.seed),
        jnp.zeros((1,) + tuple(sample.shape), jnp.float32),
    )
    state = jax.device_put(state, replicated_sharding(mesh))
    step = build_train_step(
        runner.model, runner.optimizer, runner.scheduler.lr_fn, mesh,
        sync_bn=runner.sync_bn,
    )

    def put(img, label):
        return (
            jax.device_put(np.asarray(img, np.float32), batch_sharding(mesh, 4)),
            jax.device_put(np.asarray(label, np.int32), batch_sharding(mesh, 1)),
        )

    return state, step, put


def reference_lm(ctx, runner):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_training_tpu.engine import (
        TrainState,
        build_lm_train_step,
    )
    from pytorch_distributed_training_tpu.parallel import (
        make_sp_mesh,
        replicated_sharding,
    )

    mesh = make_sp_mesh(1, devices=ctx.devices[:1])
    rep = replicated_sharding(mesh)
    params = runner.model.init(
        jax.random.PRNGKey(ctx.seed), jnp.zeros((1, runner.seq_len), jnp.int32)
    )["params"]
    state = jax.device_put(
        TrainState(
            params=params, batch_stats={},
            opt_state=runner.optimizer.init(params),
        ),
        rep,
    )
    step = build_lm_train_step(
        runner.model, runner.optimizer, runner.scheduler.lr_fn, mesh
    )

    def put(tokens, targets):
        return (
            jax.device_put(np.asarray(tokens, np.int32), rep),
            jax.device_put(np.asarray(targets, np.int32), rep),
        )

    return state, step, put


def phase_resnet50_dp4(ctx) -> dict:
    return against_one_device(
        ctx, "resnet50_dp4", "ResNet50.yml", RESNET_DP4_OVERRIDES,
        reference_resnet,
    )


def phase_lm_zero3(ctx) -> dict:
    return against_one_device(
        ctx, "lm271m_zero3", "TransformerLM-271m.yml", LM_ZERO3_OVERRIDES,
        reference_lm,
    )


# ---------------------------------------------------------------- driver

PHASES = {
    1: (phase_resnet50, phase_lm_train, phase_lm_serve),
    4: (phase_resnet50_dp4, phase_lm_zero3),
}

Context = collections.namedtuple("Context", "seed out devices ledger")


def run_phases(ctx, phases) -> bool:
    """Run every phase; a phase that raises, or reports a problem, fails the
    run (the later phases still run: each starts from a released chip)."""
    ok = True
    for phase in phases:
        try:
            record = phase(ctx)
        except Exception as e:
            traceback.print_exc()
            record = {"phase": phase.__name__, "problems": [repr(e)]}
        record["ok"] = not record["problems"]
        emit(record)
        ok = ok and record["ok"]
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=sorted(PHASES), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--out", default=os.path.join(ROOT, "run", "chip_smoke"),
        help="logs, telemetry and effective configs of the run",
    )
    args = parser.parse_args(argv)

    t0 = time.monotonic()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    refusal = None
    if device["platform"] != "tpu":
        refusal = "JAX found no TPU: the smoke never falls back to another backend"
    elif device["count"] != args.chips:
        refusal = f"--chips {args.chips} needs exactly that many devices"
    elif os.environ.get("PDT_DISABLE_PALLAS"):
        refusal = "PDT_DISABLE_PALLAS is set: the kernels would be bypassed"
    if refusal:
        emit({"ok": False, "reason": refusal, "device": device})
        return 2

    from pytorch_distributed_training_tpu.native import native_available
    from pytorch_distributed_training_tpu.utils import enable_compile_cache

    shutil.rmtree(args.out, ignore_errors=True)
    ledger = CompileLedger()
    emit({
        "phase": "setup",
        "jax": jax.__version__,
        "compile_cache_dir": enable_compile_cache(),
        "compile_cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "native_available": native_available(),
        "seed": args.seed,
    })
    ctx = Context(args.seed, args.out, devices, ledger)
    ok = run_phases(ctx, PHASES[args.chips])
    emit({"phase": "total", "wall_s": round(time.monotonic() - t0, 1),
          **ledger.since(collections.Counter())})
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
