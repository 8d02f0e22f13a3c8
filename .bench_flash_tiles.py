"""Tile sweep of the resident causal flash kernels (``flash_fwd`` and the
fused ``flash_bwd``) at one training step's attention shape: BH 64, S 2048,
bf16, causal, at D 128 (the ``lm271m`` cell) and D 64 (the old sweeps' shape).

    python .bench_flash_tiles.py            # on the chip: times every row
    python .bench_flash_tiles.py --compile  # anywhere: asks the v5e compiler
                                            # whether each row fits (no times)

A row sets the module's tile constants, clears ``_make``'s cache and times a
chain of calls inside one jit (best of three windows).  ``fwd`` rows time the
forward alone; ``bwd`` rows time forward + backward with the forward of the
``base fwd`` row and report the difference.  ``--parent PATH`` also times
another checkout's ``ops/flash_attention.py`` (loaded from the file) on the
same chip.  Rows go to stdout and ``chiprun_out/flash_tiles.jsonl``.
"""
import argparse
import importlib.util
import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from pytorch_distributed_training_tpu.ops import flash_attention as fa

FWD_TILES = [(1024, 1024), (512, 512), (1024, 512), (512, 1024)]
BWD_TILES = [(512, 1024), (512, 512), (1024, 512), (256, 1024)]
SUBS = [128, 256, 512]
ITERS = 30


def _chains(mod):
    def fwd(q, k, v):
        return jax.lax.fori_loop(
            0, ITERS, lambda _, x: mod.flash_attention(x, k, v, causal=True), q
        )

    def loss(q, k, v):
        o = mod.flash_attention(q, k, v, causal=True)
        return (o.astype(jnp.float32) ** 2).mean()

    def fwd_bwd(q, k, v):
        def body(_, x):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(x, k, v)
            return x + jnp.bfloat16(1e-3) * dq + jnp.bfloat16(1e-6) * (dk + dv)

        return jax.lax.fori_loop(0, ITERS, body, q)

    return {"fwd": jax.jit(fwd), "fwd_bwd": jax.jit(fwd_bwd)}


def _measure(mod, what, shape, compile_on):
    mod._make.cache_clear()
    fn = _chains(mod)[what]
    if compile_on is not None:
        arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=compile_on)
        fn.lower(arg, arg, arg).compile()
        return None
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
        for _ in range(3)
    )
    fn(q, k, v).block_until_ready()
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        fn(q, k, v).block_until_ready()
        dt = (time.perf_counter() - t0) / ITERS * 1e3
        best = dt if best is None else min(best, dt)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--parent", default=None)
    ap.add_argument("--shapes", default="8x2048x8x128,4x2048x16x64", help="BxSxHxD,...")
    ap.add_argument("--fwd", default=None, help="e.g. 1024x1024,512x512")
    ap.add_argument("--bwd", default=None)
    ap.add_argument("--subs", default=None, help="e.g. 256,512")
    args = ap.parse_args()
    tiles = lambda text: [tuple(int(n) for n in t.split("x")) for t in text.split(",") if t]  # noqa: E731
    fwd_tiles = FWD_TILES if args.fwd is None else tiles(args.fwd)
    bwd_tiles = BWD_TILES if args.bwd is None else tiles(args.bwd)
    subs = SUBS if args.subs is None else [int(n) for n in args.subs.split(",")]

    compile_on = None
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        compile_on = SingleDeviceSharding(topo.devices[0])
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_tiles.jsonl", "a")

    def emit(row):
        row["device"] = "compile-only" if args.compile else jax.devices()[0].device_kind
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def run(mod, what, d, row):
        try:
            ms = _measure(mod, what, d, compile_on)
        except Exception as e:  # noqa: BLE001 - a row that overflows VMEM is a result
            emit({**row, "d": "x".join(map(str, d)), "error": str(e).splitlines()[0][:200]})
            return None
        emit({**row, "d": "x".join(map(str, d)), "ms": None if ms is None else round(ms, 4)})
        return ms

    # a row's tiles are taken as given (block_q >= block_k), also where the
    # module would make the whole sequence one Q tile
    fa._BLOCK_Q_WHOLE = 0
    defaults = (fa._BLOCK_Q, fa._BLOCK_K, fa._BLOCK_Q_FUSED, fa._BLOCK_K_FUSED)
    kernels = (fa._fwd_kernel, fa._dqkv_kernel)

    def with_sub(kernel, sub):
        def kern(*refs, **kw):
            return kernel(*refs, **{**kw, "sub": fa._pick_block(sub, kw["block_q"])})

        return kern

    for d in tiles(args.shapes):
        if args.parent:
            spec = importlib.util.spec_from_file_location(
                "parent_flash",
                os.path.join(args.parent, "pytorch_distributed_training_tpu/ops/flash_attention.py"),
            )
            parent = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(parent)
            pf = run(parent, "fwd", d, {"row": "parent fwd"})
            pb = run(parent, "fwd_bwd", d, {"row": "parent fwd_bwd"})
            if pf and pb:
                emit({"row": "parent bwd", "d": "x".join(map(str, d)), "ms": round(pb - pf, 4)})
        base = run(fa, "fwd", d, {"row": "base fwd", "tiles": fa._tiles(d[1], True, True)})
        for bq, bk in fwd_tiles:
            for sub in subs:
                if sub > bq:
                    continue
                fa._BLOCK_Q, fa._BLOCK_K = bq, bk
                fa._fwd_kernel = with_sub(kernels[0], sub)
                run(fa, "fwd", d, {"row": "fwd", "tiles": (bq, bk, sub)})
        fa._BLOCK_Q, fa._BLOCK_K = defaults[:2]
        fa._fwd_kernel = kernels[0]
        for bq, bk in bwd_tiles:
            for sub in subs:
                if sub > bq:
                    continue
                fa._BLOCK_Q_FUSED, fa._BLOCK_K_FUSED = bq, bk
                fa._dqkv_kernel = with_sub(kernels[1], sub)
                both = run(fa, "fwd_bwd", d, {"row": "fwd_bwd", "tiles": (bq, bk, sub)})
                if both and base:
                    emit({"row": "bwd", "d": "x".join(map(str, d)), "tiles": (bq, bk, sub), "ms": round(both - base, 4)})
        fa._BLOCK_Q_FUSED, fa._BLOCK_K_FUSED = defaults[2:]
        fa._dqkv_kernel = kernels[1]


if __name__ == "__main__":
    main()
