"""Drive a cell's run WITHOUT the harness's look for a chip, optionally with
the timed path broken underneath, and print what ``correct`` rests on.

    python drive.py <copy root> <workload> <none|unchanged_state|half_batch|altered_token>

Used by test_correct.py only, on the CPU, in a temporary copy that holds the
toy cells (dryrun.make_copy).
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()


def break_training(mode: str):
    import jax
    import jax.numpy as jnp

    import train_distributed

    class Broken(train_distributed.Runner):
        def train_iter(self, g_img, g_label):
            if mode == "half_batch":
                # the second half of the batch never reaches the step
                half = g_img.shape[0] // 2
                g_img = jnp.concatenate([g_img[:half], g_img[:half]])
                g_label = jnp.concatenate([g_label[:half], g_label[:half]])
                return super().train_iter(g_img, g_label)
            kept = jax.tree.map(jnp.copy, self.state.params)
            super().train_iter(g_img, g_label)
            # a step that returns its parameters unchanged
            self.state = self.state.replace(params=kept)

    train_distributed.Runner = Broken


def break_serving():
    from pytorch_distributed_training_tpu.serving import InferenceEngine

    submit = InferenceEngine.submit

    def altered(self, payload, **kw):
        future = submit(self, payload, **kw)
        result = future.result

        def wrong(timeout=None):
            out = dict(result(timeout))
            tokens = out["tokens"].copy()
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 512
            out["tokens"] = tokens
            return out

        future.result = wrong
        return future

    InferenceEngine.submit = altered


def main():
    root, workload, mode = sys.argv[1:4]
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    from benchmark.common import CompileLedger, load_cell

    cell = load_cell(workload)
    kind = cell["traffic_file"]["kind"]
    if mode in ("unchanged_state", "half_batch"):
        break_training(mode)
    elif mode == "altered_token":
        break_serving()
    args = argparse.Namespace(seed=7, seconds=1.0, trace=0, dry=True, control=False)
    out_dir = os.path.join(root, "run", "benchmark", workload)
    os.makedirs(out_dir, exist_ok=True)
    driver = importlib.import_module(f"benchmark.drivers.{kind}")
    _, check, _, _ = driver.run(cell, args, out_dir, CompileLedger(), T0)
    check.print()
    print("check_correct " + json.dumps(check.correct))


if __name__ == "__main__":
    main()
