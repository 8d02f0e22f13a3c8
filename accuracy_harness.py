"""Converged-accuracy parity harness: this framework vs torch, same pixels.

The reference's only published oracle is an ImageNet accuracy table
(/root/reference/README.md:7-13).  The full 450k-iteration ImageNet run
does not fit one bench chip + no mounted dataset, so this harness produces
the scaled-down version of that evidence end to end:

  1. ``gen``   — build a REAL-JPEG ImageFolder dataset hard enough not to
     saturate: 40 Gabor-texture classes on an (orientation, frequency) grid
     whose per-image parameter jitter OVERLAPS neighboring classes, plus
     pixel noise — an irreducible Bayes error, so converged top-1 plateaus
     meaningfully below 100% and differences between trainers are visible.
  2. ``streams`` — precompute the augmented batch stream ONCE through this
     framework's input pipeline (native JPEG decode + RandomResizedCrop +
     flip, data/loader.py) into uint8 memmaps.  Both trainers then consume
     byte-identical pixels; normalization is one shared numpy function, so
     their f32 inputs are bitwise equal and the comparison isolates
     model/optimizer/BN numerics.
  3. ``ours``  — train ResNet-18 through this framework's compiled train
     step (engine/steps.py: forward, CE, backward, SGD+momentum+coupled-WD,
     BN updates as one XLA program) from a torch-ported init.
  4. ``torch`` — train the line-faithful torchvision-twin ResNet-18
     (tests/test_torch_port.py) with torch.optim.SGD + per-iter milestone
     schedule — the reference recipe's semantics — from the SAME init.

Identical recipe, identical init, identical data order: final top-1 must
agree within run-to-run noise.  ``python accuracy_harness.py all`` drives
all four stages and prints both numbers.
"""
from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

IMAGE_SIZE = 64  # training crop; source JPEGs are 96x96
N_CLASSES = 40


# ----------------------------------------------------------------------
# Stage 1: dataset generation
# ----------------------------------------------------------------------
def make_texture_dataset(
    root: str,
    n_classes: int = N_CLASSES,
    per_class_train: int = 200,
    per_class_val: int = 40,
    size: int = 96,
    seed: int = 0,
) -> None:
    """40 Gabor-texture classes over an 8x5 (orientation x frequency) grid.

    Class c -> center orientation theta_c (spacing pi/8) and spatial
    frequency f_c (geometric ladder).  Per image: theta jittered by a
    Gaussian whose sigma is ~40% of the class spacing (neighboring classes
    OVERLAP -> irreducible error), frequency jittered x U[0.85, 1.18],
    random phase, class-hue color with jitter, strong additive noise,
    random brightness/contrast.  JPEG q85 at photo-ish 96x96.
    """
    from PIL import Image

    n_orient, n_freq = 8, 5
    assert n_orient * n_freq == n_classes
    freqs = 6.0 * (1.5 ** np.arange(n_freq))  # cycles per image: 6..30
    sigma_theta = 0.4 * (np.pi / n_orient)

    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    for split, per_class in (("train", per_class_train), ("val", per_class_val)):
        rng = np.random.default_rng(seed if split == "train" else seed + 1)
        for c in range(n_classes):
            theta_c = (c % n_orient) * np.pi / n_orient
            f_c = freqs[c // n_orient]
            hue_c = (c * 0.61803) % 1.0  # golden-ratio hue spacing
            d = os.path.join(root, split, f"class_{c:03d}")
            os.makedirs(d, exist_ok=True)
            for i in range(per_class):
                theta = theta_c + rng.normal(0.0, sigma_theta)
                f = f_c * rng.uniform(0.85, 1.18)
                phase = rng.uniform(0, 2 * np.pi)
                grating = np.sin(
                    2 * np.pi * f * (xx * np.cos(theta) + yy * np.sin(theta))
                    + phase
                )
                # class hue with jitter -> RGB via a cheap cosine palette
                hue = (hue_c + rng.normal(0, 0.04)) % 1.0
                base = 0.5 + 0.5 * np.cos(
                    2 * np.pi * (hue + np.array([0.0, 1 / 3, 2 / 3]))
                )
                amp = rng.uniform(0.35, 0.55)
                img = 0.5 + amp * grating[..., None] * base[None, None, :]
                img += rng.normal(0, 0.10, img.shape)  # heavy pixel noise
                img = img * rng.uniform(0.8, 1.2) + rng.uniform(-0.08, 0.08)
                u8 = np.clip(img * 255.0, 0, 255).astype(np.uint8)
                Image.fromarray(u8).save(
                    os.path.join(d, f"img_{i:04d}.jpg"), "JPEG", quality=85
                )


# ----------------------------------------------------------------------
# Stage 2: byte-identical augmented streams (this framework's pipeline)
# ----------------------------------------------------------------------
def precompute_streams(
    root: str, out_dir: str, iters: int, batch: int, seed: int = 0
) -> None:
    """Decode + augment through the framework loader once; save uint8."""
    from pytorch_distributed_training_tpu.data import (
        DataLoader,
        RandomSampler,
        SequentialSampler,
        get_dataset,
    )
    from pytorch_distributed_training_tpu.utils import (
        make_deterministic,
        make_iter_dataloader,
    )

    os.makedirs(out_dir, exist_ok=True)
    make_deterministic(seed)
    train_ds = get_dataset("imagenet", root, "train", image_size=IMAGE_SIZE)
    loader = DataLoader(
        train_ds, batch_size=batch, sampler=RandomSampler(len(train_ds), seed=seed),
        num_workers=1, drop_last=True, output_dtype="uint8",
    )
    imgs = np.lib.format.open_memmap(
        os.path.join(out_dir, "train_imgs.npy"), mode="w+",
        dtype=np.uint8, shape=(iters, batch, IMAGE_SIZE, IMAGE_SIZE, 3),
    )
    labels = np.lib.format.open_memmap(
        os.path.join(out_dir, "train_labels.npy"), mode="w+",
        dtype=np.int32, shape=(iters, batch),
    )
    stream = make_iter_dataloader(loader)
    for it in range(iters):
        b_img, b_lab = next(stream)
        imgs[it] = b_img
        labels[it] = np.asarray(b_lab, np.int32)
    imgs.flush()
    labels.flush()
    loader.close()

    val_ds = get_dataset("imagenet", root, "val", image_size=IMAGE_SIZE)
    vloader = DataLoader(
        val_ds, batch_size=batch, sampler=SequentialSampler(len(val_ds)),
        num_workers=1, drop_last=False, output_dtype="uint8",
    )
    v_imgs, v_labs = [], []
    for b_img, b_lab in vloader:
        v_imgs.append(np.asarray(b_img))
        v_labs.append(np.asarray(b_lab, np.int32))
    vloader.close()
    np.save(os.path.join(out_dir, "val_imgs.npy"), np.concatenate(v_imgs))
    np.save(os.path.join(out_dir, "val_labels.npy"), np.concatenate(v_labs))


def _normalize(u8: np.ndarray) -> np.ndarray:
    """The ONE normalization both trainers share (bitwise-identical f32)."""
    from pytorch_distributed_training_tpu.data import IMAGENET_MEAN, IMAGENET_STD

    return ((u8.astype(np.float32) / 255.0) - IMAGENET_MEAN) / IMAGENET_STD


def _shared_init_state_dict(model_name: str = "ResNet18", seed: int = 0):
    """torch-twin ResNet init (torchvision init semantics) — the shared
    starting point for BOTH trainers.  ``model_name``: ResNet18 (basic
    blocks) or ResNet50 (bottleneck, the reference's flagship recipe
    /root/reference/config/ResNet50.yml)."""
    import sys

    import torch

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tests"))
    from test_torch_port import _TORCH_CONFIGS, TorchResNet

    block, layers = _TORCH_CONFIGS[model_name]
    torch.manual_seed(seed)
    tm = TorchResNet(block, layers, num_classes=N_CLASSES)
    return tm


def _recipe(iters: int):
    """lr/momentum/wd + milestone schedule (reference recipe shape scaled
    to batch 64; milestones at 60%/85% of the run, gamma 0.1)."""
    return dict(
        lr=0.025, momentum=0.9, weight_decay=1e-4,
        milestones=[int(iters * 0.6), int(iters * 0.85)], gamma=0.1,
    )


# ----------------------------------------------------------------------
# Stage 3: this framework (compiled step on the default platform)
# ----------------------------------------------------------------------
def train_ours(
    stream_dir: str,
    iters: int,
    eval_every: int = 0,
    log=print,
    model_name: str = "ResNet18",
    sync_bn: bool = False,
    return_state: bool = False,
    eval_in_loop: bool = True,
):
    """Train through this framework's compiled step.

    ``sync_bn``: run the DP+SyncBN path — meaningful on a multi-device
    mesh (the 8-virtual-device CPU mesh via JAX_PLATFORMS=cpu +
    xla_force_host_platform_device_count), where the batch shards over
    ``data`` and BN moments cross the mesh in-graph (ops/batch_norm.py).
    The DP==1dev convergence pin (VERDICT r4 #4) runs this twice on CPU:
    once on 1 device, once on 8 with sync_bn, same streams.

    ``return_state``: return ``(top1, final TrainState)`` instead of bare
    ``top1`` — the extension point ``.accuracy_dp_pin.py`` hashes the final
    params/batch-stats through (ADVICE r5 #3: the pin previously duplicated
    this whole function and could silently desynchronize from it).

    ``eval_in_loop``: run the (relatively expensive) validation sweep at
    every ``eval_every`` milestone; False logs the loss only — the pin's
    cadence, where only the FINAL accuracy matters.
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_training_tpu.engine import (
        build_eval_step,
        build_train_step,
        init_train_state,
    )
    from pytorch_distributed_training_tpu.models import get_model
    from pytorch_distributed_training_tpu.models.torch_port import (
        import_torch_resnet_state_dict,
    )
    from pytorch_distributed_training_tpu.optimizers import SGD
    from pytorch_distributed_training_tpu.parallel import (
        batch_sharding,
        make_mesh,
        replicated_sharding,
    )
    from pytorch_distributed_training_tpu.schedulers import multi_step_lr

    imgs = np.load(os.path.join(stream_dir, "train_imgs.npy"), mmap_mode="r")
    labels = np.load(os.path.join(stream_dir, "train_labels.npy"))
    v_imgs = np.load(os.path.join(stream_dir, "val_imgs.npy"))
    v_labs = np.load(os.path.join(stream_dir, "val_labels.npy"))
    assert iters <= imgs.shape[0], f"stream has {imgs.shape[0]} iters"
    batch = imgs.shape[1]
    rec = _recipe(iters)

    from pytorch_distributed_training_tpu.parallel.mesh import DATA_AXIS

    model = get_model(
        model_name, num_classes=N_CLASSES,
        axis_name=DATA_AXIS if sync_bn else None,
    )
    mesh = make_mesh()
    if sync_bn:
        log(f"[ours] sync_bn over {mesh.devices.size} device(s)")
    opt = SGD(lr=rec["lr"], momentum=rec["momentum"], weight_decay=rec["weight_decay"])
    state = init_train_state(
        model, opt, jax.random.PRNGKey(0),
        jnp.zeros((1, IMAGE_SIZE, IMAGE_SIZE, 3)),
    )
    # shared torch init -> bitwise-identical starting weights
    tm = _shared_init_state_dict(model_name)
    variables = import_torch_resnet_state_dict(
        {"params": state.params, "batch_stats": state.batch_stats},
        tm.state_dict(),
    )
    state = state.replace(
        params=variables["params"], batch_stats=variables["batch_stats"]
    )
    state = jax.device_put(state, replicated_sharding(mesh))
    lr_fn = multi_step_lr(rec["lr"], rec["milestones"], rec["gamma"])
    step = build_train_step(model, opt, lr_fn, mesh, sync_bn=sync_bn)
    eval_step = build_eval_step(model, mesh)
    img_sh = batch_sharding(mesh, 4)
    lab_sh = batch_sharding(mesh, 1)

    def evaluate(st):
        accs, n = [], 0
        for i in range(0, len(v_imgs), batch):
            bi = _normalize(v_imgs[i:i + batch])
            bl = v_labs[i:i + batch]
            _, acc1, _ = eval_step(
                st,
                jax.device_put(bi, img_sh),
                jax.device_put(bl, lab_sh),
            )
            accs.append(float(acc1) * len(bl))
            n += len(bl)
        return sum(accs) / n

    t0 = time.perf_counter()
    for it in range(iters):
        g_img = jax.device_put(_normalize(np.asarray(imgs[it])), img_sh)
        g_lab = jax.device_put(labels[it], lab_sh)
        state, loss = step(state, g_img, g_lab)
        if eval_every and (it + 1) % eval_every == 0:
            mid = (
                f"val@1 {evaluate(state):.2f}%  " if eval_in_loop else ""
            )
            log(
                f"[ours] iter {it + 1}/{iters} loss {float(loss):.6f} "
                f"{mid}({time.perf_counter() - t0:.0f}s)"
            )
    top1 = evaluate(state)
    log(f"[ours] FINAL iter {iters} val top-1 {top1:.2f}%")
    if return_state:
        return top1, state
    return top1


# ----------------------------------------------------------------------
# Stage 4: torch reference-semantics trainer (CPU)
# ----------------------------------------------------------------------
def train_torch(
    stream_dir: str,
    iters: int,
    eval_every: int = 0,
    log=print,
    model_name: str = "ResNet18",
):
    import torch
    import torch.nn.functional as F

    imgs = np.load(os.path.join(stream_dir, "train_imgs.npy"), mmap_mode="r")
    labels = np.load(os.path.join(stream_dir, "train_labels.npy"))
    v_imgs = np.load(os.path.join(stream_dir, "val_imgs.npy"))
    v_labs = np.load(os.path.join(stream_dir, "val_labels.npy"))
    assert iters <= imgs.shape[0]
    batch = imgs.shape[1]
    rec = _recipe(iters)

    model = _shared_init_state_dict(model_name)
    model.train()
    optim = torch.optim.SGD(
        model.parameters(), lr=rec["lr"], momentum=rec["momentum"],
        weight_decay=rec["weight_decay"],
    )
    sched = torch.optim.lr_scheduler.MultiStepLR(
        optim, milestones=rec["milestones"], gamma=rec["gamma"]
    )

    def evaluate():
        model.eval()
        correct, n = 0, 0
        with torch.no_grad():
            for i in range(0, len(v_imgs), batch):
                x = torch.from_numpy(
                    _normalize(v_imgs[i:i + batch])
                ).permute(0, 3, 1, 2)
                pred = model(x).argmax(1).numpy()
                correct += int((pred == v_labs[i:i + batch]).sum())
                n += len(pred)
        model.train()
        return 100.0 * correct / n

    t0 = time.perf_counter()
    for it in range(iters):
        x = torch.from_numpy(_normalize(np.asarray(imgs[it]))).permute(0, 3, 1, 2)
        y = torch.from_numpy(labels[it].astype(np.int64))
        optim.zero_grad(set_to_none=True)
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        optim.step()
        sched.step()  # per iteration (reference :299)
        if eval_every and (it + 1) % eval_every == 0:
            log(
                f"[torch] iter {it + 1}/{iters} loss {float(loss):.4f} "
                f"val@1 {evaluate():.2f}%  ({time.perf_counter() - t0:.0f}s)"
            )
    top1 = evaluate()
    log(f"[torch] FINAL iter {iters} val top-1 {top1:.2f}%")
    return top1


# ----------------------------------------------------------------------
# Generator parameters pinned into the stage done-markers (ADVICE r4 #3):
# the cached dataset/streams are only reused when the parameters that
# produced them match — changing N_CLASSES, per-class counts, IMAGE_SIZE,
# or seeds rebuilds instead of silently reusing stale artifacts.
_GEN_PARAMS = dict(
    n_classes=N_CLASSES, per_class_train=200, per_class_val=40, size=96,
    seed=0,
)


def _stream_params(iters: int, batch: int) -> dict:
    # streams are a pure function of the generated dataset + (iters, batch,
    # crop, seed), so the generator params fold in: a dataset rebuild must
    # also invalidate streams derived from the old dataset
    return dict(iters=iters, batch=batch, image_size=IMAGE_SIZE, seed=0,
                gen=_GEN_PARAMS)


def _stage_cached(done_path: str, params: dict, log, what: str) -> bool:
    """True if the stage's done-marker exists AND records ``params``."""
    if not os.path.exists(done_path):
        return False
    try:
        recorded = json.loads(open(done_path).read())
    except (ValueError, OSError):
        recorded = None
    if recorded != params:
        log(f"[{what}] cached artifacts were built with {recorded}, "
            f"need {params} — rebuilding")
        return False
    return True


def run_all(work_dir: str, iters: int, batch: int = 64, eval_every: int = 0,
            skip_torch: bool = False, log=print,
            model_name: str = "ResNet18", sync_bn: bool = False,
            stream_iters: int = 0) -> dict:
    """gen -> streams -> ours -> torch; cached by directory contents.

    ``stream_iters`` (default: ``iters``): length of the precomputed
    stream — a shorter-horizon run (``iters`` < ``stream_iters``) trains
    on the prefix of the longer stream, same pixels, no regeneration.
    """
    stream_iters = stream_iters or iters
    if stream_iters < iters:
        raise ValueError(
            f"stream_iters {stream_iters} shorter than the {iters}-iter run"
        )
    data_root = os.path.join(work_dir, "data")
    stream_dir = os.path.join(work_dir, f"streams_i{stream_iters}_b{batch}")
    # stage caching gates on DONE MARKERS written after the final flush, not
    # bare file existence — an interrupted generation leaves partial
    # artifacts (the stream memmap is created full-size before filling)
    # that must be rebuilt, never silently reused; the marker records the
    # generator parameters (ADVICE r4 #3)
    gen_done = os.path.join(data_root, ".done")
    if not _stage_cached(gen_done, _GEN_PARAMS, log, "gen"):
        # wipe before rebuilding: the generator only ADDS files, so a
        # parameter change (fewer images/classes) would otherwise leave
        # stale JPEGs mixed into the "rebuilt" dataset — exactly the
        # silent-staleness class the done-markers exist to prevent
        if os.path.isdir(data_root):
            shutil.rmtree(data_root)
        log("[gen] building 40-class texture JPEG dataset...")
        make_texture_dataset(data_root, **_GEN_PARAMS)
        open(gen_done, "w").write(json.dumps(_GEN_PARAMS))
    stream_done = os.path.join(stream_dir, ".done")
    if not _stage_cached(stream_done, _stream_params(stream_iters, batch), log, "streams"):
        if os.path.isdir(stream_dir):
            shutil.rmtree(stream_dir)
        log(f"[streams] precomputing {stream_iters} x {batch} augmented batches...")
        precompute_streams(data_root, stream_dir, stream_iters, batch)
        open(stream_done, "w").write(json.dumps(_stream_params(stream_iters, batch)))
    ours = train_ours(
        stream_dir, iters, eval_every, log=log, model_name=model_name,
        sync_bn=sync_bn,
    )
    result = {"ours_top1": round(ours, 2), "iters": iters, "batch": batch,
              "model": model_name}
    if not skip_torch:
        ref = train_torch(
            stream_dir, iters, eval_every, log=log, model_name=model_name
        )
        result["torch_top1"] = round(ref, 2)
        result["gap_pts"] = round(ours - ref, 2)
    return result


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("stage", choices=["gen", "streams", "ours", "torch", "all"])
    ap.add_argument("--work-dir", default=".accuracy")
    ap.add_argument("--iters", type=int, default=2000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--eval-every", type=int, default=250)
    ap.add_argument("--model", default="ResNet18",
                    choices=["ResNet18", "ResNet50"])
    ap.add_argument("--sync-bn", action="store_true",
                    help="ours: DP+SyncBN path (pair with JAX_PLATFORMS=cpu"
                         " + an 8-virtual-device mesh for the DP==1dev pin)")
    ap.add_argument("--platform", choices=["chip", "cpu"], default=None,
                    help="ours: pin the jax backend — 'cpu' forces "
                         "JAX_PLATFORMS=cpu so the ours-on-CPU vs "
                         "torch-on-CPU SAME-PLATFORM comparison (VERDICT "
                         "r5 blocker #2) is one command; 'chip' clears any "
                         "inherited CPU pin so the accelerator is used. "
                         "Default: leave the environment's choice alone.")
    ap.add_argument("--stream-iters", type=int, default=None,
                    help="length of the PRECOMPUTED stream to train from "
                         "(default: --iters). Lets shorter-horizon runs "
                         "(scaled recipes; the per-iter milestones come "
                         "from --iters) reuse one long stream prefix — "
                         "same pixels, no regeneration.")
    args = ap.parse_args()

    # must happen before the first (lazy) jax import inside train_ours —
    # jax reads JAX_PLATFORMS at backend-discovery time
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif args.platform == "chip":
        os.environ.pop("JAX_PLATFORMS", None)

    work = args.work_dir
    data_root = os.path.join(work, "data")
    stream_iters = args.stream_iters or args.iters
    stream_dir = os.path.join(work, f"streams_i{stream_iters}_b{args.batch}")
    if args.stage == "gen":
        make_texture_dataset(data_root, **_GEN_PARAMS)
    elif args.stage == "streams":
        precompute_streams(data_root, stream_dir, stream_iters, args.batch)
    elif args.stage == "ours":
        train_ours(stream_dir, args.iters, args.eval_every,
                   model_name=args.model, sync_bn=args.sync_bn)
    elif args.stage == "torch":
        train_torch(stream_dir, args.iters, args.eval_every,
                    model_name=args.model)
    else:
        out = run_all(work, args.iters, args.batch, args.eval_every,
                      model_name=args.model, sync_bn=args.sync_bn,
                      stream_iters=stream_iters)
        print(json.dumps(out))
