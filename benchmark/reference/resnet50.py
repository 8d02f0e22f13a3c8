"""Plain reference of torchvision's ResNet-50 (v1.5: the stride sits on the
3x3 convolution): forward in train-mode BatchNorm (batch statistics, biased
variance, eps 1e-5), mean cross entropy, gradients, and torch-order SGD with
momentum and coupled weight decay.  float32 ``jax.numpy``, convolution and
matmul precision ``highest``; imports nothing of the program and takes none
of its weights: they come from ``make_params(seed)``.

Parameter layout: a flat dict under torchvision's own ``state_dict`` names
(``layer2.0.conv1.weight`` in OIHW, ``bn1.weight``, ``fc.weight`` as
(out, in)), which is also the format the program's ``model.pretrained``
reads.  Running statistics are carried (0 / 1) but train mode does not read
them.

``mode``, for the controls only: ``f32`` | ``bf16`` | ``int8`` operands of
every convolution and of the classifier (see reference/lm.py).
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)
EXPANSION = 4
LAST_BN_SCALE = 0.25


def sizes_of(config: dict) -> dict:
    return {"classes": int(config["n_classes"])}


def conv_shapes(classes: int) -> dict:
    """``{torch name: shape}`` of every weight, in the network's order."""
    shapes = {"conv1.weight": (64, 3, 7, 7), "bn1": 64}
    inp = 64
    for stage, (blocks, width) in enumerate(zip(STAGES, WIDTHS), start=1):
        for b in range(blocks):
            pre = f"layer{stage}.{b}"
            out = width * EXPANSION
            shapes[f"{pre}.conv1.weight"] = (width, inp, 1, 1)
            shapes[f"{pre}.bn1"] = width
            shapes[f"{pre}.conv2.weight"] = (width, width, 3, 3)
            shapes[f"{pre}.bn2"] = width
            shapes[f"{pre}.conv3.weight"] = (out, width, 1, 1)
            shapes[f"{pre}.bn3"] = out
            if b == 0:
                shapes[f"{pre}.downsample.0.weight"] = (out, inp, 1, 1)
                shapes[f"{pre}.downsample.1"] = out
            inp = out
    shapes["fc.weight"] = (classes, inp)
    return shapes


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("classes",))
def _make_params(key, *, classes):
    params = {}
    shapes = conv_shapes(classes)
    keys = iter(jax.random.split(key, len(shapes)))
    for name, shape in shapes.items():
        k = next(keys)
        if isinstance(shape, int):  # a BatchNorm
            # the last BatchNorm of a block starts small (the "zero-gamma"
            # practice, here LAST_BN_SCALE): at scale 1 sixteen random blocks
            # amplify a rounding error 10^4 times into the gradient (PR 21)
            # and no precision could be told from another
            scale = LAST_BN_SCALE if name.endswith(".bn3") else 1.0
            params[f"{name}.weight"] = jnp.full((shape,), scale)
            params[f"{name}.bias"] = jnp.zeros((shape,))
        elif len(shape) == 4:  # kaiming normal, fan_out, relu
            fan_out = shape[0] * shape[2] * shape[3]
            params[name] = math.sqrt(2.0 / fan_out) * jax.random.normal(k, shape)
        else:
            params[name] = jax.random.normal(k, shape) / math.sqrt(shape[1])
            params["fc.bias"] = jnp.zeros((shape[0],))
    return params


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, in one jitted call."""
    return _make_params(seed_key(seed), classes=sizes["classes"])


def to_torch_state_dict(params: dict) -> dict:
    out = {k: np.asarray(v) for k, v in params.items()}
    for name, shape in conv_shapes(out["fc.weight"].shape[0]).items():
        if isinstance(shape, int):
            out[f"{name}.running_mean"] = np.zeros((shape,), np.float32)
            out[f"{name}.running_var"] = np.ones((shape,), np.float32)
    return out


def _torch_name(path) -> str:
    mods = []
    for m in path[:-1]:
        if m.startswith("layer") and "_" in m:
            stage, block = m[len("layer"):].split("_")
            mods.append(f"layer{stage}.{block}")
        else:
            mods.append({"downsample_conv": "downsample.0",
                         "downsample_bn": "downsample.1"}.get(m, m))
    leaf = {"scale": "weight", "kernel": "weight"}.get(path[-1], path[-1])
    return ".".join(mods + [leaf])


def from_program_tree(tree, sizes: dict) -> dict:
    """A parameter-shaped tree read back from the program (flat Flax names
    ``layer2_0/conv1/kernel`` in HWIO) -> this file's layout."""
    out = {}

    def walk(node, path):
        for key, val in node.items():
            if hasattr(val, "items"):
                walk(val, path + (key,))
                continue
            arr = np.asarray(val)
            if key == "kernel":
                arr = arr.T if arr.ndim == 2 else np.transpose(arr, (3, 2, 0, 1))
            out[_torch_name(path + (key,))] = arr

    walk(tree, ())
    return out


# ------------------------------------------------------------------ forward

def _fake_int8(x, axes):
    """Symmetric int8 fake quantisation over ``axes``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _lowered(op, mode, w_axes):
    """``op(x, w)`` in the arithmetic ``mode`` names.  ``int8`` quantises
    both operands (activations a sample, weights an output channel) and, in
    the backward pass, the incoming gradient a sample, straight through the
    quantisers themselves."""
    if mode == "f32":
        return op
    if mode == "bf16":
        rnd = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        return lambda x, w: op(rnd(x), rnd(w))
    if mode != "int8":
        raise ValueError(f"unknown mode {mode!r}")

    def quant(x, w):
        return _fake_int8(x, tuple(range(1, x.ndim))), _fake_int8(w, w_axes)

    @jax.custom_vjp
    def product(x, w):
        return op(*quant(x, w))

    def forward(x, w):
        kept = quant(x, w)
        return op(*kept), kept

    def backward(kept, g):
        return jax.vjp(op, *kept)[1](_fake_int8(g, tuple(range(1, g.ndim))))

    product.defvjp(forward, backward)
    return product


def _conv(x, w, stride, mode):
    """NHWC activation, OIHW weight, torch padding ``k // 2``."""
    pad = w.shape[2] // 2

    def op(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"), precision=HIGHEST,
        )

    return _lowered(op, mode, (1, 2, 3))(x, w)


def _bn(x, params, name):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    x = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    return x * params[f"{name}.weight"] + params[f"{name}.bias"]


def _bottleneck(x, p, pre, stride, mode):
    out = jax.nn.relu(_bn(_conv(x, p[f"{pre}.conv1.weight"], 1, mode), p, f"{pre}.bn1"))
    out = jax.nn.relu(_bn(_conv(out, p[f"{pre}.conv2.weight"], stride, mode), p, f"{pre}.bn2"))
    out = _bn(_conv(out, p[f"{pre}.conv3.weight"], 1, mode), p, f"{pre}.bn3")
    if f"{pre}.downsample.0.weight" in p:
        x = _bn(_conv(x, p[f"{pre}.downsample.0.weight"], stride, mode),
                p, f"{pre}.downsample.1")
    return jax.nn.relu(out + x)


def logits(params, images, mode="f32"):
    """``images [B, H, W, 3]`` -> logits ``[B, classes]``, train-mode BN."""
    x = jax.nn.relu(_bn(_conv(images, params["conv1.weight"], 2, mode), params, "bn1"))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)],
    )
    for stage, blocks in enumerate(STAGES, start=1):
        for b in range(blocks):
            stride = 2 if stage > 1 and b == 0 else 1
            block = jax.checkpoint(
                functools.partial(_bottleneck, pre=f"layer{stage}.{b}",
                                  stride=stride, mode=mode)
            )
            x = block(x, params)
    x = jnp.mean(x, axis=(1, 2))
    fc = _lowered(lambda x, w: jnp.matmul(x, w.T, precision=HIGHEST), mode, (1,))
    return fc(x, params["fc.weight"]) + params["fc.bias"]


def _loss(params, images, labels, mode):
    logp = jax.nn.log_softmax(logits(params, images, mode), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("mode",))
def loss_and_grads(params, images, labels, *, mode="f32"):
    """The whole batch at once: train-mode BN couples its rows."""
    return jax.value_and_grad(_loss)(params, images, labels, mode)


# --------------------------------------------------------------- optimizer

def lr_at(step: int, opt: dict) -> float:
    sched = opt["lr_schedule"]
    hits = sum(1 for m in sched["milestones"] if step >= m)
    return float(opt["lr"]) * float(sched["gamma"]) ** hits


@jax.jit
def sgd_step(params, bufs, grads, first, lr, wd, momentum):
    """torch.optim.SGD: ``d = g + wd p``; ``buf = d`` at the first step, then
    ``momentum buf + d``; ``p -= lr buf``."""
    def one(p, buf, g):
        d = g + wd * p
        buf = jnp.where(first, d, momentum * buf + d)
        return p - lr * buf, buf

    out = {k: one(params[k], bufs[k], grads[k]) for k in params}
    return {k: v[0] for k, v in out.items()}, {k: v[1] for k, v in out.items()}


def first_gradient(moment: dict, params0: dict, opt: dict) -> dict:
    """From the program's state after ONE step: SGD's buffer is then
    ``g + wd p0``."""
    wd = float(opt.get("weight_decay", 0.0))
    return {k: np.asarray(v) - wd * np.asarray(params0[k]) for k, v in moment.items()}


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf, reduced where the tree lives."""
    kept = {k: jnp.asarray(v) for k, v in tree.items() if "running_" not in k}
    return {k: float(v) for k, v in jax.device_get(_leaf_norms(kept)).items()}


def train_reference(params0, batches, sizes, opt, mode="f32"):
    """Follow the first ``len(batches)`` optimizer steps: losses, per-leaf
    norms of the first gradient and of the parameters' change."""
    params0 = {k: v for k, v in params0.items() if "running_" not in k}
    params = params0
    bufs = jax.tree.map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for step, (images, labels) in enumerate(batches):
        loss, grads = loss_and_grads(
            params, jnp.asarray(images, jnp.float32),
            jnp.asarray(labels, jnp.int32), mode=mode,
        )
        losses.append(float(loss))
        if step == 0:
            grad_norms = leaf_norms(grads)
        params, bufs = sgd_step(
            params, bufs, grads, step == 0, lr_at(step, opt),
            float(opt["weight_decay"]), float(opt["momentum"]),
        )
    change = jax.tree.map(jnp.subtract, params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(change)}


# ------------------------------------------------- what the drivers call

def prepare_data(seed: int, config: dict, traffic: dict, directory: str) -> dict:
    """The program's ``synthetic`` dataset: Gaussian 224x224 images made by
    its loader's workers on the host, each from its index.  The benchmark
    cannot hand the program images without the JPEG path (Open questions),
    so the images are the program's generator's; ``--seed`` orders them."""
    return {
        "name": "synthetic", "root": "/none",
        "n_classes": int(config["n_classes"]),
        "image_size": int(traffic["image_size"]),
        "n_samples": int(traffic["n_samples"]),
    }


def synthetic_image(index: int, classes: int, size: int):
    """The published recipe of that dataset, written again here: sample
    ``index`` is standard normal noise from ``default_rng(salt * 1000003 +
    index)`` plus a class-dependent shift, label ``index % classes``."""
    salt = zlib.crc32(b"train") & 0xFFFF
    rng = np.random.default_rng(salt * 1_000_003 + index)
    label = index % classes
    img = rng.standard_normal((size, size, 3), dtype=np.float32)
    return img + np.float32(0.1 * ((label % 16) - 8) / 8.0), label


def reference_batch(fed, data_cfg: dict):
    """The batch of one checked step.  Rows are taken as the program fed
    them; a sample of them is made again here from its index and must
    agree, and all labels must be consistent with distinct rows."""
    images, labels = np.asarray(fed[0], np.float32), np.asarray(fed[1], np.int64)
    classes, size = int(data_cfg["n_classes"]), int(data_cfg["image_size"])
    for row in range(0, len(images), max(1, len(images) // 4)):
        matches = [
            i for i in range(int(labels[row]), int(data_cfg["n_samples"]), classes)
            if np.array_equal(synthetic_image(i, classes, size)[0][0, :4], images[row][0, :4])
        ]
        if len(matches) != 1:
            raise ValueError(f"fed row {row} is not a sample of the dataset")
    flat = images.reshape(len(images), -1)[:, :64]
    if len({row.tobytes() for row in flat}) != len(images):
        raise ValueError("fed rows are not all different")
    return images, labels.astype(np.int32)
