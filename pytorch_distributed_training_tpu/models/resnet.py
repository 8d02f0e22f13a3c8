"""ResNet family (18/34/50/101/152) in Flax linen, TPU-native.

Replaces the reference's ``dl_lib.classification.models.get_model`` zoo
(import at train_distributed.py:25, names pinned by config/ResNet50.yml:31
and the README accuracy table, README.md:7-13).  Built for the MXU:

  - NHWC layout (TPU-native; the host pipeline emits NHWC, no transposes),
  - all normalization via :class:`~..ops.batch_norm.DistributedBatchNorm`
    so ``sync_bn`` is a constructor argument (``axis_name``), not a
    post-hoc module-tree rewrite like ``convert_sync_batchnorm``
    (train_distributed.py:196-197),
  - optional bf16 compute dtype with fp32 params and fp32 BN statistics.

Topology parity with torchvision ResNet v1.5 (the weights the reference's
accuracy table describes): 7x7/2 stem + 3x3/2 maxpool; bottleneck blocks put
the stride on the 3x3 conv; projection shortcuts are 1x1 conv + BN; explicit
torch-style padding (flax "SAME" differs for stride-2 — we match torch).

Init parity: convs use kaiming-normal fan_out (torch ``kaiming_normal_``
with ``mode='fan_out', nonlinearity='relu'``); BN scale=1 offset=0
(``zero_init_residual=False``, torchvision default); the classifier head
uses torch ``nn.Linear`` default init (kaiming-uniform a=sqrt(5) ==
U(+-1/sqrt(fan_in)) for both kernel and bias).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax.numpy as jnp
from flax import linen as nn

from ..ops.batch_norm import DistributedBatchNorm

__all__ = [
    "ResNet",
    "BasicBlock",
    "Bottleneck",
    "RESNET_CONFIGS",
    "fold_stem_kernel",
]

# torch kaiming_normal_(mode="fan_out", nonlinearity="relu")
conv_kernel_init = nn.initializers.variance_scaling(2.0, "fan_out", "normal")


def fold_stem_kernel(w7):
    """Fold a 7x7/2 stem kernel [7,7,C,O] into the space-to-depth
    equivalent [4,4,4C,O] (4x4 stride-1 conv over the 2x2-packed input).

    Exact algebra: the 7x7 stride-2 conv reads ``x[2i+a-3]``; with the 2x2
    pack ``z[p,(u,c)] = x[2p+u]`` each tap ``a`` lands at packed offset
    ``m-2 = (a-3-u)//2`` with parity ``u = (a-3) % 2`` — 4 packed taps per
    axis, one (m=0, u=0) slot left zero.  The zero slots also make the
    padding equivalence exact: the packed conv's ((2,1),(2,1)) pad reaches
    one original pixel beyond the 7x7 conv's pad-3, but only through
    zero-weight slots.  Used by the model's from-scratch init (fold a
    kaiming 7x7 draw, keeping the init distribution identical) and by the
    torchvision weight port (models/torch_port.py).
    """
    import numpy as np

    import jax

    kh, kw, c, o = w7.shape
    assert (kh, kw) == (7, 7), w7.shape
    # numpy for concrete kernels (checkpoint import, eager init — no need
    # for 49 eager device dispatches);
    # jnp .at[].set() only when tracing (the init can run under jit)
    traced = isinstance(w7, jax.core.Tracer)
    if traced:
        out = jnp.zeros((4, 4, 4 * c, o), dtype=w7.dtype)
    else:
        w7 = np.asarray(w7)
        out = np.zeros((4, 4, 4 * c, o), dtype=w7.dtype)
    for a in range(7):
        u = (a - 3) % 2
        m = (a - 3 - u) // 2 + 2
        for b in range(7):
            v = (b - 3) % 2
            n = (b - 3 - v) // 2 + 2
            sl = slice((u * 2 + v) * c, (u * 2 + v) * c + c)
            if traced:
                out = out.at[m, n, sl, :].set(w7[a, b])
            else:
                out[m, n, sl, :] = w7[a, b]
    return out


def _s2d_stem_init(key, shape, dtype):
    """Init the packed stem by folding a kaiming 7x7 draw — the from-scratch
    weight DISTRIBUTION matches the standard stem exactly."""
    _, _, c4, o = shape
    w7 = conv_kernel_init(key, (7, 7, c4 // 4, o), dtype)
    return jnp.asarray(fold_stem_kernel(w7), dtype)


def _torch_linear_kernel_init(key, shape, dtype):
    """torch ``nn.Linear`` default: kaiming_uniform(a=sqrt(5)) == U(+-1/sqrt(fan_in))."""
    fan_in = shape[0]
    bound = 1.0 / math.sqrt(fan_in)
    import jax.random as jrandom

    return jrandom.uniform(key, shape, dtype, -bound, bound)


def _torch_linear_bias_init(fan_in: int):
    bound = 1.0 / math.sqrt(fan_in)

    def init(key, shape, dtype):
        import jax.random as jrandom

        return jrandom.uniform(key, shape, dtype, -bound, bound)

    return init


class BasicBlock(nn.Module):
    """Two 3x3 convs; stride on the first (torchvision BasicBlock)."""

    features: int
    stride: int
    conv: Callable
    norm: Callable

    expansion = 1

    @nn.compact
    def __call__(self, x):
        identity = x
        out = self.conv(self.features, (3, 3), self.stride, name="conv1")(x)
        out = self.norm(name="bn1")(out)
        out = nn.relu(out)
        out = self.conv(self.features, (3, 3), 1, name="conv2")(out)
        out = self.norm(name="bn2")(out)
        if self.stride != 1 or identity.shape[-1] != self.features:
            identity = self.conv(self.features, (1, 1), self.stride, name="downsample_conv")(x)
            identity = self.norm(name="downsample_bn")(identity)
        return nn.relu(out + identity)


class Bottleneck(nn.Module):
    """1x1 reduce -> 3x3 (stride here: v1.5) -> 1x1 expand (torchvision Bottleneck)."""

    features: int
    stride: int
    conv: Callable
    norm: Callable

    expansion = 4

    @nn.compact
    def __call__(self, x):
        out_features = self.features * self.expansion
        identity = x
        out = self.conv(self.features, (1, 1), 1, name="conv1")(x)
        out = self.norm(name="bn1")(out)
        out = nn.relu(out)
        out = self.conv(self.features, (3, 3), self.stride, name="conv2")(out)
        out = self.norm(name="bn2")(out)
        out = nn.relu(out)
        out = self.conv(out_features, (1, 1), 1, name="conv3")(out)
        out = self.norm(name="bn3")(out)
        if self.stride != 1 or identity.shape[-1] != out_features:
            identity = self.conv(out_features, (1, 1), self.stride, name="downsample_conv")(x)
            identity = self.norm(name="downsample_bn")(identity)
        return nn.relu(out + identity)


class ResNet(nn.Module):
    """torchvision-topology ResNet with TPU-native distributed BN.

    Args:
      stage_sizes: blocks per stage, e.g. (3, 4, 6, 3) for ResNet-50.
      block_cls: :class:`BasicBlock` or :class:`Bottleneck`.
      num_classes: classifier width (reference: ``dataset.n_classes``).
      axis_name: mesh axis for synchronized BN statistics (``sync_bn: True``),
        or ``None`` for per-replica stats.
      dtype: compute dtype (bf16 for mixed precision); params stay fp32.
    """

    stage_sizes: Sequence[int]
    block_cls: Any
    num_classes: int
    axis_name: Optional[str] = None
    dtype: Any = jnp.float32
    # MLPerf-style stem: 2x2 space-to-depth pack + folded 4x4/1 conv,
    # numerically EQUAL to the 7x7/2 stem (fold_stem_kernel) but far
    # friendlier to the MXU (C_in 12 instead of 3, half the spatial grid).
    # Config key ``model.space_to_depth``; torchvision checkpoints port
    # through the same fold, so accuracy parity oracles stay pinned.
    space_to_depth: bool = False
    # Config key ``model.bn_stat_dtype``: batch-moment accumulation dtype
    # (ops/batch_norm.py stat_dtype); None = f32 torch-parity default.
    bn_stat_dtype: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        def conv(features, kernel, stride, name, padding=None, kernel_init=None):
            pad = padding or [(k // 2, k // 2) for k in kernel]
            return nn.Conv(
                features,
                kernel,
                strides=(stride, stride),
                padding=pad,
                use_bias=False,
                kernel_init=kernel_init or conv_kernel_init,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                name=name,
            )

        norm = functools.partial(
            DistributedBatchNorm,
            use_running_average=not train,
            axis_name=self.axis_name if train else None,
            momentum=0.1,
            epsilon=1e-5,
            dtype=self.dtype,
            stat_dtype=self.bn_stat_dtype,
        )

        x = x.astype(self.dtype)
        if self.space_to_depth:
            b, h, w, c = x.shape
            if h % 2 or w % 2:
                raise ValueError(
                    f"space_to_depth requires even input dims, got {h}x{w}"
                )
            x = x.reshape(b, h // 2, 2, w // 2, 2, c)
            x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
            # packed taps span offsets -2..+1 (see fold_stem_kernel)
            x = conv(
                64, (4, 4), 1, name="conv1",
                padding=((2, 1), (2, 1)), kernel_init=_s2d_stem_init,
            )(x)
        else:
            x = conv(64, (7, 7), 2, name="conv1")(x)
        x = norm(name="bn1")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))

        features = 64
        for stage, n_blocks in enumerate(self.stage_sizes):
            for block in range(n_blocks):
                stride = 2 if stage > 0 and block == 0 else 1
                x = self.block_cls(
                    features=features,
                    stride=stride,
                    conv=conv,
                    norm=norm,
                    name=f"layer{stage + 1}_{block}",
                )(x)
            features *= 2

        x = jnp.mean(x, axis=(1, 2))  # global average pool (AdaptiveAvgPool2d(1))
        fan_in = x.shape[-1]
        x = nn.Dense(
            self.num_classes,
            kernel_init=_torch_linear_kernel_init,
            bias_init=_torch_linear_bias_init(fan_in),
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="fc",
        )(x)
        return x.astype(jnp.float32)  # logits in fp32 for a stable loss


# name -> (block, stage_sizes), torchvision families (README.md:7-13)
RESNET_CONFIGS: dict[str, Tuple[Any, Tuple[int, ...]]] = {
    "ResNet18": (BasicBlock, (2, 2, 2, 2)),
    "ResNet34": (BasicBlock, (3, 4, 6, 3)),
    "ResNet50": (Bottleneck, (3, 4, 6, 3)),
    "ResNet101": (Bottleneck, (3, 4, 23, 3)),
    "ResNet152": (Bottleneck, (3, 8, 36, 3)),
}
