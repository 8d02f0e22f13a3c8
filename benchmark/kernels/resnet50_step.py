"""Operations one training image of ResNet-50 requires, forward and
backward, from the layer shapes: every convolution and the classifier at 2
FLOPs a multiply-add forward and twice that backward (the gradient of the
input and of the weight).  BatchNorm, ReLU, pooling and the loss are not
counted (under 1% of the multiply-adds)."""

STAGES = (3, 4, 6, 3)
WIDTHS = (64, 128, 256, 512)


def forward_macs(image_size: int = 224, classes: int = 1000) -> int:
    """Multiply-adds of one forward pass of one image."""
    size = -(-image_size // 2)                 # 7x7 stride 2, padding 3
    macs = size * size * 64 * 3 * 49
    size = -(-size // 2)                       # 3x3 max pool stride 2
    inp = 64
    for stage, (blocks, width) in enumerate(zip(STAGES, WIDTHS)):
        for b in range(blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            out_size = -(-size // stride)
            macs += size * size * inp * width               # 1x1 reduce
            macs += out_size * out_size * width * width * 9  # 3x3 (stride here)
            macs += out_size * out_size * width * width * 4  # 1x1 expand
            if b == 0:
                macs += out_size * out_size * inp * width * 4  # projection
            size, inp = out_size, width * 4
    return macs + inp * classes


def flops_per_sample(config: dict, traffic: dict) -> float:
    macs = forward_macs(int(traffic.get("image_size", 224)), int(config["n_classes"]))
    return 3 * 2.0 * macs
