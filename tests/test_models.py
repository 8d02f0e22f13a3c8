"""Model zoo: torchvision topology parity via exact parameter counts + shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_training_tpu.models import get_model, list_models

# torchvision parameter counts @ 1000 classes (conv+bn affine+fc), the
# strongest cheap topology-parity oracle available without weights.
TORCHVISION_PARAM_COUNTS = {
    "ResNet18": 11_689_512,
    "ResNet34": 21_797_672,
    "ResNet50": 25_557_032,
    "ResNet101": 44_549_160,
    "ResNet152": 60_192_808,
}

# ViT family added beyond the reference; ViT-B16 matches torchvision
# vit_b_16 (86.6M @ 1000 classes).
VIT_NAMES = {"ViT-Ti16", "ViT-S16", "ViT-B16"}


def _count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("name", ["ResNet18", "ResNet50"])
def test_param_count_parity(name):
    model = get_model(name, num_classes=1000)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False)
    )
    assert _count(variables["params"]) == TORCHVISION_PARAM_COUNTS[name]


def test_all_names_resolve():
    assert set(list_models()) == (
        set(TORCHVISION_PARAM_COUNTS) | VIT_NAMES
        | {"TransformerLM", "DeepseekV2", "SolarOpen2", "NemotronH", "OlmoHybrid",
           "Laguna"}
    )
    for name in list_models():
        get_model(name, num_classes=10)
    get_model("resnet50", num_classes=10)  # case-insensitive
    with pytest.raises(KeyError):
        get_model("VGG16", num_classes=10)


def test_vit_b16_param_count_parity():
    model = get_model("ViT-B16", num_classes=1000)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False)
    )
    # torchvision vit_b_16 @ 1000 classes
    assert _count(variables["params"]) == 86_567_656


def test_forward_shapes_and_stages():
    model = get_model("ResNet18", num_classes=7)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 7)
    assert out.dtype == jnp.float32

    # train mode returns mutated batch_stats
    out, updated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    assert out.shape == (2, 7)
    assert "batch_stats" in updated


def test_bf16_compute_fp32_params():
    model = get_model("ResNet18", num_classes=5, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 32, 32, 3), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    for leaf in jax.tree.leaves(variables["params"]):
        assert leaf.dtype == jnp.float32  # master weights stay fp32
    out = model.apply(variables, x, train=False)
    assert out.dtype == jnp.float32  # logits promoted for the loss
