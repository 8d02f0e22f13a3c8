"""Ask the TPU's compiler about every Pallas kernel on the main path.

Every other test forces the CPU, where the kernels run in interpreter mode
or give way to XLA, so none of them asks Mosaic anything.  The TPU compiler
is installed alongside JAX and compiles for a chip that is DESCRIBED, not
attached: each case below lowers one kernel at the widths ``chip_smoke.py``
runs (271M LM: batch 8 x seq 2048, 8 heads x 128, vocab 32768; ResNet-50:
128 x 1000 logits) for one device of a ``v5e:2x2`` and asserts the Mosaic
custom call is in the compiled program.  Nothing executes — a compile that
passes is not a chip run.  Whole-step compiles take minutes and stay out of
tier-1.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pytorch_distributed_training_tpu.ops.flash_attention import (
    flash_attention,
    flash_prefill,
)
from pytorch_distributed_training_tpu.ops.fused_ce import fused_cross_entropy
from pytorch_distributed_training_tpu.ops.fused_elementwise import (
    _make_add_ln,
    _make_bias_gelu,
)
from pytorch_distributed_training_tpu.ops.mla_paged_decode import mla_paged_decode
from pytorch_distributed_training_tpu.ops.paged_decode import paged_decode


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e 2x2, persistent cache off around it
    (an entry written for a described device cannot be read back without
    one: the next compile would warn and compile again)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _attn_loss(q, k, v):
    return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True)


_flash_fwd_bwd = jax.grad(_attn_loss, argnums=(0, 1, 2))


def _prefill_call(positions, heads, kv_heads):
    """A whole-prompt prefill's call of the flash forward (``ops/attention.py
    ::paged_attention``'s flash arm): one row of ``positions``, ``heads``
    query heads of 128 over ``kv_heads`` K/V heads."""
    return [((1, positions, heads, 128), None)] + [
        ((1, positions, kv_heads, 128), None)] * 2


def _ce_fwd_bwd(logits, labels):
    return jax.value_and_grad(fused_cross_entropy)(logits, labels)


# The public wrappers pick interpreter mode from ``jax.default_backend()``,
# which is the CPU here; the test steers past that to the Mosaic builders.
def _add_ln(x, delta, scale, bias):
    return _make_add_ln(False, 1e-6)(x, delta, scale, bias, x.dtype)


def _bias_gelu(u, bias):
    return _make_bias_gelu(False)(u, bias)


def _paged_decode(q, k_pool, v_pool, tables, lengths):
    return paged_decode(q, k_pool, v_pool, tables, lengths, scale=128 ** -0.5)


def _mla_paged_decode(q_lat, q_pe, pool, tables, lengths):
    return mla_paged_decode(q_lat, q_pe, pool, tables, lengths, scale=192 ** -0.5)


def _decode_call(rows, group, blocks, table, kv_heads=8):
    """One decode step's call of the paged kernel: ``rows`` slots of
    ``kv_heads`` K/V heads x ``group`` query heads of 128 over a pool of
    ``blocks`` blocks of 16 positions, ``table`` entries a row."""
    return [((rows, kv_heads, group, 128), None), ((blocks, 16, kv_heads, 128), None),
            ((blocks, 16, kv_heads, 128), None), ((rows, table), I32), ((rows,), I32)]


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
QKV_2K = [((8, 2048, 8, 128), None)] * 3
QKV_16K = [((1, 16384, 8, 128), None)] * 3
# causal sequences past one whole-sequence Q tile (2048 rows): the forward
# keeps 1024-row tiles (a 2048-row tile overflows VMEM at 4096), the fused
# backward 512 x 512 (1024 x 512 overflows it at 3072 x 64)
QKV_4K = [((4, 4096, 8, 128), None)] * 3
QKV_3K_D64 = [((4, 3072, 16, 64), None)] * 3

# (fn, [(shape, dtype or None for the case dtype)], case dtype, the kernels
# of the compiled program by their ``name=``)
CASES = {
    "flash_fwd_bf16": (_flash_fwd, QKV_2K, BF16, ["flash_fwd"]),
    "flash_fused_bwd_bf16": (
        _flash_fwd_bwd, QKV_2K, BF16, ["flash_fwd", "flash_bwd"],
    ),
    "flash_fwd_bf16_4096": (_flash_fwd, QKV_4K, BF16, ["flash_fwd"]),
    # the longest sequence the resident forward holds at 1,024-row tiles ...
    "flash_fwd_bf16_6144": (
        _flash_fwd, [((1, 6144, 8, 128), None)] * 3, BF16, ["flash_fwd"]),
    # ... and the edge of its budget, S x D = 1M: Mosaic refuses the resident
    # form there (18.63 MB of scoped VMEM for 16), the streamed one serves it
    "flash_fwd_bf16_8192": (
        _flash_fwd, [((1, 8192, 8, 128), None)] * 3, BF16, ["flash_fwd_stream"]),
    # a prefill's full layers, whole prompts (laguna-xs2.serve.code32's
    # buckets 1,024-8,192, 48 heads in groups of 6; solar-open2's 64 in groups
    # of 8 at 4,096; nemotron-3's 32 in groups of 16 and olmo-hybrid's 30 a
    # K/V head each at 1,024 and 256): no logsumexp output, a K/V head read
    # by its group through the index maps
    "flash_prefill_laguna_1024": (
        flash_prefill, _prefill_call(1024, 48, 8), BF16, ["flash_fwd"]),
    "flash_prefill_laguna_4096": (
        flash_prefill, _prefill_call(4096, 48, 8), BF16, ["flash_fwd"]),
    "flash_prefill_laguna_8192": (
        flash_prefill, _prefill_call(8192, 48, 8), BF16, ["flash_fwd_stream"]),
    "flash_prefill_solar_open2_4096": (
        flash_prefill, _prefill_call(4096, 64, 8), BF16, ["flash_fwd"]),
    "flash_prefill_nemotron_h_1024": (
        flash_prefill, _prefill_call(1024, 32, 2), BF16, ["flash_fwd"]),
    "flash_prefill_olmo_hybrid_256": (
        flash_prefill, _prefill_call(256, 30, 30), BF16, ["flash_fwd"]),
    "flash_fused_bwd_bf16_3072x64": (
        _flash_fwd_bwd, QKV_3K_D64, BF16, ["flash_fwd", "flash_bwd"],
    ),
    "flash_split_bwd_f32": (
        _flash_fwd_bwd, QKV_2K, F32,
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"],
    ),
    "flash_streamed_16k_fwd_bwd": (
        _flash_fwd_bwd, QKV_16K, BF16,
        ["flash_fwd_stream", "flash_bwd_dq_stream", "flash_bwd_dkv_stream"],
    ),
    "fused_ce_lm_16384x32768": (
        _ce_fwd_bwd, [((16384, 32768), F32), ((16384,), I32)], F32,
        ["fused_ce_fwd", "fused_ce_bwd"],
    ),
    "fused_ce_resnet_128x1000": (
        _ce_fwd_bwd, [((128, 1000), F32), ((128,), I32)], F32,
        ["fused_ce_fwd", "fused_ce_bwd"],
    ),
    "add_layernorm_16384x1024": (
        _add_ln,
        [((16384, 1024), None), ((16384, 1024), None),
         ((1024,), F32), ((1024,), F32)],
        BF16, ["fused_add_ln"],
    ),
    "bias_gelu_16384x4096": (
        _bias_gelu, [((16384, 4096), None), ((4096,), None)], BF16,
        ["fused_bias_gelu"],
    ),
    # lm271m.serve.steady: 8 slots, tables of 80 blocks, one K/V head a head
    "paged_decode_lm271m": (
        _paged_decode, _decode_call(8, 1, 1024, 80), BF16, ["paged_decode"],
    ),
    # solar-open2-250b.serve.long32: 32 slots x 288 blocks, 8 heads a K/V head
    "paged_decode_solar_open2": (
        _paged_decode, _decode_call(32, 8, 9216, 288), BF16, ["paged_decode"],
    ),
    # nemotron-3-super-120b.serve.burst32: 32 slots x 96 blocks, 2 K/V heads
    # read by groups of 16 query heads
    "paged_decode_nemotron_h": (
        _paged_decode, _decode_call(32, 16, 4096, 96, kv_heads=2), BF16,
        ["paged_decode"],
    ),
    # olmo-hybrid-7b.serve.reason32: 32 slots x 192 blocks, 30 K/V heads
    # stored as the 32 of four sublane tiles (the kernel is refused a block
    # of 30: "slice shape must be aligned to tiling (8)")
    "paged_decode_olmo_hybrid": (
        _paged_decode, _decode_call(32, 1, 4608, 192, kv_heads=32), BF16,
        ["paged_decode"],
    ),
    # config/serve-laguna-xs2.yml, a full layer: 32 slots x 544 blocks, 48 query
    # heads in groups of 6
    "paged_decode_laguna_full": (
        _paged_decode, _decode_call(32, 6, 17408, 544), BF16, ["paged_decode"],
    ),
    # ... and a window layer: the SAME kernel over the ring, 32 slots of 32
    # blocks (512 positions), 64 query heads in groups of 8
    "paged_decode_laguna_ring": (
        _paged_decode, _decode_call(32, 8, 1024, 32), BF16, ["paged_decode"],
    ),
    "paged_decode_f32": (
        _paged_decode, _decode_call(8, 1, 1024, 80), F32, ["paged_decode"],
    ),
    # deepseek-v2-lite.serve.steady32: 32 slots x 160 blocks, 16 heads over
    # the ONE latent row a position, 512 + 64 lanes in a row of five lane
    # tiles, 6,144 blocks of 16
    "mla_paged_decode_deepseek_v2_lite": (
        _mla_paged_decode,
        [((32, 16, 512), None), ((32, 16, 64), None), ((6144, 16, 640), None),
         ((32, 160), I32), ((32,), I32)],
        BF16, ["mla_paged_decode"],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, name):
    import re

    fn, arg_specs, dtype, kernels = CASES[name]
    args = [
        jax.ShapeDtypeStruct(shape, dt or dtype, sharding=chip)
        for shape, dt in arg_specs
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the kernel's name is the component of the call's op_name before
    # ``pallas_call``, bare under a scope (``.../loss_head/fused_ce_bwd/
    # pallas_call``) or wrapped where it is the outermost one
    # (``transpose(jvp(fused_ce_bwd))/pallas_call``); XLA names the
    # instruction after that component (``%fused_ce_bwd.1`` or
    # ``%transpose_jvp_fused_ce_bwd__.1``), and benchmark/xplane.py reads both
    mosaic = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op_name = re.search(r'op_name="([^"]*)/pallas_call"', line).group(1)
        kernel = re.sub(r"\w+\(|\)", "", op_name.split("/")[-1])
        assert kernel in line.split(" = ")[0], line[:120]
        mosaic.append(kernel)
    assert sorted(mosaic) == sorted(kernels), (
        f"{name}: the compiled program's Mosaic calls are named {mosaic}"
    )


EXPERT_LAYERS = {
    # DeepSeek-V2-Lite: 64 SwiGLU experts of 2048 x 1408, six a token
    "deepseek_v2_lite": (2048, dict(
        num_experts=64, top_k=6, hidden=1408, shared_hidden=2816)),
    # Nemotron-3-Super: 128 of 512 relu2 experts of 1024 x 2688 in a latent,
    # 22 a token, a shared expert of 5376 at the full width
    "nemotron_h_latent": (4096, dict(
        num_experts=512, top_k=22, hidden=2688, shared_hidden=5376,
        experts_held=(0, 128), scoring="sigmoid", activation="relu2",
        latent=1024, norm_topk_prob=True, routed_scaling_factor=5.0)),
}


@pytest.mark.parametrize("tokens", [32, 2048], ids=["decode_32_rows", "prefill_2048"])
@pytest.mark.parametrize("family", sorted(EXPERT_LAYERS))
def test_grouped_products_of_the_expert_layer_compile_for_v5e(
        chip, family, tokens, monkeypatch):
    """The dropless expert layer at the served widths: its two grouped
    products are the megablox Pallas kernel, under the scope the
    benchmark's readers look for."""
    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.ops.moe import DroplessMoE

    # the layer asks ``jax.default_backend()``, which is the CPU here
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    dim, form = EXPERT_LAYERS[family]
    layer = DroplessMoE(dim=dim, dtype=BF16, **form)
    x = jax.ShapeDtypeStruct((tokens, dim), BF16, sharding=chip)
    shapes = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((8, dim), BF16)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), shapes)
    text = jax.jit(layer.apply).lower(params, x).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all("/moe_gmm/" in line and "pallas_call" in line for line in calls)


def test_decode_step_reads_the_pool_where_it_lies(chip, monkeypatch):
    """Two layers of the 271M LM at its serving widths (8 slots, 1,024
    blocks of 16, tables of 80): the compiled decode step holds the paged
    kernel under the layer's scope, no array of a whole table's rows
    (``[8, 1280, 8, 128]``, what the gather arm builds), and updates the
    whole pool in place."""
    import re

    import numpy as np

    from pytorch_distributed_training_tpu.models.transformer_lm import TransformerLM
    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    # the routing asks ``jax.default_backend()``, which is the CPU here
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    model = TransformerLM(vocab_size=32768, max_len=2048, embed_dim=1024,
                          depth=2, num_heads=8, dtype=BF16)
    fns = build_paged_fns(model, 16, 1024)
    slots, table = 8, 80
    on_chip = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32)))["params"]
    pool = jax.eval_shape(fns.init_pool, shapes)
    keys = jax.eval_shape(lambda: jnp.stack([jax.random.PRNGKey(0)] * slots))
    row = jax.ShapeDtypeStruct((slots,), I32)
    mask = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    args = jax.tree.map(on_chip, (
        shapes, pool, row, mask, row, row,
        jax.ShapeDtypeStruct((slots, table), I32), keys, row, row))
    compiled = fns.decode_step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    assert all(re.search(r"/attn/paged_attention/[^\"]*paged_decode/pallas_call", line)
               for line in calls)
    assert not re.search(r"\[8,1280,8,128\]", text)
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                     for s in jax.tree.leaves(pool))
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes == 4 * 2 ** 25


def _state_layers():
    from pytorch_distributed_training_tpu.ops.gated_delta import GatedDeltaNet
    from pytorch_distributed_training_tpu.ops.kda import KimiDeltaAttention
    from pytorch_distributed_training_tpu.ops.mamba2 import Mamba2Mixer

    return {
        # olmo-hybrid-7b.serve.reason32: a [30, 96, 192] state a slot
        "gated_delta": GatedDeltaNet(
            num_heads=30, key_dim=96, value_dim=192, dtype=BF16, decode=True,
            state_slots=32),
        # solar-open2-250b.serve.long32: a [64, 128, 128] state a slot
        "kda": KimiDeltaAttention(
            num_heads=64, head_dim=128, dtype=BF16, decode=True, state_slots=32),
        # nemotron-3-super-120b.serve.burst32: a [128, 64, 128] state a slot
        "mamba2": Mamba2Mixer(
            num_heads=128, head_dim=64, n_groups=8, state_size=128, dtype=BF16,
            decode=True, state_slots=32),
    }


@pytest.mark.parametrize("family", ["kda", "mamba2", "gated_delta"])
def test_the_state_s_decode_step_walks_its_leaf_in_place(chip, family):
    """One state-carrying layer at its published widths, 32 slots, the
    aligned decode step with its cache donated: the compiled program writes
    both leaves where they lie (all their bytes aliased), holds no copy of
    the 134 MB (Gated DeltaNet: 71 MB) state leaf, and its temporaries are a
    twentieth of it: both
    ``while``s of ``ops/state_rows.py`` (the walk of the live rows, the one
    dense trip past half the slots live) carry the leaf and write into it."""
    import re

    import numpy as np

    layer = _state_layers()[family]
    slots, dim = 32, 4096
    x = jnp.zeros((slots, 1, dim), BF16)
    pos, rows = jnp.zeros((slots, 1), I32), jnp.zeros((slots,), I32)
    shapes = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), x, pos, rows, True))

    def step(params, cache, x, pos, rows):
        y, changed = layer.apply(
            {"params": params, "cache": cache}, x, pos, rows,
            rows_are_slots=True, mutable=["cache"])
        return y, changed["cache"]

    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (shapes["params"], shapes["cache"], x, pos, rows))
    compiled = jax.jit(step, donate_argnums=1).lower(*args).compile()
    nbytes = lambda s: int(np.prod(s.shape)) * s.dtype.itemsize  # noqa: E731
    # as the device lays a leaf out: its last axis in whole tiles of 128
    # lanes (the 192 of a Gated DeltaNet state take 256: a third more)
    laid_out = lambda s: nbytes(s) // s.shape[-1] * (-(-s.shape[-1] // 128) * 128)  # noqa: E731
    leaves = jax.tree.leaves(shapes["cache"])
    account = compiled.memory_analysis()
    assert account.alias_size_in_bytes == sum(map(laid_out, leaves))
    state = max(leaves, key=nbytes)
    assert state.dtype == jnp.float32 and state.shape[0] == slots
    assert account.temp_size_in_bytes < nbytes(state) // 20
    leaf = "f32[" + ",".join(map(str, state.shape)) + "]"
    assert not re.search(re.escape(leaf) + r"\{[^}]*\} copy\(", compiled.as_text())


def copies_by_operand(text):
    """Every ``copy`` of a compiled program's text (``compiled.as_text()``)
    as ``(name, operand's shape and layout, result's shape and layout)``:
    whose array a ``_copy.N`` line of a trace moves, and from which layout
    into which.  ``bf16[98304,576]{0,1:T(8,128)(2,1)}`` is a leaf that lies
    transposed (its first axis minor), ``{1,0:...}`` one that lies in rows."""
    import re

    array = r"\w+\[[\d,]*\](?:\{[^}]*\})?"
    named = re.compile(rf"\s*(?:ROOT )?%?([\w.\-]+) = ({array})")
    shapes = dict(m.groups() for m in map(named.match, text.splitlines()) if m)
    copy = re.compile(named.pattern + rf" copy\((?:{array} )?%?([\w.\-]+)\)")
    return [(m[1], shapes.get(m[3], "?"), m[2])
            for m in map(copy.match, text.splitlines()) if m]


def test_copies_are_listed_by_their_operand():
    text = """
  %p.1 = bf16[64,576]{0,1:T(8,128)(2,1)} parameter(0)
  %copy.7 = bf16[64,576]{1,0:T(8,128)(2,1)} copy(%p.1), sharding={replicated}
  %fusion.2 = bf16[64,576]{1,0:T(8,128)(2,1)} fusion(%copy.7, %q), kind=kLoop
  ROOT %copy.9 = bf16[64,576]{0,1:T(8,128)(2,1)} copy(bf16[64,576]{1,0:T(8,128)(2,1)} %fusion.2)
  %copy-start.1 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%r)
"""
    rows, turned = "bf16[64,576]{1,0:T(8,128)(2,1)}", "bf16[64,576]{0,1:T(8,128)(2,1)}"
    assert copies_by_operand(text) == [
        ("copy.7", turned, rows), ("copy.9", rows, turned)]


# deepseek-v2-lite.serve.steady32: a decode call of 32 slots x 160 blocks,
# and a 1 x 512 prefill (its table the prompt's own 32 blocks: over the 160
# of a slot the expanded form's float32 scores, [16, 512, 2560], are 84 MB
# of temporaries and would drown what is asked about)
@pytest.mark.parametrize("rows,positions,table,kernels", [
    (32, 1, 160, ["mla_paged_decode"]), (1, 512, 32, [])],
    ids=["decode_32_rows", "prefill_1x512"])
def test_the_latent_leaf_lies_in_rows_and_no_program_turns_it(
        chip, monkeypatch, rows, positions, table, kernels):
    """One latent attention layer at the served widths over the leaf as
    served (``[6144 x 16, lanes_up(512 + 64)]`` bfloat16, donated): the
    scatter and the kernel of a decode call, the scatter and the block gather
    of a prefill.  The leaf's parameter and result are row-major, no ``copy``
    has an operand of the leaf's shape, and the temporaries are a fraction of
    the leaf.  With a last axis of 576 (4.5 lane tiles) the compiler laid the
    leaf out transposed and every program turned all 113 MB of it round in
    front of the scatter and back in front of the output, a layer."""
    import re

    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.ops.mla import MLAttention
    from pytorch_distributed_training_tpu.ops.mla_paged_decode import lanes_up

    # the routing asks ``jax.default_backend()``, which is the CPU here
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    layer = MLAttention(
        num_heads=16, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, dtype=BF16, decode=True, paged=True, kv_block_size=16,
        kv_num_blocks=6144)
    x = jnp.zeros((rows, positions, 2048), BF16)
    pos, tables = jnp.zeros((rows, positions), I32), jnp.zeros((rows, table), I32)
    shapes = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x, pos, tables))
    (leaf,) = jax.tree.leaves(shapes["cache"])
    assert (leaf.shape, leaf.dtype) == ((6144 * 16, lanes_up(576)), BF16)

    def call(params, cache, x, pos, tables):
        y, changed = layer.apply(
            {"params": params, "cache": cache}, x, pos, tables, mutable=["cache"])
        return y, changed["cache"]

    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (shapes["params"], shapes["cache"], x, pos, tables))
    compiled = jax.jit(call, donate_argnums=1).lower(*args).compile()
    text = compiled.as_text()
    assert [re.search(r'op_name="[^"]*?(\w+)/pallas_call"', line).group(1)
            for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line] == kernels
    dims = "bf16[" + ",".join(map(str, leaf.shape)) + "]"
    header = text.split("entry_computation_layout=")[1].split("\n")[0]
    at_rest = re.findall(re.escape(dims) + r"\{([\d,]+)", header)
    assert at_rest == ["1,0", "1,0"]  # the parameter and the result
    assert not [c for c in copies_by_operand(text) if c[1].startswith(dims)]
    account = compiled.memory_analysis()
    assert account.alias_size_in_bytes == leaf.shape[0] * leaf.shape[1] * 2
    assert account.temp_size_in_bytes < 32 * 2 ** 20


def _arrays(text, dtype, last):
    """The distinct ``dtype[..., last]`` arrays of three or more axes in a
    compiled program's text."""
    import re

    return sorted(set(re.findall(rf"{dtype}\[(?:\d+,){{2,}}{last}\]", text)))


@pytest.mark.parametrize("whole_prompts", [True, False], ids=["flash_arm", "gather_arm"])
def test_a_whole_prompt_call_of_a_full_layer_builds_no_scores(chip, monkeypatch, whole_prompts):
    """One full-attention layer of Laguna at the served widths (48 query
    heads of 128 over 8, a pool of 17,408 blocks of 16, donated) on a 1 x
    4,096 prefill call.  Told that the call holds whole prompts, the layer
    writes the pool and scores through the flash forward: ONE kernel, no
    float32 ``[.., 256, 4096]`` scores, no gathered copy of the table's rows,
    and temporaries under the gather arm's scores alone; not told (a call that may start
    past position 0), it is the gather arm as it was."""
    import re

    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.ops.attention import GroupedQueryAttention

    # the routing asks ``jax.default_backend()``, which is the CPU here
    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    positions, blocks = 4096, 17408
    layer = GroupedQueryAttention(
        num_heads=48, num_kv_heads=8, head_dim=128, gate="head", query_block=256,
        dtype=BF16, decode=True, paged=True, kv_block_size=16, kv_num_blocks=blocks,
        whole_prompts=whole_prompts)
    x = jnp.zeros((1, positions, 2048), BF16)
    # the gather arm over the call's own 256 blocks, as the parent cut them
    pos, tables = jnp.zeros((1, positions), I32), jnp.zeros((1, positions // 16), I32)
    shapes = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x, pos, tables))

    def call(params, cache, x, pos, tables):
        y, changed = layer.apply(
            {"params": params, "cache": cache}, x, pos, tables, mutable=["cache"])
        return y, changed["cache"]

    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (shapes["params"], shapes["cache"], x, pos, tables))
    compiled = jax.jit(call, donate_argnums=1).lower(*args).compile()
    text = compiled.as_text()
    kernels = [re.search(r'op_name="[^"]*?(\w+)/pallas_call"', line).group(1)
               for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    account = compiled.memory_analysis()
    assert account.alias_size_in_bytes == 2 * blocks * 16 * 8 * 128 * 2
    scores = _arrays(text, "f32", positions)
    gathered = _arrays(text, "bf16", "8,128")  # [1, 256, 16, 8, 128] and the like
    if whole_prompts:
        assert kernels == ["flash_fwd"] and not scores
        assert not [a for a in gathered if ",256,16," in a]
        # 101 MB (q, k, v, their folds, the output): under the [8, 6, 256,
        # 4096] float32 scores alone
        assert account.temp_size_in_bytes < 8 * 6 * 256 * 4096 * 4
    else:
        assert kernels == [] and "f32[1,8,6,256,4096]" in scores
        assert account.temp_size_in_bytes > 8 * 6 * 256 * 4096 * 4  # 253 MB


def test_laguna_s_prefill_program_scores_through_the_flash_forward(chip, monkeypatch):
    """The first five layers of ``config/serve-laguna-xs2.yml`` (full, three
    window layers, full; one dense and four expert layers) as the paged
    build compiles a 1 x 4,096 prefill: the flash forward once a full layer
    and nowhere else, no float32 scores of a full layer, the whole cache
    tree updated in place.  The program's temporaries are the window layers'
    band and the expert layers', which stand: 472.9 MB for the parent's
    471.4 (and 2.090 GB for 2.077 over the whole 17 layers at 1 x 8,192)."""
    import re

    import numpy as np
    import yaml

    from pytorch_distributed_training_tpu.models import get_model
    from pytorch_distributed_training_tpu.ops import flash_attention as gate
    from pytorch_distributed_training_tpu.serving.decode import build_paged_fns

    monkeypatch.setattr(gate, "flash_enabled", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "config", "serve-laguna-xs2.yml")) as f:
        keys = dict(yaml.safe_load(f)["model"], num_hidden_layers=5)
    keys.pop("name")
    model = get_model("Laguna", num_classes=100352, dtype=BF16, **keys)
    positions, table = 4096, 544
    fns = build_paged_fns(model, 16, 17408, state_slots=32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), I32)))["params"]
    pool = jax.eval_shape(fns.init_pool, shapes)
    row = jax.ShapeDtypeStruct((1,), I32)
    call = jax.ShapeDtypeStruct((1, positions), I32)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
        (shapes, pool, call, call, jax.ShapeDtypeStruct((1, table), I32), row,
         jax.ShapeDtypeStruct((1, 2), jnp.uint32), row, row, row))
    compiled = fns.prefill.lower(*args).compile()
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "flash_fwd" in line]
    assert len(flash) == 2
    assert all(re.search(r"/layer[04]/attn/gqa_attention/full_attention/", line)
               for line in flash)
    assert not [a for a in _arrays(text, "f32", positions) if ",256," in a]
    account = compiled.memory_analysis()
    assert account.alias_size_in_bytes == sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(pool))
    assert account.temp_size_in_bytes < 480 * 10 ** 6
