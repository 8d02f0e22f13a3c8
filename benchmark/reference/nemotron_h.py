"""Plain reference of the Nemotron-H decoder (``model_type: nemotron_h``,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json):
a stack whose every layer is ONE mixer behind one norm, chosen a layer by
``hybrid_override_pattern``: ``M`` a Mamba-2 state-space layer, ``E`` latent
experts, ``*`` grouped-query softmax attention.  Written from the equations;
imports nothing of the program and takes nothing the program made: the
weights come from ``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``: no
cache, no batching, no chunks (the state-space recurrence runs ONE POSITION
AT A TIME in ``lax.scan``, so it owes nothing to the program's chunked form),
attention in blocks of query rows only so that 1,536 positions fit, and every
held expert applied to every token under a mask of its gates (one expert at a
time, so that the float32 copy of its weights fits and the loop compiles
once).

The equations (config keys in brackets), layer ``i`` of [num_hidden_layers]:
  x <- x + Mixer_i(RMSNorm(x));  RMSNorm(x) = x rsqrt(mean(x^2) + eps) w,
  eps = [layer_norm_epsilon]; no bias anywhere but the convolution's
  [use_conv_bias]; the residual in the model's own precision
  [residual_in_fp32 false].  Mixer_i by [hybrid_override_pattern][i].
  M (Mamba-2): H = [mamba_num_heads] heads of P = [mamba_head_dim], I = H P,
       G = [n_groups] groups of B and C, N = [ssm_state_size]:
       [z | xBC | dt] = x W_in         (I | I + 2 G N | H columns)
       xBC_t <- silu(sum_{j=0..k-1} w_j * xBC_{t-(k-1)+j} + b)   (depthwise,
       causal, k = [conv_kernel] taps; rows before the sequence are zero)
       [x | B | C] = xBC  (I | G N | G N);  head h reads group h // (H / G)
       D_t = softplus(dt_t + dt_bias)              (a head; not clamped above)
       a_t = exp(-exp(A_log) D_t)                  (ONE scalar a head)
       S_t = a_t S_{t-1} + D_t x_t B_t^T           ([P, N] a head, float32, S_0 = 0)
       y_t = S_t C_t + D_skip x_t
       out = W_out ( RMSNorm_{groups of I / G}(y * silu(z)) * w_gate_norm )
  E (latent experts): s = sigmoid(x W_r) in float32 over all
       [n_routed_experts]; the [num_experts_per_tok] experts are the largest
       of s + b_corr ([n_group] 1, [topk_group] 1: no group limit); the gates
       are s ITSELF at the chosen, over their sum [norm_topk_prob], times
       [routed_scaling_factor]: the correction bias moves the choice and not
       the gate.  u = x W_down ([hidden_size] -> [moe_latent_size]);
       r = sum_{chosen and held} g_k relu(u W1_k)^2 W2_k
           (latent -> [moe_intermediate_size] -> latent; [mlp_hidden_act] relu2,
           not gated: one up product);
       y = r W_up (latent -> hidden) + relu(x V1)^2 V2
           (hidden -> [moe_shared_expert_intermediate_size] -> hidden).
       The router reads the full width; only the dispatched rows are latent.
       No capacity, no drop.  Only the HELD experts exist here
       (``experts_held``: this chip's share of an expert-parallel layer); what
       the absent ones would have added is left out, as in the program.
  * (attention): q = x W_q -> [num_attention_heads, head_dim]; k, v ->
       [num_key_value_heads, head_dim]; query head h reads K/V head
       h // (heads / kv heads); scores q.k / sqrt(head_dim), causal softmax,
       y = (P v) W_o.  No gate, no bias, NO position term (assumed: the
       ``nemotron_h`` attention block applies none although the config
       carries ``rope_theta``).
  after the last layer RMSNorm, then logits = x W_head (untied, no bias).

Not written: the multi-token-prediction module ([num_nextn_predict_layers],
[mtp_hybrid_override_pattern]): the config does not fix its equations and
plain serving never evaluates it.  A ``-`` layer (a dense relu2 MLP of
[intermediate_size]) does not occur in the published pattern and is refused.
Each reading of the config that is an inference stands under ``assumed`` in
the configuration file.  The published model runs in bfloat16 and rounds
after every operation; the reference keeps float32 throughout.

``mode`` chooses the arithmetic, for the controls only:
  ``f32``         the reference itself;
  ``bf16``        matmul operands rounded to bfloat16 (what the configuration
                  states);
  ``int8``        matmul operands fake-quantised to int8 (per-row symmetric):
                  the nearest precision below the one the configuration states;
  ``bf16_state``  the reference, but the Mamba state rounded to bfloat16 after
                  every position: what a cache that kept it in bfloat16 gives.

Parameter layout ("reference layout"): ``tok_emb [V,D]``, ``head_w [D,V]``,
``norm_w [D]``, ``layers`` (a list, one dict a layer) and ``arch`` (the sizes
that no shape gives).  Every layer holds ``norm [D]``; an ``M`` layer
``in_proj [D, 2I+2GN+H]``, ``conv_w [k, I+2GN]``, ``conv_b [I+2GN]``,
``dt_bias [H]``, ``a_log [H]``, ``d_skip [H]``, ``gate_norm [I]``,
``out_proj [I,D]``; an ``E`` layer ``router [D,E]``, ``e_bias [E]``
(float32), ``lat_down [D,L]``, ``e_up [held,L,M]``, ``e_down [held,M,L]``,
``lat_up [L,D]``, ``s_up [D,SM]``, ``s_down [SM,D]``; a ``*`` layer ``wq
[D,H*hd]``, ``wk``, ``wv [D,Hkv*hd]``, ``wo [H*hd,D]``.  Every weight but
``e_bias`` is a bfloat16 array whose values were drawn in float32 and rounded
once (``e_bias`` holds such values in float32), so that the program (which
holds bfloat16) and the reference (which upcasts) start from the same
numbers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "int8", "bf16_state")
KINDS = "ME*"


class Arch(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_groups: int
    ssm_state: int
    taps: int
    top_k: int
    held_first: int
    held: int
    rms_eps: float
    routed_scaling: float
    pad_to: int
    query_block: int


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys (they lie at the file's top level, under the names of the source;
    the experts' total and the share held are what ``serve.model`` runs)."""
    c = config
    for key, want in (
            ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"),
            ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
            ("residual_in_fp32", False), ("tie_word_embeddings", False),
            ("use_conv_bias", True), ("n_shared_experts", 1),
            ("attention_bias", False), ("mlp_bias", False),
            ("mamba_proj_bias", False), ("use_bias", False),
            ("sliding_window", None)):
        if c.get(key, want) != want:
            raise ValueError(f"the reference does not write {key}: {c[key]!r}")
    layers = int(c["num_hidden_layers"])
    pattern = str(c["hybrid_override_pattern"])[:layers]
    if len(pattern) != layers or set(pattern) - set(KINDS):
        raise ValueError(
            f"the reference writes the layers {KINDS!r}; the first {layers} of "
            f"hybrid_override_pattern are {pattern!r}")
    heads, p = int(c["mamba_num_heads"]), int(c["mamba_head_dim"])
    if heads * p != int(c["expand"]) * int(c["hidden_size"]):
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x hidden_size")
    model = c.get("serve", {}).get("model", {})
    total = int(model.get("n_routed_experts", c["n_routed_experts"]))
    first, held = model.get("experts_held") or (0, total)
    if int(c["n_routed_experts"]) != held:
        raise ValueError(
            f"the file's n_routed_experts ({c['n_routed_experts']}) counts the "
            f"experts held, but serve.model holds {held}")
    arch = Arch(
        heads=int(c["num_attention_heads"]), kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), ssm_heads=heads, ssm_head_dim=p,
        ssm_groups=int(c["n_groups"]), ssm_state=int(c["ssm_state_size"]),
        taps=int(c["conv_kernel"]), top_k=int(c["num_experts_per_tok"]),
        held_first=int(first), held=int(held),
        rms_eps=float(c["layer_norm_epsilon"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        # every sequence is padded to a multiple of this: a configuration
        # gives its longest (bucket + new tokens), so that ONE shape compiles
        pad_to=int(c.get("reference_pad_to", 256)),
        query_block=int(c.get("reference_query_block", 512)),
    )
    assumed = c["assumed"]
    return {
        "H": arch.heads, "arch": arch, "V": int(c["vocab_size"]),
        "D": int(c["hidden_size"]), "L": layers, "pattern": pattern,
        "M": int(c["moe_intermediate_size"]), "LAT": int(c["moe_latent_size"]),
        "SM": int(c["moe_shared_expert_intermediate_size"]), "E": total,
        "router_std": float(assumed["router_logit_std"]),
        "bias_std": float(assumed["correction_bias_std"]),
        "dt_range": (float(c["time_step_min"]), float(c["time_step_max"]),
                     float(c["time_step_floor"])),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


def _uniform(key, shape, low, high):
    return jax.random.uniform(key, shape, jnp.float32, low, high)


@functools.partial(jax.jit, static_argnames=(
    "kind", "D", "M", "LAT", "SM", "E", "arch", "dt_range"))
def _make_layer(key, router_std, bias_std, *, kind, D, M, LAT, SM, E, arch, dt_range):
    k = iter(jax.random.split(key, 16))
    a = arch
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layer = {"norm": ones((D,))}
    if kind == "M":
        inner = a.ssm_heads * a.ssm_head_dim
        conv = inner + 2 * a.ssm_groups * a.ssm_state
        # assumed: the public Mamba-2 initialisation.  A = exp(A_log) uniform
        # in (1, 16) a head; dt_bias the inverse softplus of a step drawn
        # log-uniform in (time_step_min, time_step_max), not below
        # time_step_floor: a = exp(-A softplus(z + dt_bias)) with z of
        # deviation about 1 spreads from about 0.2 to 0.999, so the state
        # neither vanishes nor saturates within 1,536 positions
        low, high, floor = dt_range
        step = jnp.maximum(
            jnp.exp(_uniform(next(k), (a.ssm_heads,), np.log(low), np.log(high))),
            floor)
        layer.update(
            in_proj=_normal(next(k), (D, 2 * inner + 2 * a.ssm_groups * a.ssm_state
                                      + a.ssm_heads), D ** -0.5),
            conv_w=_normal(next(k), (a.taps, conv), a.taps ** -0.5),
            conv_b=_normal(next(k), (conv,), 0.1),
            dt_bias=jnp.log(jnp.expm1(step)).astype(jnp.bfloat16),
            a_log=jnp.log(_uniform(next(k), (a.ssm_heads,), 1.0, 16.0)).astype(
                jnp.bfloat16),
            d_skip=ones((a.ssm_heads,)),
            gate_norm=ones((inner,)),
            out_proj=_normal(next(k), (inner, D), inner ** -0.5),
        )
    elif kind == "E":
        layer.update(
            # assumed: a router whose logits spread (standard deviation
            # ``router_std`` on a unit-RMS input) and a correction bias of
            # deviation ``bias_std``, so that routing counts and the bias
            # moves some choices
            router=_normal(next(k), (D, E), router_std * D ** -0.5),
            e_bias=_normal(next(k), (E,), bias_std).astype(jnp.float32),
            lat_down=_normal(next(k), (D, LAT), D ** -0.5),
            e_up=_normal(next(k), (a.held, LAT, M), LAT ** -0.5),
            e_down=_normal(next(k), (a.held, M, LAT), M ** -0.5),
            lat_up=_normal(next(k), (LAT, D), LAT ** -0.5),
            s_up=_normal(next(k), (D, SM), D ** -0.5),
            s_down=_normal(next(k), (SM, D), SM ** -0.5),
        )
    else:
        hq, hkv = a.heads * a.head_dim, a.kv_heads * a.head_dim
        layer.update(
            wq=_normal(next(k), (D, hq), D ** -0.5),
            wk=_normal(next(k), (D, hkv), D ** -0.5),
            wv=_normal(next(k), (D, hkv), D ** -0.5),
            wo=_normal(next(k), (hq, D), hq ** -0.5),
        )
    return layer


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, one jitted call a layer:
    embeddings N(0, 0.02), matrices N(0, 1/fan_in), norms and ``D_skip`` 1,
    the router N(0, router_std^2/fan_in), the correction bias N(0,
    bias_std^2), the convolution's bias of deviation 0.1, decays as
    :func:`_make_layer` says, each drawn in float32 and rounded once to
    bfloat16."""
    key = seed_key(seed)
    shape = {k: sizes[k] for k in ("D", "M", "LAT", "SM", "E", "arch", "dt_range")}
    layers = [
        _make_layer(jax.random.fold_in(key, i), sizes["router_std"],
                    sizes["bias_std"], kind=kind, **shape)
        for i, kind in enumerate(sizes["pattern"])
    ]
    top = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {
        "tok_emb": _normal(top[0], (sizes["V"], sizes["D"]), 0.02),
        "head_w": _normal(top[1], (sizes["D"], sizes["V"]), sizes["D"] ** -0.5),
        "norm_w": jnp.ones((sizes["D"],), jnp.bfloat16),
        "layers": layers,
        "arch": {name: np.asarray(value)
                 for name, value in sizes["arch"]._asdict().items()},
    }


def arch_of(params: dict) -> Arch:
    """The sizes that travel with the weights, as static python numbers."""
    kinds = get_type_hints(Arch)
    return Arch(**{
        name: kinds[name](np.asarray(value)) for name, value in params["arch"].items()
    })


def kind_of(layer: dict) -> str:
    return "M" if "in_proj" in layer else "E" if "router" in layer else "*"


# ------------------------------------------------------------ layout bridge

def to_checkpoint_tree(params: dict) -> dict:
    """Reference layout -> the parameter tree of the program's checkpoint
    format: one ``layer{i}`` subtree a layer holding its ``norm`` and ONE of
    ``mamba`` / ``moe`` / ``attn``, as the program's documented layout has
    them."""
    def host(x):
        return np.asarray(x)

    tree = {
        "tok_embedding": host(params["tok_emb"]),
        "norm": {"scale": host(params["norm_w"])},
        "head": {"kernel": host(params["head_w"])},
    }
    names = {
        "M": ("mamba", {
            "in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
            "dt_bias": "dt_bias", "A_log": "a_log", "D": "d_skip",
            "norm": "gate_norm", "out_proj": "out_proj"}),
        "E": ("moe", {
            "router": "router", "e_score_correction_bias": "e_bias",
            "latent_down": "lat_down", "w_up": "e_up", "w_down": "e_down",
            "latent_up": "lat_up", "shared_up": "s_up", "shared_down": "s_down"}),
        "*": ("attn", {n: n for n in ("wq", "wk", "wv", "wo")}),
    }
    for i, p in enumerate(params["layers"]):
        module, leaves = names[kind_of(p)]
        tree[f"layer{i}"] = {
            "norm": {"scale": host(p["norm"])},
            module: {theirs: host(p[mine]) for theirs, mine in leaves.items()},
        }
    return tree


# ------------------------------------------------------------------ forward

def _to_bf16(x):
    """Float32 values rounded to bfloat16's 8 bits of mantissa, still
    float32.  ``reduce_precision`` and not ``astype`` there and back: the
    TPU's compiler may drop such a pair of converts as excess precision (it
    did: a state "kept in bfloat16" by ``astype`` read a logit distance of
    exactly 0.0 on the chip, PERF.md PR 34), and a control must round."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _fake_int8(x, axis):
    """Symmetric int8 fake quantisation along ``axis``: 127 levels either
    side of zero, the scale from the largest magnitude."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x, w, mode):
    """``x [..., K] @ w [K, N]`` in the arithmetic ``mode`` names."""
    if mode == "bf16":
        return jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _einsum(spec, a, b, mode):
    """The two attention products, in the arithmetic ``mode`` names (both
    contract their operands' last axis)."""
    if mode == "bf16":
        a, b = _to_bf16(a), _to_bf16(b)
    elif mode == "int8":
        a, b = _fake_int8(a, -1), _fake_int8(b, -1)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _attention(x, p, arch: Arch, mode):
    a = arch
    s = x.shape[0]
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    group = a.heads // a.kv_heads
    q = _mm(x, up("wq"), mode).reshape(s, a.kv_heads, group, a.head_dim)
    k = _mm(x, up("wk"), mode).reshape(s, a.kv_heads, a.head_dim)
    v = _mm(x, up("wv"), mode).reshape(s, a.kv_heads, a.head_dim)
    block = min(a.query_block, s)
    if s % block:
        raise ValueError(f"{s} positions are no multiple of the query block {block}")

    def rows(args):
        q_rows, first = args  # [block, Hkv, G, hd], the block's first position
        scores = _einsum("qhgd,khd->hgqk", q_rows, k, mode) * a.head_dim ** -0.5
        seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _einsum("hgqk,hdk->qhgd", probs, jnp.moveaxis(v, 0, -1), mode)

    out = jax.lax.map(rows, (
        q.reshape(s // block, block, a.kv_heads, group, a.head_dim),
        jnp.arange(0, s, block),
    )).reshape(s, a.heads * a.head_dim)
    return _mm(out, up("wo"), mode)


def _mamba(x, p, arch: Arch, mode, decay=True):
    a = arch
    s, h, hp, g, n = x.shape[0], a.ssm_heads, a.ssm_head_dim, a.ssm_groups, a.ssm_state
    inner = h * hp
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    proj = _mm(x, up("in_proj"), mode)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * n], axis=-1)
    taps_w = up("conv_w")
    padded = jnp.pad(xbc, ((a.taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(taps_w[j] * padded[j:j + s] for j in range(a.taps)) + up("conv_b"))
    xs = xbc[:, :inner].reshape(s, h, hp)
    b_in = xbc[:, inner:inner + g * n].reshape(s, g, n)
    c_out = xbc[:, inner + g * n:].reshape(s, g, n)
    # head h reads group h // (H / G)
    b_in = jnp.repeat(b_in, h // g, axis=1)
    c_out = jnp.repeat(c_out, h // g, axis=1)
    dt = jax.nn.softplus(dt + up("dt_bias"))                      # [S, H]
    keep = jnp.exp(-jnp.exp(up("a_log")) * dt) if decay else jnp.ones_like(dt)

    def position(state, step):
        x_t, b_t, c_t, dt_t, a_t = step  # [H, P], [H, N], [H, N], [H], [H]
        state = a_t[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.einsum("hpn,hn->hp", state, c_t, precision=HIGHEST)
        if mode == "bf16_state":
            state = _to_bf16(state)
        return state, y_t

    _, y = jax.lax.scan(
        position, jnp.zeros((h, hp, n), jnp.float32), (xs, b_in, c_out, dt, keep))
    y = (y + up("d_skip")[:, None] * xs).reshape(s, inner) * jax.nn.silu(z)
    y = _rms_norm(y.reshape(s, g, inner // g), 1.0, a.rms_eps).reshape(s, inner)
    return _mm(y * up("gate_norm"), up("out_proj"), mode)


def _experts(x, p, arch: Arch, mode, routed=True, shared=True, biased=True):
    """Shared expert plus the gated sum of the held routed ones: every held
    expert applied to every token's latent row, the gate zero where the
    token did not choose it."""
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    y = jnp.zeros_like(x)
    if shared:
        y = _mm(_relu2(_mm(x, up("s_up"), mode)), up("s_down"), mode)
    if not routed:
        return y
    scores = jax.nn.sigmoid(_mm(x, up("router"), mode))
    choice = scores + up("e_bias") if biased else scores
    _, top_idx = jax.lax.top_k(choice, arch.top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    top_vals = scores[rows, top_idx]
    top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True) * arch.routed_scaling
    gates = jnp.zeros_like(scores).at[rows, top_idx].set(top_vals)  # [S, E]
    held = jax.lax.dynamic_slice_in_dim(gates, arch.held_first, arch.held, axis=1)
    latent = _mm(x, up("lat_down"), mode)

    def one_expert(acc, xs):
        e_up, e_down, g = xs  # one expert's bfloat16 weights, its gates [S]
        out = _mm(_relu2(_mm(latent, e_up.astype(jnp.float32), mode)),
                  e_down.astype(jnp.float32), mode)
        return acc + g[:, None] * out, None

    routed_sum, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(latent), (p["e_up"], p["e_down"], held.T))
    return y + _mm(routed_sum, up("lat_up"), mode)


@functools.partial(jax.jit, static_argnames=("arch", "mode", "decay", "biased"))
def _layer(x, p, *, arch, mode, decay=True, biased=True):
    y = _rms_norm(x, p["norm"].astype(jnp.float32), arch.rms_eps)
    kind = kind_of(p)
    if kind == "M":
        return x + _mamba(y, p, arch, mode, decay)
    if kind == "E":
        return x + _experts(y, p, arch, mode, biased=biased)
    return x + _attention(y, p, arch, mode)


@functools.partial(jax.jit, static_argnames=(
    "arch", "mode", "routed", "shared", "biased"))
def experts_layer(x, p, *, arch, mode="f32", routed=True, shared=True, biased=True):
    """The expert layer alone over ``x [S, D]``: what the share test adds up."""
    return _experts(x, p, arch, mode, routed, shared, biased)


@functools.partial(jax.jit, static_argnames=("arch", "mode"))
def mamba_layer(x, p, *, arch, mode="f32"):
    """The Mamba-2 mixer alone over ``x [S, D]``: what the scan's tests
    compare the chunked and the one-step form with."""
    return _mamba(x, p, arch, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm_w, head_w, *, eps, mode):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), mode)


def logits_one(params, tokens, mode="f32", decay=True, biased=True):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward,
    one jitted call a layer so that one layer's float32 weights live at a
    time.  ``decay=False`` leaves the decay out of the state's update and
    ``biased=False`` the correction bias out of the choice: the tests'
    controls, never the benchmark's."""
    arch = arch_of(params)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for p in params["layers"]:
        x = _layer(x, p, arch=arch, mode=mode, decay=decay, biased=biased)
    return _head(x, params["norm_w"], params["head_w"], eps=arch.rms_eps, mode=mode)


def logits_for(params, tokens, heads, mode="f32"):
    """Logits of one sequence of any length: padded at the END to a
    multiple of the configuration's ``reference_pad_to`` (causal, and every
    other operation is a token's own or looks backwards, so the padding
    changes no kept row).  A float32 program at ``highest`` takes the chip's
    compiler ten seconds and more a shape, so a configuration names its
    longest sequence and one shape serves every request.  ``heads`` is what
    the driver passes; the weights carry it."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    arch = arch_of(params)
    if int(heads) != arch.heads:
        raise ValueError(f"heads {heads} but the weights were made for {arch.heads}")
    padded = np.zeros((-(-n // arch.pad_to) * arch.pad_to,), np.int32)
    padded[:n] = tokens
    return logits_one(params, jnp.asarray(padded), mode)[:n]
