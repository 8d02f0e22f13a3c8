"""Plain reference of the DeepSeek-V2 decoder (``model_type: deepseek_v2``,
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json and
``modeling_deepseek.py`` beside it): RMSNorm, multi-head latent attention
(MLA) with decoupled rotary keys under YaRN scaling, a dense SwiGLU MLP in the
first ``first_k_dense_replace`` layers and, after them, ``n_routed_experts``
SwiGLU experts of which every token takes its ``num_experts_per_tok`` best
plus ``n_shared_experts`` shared ones.  Written from the equations; imports
nothing of the program and takes nothing the program made: the weights come
from ``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``:
unabsorbed attention (``k_nope`` and ``v`` expanded from the latent for every
position), no cache, no batching, and every expert applied to every token
under a mask of its gates (one expert at a time, so that the float32 copy
of its weights fits and the loop compiles once).

The equations (config keys in brackets), one layer:
  h = x + MLA(RMSNorm(x));  x' = h + FFN(RMSNorm(h))
  RMSNorm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w
  MLA: q = x W_q -> per head q_nope [qk_nope_head_dim] | q_pe [qk_rope_head_dim]
       x W_kv_a -> c [kv_lora_rank] | k_pe [qk_rope_head_dim, one for all heads]
       c <- RMSNorm(c); c W_kv_b -> per head k_nope | v [v_head_dim]
       rotary on q_pe and k_pe only (pairs de-interleaved, then rotate-half)
       scores (q_nope.k_nope + q_pe.k_pe) * (nope+rope)^-1/2 * m^2, causal
       softmax, o = P v, concat_h(o) W_o
  YaRN: f_i = theta^(-2i/d); inv_freq = f_i/factor * ramp + f_i * (1 - ramp),
       ramp linear between find_correction_range(beta_fast, beta_slow);
       cos/sin scale mscale(factor, mscale)/mscale(factor, mscale_all_dim);
       m = mscale(factor, mscale_all_dim) = 0.1 * mscale_all_dim * ln(factor) + 1
  dense FFN: W_down(silu(W_gate x) * W_up x)
  expert FFN: s = softmax(x W_g) in float32 over all experts; the top-k of s
       (greedy, n_group 1); gates are those s values as they are
       (norm_topk_prob false) * routed_scaling_factor;
       y = sum_k s_k Expert_{i_k}(x) + Shared(x), Shared one SwiGLU of width
       n_shared_experts * moe_intermediate_size.  No capacity, no drop.
  after the last layer RMSNorm, then logits = x W_head (untied, no bias).

Departures from ``modeling_deepseek.py``: none in the mathematics.  The
published model runs in bfloat16 and rounds after every operation; the
reference keeps float32 throughout (that is what makes it the reference).
``norm_topk_prob: true``, ``topk_method`` other than ``greedy`` and a
``q_lora_rank`` are not written and raise.

``mode`` chooses the matmul arithmetic, for the controls only:
  ``f32``   the reference itself;
  ``bf16``  operands rounded to bfloat16 (what the configuration states);
  ``int8``  operands fake-quantised to int8 (per-row symmetric): the nearest
            precision below the one the configuration states.

Parameter layout ("reference layout"): ``tok_emb [V,D]``, ``head_w [D,V]``,
``norm_w [D]``, ``layers`` (a list, one dict a layer) and ``arch`` (the sizes
that no shape gives: they travel with the weights).  A layer holds
``attn_norm [D]``, ``wq [D,H*(dn+dr)]``, ``wkv_a [D,r+dr]``, ``kv_norm [r]``,
``wkv_b [r,H*(dn+dv)]``, ``wo [H*dv,D]``, ``ffn_norm [D]`` and either
``w_gate [D,I]``, ``w_up [D,I]``, ``w_down [I,D]`` or ``router [D,E]``,
``e_gate [E,D,M]``, ``e_up [E,D,M]``, ``e_down [E,M,D]``, ``s_gate [D,nM]``,
``s_up [D,nM]``, ``s_down [nM,D]``.  Every weight is a bfloat16 array whose
values were drawn in float32 and rounded once, so that the program (which
holds bfloat16) and the reference (which upcasts) start from the same numbers.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Arch(NamedTuple):
    heads: int
    qk_nope: int
    qk_rope: int
    v_head: int
    kv_rank: int
    top_k: int
    first_dense: int
    rms_eps: float
    rope_theta: float
    yarn_factor: float
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    yarn_original_max: int
    routed_scaling: float
    pad_to: int


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys (they lie at the file's top level, under the names of the source)."""
    c = config
    for key, want in (("q_lora_rank", None), ("norm_topk_prob", False),
                      ("topk_method", "greedy"), ("n_group", 1),
                      ("scoring_func", "softmax"), ("hidden_act", "silu"),
                      ("attention_bias", False), ("tie_word_embeddings", False),
                      ("moe_layer_freq", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"the reference does not write {key}: {c[key]!r}")
    if c["rope_scaling"]["type"] != "yarn":
        raise ValueError("the reference writes YaRN rotary scaling only")
    rope = c["rope_scaling"]
    arch = Arch(
        heads=int(c["num_attention_heads"]), qk_nope=int(c["qk_nope_head_dim"]),
        qk_rope=int(c["qk_rope_head_dim"]), v_head=int(c["v_head_dim"]),
        kv_rank=int(c["kv_lora_rank"]), top_k=int(c["num_experts_per_tok"]),
        first_dense=int(c["first_k_dense_replace"]),
        rms_eps=float(c["rms_norm_eps"]), rope_theta=float(c["rope_theta"]),
        yarn_factor=float(rope["factor"]), yarn_beta_fast=float(rope["beta_fast"]),
        yarn_beta_slow=float(rope["beta_slow"]), yarn_mscale=float(rope["mscale"]),
        yarn_mscale_all_dim=float(rope["mscale_all_dim"]),
        yarn_original_max=int(rope["original_max_position_embeddings"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        # every sequence is padded to a multiple of this: a configuration
        # gives its longest (bucket + new tokens), so that ONE shape compiles
        pad_to=int(c.get("reference_pad_to", 256)),
    )
    return {
        "H": arch.heads, "arch": arch, "V": int(c["vocab_size"]),
        "D": int(c["hidden_size"]), "L": int(c["num_hidden_layers"]),
        "I": int(c["intermediate_size"]), "M": int(c["moe_intermediate_size"]),
        "E": int(c["n_routed_experts"]), "NS": int(c["n_shared_experts"]),
        "router_std": float(config["assumed"]["router_logit_std"]),
    }


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("D", "I", "M", "E", "NS", "arch", "dense"))
def _make_layer(key, router_std, *, D, I, M, E, NS, arch, dense):
    k = iter(jax.random.split(key, 16))
    a = arch
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layer = {
        "attn_norm": ones((D,)), "ffn_norm": ones((D,)), "kv_norm": ones((a.kv_rank,)),
        "wq": _normal(next(k), (D, a.heads * (a.qk_nope + a.qk_rope)), D ** -0.5),
        "wkv_a": _normal(next(k), (D, a.kv_rank + a.qk_rope), D ** -0.5),
        "wkv_b": _normal(next(k), (a.kv_rank, a.heads * (a.qk_nope + a.v_head)),
                         a.kv_rank ** -0.5),
        "wo": _normal(next(k), (a.heads * a.v_head, D), (a.heads * a.v_head) ** -0.5),
    }
    if dense:
        layer.update(
            w_gate=_normal(next(k), (D, I), D ** -0.5),
            w_up=_normal(next(k), (D, I), D ** -0.5),
            w_down=_normal(next(k), (I, D), I ** -0.5),
        )
    else:
        layer.update(
            # assumed: a router whose logits spread (standard deviation
            # ``router_std`` on a unit-RMS input), so that routing counts
            router=_normal(next(k), (D, E), router_std * D ** -0.5),
            e_gate=_normal(next(k), (E, D, M), D ** -0.5),
            e_up=_normal(next(k), (E, D, M), D ** -0.5),
            e_down=_normal(next(k), (E, M, D), M ** -0.5),
            s_gate=_normal(next(k), (D, NS * M), D ** -0.5),
            s_up=_normal(next(k), (D, NS * M), D ** -0.5),
            s_down=_normal(next(k), (NS * M, D), (NS * M) ** -0.5),
        )
    return layer


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, one jitted call a layer (a
    whole model in float32 would not fit): embeddings N(0, 0.02), matrices
    N(0, 1/fan_in), norms 1, the router N(0, router_std^2/fan_in), each drawn
    in float32 and rounded once to bfloat16."""
    key = seed_key(seed)
    arch = sizes["arch"]
    shape = {k: sizes[k] for k in ("D", "I", "M", "E", "NS")}
    layers = [
        _make_layer(jax.random.fold_in(key, i), sizes["router_std"], arch=arch,
                    dense=i < arch.first_dense, **shape)
        for i in range(sizes["L"])
    ]
    top = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {
        "tok_emb": _normal(top[0], (sizes["V"], sizes["D"]), 0.02),
        "head_w": _normal(top[1], (sizes["D"], sizes["V"]), sizes["D"] ** -0.5),
        "norm_w": jnp.ones((sizes["D"],), jnp.bfloat16),
        "layers": layers,
        "arch": {name: np.asarray(value) for name, value in arch._asdict().items()},
    }


def arch_of(params: dict) -> Arch:
    """The sizes that travel with the weights, as static python numbers."""
    kinds = get_type_hints(Arch)
    return Arch(**{
        name: kinds[name](np.asarray(value)) for name, value in params["arch"].items()
    })


# ------------------------------------------------------------ layout bridge

def to_checkpoint_tree(params: dict) -> dict:
    """Reference layout -> the parameter tree of the program's checkpoint
    format: one ``layer{i}`` subtree a layer; an expert layer's gate and up
    projections side by side in one tensor a kind (``[.., 2 x width]``, the
    gate first), as the program's documented layout has them."""
    def host(x):
        return np.asarray(x)

    tree = {
        "tok_embedding": host(params["tok_emb"]),
        "norm": {"scale": host(params["norm_w"])},
        "head": {"kernel": host(params["head_w"])},
    }
    for i, p in enumerate(params["layers"]):
        layer = {
            "attn_norm": {"scale": host(p["attn_norm"])},
            "ffn_norm": {"scale": host(p["ffn_norm"])},
            "attn": {
                "wq": host(p["wq"]), "wkv_a": host(p["wkv_a"]),
                "kv_norm": host(p["kv_norm"]), "wkv_b": host(p["wkv_b"]),
                "wo": host(p["wo"]),
            },
        }
        if "router" in p:
            layer["moe"] = {
                "router": host(p["router"]),
                "w_gate_up": np.concatenate([host(p["e_gate"]), host(p["e_up"])], -1),
                "w_down": host(p["e_down"]),
                "shared_gate_up": np.concatenate([host(p["s_gate"]), host(p["s_up"])], -1),
                "shared_down": host(p["s_down"]),
            }
        else:
            layer["mlp"] = {
                "gate_up": np.concatenate([host(p["w_gate"]), host(p["w_up"])], -1),
                "down": host(p["w_down"]),
            }
        tree[f"layer{i}"] = layer
    return tree


# ------------------------------------------------------------------ forward

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
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _einsum(spec, a, b, mode):
    """The two attention products, in the arithmetic ``mode`` names (both
    contract their operands' last axis)."""
    if mode == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "int8":
        a, b = _fake_int8(a, -1), _fake_int8(b, -1)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(arch: Arch) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s frequencies: the published ones
    divided by ``factor`` where a dimension turns fewer than ``beta_slow``
    times over the original context, kept where it turns more than
    ``beta_fast`` times, and a linear ramp between."""
    dim, base = arch.qk_rope, arch.rope_theta

    def correction_dim(rotations):
        return dim * math.log(arch.yarn_original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(arch.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(arch.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (freq / arch.yarn_factor * ramp + freq * (1 - ramp)).astype(np.float32)


def _rotary(x, positions, arch: Arch):
    """``apply_rotary_pos_emb`` of ``modeling_deepseek.py`` on ``x [S, ..., d]``:
    the pairs ``(x0, x1), (x2, x3), ...`` are de-interleaved into two halves,
    then rotated as halves."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(arch))
    scale = yarn_mscale(arch.yarn_factor, arch.yarn_mscale) / yarn_mscale(
        arch.yarn_factor, arch.yarn_mscale_all_dim)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[-1],)
    cos = (jnp.cos(angles) * scale).reshape(shape)
    sin = (jnp.sin(angles) * scale).reshape(shape)
    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1)


def _mla(x, p, arch: Arch, mode, rotary=True):
    a = arch
    s = x.shape[0]
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    q = _mm(x, up("wq"), mode).reshape(s, a.heads, a.qk_nope + a.qk_rope)
    q_nope, q_pe = q[..., :a.qk_nope], q[..., a.qk_nope:]
    ckv = _mm(x, up("wkv_a"), mode)
    c = _rms_norm(ckv[:, :a.kv_rank], up("kv_norm"), a.rms_eps)
    k_pe = ckv[:, a.kv_rank:]
    kv = _mm(c, up("wkv_b"), mode).reshape(s, a.heads, a.qk_nope + a.v_head)
    k_nope, v = kv[..., :a.qk_nope], kv[..., a.qk_nope:]
    if rotary:
        positions = jnp.arange(s)
        q_pe = _rotary(q_pe, positions, a)
        k_pe = _rotary(k_pe, positions, a)
    scale = (a.qk_nope + a.qk_rope) ** -0.5 * yarn_mscale(
        a.yarn_factor, a.yarn_mscale_all_dim) ** 2
    scores = (
        _einsum("qhd,khd->hqk", q_nope, k_nope, mode)
        + _einsum("qhd,kd->hqk", q_pe, k_pe, mode)
    ) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _einsum("hqk,hdk->qhd", probs, jnp.moveaxis(v, 0, -1), mode)
    return _mm(out.reshape(s, a.heads * a.v_head), up("wo"), mode)


def _swiglu(x, gate, up, down, mode):
    return _mm(jax.nn.silu(_mm(x, gate, mode)) * _mm(x, up, mode), down, mode)


def _experts(x, p, arch: Arch, mode, routed=True):
    """Shared expert plus the gated sum of the routed ones: every expert
    applied to every token, the gate zero where the token did not choose it."""
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    y = _swiglu(x, up("s_gate"), up("s_up"), up("s_down"), mode)
    if not routed:
        return y
    scores = jax.nn.softmax(_mm(x, up("router"), mode), axis=-1)
    top_vals, top_idx = jax.lax.top_k(scores, arch.top_k)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_idx
    ].set(top_vals * arch.routed_scaling)  # [S, E]
    def one_expert(acc, xs):
        e_gate, e_up, e_down, g = xs  # one expert's bfloat16 weights, gates [S]
        out = _swiglu(x, e_gate.astype(jnp.float32), e_up.astype(jnp.float32),
                      e_down.astype(jnp.float32), mode)
        return acc + g[:, None] * out, None

    routed_sum, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (p["e_gate"], p["e_up"], p["e_down"], gates.T)
    )
    return y + routed_sum


@functools.partial(jax.jit, static_argnames=("arch", "mode", "rotary", "routed"))
def _layer(x, p, *, arch, mode, rotary=True, routed=True):
    eps = arch.rms_eps
    h = x + _mla(_rms_norm(x, p["attn_norm"].astype(jnp.float32), eps), p, arch,
                 mode, rotary)
    y = _rms_norm(h, p["ffn_norm"].astype(jnp.float32), eps)
    if "router" in p:
        return h + _experts(y, p, arch, mode, routed)
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    return h + _swiglu(y, up("w_gate"), up("w_up"), up("w_down"), mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm_w, head_w, *, eps, mode):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), mode)


def logits_one(params, tokens, mode="f32", rotary=True, routed=True):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward,
    one jitted call a layer so that one layer's float32 weights live at a
    time.  ``rotary=False`` and ``routed=False`` leave that part of the
    mathematics out: the tests' controls, never the benchmark's."""
    arch = arch_of(params)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for p in params["layers"]:
        x = _layer(x, p, arch=arch, mode=mode, rotary=rotary, routed=routed)
    return _head(x, params["norm_w"], params["head_w"], eps=arch.rms_eps, mode=mode)


def logits_for(params, tokens, heads, mode="f32"):
    """Logits of one sequence of any length: padded at the END to a
    multiple of the configuration's ``reference_pad_to`` (causal, and every
    other operation is a token's own, so the padding changes no kept row).
    A float32 program at ``highest`` takes the chip's compiler ten seconds
    and more a shape, so a configuration names its longest sequence and one
    shape serves every request.  ``heads`` is what the driver passes; the
    weights carry it."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    arch = arch_of(params)
    if int(heads) != arch.heads:
        raise ValueError(f"heads {heads} but the weights were made for {arch.heads}")
    padded = np.zeros((-(-n // arch.pad_to) * arch.pad_to,), np.int32)
    padded[:n] = tokens
    return logits_one(params, jnp.asarray(padded), mode)[:n]
