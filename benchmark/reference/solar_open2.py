"""Plain reference of the Solar-Open2 decoder (``model_type: solar_open2``,
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json): a
hybrid of gated delta-rule linear attention (Kimi Delta Attention, the
config's ``kda_*`` keys and ``linear_attn_config``; Kimi Linear,
arXiv:2510.26692) and grouped-query softmax attention without positions,
over dropless experts in every layer.  Written from the equations; imports
nothing of the program and takes nothing the program made: the weights come
from ``make_params(seed)``.

Everything is float32 ``jax.numpy`` with matmul precision ``highest``: no
cache, no batching, no chunks (the recurrence runs one position at a time in
``lax.scan``), attention in blocks of query rows only so that 4,608
positions fit, and every held expert applied to every token under a mask of
its gates (one expert at a time, so that the float32 copy of its weights
fits and the loop compiles once).

The equations (config keys in brackets), one layer ``l``:
  h = x + Mixer_l(RMSNorm(x));  x' = h + Experts(RMSNorm(h))
  RMSNorm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w;  no bias but dt_bias
  Mixer_l is GQA where l is in [gqa_layers], else KDA.
  GQA: q = x W_q -> [num_attention_heads, head_dim]; k, v = x W_k, x W_v ->
       [num_key_value_heads, head_dim]; no rotary, no other position term
       [use_rope false]; query head h reads K/V head h // (heads / kv heads);
       scores q.k / sqrt(head_dim), causal softmax, o = P v;
       [use_gqa_gate] o <- o * sigmoid(x W_gate) (element-wise, one column a
       value of o); y = o W_o
  KDA, H = [linear_attn_config.num_heads] heads of d = [.head_dim]:
       q~, k~, v~ = x W_q, x W_k, x W_v  (each hidden -> H d)
       q_t = silu(sum_{j=0..3} c^q_j * q~_{t-3+j})  (depthwise, causal,
       [.short_conv_kernel_size] taps, no bias; rows before the sequence are
       zero), the same for k and v
       q^ = q / sqrt(|q|^2 + 1e-6) * d^-1/2,  k^ = k / sqrt(|k|^2 + 1e-6)
       a_t = -exp(A_log_h) * softplus(W_f2 (W_f1 x_t) + dt_bias)   (per channel;
       [kda_use_full_proj false]: W_f, W_g low-rank, hidden -> d -> H d)
       b_t = 2 sigmoid(W_b x_t)   ([kda_allow_neg_eigval]: the 2)
       S' = diag(exp(a_t)) S_{t-1};  S_t = S' + b_t k^_t (v_t - S'^T k^_t)^T
       o_t = S_t^T q^_t;  S_0 = 0, float32
       y = W_o [ RMSNorm_d(o_t; w_o_norm) * sigmoid(W_g2 (W_g1 x_t)) ]
  Experts: p = softmax(x W_r) in float32 over all [n_routed_experts]; the
       [num_experts_per_tok] largest; gates p_i / sum of the chosen
       [norm_topk_prob] * [routed_scaling_factor];
       y = sum_{chosen and held} g_i E_i(x) + Shared(x),
       E(x) = W_down(silu(W_gate x) * W_up x) of width [moe_intermediate_size],
       Shared one such of width n_shared_experts * moe_intermediate_size.
       No capacity, no drop.  Only the HELD experts exist here
       (``experts_held``: this chip's share of an expert-parallel layer); what
       the absent ones would have added is left out, as in the program.
  after the last layer RMSNorm, then logits = x W_head (untied, no bias).

Departures from the published description: none in the mathematics as read
above; three details the config leaves open are set by the family's
convention and listed under ``assumed`` in the configuration file (softmax
router scores, element-wise GQA gate, low-rank decay and gate projections of
rank ``head_dim``).  The published model runs in bfloat16 and rounds after
every operation; the reference keeps float32 throughout.

``mode`` chooses the arithmetic, for the controls only:
  ``f32``         the reference itself;
  ``bf16``        matmul operands rounded to bfloat16 (what the configuration
                  states);
  ``int8``        matmul operands fake-quantised to int8 (per-row symmetric):
                  the nearest precision below the one the configuration states;
  ``bf16_state``  the reference, but the KDA state rounded to bfloat16 after
                  every position: what a cache that kept it in bfloat16 gives.

Parameter layout ("reference layout"): ``tok_emb [V,D]``, ``head_w [D,V]``,
``norm_w [D]``, ``layers`` (a list, one dict a layer) and ``arch`` (the sizes
that no shape gives).  Every layer holds ``attn_norm [D]``, ``ffn_norm [D]``,
``router [D,E]``, ``e_gate [held,D,M]``, ``e_up``, ``e_down [held,M,D]``,
``s_gate [D,nM]``, ``s_up``, ``s_down [nM,D]``; a GQA layer ``wq [D,H*hd]``,
``wk``, ``wv [D,Hkv*hd]``, ``w_gate [D,H*hd]``, ``wo [H*hd,D]``; a KDA layer
``kq``, ``kk``, ``kv [D,H*d]``, ``conv_q``, ``conv_k``, ``conv_v [taps,H*d]``,
``f1 [D,d]``, ``f2 [d,H*d]``, ``dt_bias [H*d]``, ``a_log [H]``, ``wb [D,H]``,
``g1 [D,d]``, ``g2 [d,H*d]``, ``o_norm [d]``, ``ko [H*d,D]``.  Every weight is a
bfloat16 array whose values were drawn in float32 and rounded once, so that
the program (which holds bfloat16) and the reference (which upcasts) start
from the same numbers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, get_type_hints

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("f32", "bf16", "int8", "bf16_state")


class Arch(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    lin_heads: int
    lin_dim: int
    taps: int
    top_k: int
    held_first: int
    held: int
    rms_eps: float
    routed_scaling: float
    gqa_gate: bool
    neg_eigval: bool
    pad_to: int
    query_block: int


def sizes_of(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's published
    keys (they lie at the file's top level, under the names of the source;
    the experts' total and the share held are what ``serve.model`` runs)."""
    c = config
    for key, want in (("use_rope", False), ("kda_use_full_proj", False),
                      ("first_k_dense_replace", 0), ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if c.get(key, want) != want:
            raise ValueError(f"the reference does not write {key}: {c[key]!r}")
    linear = c["linear_attn_config"]
    if linear.get("num_kv_heads") is not None:
        raise ValueError("the reference writes KDA with as many K/V as query heads")
    model = c.get("serve", {}).get("model", {})
    total = int(model.get("n_routed_experts", c["n_routed_experts"]))
    first, held = model.get("experts_held") or (0, total)
    if int(c["n_routed_experts"]) != held:
        raise ValueError(
            f"the file's n_routed_experts ({c['n_routed_experts']}) counts the "
            f"experts held, but serve.model holds {held}")
    layers = int(c["num_hidden_layers"])
    arch = Arch(
        heads=int(c["num_attention_heads"]), kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), lin_heads=int(linear["num_heads"]),
        lin_dim=int(linear["head_dim"]), taps=int(linear["short_conv_kernel_size"]),
        top_k=int(c["num_experts_per_tok"]), held_first=int(first), held=int(held),
        rms_eps=float(c["rms_norm_eps"]),
        routed_scaling=float(c["routed_scaling_factor"]),
        gqa_gate=bool(c["use_gqa_gate"]), neg_eigval=bool(c["kda_allow_neg_eigval"]),
        # every sequence is padded to a multiple of this: a configuration
        # gives its longest (bucket + new tokens), so that ONE shape compiles
        pad_to=int(c.get("reference_pad_to", 256)),
        query_block=int(c.get("reference_query_block", 512)),
    )
    return {
        "H": arch.heads, "arch": arch, "V": int(c["vocab_size"]),
        "D": int(c["hidden_size"]), "L": layers,
        "M": int(c["moe_intermediate_size"]), "E": total,
        "NS": int(c["n_shared_experts"]),
        "full": tuple(i in c["gqa_layers"] for i in range(layers)),
        "router_std": float(c["assumed"]["router_logit_std"]),
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


@functools.partial(jax.jit, static_argnames=("D", "M", "E", "NS", "arch", "full"))
def _make_layer(key, router_std, *, D, M, E, NS, arch, full):
    k = iter(jax.random.split(key, 32))
    a = arch
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layer = {
        "attn_norm": ones((D,)), "ffn_norm": ones((D,)),
        # assumed: a router whose logits spread (standard deviation
        # ``router_std`` on a unit-RMS input), so that routing counts
        "router": _normal(next(k), (D, E), router_std * D ** -0.5),
        "e_gate": _normal(next(k), (a.held, D, M), D ** -0.5),
        "e_up": _normal(next(k), (a.held, D, M), D ** -0.5),
        "e_down": _normal(next(k), (a.held, M, D), M ** -0.5),
        "s_gate": _normal(next(k), (D, NS * M), D ** -0.5),
        "s_up": _normal(next(k), (D, NS * M), D ** -0.5),
        "s_down": _normal(next(k), (NS * M, D), (NS * M) ** -0.5),
    }
    if full:
        hq, hkv = a.heads * a.head_dim, a.kv_heads * a.head_dim
        layer.update(
            wq=_normal(next(k), (D, hq), D ** -0.5),
            wk=_normal(next(k), (D, hkv), D ** -0.5),
            wv=_normal(next(k), (D, hkv), D ** -0.5),
            w_gate=_normal(next(k), (D, hq), D ** -0.5),
            wo=_normal(next(k), (hq, D), hq ** -0.5),
        )
        return layer
    hd, d = a.lin_heads * a.lin_dim, a.lin_dim
    # assumed: decays that spread.  The rate of a channel is
    # exp(A_log) * softplus(z + dt_bias) with z of deviation about 1:
    # A = exp(A_log) uniform in (0.5, 2) a head, dt_bias the inverse softplus
    # of a step drawn log-uniform in (0.001, 0.1) a channel, so that
    # alpha = exp(-rate) lies mostly in (0.5, 0.999) and the state neither
    # vanishes nor saturates over some thousands of positions
    step = jnp.exp(_uniform(next(k), (hd,), np.log(1e-3), np.log(1e-1)))
    layer.update(
        kq=_normal(next(k), (D, hd), D ** -0.5),
        kk=_normal(next(k), (D, hd), D ** -0.5),
        kv=_normal(next(k), (D, hd), D ** -0.5),
        conv_q=_normal(next(k), (a.taps, hd), a.taps ** -0.5),
        conv_k=_normal(next(k), (a.taps, hd), a.taps ** -0.5),
        conv_v=_normal(next(k), (a.taps, hd), a.taps ** -0.5),
        f1=_normal(next(k), (D, d), D ** -0.5),
        f2=_normal(next(k), (d, hd), d ** -0.5),
        dt_bias=jnp.log(jnp.expm1(step)).astype(jnp.bfloat16),
        a_log=jnp.log(_uniform(next(k), (a.lin_heads,), 0.5, 2.0)).astype(jnp.bfloat16),
        wb=_normal(next(k), (D, a.lin_heads), D ** -0.5),
        g1=_normal(next(k), (D, d), D ** -0.5),
        g2=_normal(next(k), (d, hd), d ** -0.5),
        o_norm=ones((d,)),
        ko=_normal(next(k), (hd, D), hd ** -0.5),
    )
    return layer


def make_params(seed: int, sizes: dict) -> dict:
    """Weights from the seed, on the device, one jitted call a layer:
    embeddings N(0, 0.02), matrices N(0, 1/fan_in), norms 1, the router
    N(0, router_std^2/fan_in), the decays as :func:`_make_layer` says, each
    drawn in float32 and rounded once to bfloat16."""
    key = seed_key(seed)
    arch = sizes["arch"]
    shape = {k: sizes[k] for k in ("D", "M", "E", "NS")}
    layers = [
        _make_layer(jax.random.fold_in(key, i), sizes["router_std"], arch=arch,
                    full=sizes["full"][i], **shape)
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
    format: one ``layer{i}`` subtree a layer; the experts' gate and up
    projections side by side in one tensor a kind (``[.., 2 x width]``, the
    gate first); a KDA layer's three projections and its three convolutions
    side by side too (q, k, v in that order), as the program's documented
    layout has them."""
    def host(x):
        return np.asarray(x)

    def side_by_side(*names):
        return lambda p: np.concatenate([host(p[n]) for n in names], -1)

    tree = {
        "tok_embedding": host(params["tok_emb"]),
        "norm": {"scale": host(params["norm_w"])},
        "head": {"kernel": host(params["head_w"])},
    }
    for i, p in enumerate(params["layers"]):
        layer = {
            "attn_norm": {"scale": host(p["attn_norm"])},
            "ffn_norm": {"scale": host(p["ffn_norm"])},
            "moe": {
                "router": host(p["router"]),
                "w_gate_up": side_by_side("e_gate", "e_up")(p),
                "w_down": host(p["e_down"]),
                "shared_gate_up": side_by_side("s_gate", "s_up")(p),
                "shared_down": host(p["s_down"]),
            },
        }
        if "wq" in p:
            layer["attn"] = {n: host(p[n]) for n in ("wq", "wk", "wv", "w_gate", "wo")}
        else:
            layer["kda"] = {
                "w_qkv": side_by_side("kq", "kk", "kv")(p),
                "conv_w": side_by_side("conv_q", "conv_k", "conv_v")(p),
                "w_f1": host(p["f1"]), "w_f2": host(p["f2"]),
                "dt_bias": host(p["dt_bias"]), "A_log": host(p["a_log"]),
                "w_b": host(p["wb"]), "w_g1": host(p["g1"]), "w_g2": host(p["g2"]),
                "o_norm": host(p["o_norm"]), "w_o": host(p["ko"]),
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
    elif mode not in MODES:
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


def _gqa(x, p, arch: Arch, mode):
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
    if a.gqa_gate:
        out = out * jax.nn.sigmoid(_mm(x, up("w_gate"), mode))
    return _mm(out, up("wo"), mode)


def _short_conv(pre, taps_w):
    """``silu(sum_j c_j * pre[t - (taps-1) + j])``, zeros before the sequence."""
    taps = taps_w.shape[0]
    padded = jnp.pad(pre, ((taps - 1, 0), (0, 0)))
    s = pre.shape[0]
    return jax.nn.silu(sum(taps_w[j] * padded[j:j + s] for j in range(taps)))


def _kda(x, p, arch: Arch, mode, delta=True):
    a = arch
    s, h, d = x.shape[0], a.lin_heads, a.lin_dim
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    heads = lambda t: t.reshape(s, h, d)  # noqa: E731
    q = heads(_short_conv(_mm(x, up("kq"), mode), up("conv_q")))
    k = heads(_short_conv(_mm(x, up("kk"), mode), up("conv_k")))
    v = heads(_short_conv(_mm(x, up("kv"), mode), up("conv_v")))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    rate = jax.nn.softplus(_mm(_mm(x, up("f1"), mode), up("f2"), mode) + up("dt_bias"))
    log_decay = -jnp.exp(up("a_log"))[:, None] * heads(rate)  # [S, H, d]
    beta = jax.nn.sigmoid(_mm(x, up("wb"), mode)) * (2.0 if a.neg_eigval else 1.0)

    def position(state, xs):
        q_t, k_t, v_t, a_t, b_t = xs  # [H, d] x 4, [H]
        decayed = state * jnp.exp(a_t)[:, :, None]
        seen = jnp.einsum("hkv,hk->hv", decayed, k_t, precision=HIGHEST)
        change = b_t[:, None] * (v_t - seen if delta else v_t)
        state = decayed + k_t[:, :, None] * change[:, None, :]
        out = jnp.einsum("hkv,hk->hv", state, q_t, precision=HIGHEST)
        if mode == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, out

    _, out = jax.lax.scan(
        position, jnp.zeros((h, d, d), jnp.float32), (q, k, v, log_decay, beta))
    out = _rms_norm(out, up("o_norm"), a.rms_eps).reshape(s, h * d)
    out = out * jax.nn.sigmoid(_mm(_mm(x, up("g1"), mode), up("g2"), mode))
    return _mm(out, up("ko"), mode)


def _swiglu(x, gate, up, down, mode):
    return _mm(jax.nn.silu(_mm(x, gate, mode)) * _mm(x, up, mode), down, mode)


def _experts(x, p, arch: Arch, mode, routed=True, shared=True):
    """Shared expert plus the gated sum of the held routed ones: every held
    expert applied to every token, the gate zero where the token did not
    choose it."""
    up = lambda name: p[name].astype(jnp.float32)  # noqa: E731
    y = jnp.zeros_like(x)
    if shared:
        y = _swiglu(x, up("s_gate"), up("s_up"), up("s_down"), mode)
    if not routed:
        return y
    scores = jax.nn.softmax(_mm(x, up("router"), mode), axis=-1)
    top_vals, top_idx = jax.lax.top_k(scores, arch.top_k)
    top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True) * arch.routed_scaling
    gates = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], top_idx
    ].set(top_vals)  # [S, E]
    held = jax.lax.dynamic_slice_in_dim(gates, arch.held_first, arch.held, axis=1)

    def one_expert(acc, xs):
        e_gate, e_up, e_down, g = xs  # one expert's bfloat16 weights, gates [S]
        out = _swiglu(x, e_gate.astype(jnp.float32), e_up.astype(jnp.float32),
                      e_down.astype(jnp.float32), mode)
        return acc + g[:, None] * out, None

    routed_sum, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (p["e_gate"], p["e_up"], p["e_down"], held.T)
    )
    return y + routed_sum


@functools.partial(jax.jit, static_argnames=("arch", "mode", "delta", "routed"))
def _layer(x, p, *, arch, mode, delta=True, routed=True):
    eps = arch.rms_eps
    y = _rms_norm(x, p["attn_norm"].astype(jnp.float32), eps)
    h = x + (_gqa(y, p, arch, mode) if "wq" in p else _kda(y, p, arch, mode, delta))
    return h + _experts(
        _rms_norm(h, p["ffn_norm"].astype(jnp.float32), eps), p, arch, mode, routed)


@functools.partial(jax.jit, static_argnames=("arch", "mode", "routed", "shared"))
def experts_layer(x, p, *, arch, mode="f32", routed=True, shared=True):
    """The expert layer alone over ``x [S, D]``: what the share test adds up."""
    return _experts(x, p, arch, mode, routed, shared)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head(x, norm_w, head_w, *, eps, mode):
    x = _rms_norm(x, norm_w.astype(jnp.float32), eps)
    return _mm(x, head_w.astype(jnp.float32), mode)


def logits_one(params, tokens, mode="f32", delta=True, routed=True):
    """Logits ``[S, V]`` of one sequence ``tokens [S]``: the whole forward,
    one jitted call a layer so that one layer's float32 weights live at a
    time.  ``delta=False`` leaves the delta term (``- S'^T k``) out of the
    KDA update and ``routed=False`` the routed experts out of the sum: the
    tests' controls, never the benchmark's."""
    arch = arch_of(params)
    x = params["tok_emb"][tokens].astype(jnp.float32)
    for p in params["layers"]:
        x = _layer(x, p, arch=arch, mode=mode, delta=delta, routed=routed)
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

