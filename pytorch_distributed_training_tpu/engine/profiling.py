"""Config-gated ``jax.profiler`` trace hooks.

The reference has no profiling subsystem at all (SURVEY.md §5.1: only tqdm
progress bars + an ineffective ``cudnn.benchmark`` toggle); the rebuild adds
the TPU-native one: an XLA trace window captured with ``jax.profiler`` that
can be opened in TensorBoard / Perfetto (HLO timelines, per-op HBM + MXU
utilization).  Config-gated so default behavior matches the reference:

.. code-block:: yaml

    training:
      profile:
        dir: run/profile     # trace output directory (required)
        start_iter: 10       # window opens after this iteration completes,
                             # so iterations start_iter+1 .. start_iter+n_iters
                             # are traced (default 10: skips the XLA-compile
                             # iterations, which would dwarf the timeline)
        n_iters: 5           # number of traced iterations (default 5)

The Runner calls :meth:`after_step` once per iteration on the rank-0 host
only.  Validation and checkpoint I/O force-close the window so only
steady-state train steps land in the trace; if that close happens before any
traced iteration completed, the window re-arms and retries after the
interruption (a partial window logs a warning instead).
"""
from __future__ import annotations

import logging
from collections.abc import Mapping
from typing import Any, Dict, Optional

__all__ = ["TraceProfiler", "decompose_lm_step"]


class TraceProfiler:
    """One bounded ``jax.profiler`` trace window over the training loop."""

    def __init__(self, directory: str, start_iter: int = 10, n_iters: int = 5,
                 logger: Optional[logging.Logger] = None):
        if n_iters <= 0:
            raise ValueError(f"profile.n_iters must be positive, got {n_iters}")
        self.directory = directory
        self.start_iter = int(start_iter)
        self.n_iters = int(n_iters)
        self._active = False
        self._done = False
        self._log = logger or logging.getLogger(__name__)

    @classmethod
    def from_config(
        cls, train_cfg: Dict[str, Any], logger: Optional[logging.Logger] = None
    ) -> Optional["TraceProfiler"]:
        """Build from the ``training.profile`` config section (None if absent)."""
        prof_cfg = train_cfg.get("profile")
        if prof_cfg is None or prof_cfg is False:
            return None
        # an empty mapping is a *misconfiguration* (user enabled the section
        # but gave no keys) — fall through so the 'dir' check raises
        if not isinstance(prof_cfg, Mapping):
            raise ValueError(
                f"training.profile must be a mapping with a 'dir' key, got {prof_cfg!r}"
            )
        if "dir" not in prof_cfg:
            raise ValueError("training.profile.dir is required when profiling is enabled")
        return cls(
            directory=prof_cfg["dir"],
            start_iter=prof_cfg.get("start_iter", 10),
            n_iters=prof_cfg.get("n_iters", 5),
            logger=logger,
        )

    def after_step(self, iteration: int, sync=None) -> None:
        """Open/close the trace window; called once AFTER each iteration, so
        opening when ``iteration == start_iter`` traces iterations
        ``start_iter+1 .. start_iter+n_iters`` inclusive.

        ``sync``: optional pytree of device arrays (e.g. the train state) to
        ``block_until_ready`` on at the window boundaries — required for the
        trace to actually contain the device timeline, since the steady-state
        loop never otherwise syncs (JAX dispatch is async; without the barrier
        ``stop_trace`` could fire while the traced steps are still enqueued).
        Blocking happens only at the two boundary crossings, not per step.
        """
        import jax

        if self._done:
            return
        if not self._active and iteration >= self.start_iter:
            if sync is not None:
                jax.block_until_ready(sync)  # keep prior async work out of the window
            jax.profiler.start_trace(self.directory)
            self._active = True
            self._opened_at = iteration
            self._last_step = iteration
            self._log.info("profiler: trace started after iter %d -> %s",
                           iteration, self.directory)
        elif self._active:
            self._last_step = iteration
            if iteration >= self._opened_at + self.n_iters:
                self.stop(sync=sync)

    def stop(self, sync=None) -> None:
        """Close the window if open — also called before validation/checkpoint
        work so only steady-state train iterations land in the trace.  An early
        close that captured ZERO iterations discards the window and re-arms it
        (retry after the interruption); a partial capture logs a warning."""
        import jax

        if not self._active:
            return
        if sync is not None:
            jax.block_until_ready(sync)
        jax.profiler.stop_trace()
        self._active = False
        captured = self._last_step - self._opened_at
        if captured <= 0:
            # e.g. validation fired at the very iteration the window opened:
            # nothing traced yet — wait for the next quiet iteration instead
            self._log.warning(
                "profiler: window closed before any iteration was traced; "
                "re-arming (will retry after the interruption)"
            )
            return
        self._done = True
        if captured < self.n_iters:
            self._log.warning(
                "profiler: window closed early: %d of %d iterations captured -> %s",
                captured, self.n_iters, self.directory,
            )
        else:
            self._log.info("profiler: trace stopped -> %s", self.directory)

    def finalize(self) -> None:
        """Loop-exit hook: close any open window and warn if the configured
        window never produced a trace (e.g. ``start_iter >= train_iters``)."""
        self.stop()
        if not self._done:
            self._log.warning(
                "profiler: no trace captured (start_iter=%d never reached or "
                "every window was interrupted) -> %s",
                self.start_iter, self.directory,
            )


# ---------------------------------------------------------------------------
# Programmatic step-time decomposition (``bench.py decompose``)
# ---------------------------------------------------------------------------
#
# The TensorBoard trace above answers "what does iteration N look like" for a
# human; it cannot drive an optimization loop.  ``decompose_lm_step`` answers
# the machine-readable version: it re-times each component family of the LM
# training step as an ISOLATED compiled probe at the step's exact shapes —
# the same modules (same flash-attention dispatch, same Pallas CE kernel,
# same optimizer tree-map) with the surrounding step stripped away — and
# buckets the full step time against those probe times.  Each probe chains
# ``iters`` fwd+bwd executions inside one compiled ``fori_loop`` (gradients
# folded into the carry so DCE cannot drop the backward) and syncs once via
# scalar materialization, the same anti-async discipline as bench.py.
#
# The bucket sums are NORMALIZED to the measured step time: isolated probes
# both undercount (no overlap constraints, better fusion in isolation) and
# overcount (no inter-component fusion), so the raw sum lands near — not at —
# step_ms.  ``raw_ms`` keeps the unscaled measurements honest; ``buckets``
# rescales proportionally when the raw sum overflows step_ms and otherwise
# assigns the shortfall to ``host_infeed`` (dispatch gaps + infeed stall —
# everything the device probes cannot see).  By construction the published
# buckets sum to step_ms exactly.


def _scalar_sync(tree) -> float:
    """Force execution of everything ``tree`` depends on.

    Host materialization of one element: the value cannot reach the host
    before every step it depends on has run."""
    import jax

    leaf = jax.tree_util.tree_leaves(tree)[0]
    return float(leaf.ravel()[0])


def _timed_ms(many, carry, iters: int, windows: int) -> float:
    """Best-of-``windows`` device ms per fori iteration of ``many(carry)``."""
    import time

    _scalar_sync(many(carry))  # compile + warm outside the timed windows
    best = None
    for _ in range(max(1, windows)):
        t0 = time.perf_counter()
        _scalar_sync(many(carry))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best / iters * 1e3


def _grad_chain(loss_fn, params, x, iters: int, n_rep: int = 1):
    """Compiled probe: ``iters`` fori iterations, each running ``n_rep``
    sequential fwd+bwd passes of ``loss_fn(params, x)`` with the gradients
    folded back into the carry (params AND activations — dropping either
    would let XLA dead-code the corresponding backward matmuls)."""
    import jax
    import jax.numpy as jnp

    grad = jax.grad(loss_fn, argnums=(0, 1))

    @jax.jit
    def many(carry):
        def body(_, c):
            p, a = c
            for _ in range(n_rep):
                dp, da = grad(p, a)
                p = jax.tree_util.tree_map(
                    lambda w, g: w - jnp.asarray(1e-12, w.dtype) * g.astype(w.dtype),
                    p, dp,
                )
                a = a + jnp.asarray(1e-12, a.dtype) * da.astype(a.dtype)
            return (p, a)

        return jax.lax.fori_loop(0, iters, body, carry)

    return many, (params, x)


def _sq_loss(y) -> "Any":
    """f32 sum-of-squares over a pytree — the probe objective (cheap, dense
    cotangents everywhere, dtype-safe for bf16 outputs)."""
    import jax
    import jax.numpy as jnp

    return sum(
        (leaf.astype(jnp.float32) ** 2).sum()
        for leaf in jax.tree_util.tree_leaves(y)
    )


def decompose_lm_step(
    lm,
    optimizer,
    params,
    opt_state,
    tokens,
    labels,
    step_ms: float,
    *,
    lr: float = 3e-4,
    iters: int = 10,
    windows: int = 3,
    ema=None,
    ema_decay: Optional[float] = None,
) -> Dict[str, Any]:
    """Decompose one LM training step into component-family buckets (ms).

    Args:
      lm: the :class:`~..models.transformer_lm.TransformerLM` the step runs
        (its fields pin the probe shapes and the fused/remat configuration).
      optimizer / params / opt_state: the live objects from the step — the
        optimizer probe times the REAL update (fused or per-leaf) on the
        real tree.
      tokens / labels: one step's ``[B, S]`` int32 batch (labels feed the
        CE probe so the Pallas fused-CE dispatch matches the step).
      step_ms: the measured full-step time to decompose against.
      ema / ema_decay: pass the step's EMA tree + decay so the optimizer
        bucket includes the smoothing update exactly as the step runs it
        (fused fold or post-hoc tree-map).

    Returns a JSON-ready dict: ``buckets`` (attention / mlp_matmul /
    elementwise / ce_softmax / optimizer / host_infeed — sums to ``step_ms``
    exactly), ``raw_ms`` (unscaled probe times), ``residual_ms`` (signed
    ``step_ms - sum(raw)``; negative = probes overlap-overcount),
    ``overlap_factor`` (``sum(raw) / step_ms``).
    """
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from ..models.vit import MLP
    from ..ops import cross_entropy_loss
    from ..ops.attention import MultiHeadAttention

    batch, seq = tokens.shape
    embed, depth = lm.embed_dim, lm.depth
    rng = jax.random.PRNGKey(0)
    x0 = jax.random.normal(rng, (batch, seq, embed), lm.dtype)

    # -- attention: depth x MHA (qkv/out projections + causal core) --------
    mha = MultiHeadAttention(
        num_heads=lm.num_heads, causal=True, dtype=lm.dtype,
        flash_mesh=lm.flash_mesh,
    )
    p_attn = mha.init(rng, x0)["params"]
    many, carry = _grad_chain(
        lambda p, x: _sq_loss(mha.apply({"params": p}, x)),
        p_attn, x0, iters, n_rep=depth,
    )
    attention_ms = _timed_ms(many, carry, iters, windows)

    # -- MLP matmuls: depth x (fc1 + gelu + fc2), fused-tails aware --------
    mlp = MLP(
        hidden=int(embed * lm.mlp_ratio), out=embed, dtype=lm.dtype,
        fused_tails=lm.fused_tails,
    )
    p_mlp = mlp.init(rng, x0)["params"]
    many, carry = _grad_chain(
        lambda p, x: _sq_loss(mlp.apply({"params": p}, x)),
        p_mlp, x0, iters, n_rep=depth,
    )
    mlp_ms = _timed_ms(many, carry, iters, windows)

    # -- layernorm / residual / elementwise tails --------------------------
    # The block skeleton with attention and the MLP replaced by identity:
    # every op here exists in the real program (ln1, residual add, ln2,
    # residual add, per block; final ln) and vice versa — except the one
    # pos-embedding add, noise next to 5*depth [B,S,E] ops.
    class _ElemProbe(nn.Module):
        depth: int
        fused: bool
        dtype: Any

        @nn.compact
        def __call__(self, x):
            if self.fused:
                from ..ops.fused_elementwise import FusedResidualLayerNorm
            for i in range(self.depth):
                y = nn.LayerNorm(dtype=self.dtype, name=f"ln1_{i}")(x)
                if self.fused:
                    x, y2 = FusedResidualLayerNorm(
                        dtype=self.dtype, name=f"ln2_{i}")(x, y)
                else:
                    x = x + y
                    y2 = nn.LayerNorm(dtype=self.dtype, name=f"ln2_{i}")(x)
                x = x + y2
            return nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)

    elem = _ElemProbe(depth=depth, fused=lm.fused_tails, dtype=lm.dtype)
    p_elem = elem.init(rng, x0)["params"]
    many, carry = _grad_chain(
        lambda p, x: _sq_loss(elem.apply({"params": p}, x)),
        p_elem, x0, iters,
    )
    elementwise_ms = _timed_ms(many, carry, iters, windows)

    # -- CE + softmax (incl. the untied head projection [E, V]) ------------
    head = nn.Dense(lm.vocab_size, dtype=jnp.float32)
    p_head = head.init(rng, x0)["params"]
    flat_labels = labels.reshape(-1)

    def ce_loss(p, x):
        logits = head.apply({"params": p}, x)
        return cross_entropy_loss(
            logits.reshape(-1, lm.vocab_size), flat_labels
        )

    many, carry = _grad_chain(ce_loss, p_head, x0, iters)
    ce_ms = _timed_ms(many, carry, iters, windows)

    # -- optimizer (+ EMA) update: the real update on the real tree --------
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 1e-6), params
    )
    fold_ema = ema_decay is not None and getattr(optimizer, "fused", False)

    @jax.jit
    def opt_many(carry):
        def body(_, c):
            p, s, e = c
            if fold_ema:
                p, s, e = optimizer.update_with_ema(
                    grads, s, p, lr, e, float(ema_decay)
                )
            else:
                p, s = optimizer.update(grads, s, p, lr)
                if ema_decay is not None:
                    d = float(ema_decay)
                    e = jax.tree_util.tree_map(
                        lambda a, b: d * a + (1.0 - d) * b, e, p
                    )
            return (p, s, e)

        return jax.lax.fori_loop(0, iters, body, carry)

    ema0 = ema if ema is not None else params
    optimizer_ms = _timed_ms(opt_many, (params, opt_state, ema0), iters, windows)

    raw = {
        "attention": attention_ms,
        "mlp_matmul": mlp_ms,
        "elementwise": elementwise_ms,
        "ce_softmax": ce_ms,
        "optimizer": optimizer_ms,
    }
    raw_sum = sum(raw.values())
    residual = step_ms - raw_sum
    if residual >= 0:
        buckets = dict(raw)
        buckets["host_infeed"] = residual
    else:
        # probes overcount (isolation lost overlap/fusion): rescale so the
        # published decomposition still partitions the step exactly
        scale = step_ms / raw_sum
        buckets = {k: v * scale for k, v in raw.items()}
        buckets["host_infeed"] = 0.0
    return {
        "step_ms": round(step_ms, 3),
        "buckets": {k: round(v, 3) for k, v in buckets.items()},
        "raw_ms": {k: round(v, 3) for k, v in raw.items()},
        "residual_ms": round(residual, 3),
        "overlap_factor": round(raw_sum / step_ms, 3) if step_ms else None,
        "iters": iters,
        "windows": windows,
    }
