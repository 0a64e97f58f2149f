"""Reversible two-stream residual stacks (RevNet / Reformer style).

A pre-norm TransformerLayer is the sum of two residual branches:
``F(x) = attn(norm(x))`` and ``G(x) = ffn(norm(x))``. Splitting the stream
in two makes the layer *invertible*::

    y1 = x1 + F(x2)          x2 = y2 - G(y1)
    y2 = x2 + G(y1)          x1 = y1 - F(x2)

so the backward pass can RECONSTRUCT every layer's inputs from its outputs
instead of saving them: activation memory is O(1) in depth (only the final
``(y1, y2)`` pair is a residual of the whole stack) where both the plain
scan and remat-"full" keep an O(L) stack of carries. Implemented as one
``jax.custom_vjp`` over the stacked-params scan; the backward runs its own
``reverse=True`` scan, inverting and then VJP-ing one layer at a time.

Composition and gating:
  * Requires a residual-decomposable inner layer — one exposing the
    ``attn_branch`` / ``ffn_branch`` interface (``TransformerLayer``, any
    mixer/FFN inside it). ``Block`` / heterogeneous / non-residual layouts
    cannot invert and fail at build time with a clear error.
  * ``residual_dropout`` must be 0: a sampled mask breaks exact inversion.
  * Supersedes ``remat_policy`` inside the stack (there is nothing left to
    checkpoint — inversion already recomputes from structure); remat still
    applies to everything outside the stack.
  * Training-only knob: the decode interface (``init_states`` / ``prefill``
    / ``extend_step``) is single-stream and raises on reversible stacks.
  * Side outputs (summaries, aux losses) from inner layers are dropped —
    the custom_vjp boundary cannot re-emit them.

Numerics: inversion recovers inputs up to one rounding of the residual add
(exact to ~1e-6 relative in fp32); gradients match the plain two-stream
autodiff to the same order.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.module import functional

__all__ = ["validate_reversible", "reversible_forward", "rev_stack"]


def validate_reversible(layer_module) -> None:
    """Build-time gate: raises ValueError for non-invertible layouts."""
    missing = [m for m in ("attn_branch", "ffn_branch")
               if not hasattr(layer_module, m)]
    if missing:
        raise ValueError(
            "reversible=True requires a residual-decomposable layer "
            "exposing the attn_branch/ffn_branch interface (e.g. "
            f"TransformerLayer); {type(layer_module).__name__} lacks "
            f"{missing}. Heterogeneous Blocks and non-residual mixers "
            "cannot be inverted — use remat_policy instead.")
    rate = getattr(layer_module.config, "residual_dropout", 0.0)
    if rate:
        raise ValueError(
            f"reversible=True is incompatible with residual_dropout={rate}: "
            "a sampled dropout mask cannot be reconstructed during "
            "inversion. Set residual_dropout=0 (or reversible=False).")


def _zero_cotangent(x):
    """Cotangent for a non-differentiated primal input: float0 for integer
    leaves (positions), zeros for float leaves."""
    if jnp.issubdtype(x.dtype, jnp.floating) or \
            jnp.issubdtype(x.dtype, jnp.complexfloating):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


def rev_stack(layer, params, x1, x2, positions=None, *, is_training=True,
              use_custom_vjp: bool = True):
    """Runs the two-stream reversible scan over stacked ``params``.

    ``layer`` is the (shared) inner module; ``params`` its stacked weights
    with a leading layer axis. Returns ``(y1, y2)``. With
    ``use_custom_vjp=False`` the same math runs under plain autodiff — the
    reference the custom backward is tested against.
    """

    def call(params_i, method, h, pos):
        inputs = {"x": h}
        if method == "attn_branch":
            inputs["positions"] = pos
        out, _ = functional(layer, state=params_i, inputs=inputs,
                            prng_key=None, is_training=is_training,
                            method=method)
        return out

    def fwd_scan(params, x1, x2, pos):
        def step(carry, p_i):
            h1, h2 = carry
            y1 = h1 + call(p_i, "attn_branch", h2, pos)
            y2 = h2 + call(p_i, "ffn_branch", y1, pos)
            return (y1, y2), None

        (y1, y2), _ = jax.lax.scan(step, (x1, x2), params)
        return y1, y2

    if not use_custom_vjp:
        return fwd_scan(params, x1, x2, positions)

    @jax.custom_vjp
    def stack(params, x1, x2, pos):
        return fwd_scan(params, x1, x2, pos)

    def stack_fwd(params, x1, x2, pos):
        y1, y2 = fwd_scan(params, x1, x2, pos)
        # O(1)-in-depth residuals: the stacked params (already resident) and
        # the FINAL stream pair only — no per-layer activation stack.
        return (y1, y2), (params, y1, y2, pos)

    def stack_bwd(res, cts):
        params, y1, y2, pos = res
        dy1, dy2 = cts

        def back(carry, p_i):
            h1, h2, d1, d2 = carry
            # Invert: x2 = y2 - G(y1); x1 = y1 - F(x2) — recomputing each
            # branch under jax.vjp to get its pullback in the same pass.
            g_out, g_vjp = jax.vjp(
                lambda p, h: call(p, "ffn_branch", h, pos), p_i, h1)
            x2 = h2 - g_out
            f_out, f_vjp = jax.vjp(
                lambda p, h: call(p, "attn_branch", h, pos), p_i, x2)
            x1 = h1 - f_out
            # RevNet adjoint: y2 depends on y1 through G, so the total
            # y1-cotangent is dy1 + G^T dy2; x2 then collects dy2 + F^T of it.
            dp_g, dg_h1 = g_vjp(d2)
            t1 = d1 + dg_h1
            dp_f, df_x2 = f_vjp(t1)
            dx1 = t1
            dx2 = d2 + df_x2
            dp = jax.tree.map(jnp.add, dp_g, dp_f)
            return (x1, x2, dx1, dx2), dp

        (_, _, dx1, dx2), dparams = jax.lax.scan(
            back, (y1, y2, dy1, dy2), params, reverse=True)
        dpos = jax.tree.map(_zero_cotangent, pos)
        return dparams, dx1, dx2, dpos

    stack.defvjp(stack_fwd, stack_bwd)
    return stack(params, x1, x2, positions)


def reversible_forward(repeat, x, positions: Optional[jax.Array] = None):
    """The ``Repeat.forward`` path when ``cfg.reversible`` is set: duplicate
    the stream, run the reversible scan, merge. The same function runs in
    train and eval (custom_vjp is transparent when not differentiated), so
    the model computes identically in both modes."""
    validate_reversible(repeat.layer)
    params = repeat.state["layer"]
    ctx = repeat._ctx
    y1, y2 = rev_stack(
        repeat.layer, params, x, x, positions,
        is_training=ctx.is_training)
    # Merge by averaging: keeps the output magnitude of one stream (the
    # final RMSNorm sees the same scale as a single-stream stack).
    return 0.5 * (y1 + y2)
