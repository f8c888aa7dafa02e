"""Pure-jnp oracle for Binomial option pricing (paper Table I: lws=255,
4194304 samples, 1:1 buffers, 1:255 out pattern, uses local memory).

European call priced on a recombining binomial tree with N=254 steps
(so each option's tree has lws=255 leaves, matching the OpenCL kernel
that maps one option per work-group of 255 work-items)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

STEPS = 254
RISKFREE = 0.02
VOLATILITY = 0.30


def tree_coefficients(t_years, *, steps: int = STEPS):
    """Per-option tree constants (vdt, pu, pd, disc), each shaped like
    ``t_years``.  The price's error grows about ``steps`` times faster than
    ``pu``'s (it shifts the mean of a ``steps``-trial binomial), so the
    Pallas kernel takes these from here rather than recomputing them with
    its own exp and divide."""
    dt = t_years / steps
    vdt = VOLATILITY * jnp.sqrt(dt)
    u = jnp.exp(vdt)
    d = 1.0 / u
    a = jnp.exp(RISKFREE * dt)
    pu = (a - d) / (u - d)
    pd = 1.0 - pu
    disc = jnp.exp(-RISKFREE * dt)
    return vdt, pu, pd, disc


def price_options(s0, strike, t_years, *, steps: int = STEPS):
    """s0/strike/t_years: (n,) arrays -> (n,) option values."""
    vdt, pu, pd, disc = tree_coefficients(t_years, steps=steps)
    j = jnp.arange(steps + 1, dtype=jnp.float32)
    # leaf prices: S * u^j * d^(steps-j)
    sT = s0[:, None] * jnp.exp(vdt[:, None] * (2.0 * j[None, :] - steps))
    v = jnp.maximum(sT - strike[:, None], 0.0)

    def body(i, v):
        # v[:, :steps+1-i] = disc * (pd*v[:, :-1] + pu*v[:, 1:]) -- fixed
        # width with trailing garbage, masked out by construction
        vn = disc[:, None] * (pd[:, None] * v[:, :-1] + pu[:, None] * v[:, 1:])
        return jnp.concatenate([vn, v[:, -1:]], axis=1)

    v = jax.lax.fori_loop(0, steps, body, v)
    return v[:, 0]
