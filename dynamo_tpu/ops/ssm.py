"""The Mamba-2 state-space recurrence, two forms of one equation.

Per head, with state ``h ∈ R^{P×N}``, scalar decay rate ``A < 0`` and
step ``Δ_t ≥ 0``::

    h_t = exp(Δ_t A) · h_{t−1} + Δ_t · x_t ⊗ B_t
    y_t = h_t C_t + D · x_t

``B`` and ``C`` are shared by the heads of a group (``G`` groups, head
``h`` uses group ``h // (H / G)``). A token with ``Δ_t = 0`` leaves the
state as it was and adds nothing: that is how the caller marks pad
positions and idle rows.

- ``ssm_decode_update``: one token: the elementwise update of the state
  and the read-out against ``C`` (on the chip XLA makes two fusions of
  it a layer, three passes over the state: PERF.md section 5).
- ``ssd_chunked_scan``: a run of ``S`` tokens from a given state, in the
  chunked (state-space duality) form: inside a chunk of ``Q`` tokens the
  outputs are matrix products against a ``Q × Q`` decay-masked score
  matrix, across chunks one state is carried. It computes the recurrence
  above, not an approximation of it; ``tests/test_falcon_h1_reference.py``
  holds it to the token-by-token form.

XLA on every platform: no kernel, no switch. The state and the decays
stay float32; the products take their operands in the activations' dtype
and accumulate in float32.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _heads_of_groups(m: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: each group's row for its heads."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def ssm_decode_update(
    x: jax.Array,    # [B, H, P]
    dt: jax.Array,   # [B, H] float32, 0 where the row has no token
    a: jax.Array,    # [H] float32, negative
    bm: jax.Array,   # [B, G, N]
    cm: jax.Array,   # [B, G, N]
    d: jax.Array,    # [H]
    h: jax.Array,    # [B, H, P, N] float32
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, H, P] float32, new state [B, H, P, N] float32)."""
    heads = x.shape[1]
    f32 = jnp.float32
    x = x.astype(f32)
    bh = _heads_of_groups(bm.astype(f32), heads)              # [B, H, N]
    ch = _heads_of_groups(cm.astype(f32), heads)
    decay = jnp.exp(dt * a)                                   # [B, H]
    h = (h * decay[:, :, None, None]
         + (dt[:, :, None] * x)[..., None] * bh[:, :, None, :])
    y = jnp.sum(h * ch[:, :, None, :], axis=-1) + d.astype(f32)[None, :, None] * x
    return y, h


def ssd_chunked_scan(
    x: jax.Array,    # [B, S, H, P]
    dt: jax.Array,   # [B, S, H] float32, 0 at pad positions
    a: jax.Array,    # [H] float32, negative
    bm: jax.Array,   # [B, S, G, N]
    cm: jax.Array,   # [B, S, G, N]
    d: jax.Array,    # [H]
    h0: jax.Array,   # [B, H, P, N] float32: the state before the run
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """(y [B, S, H, P] float32, state after the run [B, H, P, N] float32)."""
    b, s, heads, p = x.shape
    g, n = bm.shape[-2:]
    k = heads // g
    f32 = jnp.float32
    q = min(chunk, s)
    pad = -s % q
    if pad:     # Δ = 0 there: no effect on the state, outputs dropped
        x, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    c = (s + pad) // q
    act = x.dtype
    xc = x.reshape(b, c, q, g, k, p)
    dtc = dt.reshape(b, c, q, g, k)
    bc = bm.reshape(b, c, q, g, n)
    cc = cm.reshape(b, c, q, g, n)
    cum = jnp.cumsum(dtc * a.reshape(g, k), axis=2)           # [B, C, Q, G, K] ≤ 0
    total = cum[:, :, -1]                                     # [B, C, G, K]
    cum_h = cum.transpose(0, 1, 3, 4, 2)                      # [B, C, G, K, Q]
    dt_h = dtc.transpose(0, 1, 3, 4, 2)

    # inside a chunk: y_t += Σ_{s≤t} (C_t·B_s) exp(cum_t − cum_s) Δ_s x_s
    # (the Q x Q matrices keep t, s as their minor dimensions: whole tiles)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", cc, bc,
                        preferred_element_type=f32)           # [B, C, G, t, s]
    diff = cum_h[..., :, None] - cum_h[..., None, :]          # [B, C, G, K, t, s]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), diff, -jnp.inf))
    m = scores[:, :, :, None] * decay * dt_h[..., None, :]
    y = jnp.einsum("bcgkts,bcsgkp->bctgkp", m.astype(act), xc,
                   preferred_element_type=f32)

    # what each chunk adds to the state by its end
    xw = (xc.astype(f32)
          * (jnp.exp(total[:, :, None] - cum) * dtc)[..., None]).astype(act)
    adds = jnp.einsum("bcsgn,bcsgkp->bcgkpn", bc, xw,
                      preferred_element_type=f32)             # [B, C, G, K, P, N]

    # across chunks: the state each chunk starts from
    def carry(h, inp):
        add, tot = inp
        return h * jnp.exp(tot)[..., None, None] + add, h

    h_end, h_start = jax.lax.scan(
        carry, h0.reshape(b, g, k, p, n).astype(f32),
        (adds.transpose(1, 0, 2, 3, 4, 5), total.transpose(1, 0, 2, 3)))
    h_start = h_start.transpose(1, 0, 2, 3, 4, 5)             # [B, C, G, K, P, N]
    y = y + jnp.einsum("bctgn,bcgkpn->bctgkp", cc, h_start.astype(act),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y + d.astype(f32).reshape(g, k)[:, :, None] * xc.astype(f32)
    y = y.reshape(b, s + pad, heads, p)[:, :s]
    return y, h_end.reshape(b, heads, p, n)
