"""Manifold-constrained hyper-connections (mHC): the residual path of
``model_type: xing4_0`` (docs/models.md).

Every other family adds a sublayer's output to one residual stream,
``hidden = hidden + F(norm(hidden))``. Here a token carries ``n =
hc_mult`` streams ``X [n, D]``. Each sublayer (attention, feed-forward)
reads its input as a per-token mix of the streams and writes back
through a per-token ``n x n`` matrix that ``hc_sinkhorn_iters`` Sinkhorn
iterations make doubly stochastic, with parameters of its own
(``phi [n D, 2n + n^2]``, ``b [2n + n^2]``, ``alpha [3]``: the columns
are pre | post | res, res row-major):

    x^     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)      no learned weight
    H_pre  = sigmoid(alpha_pre  x^ phi_pre  + b_pre)     [n]
    H_post = 2 sigmoid(alpha_post x^ phi_post + b_post)  [n]
    M      = exp(clip(alpha_res mat(x^ phi_res) + b_res, hc_res_clamp))
    M      = rows(cols(M)), hc_sinkhorn_iters times      cols: M / (column sums + hc_eps)
    u      = sum_i H_pre[i] X[i];   y = F(u)
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

The streams start as ``n`` copies of the embedding and are summed after
the last layer (models/deepseek.forward_counted), so everything outside
the trunk keeps ``[.., D]``.

Shaped for the chip: the streams ride as ``[B, S, n D]`` (a token's
streams side by side on the lanes: in ``[.., n, D]`` the tiled layout
would pad the 4 to a whole sublane group), the coefficients as ``[n,
T]`` and ``[n, n, T]`` with tokens minor, the Sinkhorn iterations are
one kernel over slabs of tokens (ops/sinkhorn.py: XLA makes four small
fusions of every iteration), and the mixes are ``n`` and ``n^2 + n``
scaled adds of ``[T, D]`` slabs.
The coefficients are float32 whatever the streams' dtype; ``phi``, ``b``
and ``alpha`` are kept in float32 (0.34 M values a sublayer at the
published widths).

Named scopes, inside ``attn`` and ``mlp``: ``mhc_coeff`` (norm,
projection, sigmoids), ``mhc_sinkhorn``, ``mhc_mix`` (the read and the
update); ``mhc_fan`` around the fan-out and the read-out.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from ..engine.config import ModelConfig
from ..ops.sinkhorn import sinkhorn

# published keys of a changed residual path, refused under any other
# model_type (models.published, through models/deepseek.py): served
# with a plain residual path they would give wrong tokens
CLAIMED_PREFIXES = ("hc_", "mhc_", "hyper_connection")
CLAIM = ("hyper-connection keys ({keys}) and no family here implements its "
         "residual path (xing4_0 is the one family with mixed residual "
         "streams: models/mhc.py)")


def claimed_keys(config: dict) -> List[str]:
    return sorted(k for k in config if k.startswith(CLAIMED_PREFIXES))


def config_fields(config: dict) -> dict:
    """ModelConfig's hyper-connection fields from the published keys of
    ``model_type: xing4_0`` (latent attention with mixed residual
    streams: models/deepseek.py over models/mhc.py)."""
    if not (config.get("kv_lora_rank") or 0) > 0:
        raise NotImplementedError(
            "xing4_0 without kv_lora_rank: the mixed residual streams are "
            "served over latent attention only (models/deepseek.py)")
    return dict(
        hc_mult=int(config.get("hc_mult", 1)),
        hc_sinkhorn_iters=int(config.get("hc_sinkhorn_iters", 20)),
        hc_eps=float(config.get("hc_eps", 1e-6)),
        hc_res_clamp=(float(config.get("mhc_h_res_clamp_min", -30.0)),
                      float(config.get("mhc_h_res_clamp_max", 30.0))),
    )


SUBLAYERS = ("attn", "mlp")
PARAM_KEYS = tuple(f"hc_{sub}_{part}" for sub in SUBLAYERS
                   for part in ("phi", "b", "alpha"))


def n_coefficients(n: int) -> int:
    """Columns of a sublayer's ``phi``: pre, post and the n x n res."""
    return 2 * n + n * n


def init_params(cfg: ModelConfig, n_layers: int, key: jax.Array) -> Dict:
    """Random mHC tensors of ``n_layers`` layers: ``phi`` fan-in scaled
    (``x^ phi`` then has unit variance, ``x^`` having unit mean square),
    ``alpha`` 1 and ``b`` standard normal, so the mixing matrices differ
    from token to token and lie far from the identity and from the
    uniform matrix."""
    n = cfg.hc_mult
    nd, c = n * cfg.hidden_size, n_coefficients(n)
    out = {}
    for i, sub in enumerate(SUBLAYERS):
        kp, kb = jax.random.split(jax.random.fold_in(key, i))
        out[f"hc_{sub}_phi"] = jax.random.normal(
            kp, (n_layers, nd, c), jnp.float32) * nd ** -0.5
        out[f"hc_{sub}_b"] = jax.random.normal(kb, (n_layers, c), jnp.float32)
        out[f"hc_{sub}_alpha"] = jnp.ones((n_layers, 3), jnp.float32)
    return out


def fan_out(hidden: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[B, S, D] -> the streams [B, S, n D]: n copies."""
    with jax.named_scope("mhc_fan"):
        return jnp.tile(hidden, (1, 1, cfg.hc_mult))


def read_out(streams: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The streams [B, S, n D] -> their sum [B, S, D]."""
    with jax.named_scope("mhc_fan"):
        parts = _slabs(streams, cfg.hc_mult)
        return sum(parts[1:], parts[0]).astype(streams.dtype)


def _slabs(streams: jax.Array, n: int):
    """The n streams, each [B, S, D] in float32."""
    d = streams.shape[-1] // n
    return [streams[..., i * d:(i + 1) * d].astype(jnp.float32)
            for i in range(n)]


def coefficients(streams: jax.Array, lp: Dict, sub: str, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(H_pre [n, T], H_post [n, T], H_res [n, n, T]) of sublayer
    ``sub`` for the tokens of ``streams`` [B, S, n D], in float32."""
    n = cfg.hc_mult
    b, s, nd = streams.shape
    with jax.named_scope("mhc_coeff"):
        x = streams.reshape(b * s, nd).astype(jnp.float32)
        inv_rms = jax.lax.rsqrt(jnp.mean(x * x, axis=-1) + cfg.hc_eps)
        # the norm has no weight: it is one scalar a token and moves
        # behind the projection, so x^ [T, n D] is never written
        z = jnp.einsum("tk,kc->ct", x, lp[f"hc_{sub}_phi"],
                       precision=jax.lax.Precision.HIGHEST) * inv_rms[None, :]
        alpha = jnp.repeat(lp[f"hc_{sub}_alpha"], jnp.asarray([n, n, n * n]),
                           total_repeat_length=n_coefficients(n))
        z = alpha[:, None] * z + lp[f"hc_{sub}_b"][:, None]
        h_pre = jax.nn.sigmoid(z[:n])
        h_post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    with jax.named_scope("mhc_sinkhorn"):
        h_res = sinkhorn(z[2 * n:], n, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                         cfg.hc_res_clamp).reshape(n, n, b * s)
    return h_pre, h_post, h_res


def read(streams: jax.Array, lp: Dict, sub: str, cfg: ModelConfig):
    """A sublayer's input ``u`` [B, S, D] and what :func:`write` needs
    to put its output back."""
    h_pre, h_post, h_res = coefficients(streams, lp, sub, cfg)
    b, s, _ = streams.shape
    with jax.named_scope("mhc_mix"):
        w = h_pre.reshape(-1, b, s, 1)
        u = sum(w[i] * x for i, x in enumerate(_slabs(streams, cfg.hc_mult)))
    return u.astype(streams.dtype), (h_res, h_post)


def write(streams: jax.Array, y: jax.Array, coeffs, cfg: ModelConfig
          ) -> jax.Array:
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` on [B, S, n D]."""
    h_res, h_post = coeffs
    n = cfg.hc_mult
    b, s, _ = streams.shape
    with jax.named_scope("mhc_mix"):
        res = h_res.reshape(n, n, b, s, 1)
        post = h_post.reshape(n, b, s, 1)
        x, yf = _slabs(streams, n), y.astype(jnp.float32)
        out = [sum((res[i, j] * x[j] for j in range(n)), post[i] * yf)
               for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(streams.dtype)
