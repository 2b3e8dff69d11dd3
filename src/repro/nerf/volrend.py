"""Volume rendering: alpha compositing of ray samples (Feature Computation tail).

Standard emission-absorption model [Levoy'88, NeRF Eq. 3]:
  alpha_i = 1 - exp(-sigma_i * delta_i)
  T_i     = prod_{j<i} (1 - alpha_j)
  w_i     = T_i * alpha_i
  C       = sum_i w_i * c_i ;  D = sum_i w_i * t_i  (depth used by SPARW Eq. 1)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("composite")
def composite(
    sigmas: jnp.ndarray,  # [R, N]
    rgbs: jnp.ndarray,  # [R, N, 3]
    t_vals: jnp.ndarray,  # [R, N]
    far: float,
    white_bkgd: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (color [R,3], depth [R], weights [R,N]).

    Depth of rays that hit nothing is ``far`` (the paper's "void" pixels get
    infinite depth; we use the far plane as the skybox distance so that voids
    warp like a skybox and are depth-testable — see core/sparw.py).
    """
    deltas = jnp.diff(t_vals, axis=-1)
    deltas = jnp.concatenate([deltas, deltas[:, -1:]], axis=-1)
    alpha = 1.0 - jnp.exp(-jnp.maximum(sigmas, 0.0) * deltas)
    trans = jnp.cumprod(1.0 - alpha + 1e-10, axis=-1)
    trans = jnp.concatenate([jnp.ones_like(trans[:, :1]), trans[:, :-1]], axis=-1)
    weights = trans * alpha  # [R, N]
    acc = weights.sum(axis=-1)  # [R]
    # float32 precision: a TPU's default matmul is one bfloat16 pass, and
    # the depth feeds the warp's reprojection
    hi = jax.lax.Precision.HIGHEST
    color = jnp.einsum("rn,rnc->rc", weights, rgbs, precision=hi)
    depth = (jnp.einsum("rn,rn->r", weights, t_vals, precision=hi)
             + (1.0 - acc) * far)
    if white_bkgd:
        color = color + (1.0 - acc)[:, None]
    return color, depth, weights
