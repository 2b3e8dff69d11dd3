"""Pallas TPU kernel: fused radiance decoder (Feature Computation ``F``).

The paper's NPU keeps MLP weights in a dedicated 96 KB weight buffer and
streams interpolated features through the MAC array. Here the whole decoder
runs fused in VMEM: weights are block-resident for every grid step (they
fit — 10–100 KB, §II-C), activations never round-trip to HBM. Two decoders
share the kernel's name:

* the repository's MLP (``density=None``): a 2-layer MLP with sigma and rgb
  heads, sample-major::

    feats [S, C] , direnc [S, 9-padded-to-16]  →  out [S, 4] = (sigma, rgb)

* DVGO's fine stage (``density=(shift, voxel_size)``): the raw density is
  post-activated, ``softplus(density + shift) / voxel_size``, and rgbnet
  (``[k0, pe(d)] → 128 → 128 → 3``, sigmoid) decodes the k0 features with
  the view encoding ``pe(d) = [d, sin(d 2^k), cos(d 2^k)]``, computed here
  from the 3-float direction. Channel-major, so that the sample axis is on
  the 128 lanes and a 13-channel row is not padded to 128::

    feats [C, S] (row 0 raw density, rows 1.. k0), dirs [3, S]
        →  out [4, S] = (sigma, rgb)

  rgbnet's first layer arrives split by its input rows (k0 with a zero
  column for the density row, d, the sines, the cosines), so the 39-wide
  input is never concatenated.

Grid over sample blocks; MXU-aligned hidden width (default 64/128). The
fused tick's decode runs as :func:`fused_nerf_mlp`; every staged render's
(the admission prime's among them) as :func:`staged_nerf_mlp`, the same
kernel under its own name so a profile tells the two apart.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret


def _dot(a, b):
    # HIGHEST: Mosaic's default for float32 operands is one bfloat16 pass
    # (~3e-3 relative error against the float32 oracle on a TPU v5e)
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _mlp_kernel(x_ref, d_ref, w1_ref, b1_ref, w2_ref, b2_ref, ws_ref, wr_ref,
                br_ref, out_ref):
    x = x_ref[...]  # [blk, C]
    h = jnp.maximum(_dot(x, w1_ref[...]) + b1_ref[...], 0.0)
    h = jnp.maximum(_dot(h, w2_ref[...]) + b2_ref[...], 0.0)
    sigma = jax.nn.softplus(_dot(h, ws_ref[...]))
    rgb_in = jnp.concatenate([h, d_ref[...]], axis=-1)
    rgb = jax.nn.sigmoid(_dot(rgb_in, wr_ref[...]) + br_ref[...])
    out_ref[...] = jnp.concatenate([sigma, rgb], axis=-1).astype(out_ref.dtype)


def _rgbnet_kernel(x_ref, d_ref, w1k_ref, w1d_ref, w1s_ref, w1c_ref,
                   freq_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, out_ref,
                   *, shift: float, voxel_size: float):
    d = d_ref[...]  # [3, blk]
    # [3 pe, blk]: d_a 2^k, the frequency fastest within each axis (the
    # frequency matrix holds powers of two, so each product is exact)
    z = _dot(freq_ref[...], d)
    h = (_dot(w1k_ref[...], x_ref[...]) + _dot(w1d_ref[...], d)
         + _dot(w1s_ref[...], jnp.sin(z)) + _dot(w1c_ref[...], jnp.cos(z))
         + b1_ref[...])
    h = jnp.maximum(h, 0.0)
    h = jnp.maximum(_dot(w2_ref[...], h) + b2_ref[...], 0.0)
    rgb = jax.nn.sigmoid(_dot(w3_ref[...], h) + b3_ref[...])
    sigma = jax.nn.softplus(x_ref[0:1, :] + shift) / voxel_size
    out_ref[0:1, :] = sigma.astype(out_ref.dtype)
    out_ref[1:4, :] = rgb.astype(out_ref.dtype)


def _full(shape):
    return pl.BlockSpec(shape, lambda i: tuple(0 for _ in shape))


def _mlp_call(feats, direnc, weights, block, interpret):
    s, c = feats.shape
    dd = direnc.shape[1]
    h = weights[0].shape[1]
    assert s % block == 0, (s, block)
    return pl.pallas_call(
        _mlp_kernel,
        grid=(s // block,),
        in_specs=[
            pl.BlockSpec((block, c), lambda i: (i, 0)),
            pl.BlockSpec((block, dd), lambda i: (i, 0)),
            _full((c, h)), _full((1, h)), _full((h, h)), _full((1, h)),
            _full((h, 1)), _full((h + dd, 3)), _full((1, 3)),
        ],
        out_specs=pl.BlockSpec((block, 4), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 4), feats.dtype),
        interpret=resolve_interpret(interpret),
    )(feats, direnc, *weights)


def _rgbnet_call(feats, dirs, weights, density, block, interpret):
    c, s = feats.shape
    assert s % block == 0, (s, block)
    shift, voxel_size = density
    return pl.pallas_call(
        functools.partial(_rgbnet_kernel, shift=shift, voxel_size=voxel_size),
        grid=(s // block,),
        in_specs=[pl.BlockSpec((c, block), lambda i: (0, i)),
                  pl.BlockSpec((3, block), lambda i: (0, i))]
        + [_full(w.shape) for w in weights],
        out_specs=pl.BlockSpec((4, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, s), feats.dtype),
        interpret=resolve_interpret(interpret),
    )(feats, dirs, *weights)


def _decode(feats, dirs, weights, block, interpret, density):
    if density is None:
        return _mlp_call(feats, dirs, weights, block, interpret)
    return _rgbnet_call(feats, dirs, weights, density, block, interpret)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "density"))
def fused_nerf_mlp(feats: jnp.ndarray, dirs: jnp.ndarray, *weights,
                   block: int = 512, interpret: bool | None = None,
                   density: Optional[Tuple[float, float]] = None
                   ) -> jnp.ndarray:
    """The fused tick's decoder. With ``density=None``, the repository's
    MLP: ``dirs`` is the direction encoding, ``weights`` are ``(w1, b1, w2,
    b2, w_sigma, w_rgb, b_rgb)`` with row-vector biases, and the result is
    ``[S, 4]`` = (softplus sigma, sigmoid rgb). With ``density=(shift,
    voxel_size)``, DVGO's fine stage, channel-major: ``feats [C, S]``,
    ``dirs [3, S]``, ``weights`` as :func:`repro.kernels.ops.dvgo_rgbnet`
    lays them out, and the result ``[4, S]``. ``S`` must be a multiple of
    ``block`` (ops.py pads)."""
    return _decode(feats, dirs, weights, block, interpret, density)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "density"))
def staged_nerf_mlp(feats: jnp.ndarray, dirs: jnp.ndarray, *weights,
                    block: int = 512, interpret: bool | None = None,
                    density: Optional[Tuple[float, float]] = None
                    ) -> jnp.ndarray:
    """:func:`fused_nerf_mlp` for the staged renders (the admission prime
    among them), under its own name."""
    return _decode(feats, dirs, weights, block, interpret, density)
