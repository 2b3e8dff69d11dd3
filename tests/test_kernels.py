"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import streaming
from repro.kernels import ops, ref
from repro.nerf import grids, mlp


# ---------------------------------------------------------------------------
# gather_trilerp (the GU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("res,edge,cap,n,c", [
    (32, 8, 128, 1500, 4),
    (48, 8, 256, 3000, 8),
    (48, 16, 512, 2000, 12),
    (24, 8, 64, 500, 16),
])
def test_gather_trilerp_shapes(res, edge, cap, n, c):
    cfg = streaming.StreamingCfg(grid_res=res, mvoxel_edge=edge, capacity=cap)
    table = jax.random.normal(jax.random.key(res + n), (res**3, c))
    pts = jax.random.uniform(jax.random.key(n), (n, 3), minval=-1, maxval=1)
    got = ops.gather_features_streaming(table, pts, cfg)
    ids, w = grids.corner_ids_weights(pts, res)
    want = ref.gather_trilerp_ref(table, ids, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_gather_trilerp_piled_mvoxel_exact():
    """64 samples piled into one MVoxel at 8 columns per RIT block fill 8
    blocks of the streaming kernel — still exact."""
    cfg = streaming.StreamingCfg(grid_res=32, mvoxel_edge=8, capacity=8)
    table = jax.random.normal(jax.random.key(0), (32**3, 4))
    pts = jnp.concatenate([
        jnp.zeros((64, 3)) + 0.01,  # pile into one mvoxel
        jax.random.uniform(jax.random.key(1), (200, 3), minval=-1, maxval=1),
    ])
    got = ops.gather_features_streaming(table, pts, cfg)
    ids, w = grids.corner_ids_weights(pts, 32)
    want = ref.gather_trilerp_ref(table, ids, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_trilerp_dtypes(dtype):
    cfg = streaming.StreamingCfg(grid_res=32, mvoxel_edge=8, capacity=128)
    table = jax.random.normal(jax.random.key(7), (32**3, 8)).astype(dtype)
    pts = jax.random.uniform(jax.random.key(8), (800, 3), minval=-1, maxval=1)
    got = ops.gather_features_streaming(table, pts, cfg)
    ids, w = grids.corner_ids_weights(pts, 32)
    want = ref.gather_trilerp_ref(table.astype(jnp.float32), ids, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# fused NeRF MLP (the NPU Feature Computation)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,cin,hidden,block", [
    (1000, 8, 64, 256),
    (555, 16, 32, 128),
    (64, 4, 128, 64),
])
def test_fused_mlp_shapes(n, cin, hidden, block):
    dcfg = mlp.DecoderCfg(mode="mlp", in_channels=cin, hidden=hidden)
    params = mlp.decoder_init(jax.random.key(1), dcfg)
    feats = jax.random.normal(jax.random.key(2), (n, cin))
    dirs = jax.random.normal(jax.random.key(3), (n, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    enc = mlp._dir_enc(dirs)
    sig, rgb = ops.nerf_mlp(feats, enc, params, block=block)
    want = ref.nerf_mlp_ref(feats, enc, params["w1"], params["b1"],
                            params["w2"], params["b2"], params["w_sigma"],
                            params["w_rgb"], params["b_rgb"])
    got = jnp.concatenate([sig[:, None], rgb], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-5)


def test_fused_mlp_matches_decoder_path():
    """Kernel output == repro.nerf.mlp.decode (the model's own decoder)."""
    dcfg = mlp.DecoderCfg(mode="mlp", in_channels=8, hidden=64)
    params = mlp.decoder_init(jax.random.key(9), dcfg)
    feats = jax.random.normal(jax.random.key(10), (300, 8))
    dirs = jax.random.normal(jax.random.key(11), (300, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    sig_m, rgb_m = mlp.decode(params, feats, dirs, dcfg)
    sig_k, rgb_k = ops.nerf_mlp(feats, mlp._dir_enc(dirs), params)
    np.testing.assert_allclose(np.asarray(sig_k), np.asarray(sig_m), atol=2e-5)
    np.testing.assert_allclose(np.asarray(rgb_k), np.asarray(rgb_m), atol=2e-5)


def _plain_rgbnet(params, feats, dirs, pe, shift, voxel):
    """DVGO's fine-stage decoder written out row by row: the view encoding
    built entry by entry in DVGO's order (d, then the sines and the
    cosines, each axis by axis with the frequency fastest), rgbnet over
    ``[k0, pe]``, and the post-activated density."""
    d = np.asarray(dirs, np.float64)
    sines = [np.sin(d[:, a] * 2.0 ** k) for a in range(3) for k in range(pe)]
    coses = [np.cos(d[:, a] * 2.0 ** k) for a in range(3) for k in range(pe)]
    enc = np.stack([d[:, 0], d[:, 1], d[:, 2], *sines, *coses], axis=-1)
    f = np.asarray(feats, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in params.items()}
    x = np.concatenate([f[:, 1:], enc], axis=-1)
    h = np.maximum(x @ w["w1"] + w["b1"], 0.0)
    h = np.maximum(h @ w["w2"] + w["b2"], 0.0)
    rgb = 1.0 / (1.0 + np.exp(-(h @ w["w3"] + w["b3"])))
    sigma = np.logaddexp(f[:, 0] + shift, 0.0) / voxel
    return sigma, rgb


@pytest.mark.parametrize("n,channels,hidden,pe,block", [
    (3000, 13, 128, 4, 1024),   # the published widths, a padded last block
    (700, 5, 32, 2, 256),
    (256, 9, 64, 3, 128),
])
def test_rgbnet_kernel_matches_plain_rgbnet(n, channels, hidden, pe, block):
    dcfg = mlp.DecoderCfg(mode="rgbnet", in_channels=channels, hidden=hidden,
                          viewbase_pe=pe, voxel_size=2.0 / 159)
    params = mlp.decoder_init(jax.random.key(n), dcfg)
    params = {k: v + 0.05 * jax.random.normal(jax.random.key(7), v.shape)
              if k.startswith("b") else v for k, v in params.items()}
    feats = 4.0 * jax.random.normal(jax.random.key(2), (n, channels))
    dirs = jax.random.normal(jax.random.key(3), (n, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    want_sigma, want_rgb = _plain_rgbnet(params, feats, dirs, pe,
                                         dcfg.density_shift, dcfg.voxel_size)
    for tick in (False, True):
        sig, rgb = ops.dvgo_rgbnet(feats, dirs, params, dcfg, block=block,
                                   tick=tick)
        assert sig.shape == (n,) and rgb.shape == (n, 3)
        np.testing.assert_allclose(np.asarray(sig), want_sigma, rtol=2e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(rgb), want_rgb, atol=2e-6)
    # the program's plain decoder (the reference backend) agrees too
    sig_m, rgb_m = mlp.decode(params, feats, dirs, dcfg)
    np.testing.assert_allclose(np.asarray(sig_m), want_sigma, rtol=2e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(rgb_m), want_rgb, atol=2e-6)


def test_rgbnet_shift_gives_alpha_init_at_zero_density():
    """DVGO's ``act_shift``: a raw density of 0 gives ``alpha_init`` over
    one voxel of travel."""
    dcfg = mlp.DecoderCfg(mode="rgbnet", in_channels=13, hidden=128,
                          alpha_init=1e-2, voxel_size=2.0 / 159)
    sigma = mlp.density_activation(jnp.zeros((1,)), dcfg)
    alpha = 1.0 - jnp.exp(-sigma * dcfg.voxel_size)
    np.testing.assert_allclose(np.asarray(alpha), [1e-2], rtol=1e-5)
    assert dcfg.pe_dims == 27 and dcfg.rgbnet_in == 39
    assert mlp.decoder_flops(dcfg) == 43520


# ---------------------------------------------------------------------------
# flash attention (LM hot-spot)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,h,kvh,s,d,causal", [
    (2, 4, 2, 256, 64, True),
    (1, 8, 8, 128, 32, True),
    (2, 4, 1, 192, 64, True),
    (1, 2, 2, 128, 64, False),
])
def test_flash_attention(b, h, kvh, s, d, causal):
    q = jax.random.normal(jax.random.key(0), (b, h, s, d))
    k = jax.random.normal(jax.random.key(1), (b, kvh, s, d))
    v = jax.random.normal(jax.random.key(2), (b, kvh, s, d))
    got = ops.mha(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("s,causal", [(100, False), (100, True), (70, False)])
def test_flash_attention_padded_kv_masked(s, causal):
    """Non-block-multiple seq lengths: padded KV rows are masked inside the
    kernel (no silent fallback to the reference implementation)."""
    q = jax.random.normal(jax.random.key(0), (1, 2, s, 32))
    k = jax.random.normal(jax.random.key(1), (1, 2, s, 32))
    v = jax.random.normal(jax.random.key(2), (1, 2, s, 32))
    got = ops.mha(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


def test_flash_attention_block_invariance():
    q = jax.random.normal(jax.random.key(3), (1, 2, 256, 64))
    k = jax.random.normal(jax.random.key(4), (1, 2, 256, 64))
    v = jax.random.normal(jax.random.key(5), (1, 2, 256, 64))
    a = ops.mha(q, k, v, block_q=32, block_k=32)
    b = ops.mha(q, k, v, block_q=128, block_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
