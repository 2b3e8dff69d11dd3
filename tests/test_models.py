"""Per-arch reduced smokes (all 10 assigned archs) + mixer equivalences, and
DVGO's fine stage against the benchmark's plain reference."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import lm, mamba, moe, xlstm
from repro.nerf import models
from repro.optim import adamw_init


def _batch_for(cfg, B=2, S=16):
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (B, S), 0,
                                     cfg.vocab_size),
        "targets": jax.random.randint(jax.random.key(2), (B, S), 0,
                                      cfg.vocab_size),
    }
    if cfg.encoder_layers > 0:
        batch["frame_embeds"] = 0.02 * jax.random.normal(
            jax.random.key(3), (B, cfg.enc_seq_len, cfg.d_model))
    if cfg.num_image_tokens > 0:
        batch["image_embeds"] = 0.02 * jax.random.normal(
            jax.random.key(4), (B, cfg.num_image_tokens, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", registry.list_archs())
def test_arch_smoke_forward_and_train(arch):
    """REDUCED config of the same family: one forward + one train step on
    CPU, asserting output shapes and finiteness (the assignment's smoke)."""
    cfg = registry.get_reduced(arch)
    assert cfg.family == registry.get(arch).family
    params = lm.init_params(cfg, jax.random.key(0))
    B, S = 2, 16
    batch = _batch_for(cfg, B, S)
    loss, metrics = lm.loss_fn(params, batch, cfg)
    assert jnp.isfinite(loss), arch
    h, _ = lm.backbone(params, batch["tokens"], cfg,
                       extra_embeds=batch.get("image_embeds"))
    assert h.shape == (B, S + cfg.num_image_tokens, cfg.d_model)

    step = lm.make_train_step(cfg)
    p2, o2, m = step(params, adamw_init(params), batch, jnp.asarray(0))
    assert jnp.isfinite(m["loss"]), arch
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                                - b.astype(jnp.float32)).sum()),
                     params, p2))
    assert delta > 0.0, arch


@pytest.mark.parametrize("arch", registry.list_archs())
def test_arch_smoke_prefill_decode(arch):
    cfg = registry.get_reduced(arch)
    params = lm.init_params(cfg, jax.random.key(0))
    B, S = 2, 12
    batch = _batch_for(cfg, B, S)
    prefill = lm.make_prefill_step(cfg, cache_len=S + 4)
    logits, caches = prefill(params, batch)
    assert logits.shape == (B, cfg.vocab_size)
    assert jnp.isfinite(logits).all(), arch
    decode = lm.make_decode_step(cfg)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    idx = jnp.asarray(S + cfg.num_image_tokens, jnp.int32)
    logits2, caches = decode(params, caches, tok, idx)
    assert jnp.isfinite(logits2).all(), arch


def test_decode_matches_prefill_dense():
    cfg = registry.get_reduced("qwen2.5-32b")
    params = lm.init_params(cfg, jax.random.key(0))
    B, S = 2, 12
    batch = _batch_for(cfg, B, S)
    logits, caches = lm.make_prefill_step(cfg, cache_len=S + 2)(params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    lg, _ = lm.make_decode_step(cfg)(params, caches, tok,
                                     jnp.asarray(S, jnp.int32))
    batch2 = {"tokens": jnp.concatenate([batch["tokens"], tok], 1)}
    lg2, _ = lm.make_prefill_step(cfg, cache_len=S + 2)(params, batch2)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg2), atol=2e-4)


def test_param_counts_match_published():
    """Full configs land on the published sizes (param accounting)."""
    expect = {
        "llama4-maverick-400b-a17b": (400e9, 17e9),
        "jamba-1.5-large-398b": (398e9, 94e9),
        "qwen2.5-32b": (32.5e9, None),
        "deepseek-coder-33b": (33e9, None),
    }
    for arch, (total, active) in expect.items():
        cfg = registry.get(arch)
        assert abs(cfg.param_count() - total) / total < 0.08, arch
        if active:
            got = cfg.active_param_count()
            assert abs(got - active) / active < 0.08, arch


def test_moe_einsum_equals_streaming():
    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      dtype="float32", moe_num_experts=8, moe_top_k=2,
                      moe_d_ff=48, layer_pattern=(LayerSpec(ffn="moe"),))
    p = moe.moe_init(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (3, 40, 32))
    y1, a1 = moe.moe_einsum(p, x, cfg)
    y2, a2 = moe.moe_streaming(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)
    assert float(a1) == pytest.approx(float(a2))


def test_moe_grad_flows_both_dispatches():
    cfg = ModelConfig(name="t", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=32, vocab_size=64,
                      dtype="float32", moe_num_experts=4, moe_top_k=2,
                      moe_d_ff=24, layer_pattern=(LayerSpec(ffn="moe"),))
    p = moe.moe_init(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, 16))
    for fn in (moe.moe_einsum, moe.moe_streaming):
        g = jax.grad(lambda pp: fn(pp, x, cfg)[0].sum())(p)
        assert float(jnp.abs(g["wg"]).sum()) > 0


def test_mlstm_chunked_equals_scan():
    cfg = ModelConfig(name="x", family="ssm", num_layers=1, d_model=64,
                      num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=64,
                      dtype="float32", xlstm_heads=4,
                      layer_pattern=(LayerSpec(mixer="mlstm", ffn="none"),))
    p = xlstm.mlstm_init(jax.random.key(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.key(1), (2, 32, 64))
    y1, s1 = xlstm.mlstm_scan(p, x, cfg)
    y2, s2 = xlstm.mlstm_chunked(p, x, cfg, chunk=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1.c), np.asarray(s2.c), atol=1e-4)


def test_mamba_chunk_size_invariance_and_decode():
    cfg = ModelConfig(name="m", family="hybrid", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=4, d_ff=64, vocab_size=64,
                      dtype="float32", mamba_d_state=8,
                      layer_pattern=(LayerSpec(mixer="mamba"),))
    p = mamba.mamba_init(jax.random.key(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.key(1), (2, 16, 32))
    y4, s4 = mamba.mamba_chunked(p, x, cfg, chunk=4)
    y16, s16 = mamba.mamba_chunked(p, x, cfg, chunk=16)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y16), atol=1e-5)
    # prefix + decode == full
    _, sp = mamba.mamba_chunked(p, x[:, :15], cfg, chunk=15)
    yd, _ = mamba.mamba_decode(p, x[:, 15:16], cfg, sp)
    np.testing.assert_allclose(np.asarray(yd[:, 0]), np.asarray(y16[:, 15]),
                               atol=1e-5)


def test_local_attention_is_banded():
    """Chunked-local attention ignores tokens beyond the window."""
    from repro.models import attention as attn

    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=4, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32", local_window=8, q_block=16)
    p = attn.attn_init(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 64, 32))
    y1 = attn.attn_train(p, x, cfg, local=True)
    # perturb a token > window away from the last position
    x2 = x.at[:, 10].add(5.0)
    y2 = attn.attn_train(p, x2, cfg, local=True)
    np.testing.assert_allclose(np.asarray(y1[:, -1]), np.asarray(y2[:, -1]),
                               atol=1e-5)  # last token unaffected
    assert float(jnp.abs(y1[:, 10] - y2[:, 10]).max()) > 1e-3


# ---------------------------------------------------------------------------
# DVGO's fine stage against the benchmark's plain reference of it
# ---------------------------------------------------------------------------


def _bench_fine(**over):
    """The benchmark's ``dvgo-fine`` configuration at its ``TINY`` size
    (with ``over``), its architecture module (``bench/arch/dvgo_fine.py``,
    plain ``jax.numpy`` that imports nothing of the program) and the
    harness."""
    import json
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    import run_cell

    cfg = json.loads((bench / "configs" / "dvgo-fine.json").read_text())
    arch = run_cell.load_arch(cfg)
    cfg.update(arch.TINY, **over)
    return cfg, arch, run_cell


@pytest.mark.parametrize("backend", ["reference", "streaming"])
def test_dvgo_fine_query_field_matches_the_plain_reference(backend):
    """The program's fine-stage model (post-activated density channel,
    rgbnet over k0 and the view encoding) against the reference's field,
    on seeded weights: the scene's density baked into the raw channel,
    k0 N(0, 1), rgbnet N(0, 1/fan-in) with biases moved off zero."""
    cfg, arch, run_cell = _bench_fine()
    weights = arch.make_weights(cfg, 2**33 + 5)
    weights["decoder"] = {
        k: v + (0.1 if k.startswith("b") else 0.0)
        for k, v in weights["decoder"].items()}
    rcfg = run_cell.render_config(cfg)
    model, params = arch.build_model(cfg, rcfg, weights)
    if backend == "reference":
        model = models.NerfModel(dataclasses.replace(model.cfg,
                                                     backend="reference"))
    params = model.prepare_streaming(params)
    n = 2048
    pts = jax.random.uniform(jax.random.key(4), (n, 3), minval=-1.0,
                             maxval=1.0)
    dirs = jax.random.normal(jax.random.key(5), (n, 3))
    dirs = dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)
    sigma, rgb = model.query_field(params, pts, dirs)
    want_sigma, want_rgb = arch.field(weights, pts, dirs,
                                      arch.reference_key(cfg), "highest")
    assert float(want_sigma.max()) > 10.0 and float(want_sigma.min()) >= 0.0
    np.testing.assert_allclose(np.asarray(sigma), np.asarray(want_sigma),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rgb), np.asarray(want_rgb),
                               atol=1e-5)


def test_dvgo_fine_fused_tick_matches_the_reference():
    """One session of 4 frames served by the fused tick of the fine-stage
    model, built as the benchmark builds it (``repro.api`` ->
    ``RenderServeEngine``, streaming backend, pooled holes), against the
    plain reference's render of the same window; and the tick's decode
    counters: every row of the pooled hole bucket and of the co-rendered
    references decoded, the bucket's rows past the hole total as
    padding."""
    import traffic
    from repro.serve.render_engine import RenderSession

    import reference

    cfg, arch, run_cell = _bench_fine()
    weights = arch.make_weights(cfg, 2**31 + 9)
    engine = run_cell.build_engine(cfg, weights)
    mix = traffic.load("preview")
    mix["motion"]["step_deg"] = [3.0, 3.0]  # holes at 16x16 pixels
    spec = traffic.sessions(mix, cfg["window"], 7)[0]
    poses = traffic.orbit_poses(spec, mix["motion"])
    sess = RenderSession(sid=0, poses=list(poses))
    metrics = engine.run([sess])
    got = np.stack([np.asarray(f) for f in sess.frames]).reshape(
        len(poses), -1, 3)
    cam = reference.Camera(cfg["res"], cfg["fov_deg"])
    want = reference.render_window(arch, weights, cfg, cam, poses, 0,
                                   len(poses), "highest")
    assert sum(want["hole_counts"]) > 0
    err = np.abs(got - want["frames"])
    assert float(np.percentile(err, 99)) < 1e-5
    assert float(err.mean()) < 1e-6
    dec = metrics["rit_overflow"]["decode"]
    hw, ns = cfg["res"] ** 2, cfg["num_samples"]
    slots, bucket = cfg["num_slots"], cfg["pool_bucket"]
    ticks = metrics["ticks"]
    assert dec["rows"] == ticks * slots * (bucket + hw) * ns
    live_holes = metrics["rit_overflow"]["hole"]["samples"]
    assert dec["pad_rows"] == ticks * slots * bucket * ns - live_holes
    assert 0.0 < dec["pad_share"] < 1.0
