"""Record the scoped device trace that ``test_stage_trace.py`` reads.

    python3 bench/tests/record_scoped_trace.py [out_dir]    # on a TPU

``record_trace.py``'s probes with stage scopes and host spans, traced:
three runs of ``_tick_probe`` (a matmul and a ``tanh`` under the ``warp``
scope; a ``fused_gather_dual`` kernel and a ``sin``, which XLA may fuse
with the ``tanh``, under ``gather``), a 0.2 s
pause outside any span, two runs of ``_prime_probe`` (a ``fori_loop``
under ``warp`` whose trip count is an argument, so the loop stays a
``while``) with a 50 ms ``serve.step`` span and a 20 ms pause between
them inside one ``bench.step`` span, and one ``fused_nerf_mlp`` call.
Then the layout of the trace (planes, lines, the first events of each
line) is printed, to read the expected numbers from by hand.
``data/scoped_trace.xplane.pb`` is the file this wrote on a TPU v5e.
"""
import glob
import json
import os
import sys
import time
from pathlib import Path


def main():
    sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax, jax.numpy as jnp
    from repro.kernels import streaming_pipeline as sp, fused_nerf_mlp
    from repro.nerf import mlp

    print(jax.devices(), jax.devices()[0].device_kind, flush=True)
    num_mv, p, c, cap = 8, 729, 12, 128
    key = jax.random.PRNGKey(0)
    k = jax.random.split(key, 8)
    tbl = jax.random.normal(k[0], (num_mv, p, c))
    ids = jax.random.randint(k[1], (num_mv, 8, cap), 0, p)
    w = jax.random.uniform(k[2], (num_mv, 8, cap))
    hi = jax.lax.Precision.HIGHEST

    def _tick_probe(tbl, ids, w, x):
        with jax.named_scope("warp"):
            a = jnp.tanh(jnp.matmul(x, x, precision=hi)) * 2.0
        with jax.named_scope("gather"):
            oh, orr = sp.fused_gather_dual(tbl, ids, w, ids, w, num_seg=1)
            b = jnp.sin(a) + oh.sum() + orr.sum()
        return b.sum()

    def _prime_probe(x, n):
        with jax.named_scope("warp"):
            y = jax.lax.fori_loop(
                0, n, lambda i, c: jnp.tanh(jnp.matmul(c, x, precision=hi)),
                x)
        return y.sum()

    tick = jax.jit(_tick_probe)
    prime = jax.jit(_prime_probe)
    x = jax.random.normal(k[3], (1024, 1024)) / 32.0
    n = jnp.int32(4)
    dec = mlp.decoder_init(k[4], mlp.DecoderCfg(mode="mlp", in_channels=c, hidden=64))
    feats = jax.random.normal(k[5], (4096, c)); dirs = jax.random.normal(k[6], (4096, 3))
    direnc = mlp._dir_enc(dirs)
    args = (feats, direnc, dec["w1"], dec["b1"][None, :], dec["w2"], dec["b2"][None, :], dec["w_sigma"], dec["w_rgb"], dec["b_rgb"][None, :])
    mlpf = jax.jit(lambda *a: fused_nerf_mlp.fused_nerf_mlp(*a, block=512))
    jax.block_until_ready((tick(tbl, ids, w, x), prime(x, n), mlpf(*args)))
    out = sys.argv[1] if len(sys.argv) > 1 else ".bench_trace/scoped_trace"
    os.makedirs(out, exist_ok=True)
    jax.profiler.start_trace(out)
    for i in range(3):
        jax.block_until_ready(tick(tbl, ids, w, x))
    time.sleep(0.2)
    with jax.profiler.TraceAnnotation("bench.step"):
        jax.block_until_ready(prime(x, n))
        with jax.profiler.TraceAnnotation("serve.step"):
            time.sleep(0.05)
        time.sleep(0.02)
        jax.block_until_ready(prime(x, n))
    jax.block_until_ready(mlpf(*args))
    jax.profiler.stop_trace()
    files = glob.glob(out + "/**/*.xplane.pb", recursive=True)
    print("files", files, [os.path.getsize(f) for f in files])
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[0])
    for plane in pd.planes:
        print("PLANE", plane.name, "stats", [(s[0], str(s[1])[:60]) for s in plane.stats][:10])
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", line.name, len(evs))
            for e in evs[:6]:
                print("    EV", e.name, e.start_ns, e.duration_ns, [(s[0], str(s[1])[:100]) for s in e.stats][:12])
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            print("    NAMES", json.dumps(dict(list(names.items())[:60])))


if __name__ == "__main__":
    main()
