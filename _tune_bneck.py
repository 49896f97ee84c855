"""Dev driver: isolate the fused-bottleneck kernels at RN50 stage
shapes, time them with scan (cancels the per-dispatch overhead), and
sweep the block-size knobs.

Usage: python _tune_bneck.py [stage ...] [--sweep]
"""

import sys
import time

import jax
import jax.numpy as jnp

import rocm_apex_tpu.ops.fused_bottleneck as fb

STAGES = {
    "l1": (128, 56, 56, 64, 256),
    "l2": (128, 28, 28, 128, 512),
    "l3": (128, 14, 14, 256, 1024),
    "l4": (128, 7, 7, 512, 2048),
}
ITERS = 30


def scan_time(make_step, init):
    """ms/iter via scan-length differencing (bench.py idiom)."""
    def run(n):
        @jax.jit
        def f(c):
            return jax.lax.scan(lambda c, _: (make_step(c), None),
                                c, None, length=n)[0]
        return f

    f1, f2 = run(ITERS), run(2 * ITERS)
    c = f1(init)
    jax.tree_util.tree_map(
        lambda t: float(t.reshape(-1)[0].astype(jnp.float32)), c)
    c = f2(init)
    float(jax.tree_util.tree_leaves(c)[0].reshape(-1)[0].astype(jnp.float32))

    def best(f):
        ts = []
        for _ in range(2):
            t0 = time.perf_counter()
            r = f(init)
            float(jax.tree_util.tree_leaves(r)[0].reshape(-1)[0]
                  .astype(jnp.float32))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    return max(best(f2) - best(f1), 1e-9) / ITERS * 1000


def bench_stage(st):
    n, h, w_, c, cout = STAGES[st]
    m = n * h * w_
    key = jax.random.PRNGKey(0)
    x4 = (jax.random.normal(key, (n, h, w_, c)) * 0.5).astype(jnp.bfloat16)
    w3 = (jax.random.normal(key, (3, 3, c, c)) * 0.05).astype(jnp.bfloat16)
    w1 = (jax.random.normal(key, (c, cout)) * 0.05).astype(jnp.bfloat16)
    a = jnp.ones((c,), jnp.float32)
    b = jnp.zeros((c,), jnp.float32)
    mu = jnp.zeros((c,), jnp.float32)
    rs = jnp.ones((c,), jnp.float32)
    gbmap = m * c * 2 / 1e9

    fb31 = lambda x: fb.conv3x3_bn_act(x, w3, a, b, stats=True)
    def step_c3f(x):
        y, (s1, s2) = fb31(x)
        return x + (s1[0] * 1e-30).astype(x.dtype)
    t = scan_time(step_c3f, x4)
    print(f"{st} conv3x3 fwd: {t:7.3f} ms ({2*gbmap/(t/1e3):5.0f} GB/s)")

    def step_c3x(x):
        y = jax.lax.conv_general_dilated(
            x, w3, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return x + (jnp.sum(y[0, 0, 0, :1]) * 1e-30).astype(x.dtype)
    t = scan_time(step_c3x, x4)
    print(f"{st} conv3x3 XLA: {t:7.3f} ms ({2*gbmap/(t/1e3):5.0f} GB/s)")

    def step_c3b(x):
        g, dw, r1, r2 = fb.conv3x3_bn_act_bwd(
            x, w3, x, None, (a, b), (mu, rs))
        return x + (r1[:1] * 1e-30).astype(x.dtype)
    t = scan_time(step_c3b, x4)
    print(f"{st} conv3x3 bwd: {t:7.3f} ms ({3*gbmap/(t/1e3):5.0f} GB/s)")

    x2 = x4.reshape(m, c)
    def step_m1(x):
        y, (s1, s2) = fb.conv1x1_bn_act(x, w1, a, b, stats=True)
        return x + (s1[0] * 1e-30).astype(x.dtype)
    t = scan_time(step_m1, x2)
    tr = gbmap * (1 + cout / c)
    print(f"{st} conv1x1 fwd: {t:7.3f} ms ({tr/(t/1e3):5.0f} GB/s)")

    e_big = jnp.ones((m, cout), jnp.bfloat16)
    def step_m1b(e):
        g, dw, r1, r2 = fb.conv1x1_bn_act_bwd(
            e, w1, x2, prologue=(a, b), reduce_stats=(mu, rs))
        return e + (r1[:1] * 1e-30).astype(e.dtype)
    t = scan_time(step_m1b, e_big)
    tr = gbmap * (2 + 2 * cout / c)
    print(f"{st} conv1x1 bwd: {t:7.3f} ms ({tr/(t/1e3):5.0f} GB/s)")
    print(flush=True)


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    for kv in (a for a in sys.argv[1:] if a.startswith("--set=")):
        k, v = kv[6:].split(":")
        fb.config[k] = int(v)
    print("config:", fb.config, flush=True)
    for st in args or list(STAGES):
        bench_stage(st)
