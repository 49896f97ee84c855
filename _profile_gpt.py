"""Dev driver: device-profile the flagship GPT bench step and print the
per-fusion breakdown.

Usage: python _profile_gpt.py [iters] [--dropout=R] — runs bench.py's
exact step under jax.profiler.trace and aggregates with
profiler.op_stats.  --dropout=0.1 profiles the TRAINING config
(in-kernel attention dropout + rbg hidden-dropout keys), matching
``python bench.py --dropout=0.1``.
"""

import sys

import jax
import jax.numpy as jnp

from rocm_apex_tpu.amp import LossScaler
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel, gpt_loss_fn
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
from rocm_apex_tpu import profiler

BATCH = 16
SEQ = 1024
_pos = [a for a in sys.argv[1:] if not a.startswith("--")]
ITERS = int(_pos[0]) if _pos else 20
DROPOUT = 0.0
for _a in sys.argv[1:]:
    if _a.startswith("--dropout="):
        DROPOUT = float(_a.split("=", 1)[1])


def main():
    cfg = GPTConfig(
        vocab_size=32768,
        hidden_size=1024,
        num_layers=8,
        num_attention_heads=8,
        max_position_embeddings=SEQ,
        hidden_dropout=DROPOUT,
        attention_dropout=DROPOUT,
        tensor_parallel_size=1,
    )
    model = GPTModel(cfg)
    opt = MixedPrecisionAdam(1e-4, weight_decay=0.01)
    scaler = LossScaler(loss_scale="dynamic")

    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (BATCH, SEQ), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    params32 = model.init(jax.random.PRNGKey(1), tokens[:1])
    state = opt.init(params32)
    sstate = scaler.init()
    if DROPOUT > 0.0 and jax.default_backend() == "tpu":
        rng0 = jax.random.key(2, impl="rbg")
    else:
        rng0 = jax.random.PRNGKey(2)

    def one_step(carry, _):
        state, sstate, rng = carry
        rng, step_rng = jax.random.split(rng)

        def loss_fn(params):
            losses = model.apply(
                params, tokens, labels=labels,
                deterministic=DROPOUT == 0.0,
                rngs={"dropout": step_rng} if DROPOUT > 0.0 else None,
            )
            return gpt_loss_fn(losses) * scaler.loss_scale(sstate)

        scaled, grads = jax.value_and_grad(loss_fn)(state.model)
        inv_scale = 1.0 / scaler.loss_scale(sstate)
        state2, found_inf = opt.step_and_probe(
            state, grads, grad_scale=inv_scale
        )
        sstate2, _ = scaler.update(sstate, found_inf)
        return (state2, sstate2, rng), scaled * inv_scale

    @jax.jit
    def runN(state, sstate):
        (state, sstate, _), losses = jax.lax.scan(
            one_step, (state, sstate, rng0), None, length=ITERS, unroll=2
        )
        return state, sstate, losses

    state, sstate, losses = runN(state, sstate)
    float(losses[-1])  # warmup

    import tempfile
    log_dir = tempfile.mkdtemp(prefix="gpt_prof_")
    with profiler.trace(log_dir):
        state, sstate, losses = runN(state, sstate)
        float(losses[-1])

    stats = profiler.op_stats(log_dir, merge_numeric_suffix=False)
    total = sum(s.total_ms for s in stats if s.name != "while")
    print(f"device total (sans while): {total:.1f} ms over {ITERS} steps "
          f"= {total / ITERS:.2f} ms/step")

    hlo = runN.lower(state, sstate).compile().as_text()
    defs = {}
    for line in hlo.splitlines():
        t = line.strip()
        if t.startswith("%") and "= " in t:
            nm = t[1:].split(" ")[0]
            defs.setdefault(nm, t[:240])

    import re as _re

    opnames = {}
    for line in hlo.splitlines():
        t = line.strip()
        if t.startswith("%") and "op_name=" in t:
            nm = t[1:].split(" ")[0]
            m = _re.search(r'op_name="([^"]+)"', t)
            if m:
                opnames[nm] = m.group(1)

    def sig(s):
        d = defs.get(s.name, "")
        m = _re.match(r"%\S+ = (\(?[a-z0-9]+\[[\d,]*\])", d)
        shape = m.group(1) if m else "?"
        op = opnames.get(s.name, "")
        # canonical: strip jit/while/layer indices; mark bwd (transpose)
        op = op.replace("jit(runN)/while/body/closed_call/", "")
        bwd = "transpose(jvp" in op
        op = _re.sub(r"transpose\(jvp\(GPTModel\)\)/", "", op)
        op = _re.sub(r"jvp\(GPTModel\)/", "", op)
        op = _re.sub(r"layer_\d+", "layer", op)
        kind = _re.sub(r"\.\d+$", "", s.name)
        tag = "BWD " if bwd else ""
        return f"{tag}{op or kind} -> {shape}"

    groups = {}
    for s in stats:
        if s.name == "while":
            continue
        k = sig(s)
        g = groups.setdefault(k, [0.0, 0, 0.0])
        g[0] += s.total_ms
        g[1] += s.count
        g[2] = max(g[2], s.tflops_sec)
    print(f"{'ms/step':>8} {'cnt/step':>8} {'tflops':>7}  signature")
    for k, (ms, cnt, tf) in sorted(groups.items(), key=lambda kv: -kv[1][0]):
        if ms / ITERS < 0.04:
            continue
        print(f"{ms / ITERS:8.3f} {cnt / ITERS:8.1f} {tf:7.1f}  {k[:120]}")


if __name__ == "__main__":
    main()
