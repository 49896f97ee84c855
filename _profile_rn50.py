"""Dev driver: device-profile the RN50 bench step (fused or unfused)
and print the per-fusion breakdown.

Usage: python _profile_rn50.py [fused(0|1)] [iters]
"""

import sys
import tempfile

import jax
import jax.numpy as jnp
import optax

from rocm_apex_tpu import amp, models, profiler
from rocm_apex_tpu.optimizers import FusedAdam

FUSED = bool(int(sys.argv[1])) if len(sys.argv) > 1 else True
ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 10
BATCH, SIZE = 128, 224


def main():
    model = models.resnet50(
        num_classes=1000, dtype=jnp.bfloat16, fused=FUSED
    )
    x0 = jnp.zeros((BATCH, SIZE, SIZE, 3))
    variables = model.init(jax.random.PRNGKey(0), x0)
    params, batch_stats = variables["params"], variables["batch_stats"]
    optimizer = FusedAdam(1e-3, weight_decay=1e-4)
    params, optimizer, amp_state = amp.initialize(
        params, optimizer, opt_level="O5"
    )
    opt_state = optimizer.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SIZE, SIZE, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (BATCH,), 0, 1000)

    def one_step(carry, _):
        params, batch_stats, opt_state, scaler_states = carry
        st = amp_state.replace(scaler_states=scaler_states)

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x.astype(jnp.bfloat16),
                mutable=["batch_stats"],
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), y
            ).mean()
            return amp.scale_loss(ce, st), (mut["batch_stats"], ce)

        (_, (bs2, ce)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        grads, found_inf = amp.unscale_grads(grads, st)
        st2, skip = amp.update_scale(st, found_inf)
        updates, opt2 = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        new_params = amp.skip_step(skip, new_params, params)
        opt2 = amp.skip_step(skip, opt2, opt_state)
        return (new_params, bs2, opt2, st2.scaler_states), ce

    @jax.jit
    def runN(params, batch_stats, opt_state, scaler_states):
        carry, ces = jax.lax.scan(
            one_step, (params, batch_stats, opt_state, scaler_states),
            None, length=ITERS,
        )
        return carry, ces

    carry, ces = runN(params, batch_stats, opt_state, amp_state.scaler_states)
    float(ces[-1])

    log_dir = tempfile.mkdtemp(prefix="rn50_prof_")
    with profiler.trace(log_dir):
        carry, ces = runN(*carry)
        float(ces[-1])

    stats = profiler.op_stats(log_dir, merge_numeric_suffix=False)
    total = sum(s.total_ms for s in stats if s.name != "while")
    print(f"fused={FUSED} device total (sans while): {total:.1f} ms / "
          f"{ITERS} steps = {total / ITERS:.2f} ms/step")

    import re as _re
    groups = {}
    for s in stats:
        if s.name == "while":
            continue
        kind = _re.sub(r"\.\d+$", "", s.name)
        g = groups.setdefault(kind, [0.0, 0, 0.0])
        g[0] += s.total_ms
        g[1] += s.count
        g[2] = max(g[2], s.tflops_sec)
    print(f"{'ms/step':>8} {'cnt/step':>9} {'tflops':>7}  kind")
    for k, (ms, cnt, tf) in sorted(groups.items(), key=lambda kv: -kv[1][0]):
        if ms / ITERS < 0.05:
            continue
        print(f"{ms / ITERS:8.3f} {cnt / ITERS:9.1f} {tf:7.1f}  {k[:100]}")


if __name__ == "__main__":
    main()
