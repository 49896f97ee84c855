"""Rehearsals of the four-chip cell that is not built yet
(`gpt1p3b-train-dp2tp2`, PERF.md, Open questions): the dp2 x tp2
`shard_map` train step over the library's pieces, as `chip_smoke.py`
phase 3 builds it, from the Cerebras-GPT-1.3B configuration file.

* on four virtual devices at toy width: the mesh path runs, its loss is
  finite and falls, and all four devices hold the state;
* compiled for a described `v5e:2x2` at the published widths and a depth
  of two layers: what the chip's compiler would refuse is refused here.
  `rehearse_full_depth()` is the same at 24 layers, run by hand; what its
  `memory_analysis()` said is in PERF.md as "compiled, not run".

Nothing here is a chip result, and nothing here is part of a cell: the PR
that adds the cell turns `mesh_step` into the `gpt2` family's
`train_setup`.
"""

import importlib.util
import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from benchmarks.families import gpt2

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ = 2048
EVEN_VOCAB = 50258  # 50257 does not split over tp 2: one pad row (assumed)


def cerebras(**overrides):
    config = json.loads(
        (ROOT / "benchmarks/configs/cerebras-gpt-1.3b.json").read_text())
    return dict(config, **overrides)


def mesh_step(config, devices, recompute=False):
    """(mesh, model, init, step): the O5 Adam step on data 2 x tensor 2.
    The state is in the library's idiom for tensor parallelism: declared
    replicated, each device holding its own rank's slice."""
    from rocm_apex_tpu.amp import all_finite
    from rocm_apex_tpu.models.gpt import GPTModel
    from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
    from rocm_apex_tpu.transformer import parallel_state
    from rocm_apex_tpu.transformer.amp import GradScaler

    mesh = parallel_state.initialize_model_parallel(2, 1, devices=devices)
    data, tensor = parallel_state.DATA_AXIS, parallel_state.TENSOR_AXIS
    cfg = gpt2.model_config(
        config, tensor_parallel_size=2, params_dtype=jnp.float32,
        dtype=jnp.bfloat16, checkpoint_activations=recompute)
    model = GPTModel(cfg)
    opt = MixedPrecisionAdam(2e-4, weight_decay=0.1)
    scaler = GradScaler(axis_names=(tensor,))

    def smap(f, in_specs, out_specs):
        return jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)

    def local_init(tokens):
        return opt.init(model.init(jax.random.PRNGKey(1), tokens))

    def local_step(state, sstate, tokens, labels):
        def loss_fn(p):
            mean = model.apply(
                p, tokens, labels=labels, loss_reduction="mean",
                deterministic=True)
            return mean * scaler.loss_scale(sstate)

        scaled, grads = jax.value_and_grad(loss_fn)(state.model)
        inv_scale = 1.0 / scaler.loss_scale(sstate)
        grads = jax.lax.pmean(grads, data)
        sstate2, skip = scaler.update(sstate, ~all_finite(grads))
        state = opt.step(state, grads, grad_scale=inv_scale, skip=skip)
        return state, sstate2, jax.lax.pmean(scaled * inv_scale, data)

    rep, split = P(), P(data)
    init = smap(local_init, (rep,), rep)
    step = smap(local_step, (rep, rep, split, split), (rep, rep, rep))
    return mesh, model, scaler, init, step


def test_dp2_tp2_step_runs_on_four_virtual_devices():
    from rocm_apex_tpu.transformer import parallel_state

    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs four (virtual) devices")
    config = cerebras(
        n_embd=64, n_layer=2, n_head=2, n_inner=256, n_positions=32,
        vocab_size=258)
    try:
        mesh, model, scaler, init, step = mesh_step(config, devices)
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (4, 32), 0, 258, jnp.int32)
        labels = jnp.roll(tokens, -1, axis=1)
        state = jax.jit(init)(tokens[:1])
        sstate = scaler.init()
        losses = []
        run = jax.jit(step)
        for _ in range(3):
            state, sstate, loss = run(state, sstate, tokens, labels)
            losses.append(float(loss))
        holders = {
            shard.device for leaf in jax.tree_util.tree_leaves(state)
            for shard in leaf.addressable_shards}
    finally:
        parallel_state.destroy_model_parallel()
    assert np.all(np.isfinite(losses)), losses
    assert losses[2] < losses[0]  # three Adam steps on one batch
    assert holders == set(devices)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no TPU library (libtpu) is installed here")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")


def compile_for_v5e(topo, layers, batch, recompute):
    """The step compiled for the described four chips: global batch
    ``batch`` sequences of 2048 over data 2. Returns the executable."""
    from jax.experimental.compilation_cache import compilation_cache
    from rocm_apex_tpu.ops import _pallas
    from rocm_apex_tpu.transformer import parallel_state

    on_tpu = _pallas.on_tpu
    _pallas.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        config = cerebras(n_layer=layers, vocab_size=EVEN_VOCAB)
        mesh, model, scaler, init, step = mesh_step(
            config, list(topo.devices), recompute=recompute)
        rep = NamedSharding(mesh, P())
        split = NamedSharding(mesh, P(parallel_state.DATA_AXIS))

        def on(tree, sharding):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=sharding), tree)

        one = jax.ShapeDtypeStruct((1, SEQ), jnp.int32)
        state = on(jax.eval_shape(init, one), rep)
        sstate = on(jax.eval_shape(scaler.init), rep)
        tokens = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32, sharding=split)
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            state, sstate, tokens, tokens).compile()
    finally:
        parallel_state.destroy_model_parallel()
        _pallas.on_tpu = on_tpu
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def test_dp2_tp2_step_compiles_for_v5e_2x2(topo):
    compiled = compile_for_v5e(topo, layers=2, batch=2, recompute=False)
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel"
    assert "all-reduce" in text, "no collective"


def rehearse_full_depth():
    """By hand (`PYTHONPATH=. python tests/benchmarks/test_cell3_rehearsal.py`):
    the 24-layer step for `v5e:2x2` at a few batches, with and without
    recomputation, and what each would hold on a chip."""
    from jax.experimental import topologies

    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    desc = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    for batch, recompute in ((2, False), (4, False), (4, True), (8, True)):
        try:
            m = compile_for_v5e(desc, 24, batch, recompute).memory_analysis()
            print(
                f"global batch {batch} x {SEQ}, recompute {recompute}: "
                f"arguments {m.argument_size_in_bytes / 2**30:.2f} GiB, "
                f"temporaries {m.temp_size_in_bytes / 2**30:.2f} GiB, "
                f"outputs {m.output_size_in_bytes / 2**30:.2f} GiB "
                f"(aliased {m.alias_size_in_bytes / 2**30:.2f}) per chip",
                flush=True)
        except Exception as e:  # noqa: BLE001 - the refusal is the result
            print(f"global batch {batch}, recompute {recompute}: refused: "
                  f"{str(e)[:300]}", flush=True)


if __name__ == "__main__":
    rehearse_full_depth()
