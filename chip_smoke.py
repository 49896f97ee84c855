#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the two halves of the main path once each, at the full width of
the 134M GPT (`rocm_apex_tpu/models/gpt_134m.py`), through the entry
points a user calls, in ONE process and with random weights made from a
seed:

  phase 0  device: a TPU whose `device_kind` is in the peaks table, or
           the run stops before any model is built
  phase 1  trainer: the O5 train step (bf16 compute, fp32 masters in
           `MixedPrecisionAdam`, dynamic `LossScaler`, fused LM head,
           dropout 0.1 under the 'rbg' key), jitted and donated, on a
           fixed batch; then one packed-optimizer update against the
           tree update it must agree with
  phase 2  server: `InferenceEngine` over a paged cache with donated
           buffers, two waves of greedy requests driven through
           `add_request` / `step()`, checked against a float32
           full-sequence forward of the same weights with `jax.numpy`
           attention
  phase 3  (four or more chips) the phase-1 model on the dp2 x tp2 mesh
           of `parallel_state.initialize_model_parallel(2, 1)`

Any failed check raises; nothing is caught. The last line of standard
output is the pass line, one JSON object naming the device, and it is
printed only after the last phase. `--cpu-rehearsal` runs the same code
at toy shapes on whatever backend JAX has, checks no Mosaic kernels
(off the chip the kernels run in the Pallas interpreter) and prints no
pass line; it exists to debug the script before spending chip time.

The compile cache goes where `JAX_COMPILATION_CACHE_DIR` says, or to
`.jax_cache` in the checkout; the counters printed at the end say how
much of the run compiled.
"""

import argparse
import dataclasses
import importlib.metadata
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from rocm_apex_tpu import monitor
from rocm_apex_tpu.amp import LossScaler, all_finite
from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.inference.engine import shard_tp1_params
from rocm_apex_tpu.models import gpt_134m
from rocm_apex_tpu.models.gpt import GPTModel
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
from rocm_apex_tpu.optimizers.packed import PackedOptimizerStep
from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.amp import GradScaler
from rocm_apex_tpu.utils.compile_cache import (
    CompileCacheCounters,
    enable_compile_cache,
)

TRAIN_STEPS = 8  # after the compiling call
SERVE_REQUESTS = 12  # per wave
SERVE_NEW_TOKENS = 32
# The engine computes in bf16; the reference forward is float32. A token
# the engine picked must score within this many logit units of the
# reference's best token (logits here have a standard deviation of ~0.6;
# a wrong page or mask moves the pick by whole units).
SERVE_LOGIT_TOL = 0.1
# tp=1 on one chip against dp2 x tp2 on four, same weights, no dropout:
# the two differ only in bf16 summation order
MESH_LOSS_TOL = 2e-2
# packed (Mosaic) against tree (XLA) Adam, one step from the same fp32
# masters: a few ulps of the update
PACKED_RTOL, PACKED_ATOL = 1e-5, 1e-7

# Mosaic kernels each compiled program must contain on the chip
TRAIN_KERNELS = (
    "_fwd_single_kernel",  # flash attention forward, in-kernel dropout
    "_bwd_merged_kernel",  # flash attention backward
    "_ln_fwd_kernel",  # residual + dropout + LayerNorm
    "_ln_bwd_kernel",
)
SERVE_KERNELS = (
    "_decode_paged_kernel",  # paged decode / chunk-against-cache
    "_seg_fwd_kernel",  # intra-chunk causal attention
    "_ln_fwd_kernel",
)


@dataclasses.dataclass(frozen=True)
class Sizes:
    width: dict  # overrides on gpt_134m.WIDTH
    batch: int
    seq: int
    slots: int
    capacity: int
    page_size: int
    budget: int
    prompt_lens: tuple


FULL = Sizes(
    width={},
    batch=gpt_134m.TRAIN_BATCH,
    seq=gpt_134m.TRAIN_SEQ,
    slots=gpt_134m.SERVE_SLOTS,
    capacity=gpt_134m.SERVE_CAPACITY,
    page_size=gpt_134m.SERVE_PAGE_SIZE,
    budget=gpt_134m.SERVE_PREFILL_BUDGET,
    prompt_lens=gpt_134m.SERVE_PROMPT_LENS,
)
TOY = Sizes(
    width=dict(
        vocab_size=512, hidden_size=128, num_layers=2, num_attention_heads=2
    ),
    batch=4,
    seq=128,
    slots=4,
    capacity=128,
    page_size=16,
    budget=32,
    prompt_lens=(4, 8, 16, 32, 90),
)


def say(msg):
    print(msg, flush=True)


class MosaicAudit:
    """Reads the StableHLO of every program JAX compiles (dumped to a
    scratch directory) and reports the Mosaic kernels in each: the
    check that nothing on the chip path fell back to the interpreter or
    to a reference path."""

    def __init__(self, enforce):
        self.enforce = enforce  # off for the CPU rehearsal
        self.dir = pathlib.Path(tempfile.mkdtemp(prefix="chip_smoke_ir_"))
        jax.config.update("jax_dump_ir_to", str(self.dir))
        self._seen = set()

    def new_programs(self):
        """{program name: {kernel name: count}} for programs compiled
        since the last call that hold at least one `tpu_custom_call`."""
        found = {}
        for path in sorted(self.dir.glob("*_compile.mlir")):
            if path in self._seen:
                continue
            self._seen.add(path)
            text = path.read_text(errors="replace")
            if "tpu_custom_call" not in text:
                continue
            name = re.sub(r"^jax_ir\d+_|_compile$", "", path.stem)
            kernels = found.setdefault(name, {})
            for k in re.findall(r'kernel_name = "([^"]*)"', text):
                kernels[k] = kernels.get(k, 0) + 1
        return found

    def require(self, phase, program_pattern, kernels):
        programs = self.new_programs()
        for name, ks in programs.items():
            say(f"[{phase}] mosaic kernels in {name}: {ks}")
        if not self.enforce:
            return
        matches = [
            ks for name, ks in programs.items()
            if re.search(program_pattern, name)
        ]
        if not matches:
            raise AssertionError(
                f"{phase}: no compiled program matching "
                f"{program_pattern!r} holds a tpu_custom_call "
                f"(programs with kernels: {sorted(programs)})"
            )
        for want in kernels:
            if not any(want in ks for ks in matches):
                raise AssertionError(
                    f"{phase}: Mosaic kernel {want} missing from "
                    f"{program_pattern!r}: the chip path fell back"
                )

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def phase0_device(rehearsal):
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not rehearsal and jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: no accelerator (JAX found {device}); "
            f"nothing was run"
        )
    versions = {"jax": jax.__version__}
    for pkg in ("jaxlib", "libtpu"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = "absent"
    say(f"[phase 0] device {device} versions {versions}")
    if not rehearsal:
        # a device outside the one peaks table raises here
        peak_flops, peak_bytes = monitor.chip_peaks(device["kind"])
        say(
            f"[phase 0] peaks table row: {peak_flops:.3g} FLOP/s, "
            f"{peak_bytes:.3g} B/s"
        )
    return device


def train_pieces(sizes, tensor_parallel_size=1):
    """(cfg, optimizer) of the O5 recipe at ``sizes``."""
    cfg = gpt_134m.train_config(
        seq=sizes.seq, tensor_parallel_size=tensor_parallel_size,
        **sizes.width,
    )
    opt = MixedPrecisionAdam(
        gpt_134m.LEARNING_RATE, weight_decay=gpt_134m.WEIGHT_DECAY
    )
    return cfg, opt


def loss_and_grads(model, scaler, sstate, params, tokens, labels, step_rng):
    """(unscaled loss, scaled grads, 1/scale) of one dropout-on step."""

    def loss_fn(p):
        mean = model.apply(
            p, tokens, labels=labels, loss_reduction="mean",
            deterministic=False, rngs={"dropout": step_rng},
        )
        return mean * scaler.loss_scale(sstate)

    scaled, grads = jax.value_and_grad(loss_fn)(params)
    inv_scale = 1.0 / scaler.loss_scale(sstate)
    return scaled * inv_scale, grads, inv_scale


def fixed_batch(cfg, sizes):
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (sizes.batch, sizes.seq), 0, cfg.vocab_size
    )
    return tokens, jnp.roll(tokens, -1, axis=1)


def check_losses(phase, losses, skipped):
    say(f"[{phase}] losses {[round(x, 4) for x in losses]} skipped {skipped}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{phase}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{phase}: loss did not fall: {losses[0]} -> {losses[-1]}"
        )
    if not skipped < len(losses):
        raise AssertionError(f"{phase}: every step was skipped")


def phase1_trainer(sizes, audit):
    cfg, opt = train_pieces(sizes)
    model = GPTModel(cfg)
    scaler = LossScaler(loss_scale="dynamic")
    tokens, labels = fixed_batch(cfg, sizes)

    def train_step(state, sstate, rng, tokens, labels):
        rng, step_rng = jax.random.split(rng)
        loss, grads, inv_scale = loss_and_grads(
            model, scaler, sstate, state.model, tokens, labels, step_rng
        )
        state, found_inf = opt.step_and_probe(
            state, grads, grad_scale=inv_scale
        )
        sstate, _ = scaler.update(sstate, found_inf)
        return state, sstate, rng, loss, found_inf

    def eval_loss(params, tokens, labels):
        return model.apply(
            params, tokens, labels=labels, loss_reduction="mean",
            deterministic=True,
        )

    params32 = model.init(jax.random.PRNGKey(1), tokens[:1])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params32))
    state = opt.init(params32)
    sstate = scaler.init()
    rng = gpt_134m.dropout_key(cfg.hidden_dropout)
    # before the first donating call: state.master aliases params32
    loss0 = float(jax.jit(eval_loss)(state.model, tokens, labels))
    say(f"[phase 1] {n_params / 1e6:.1f}M parameters, eval loss {loss0:.4f}")

    step = jax.jit(train_step, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    state, sstate, rng, loss, bad = step(state, sstate, rng, tokens, labels)
    losses, skipped = [float(loss)], int(bad)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    pending = []
    for _ in range(TRAIN_STEPS):
        state, sstate, rng, loss, bad = step(
            state, sstate, rng, tokens, labels
        )
        pending.append((loss, bad))
    jax.block_until_ready(pending)
    step_ms = (time.perf_counter() - t0) / TRAIN_STEPS * 1e3
    losses += [float(l) for l, _ in pending]
    skipped += sum(int(b) for _, b in pending)
    check_losses("phase 1", losses, skipped)
    audit.require("phase 1", r"train_step", TRAIN_KERNELS)
    stats = jax.devices()[0].memory_stats() or {}
    say(
        f"[phase 1] first call (compile + step) {compile_s:.1f} s, then "
        f"{step_ms:.1f} ms per step over {TRAIN_STEPS} steps, "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
    )
    del state, pending

    # The O5 recipe's update is XLA-fused tree math (optimizers/mixed.py
    # says why). The packed optimizer's kernels are the Mosaic route to
    # the same update: one step of each from the same masters.
    params32 = model.init(jax.random.PRNGKey(1), tokens[:1])
    grads = jax.tree_util.tree_map(
        lambda p: (p * 1e-3 + 1e-5).astype(cfg.dtype), params32
    )
    packed = PackedOptimizerStep(
        "adam", gpt_134m.LEARNING_RATE, weight_decay=gpt_134m.WEIGHT_DECAY
    )

    def packed_update(state, grads):
        state, bad = packed.step_and_probe(state, grads, grad_scale=0.5)
        return packed.masters(state), bad

    def tree_update(state, grads):
        state, bad = opt.step_and_probe(state, grads, grad_scale=0.5)
        return state.master, bad

    got, bad_p = jax.jit(packed_update)(packed.init(params32), grads)
    want, bad_t = jax.jit(tree_update)(opt.init(params32), grads)
    if bool(bad_p) or bool(bad_t):
        raise AssertionError("phase 1: optimizer probe flagged finite grads")
    worst = 0.0
    for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    ):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_allclose(g, w, rtol=PACKED_RTOL, atol=PACKED_ATOL)
        worst = max(worst, float(np.max(np.abs(g - w))))
    say(f"[phase 1] packed vs tree Adam masters: max abs diff {worst:.3g}")
    audit.require("phase 1", r"packed_update", ())
    return loss0


def drive(engine, prompts):
    for prompt in prompts:
        engine.add_request(prompt, max_new_tokens=SERVE_NEW_TOKENS)
    results = []
    while engine.has_work():
        results.extend(engine.step())
    return results


def phase2_server(sizes, audit, on_chip):
    cfg = gpt_134m.serve_config(
        max_position_embeddings=sizes.capacity, **sizes.width
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    engine = InferenceEngine(
        model, params,
        num_slots=sizes.slots, capacity=sizes.capacity,
        sampling=SamplingParams(temperature=0.0), seed=0,
        prefill_token_budget=sizes.budget,
        paged=True, page_size=sizes.page_size,
        retrace_policy="raise",
    )
    if on_chip and not engine.donate_buffers:
        raise AssertionError("phase 2: the engine did not donate on a TPU")

    rng = np.random.RandomState(0)

    def wave():
        # every prompt length appears; the rest are drawn
        lens = list(sizes.prompt_lens) + list(
            rng.choice(sizes.prompt_lens, SERVE_REQUESTS - len(sizes.prompt_lens))
        )
        return [
            rng.randint(1, cfg.vocab_size, size=int(n)).tolist() for n in lens
        ]

    t0 = time.perf_counter()
    first = drive(engine, wave())
    wave1_s = time.perf_counter() - t0
    audit.require("phase 2", r"_mixed", SERVE_KERNELS)
    # warmed up: from here a compile is a failure (the sentinel raises
    # out of step())
    engine.reset_stats()
    t0 = time.perf_counter()
    second = drive(engine, wave())
    wave2_s = time.perf_counter() - t0
    late = audit.new_programs()
    if late:
        raise AssertionError(f"phase 2: compiled in the second wave: {late}")

    results = first + second
    if len(results) != 2 * SERVE_REQUESTS:
        raise AssertionError(f"phase 2: {len(results)} results")
    for r in results:
        if r.finish_reason != "length" or len(r.tokens) != SERVE_NEW_TOKENS:
            raise AssertionError(
                f"phase 2: request {r.request_id} ended "
                f"{r.finish_reason!r} after {len(r.tokens)} tokens"
            )
        if min(r.tokens) < 0 or max(r.tokens) >= cfg.vocab_size:
            raise AssertionError(f"phase 2: token id out of range: {r}")
    if engine.mixed_trace_count != 1:
        raise AssertionError(
            f"phase 2: mixed_trace_count {engine.mixed_trace_count}"
        )
    if engine.retrace_sentinel.tripped:
        raise AssertionError(
            f"phase 2: compiles after warm-up: "
            f"{engine.retrace_sentinel.status()}"
        )
    if engine.pages_used != 0:
        raise AssertionError(f"phase 2: {engine.pages_used} pages leaked")

    # Reference: a float32 forward of the same weights over prompt +
    # generated tokens with jax.numpy attention (no cache, no attention
    # kernel, full-precision matmuls). Each token the
    # engine picked must be the reference's best, or within the bf16
    # tolerance of it. Checked on the shortest and the longest prompt
    # (single-chunk and multi-chunk prefill, one page and many).
    ref_model = GPTModel(
        dataclasses.replace(cfg, attention_impl="jnp", dtype=jnp.float32)
    )

    @jax.jit
    def ref_logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            return ref_model.apply(params, tokens).astype(jnp.float32)

    by_len = sorted(results, key=lambda r: len(r.prompt))
    checked = (by_len[0], by_len[-1])
    # one padded length, one compile: under the causal mask a position's
    # logits do not depend on what follows it
    width = max(len(r.prompt) + len(r.tokens) for r in checked)
    worst, exact, total = 0.0, 0, 0
    for r in checked:
        seq = r.prompt + r.tokens
        padded = jnp.asarray([seq + [0] * (width - len(seq))], jnp.int32)
        logits = np.asarray(ref_logits(params, padded))[0]
        rows = logits[len(r.prompt) - 1 : len(seq) - 1]
        if not np.all(np.isfinite(rows)):
            raise AssertionError("phase 2: non-finite reference logits")
        gaps = rows.max(axis=-1) - rows[np.arange(len(r.tokens)), r.tokens]
        worst = max(worst, float(gaps.max()))
        exact += int((gaps == 0).sum())
        total += len(r.tokens)
    say(
        f"[phase 2] engine tokens vs float32 reference: {exact}/{total} "
        f"are the reference argmax, worst logit gap {worst:.4f} "
        f"(tolerance {SERVE_LOGIT_TOL})"
    )
    if worst > SERVE_LOGIT_TOL:
        raise AssertionError(
            f"phase 2: engine token {worst:.4f} logits below the "
            f"reference's best"
        )
    say(
        f"[phase 2] {len(results)} requests, wave 1 (compiles) "
        f"{wave1_s:.1f} s, wave 2 {wave2_s:.1f} s, "
        f"donate_buffers={engine.donate_buffers}, "
        f"mixed_trace_count={engine.mixed_trace_count}, pages back"
    )


def phase3_mesh(sizes, audit, on_chip, loss_one_chip):
    """The phase-1 model and batch on dp2 x tp2, from the same tp=1
    weights cut into tensor-parallel shards."""
    devices = jax.devices()[:4]
    mesh = parallel_state.initialize_model_parallel(2, 1, devices=devices)
    data, tensor = parallel_state.DATA_AXIS, parallel_state.TENSOR_AXIS
    cfg1, opt = train_pieces(sizes)
    cfg, _ = train_pieces(sizes, tensor_parallel_size=2)
    model = GPTModel(cfg)
    scaler = GradScaler(axis_names=(tensor,))
    tokens, labels = fixed_batch(cfg, sizes)
    params1 = GPTModel(cfg1).init(jax.random.PRNGKey(1), tokens[:1])
    params = shard_tp1_params(model, params1, mesh, tokens[:1])

    def smap(f, in_specs, out_specs):
        return jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def local_eval(state, tokens, labels):
        loss = model.apply(
            state.model, tokens, labels=labels, loss_reduction="mean",
            deterministic=True,
        )
        return jax.lax.pmean(loss, data)

    def local_step(state, sstate, rng, tokens, labels):
        rng, step_rng = jax.random.split(rng)
        # each data-parallel rank draws its own masks
        step_rng = jax.random.fold_in(step_rng, jax.lax.axis_index(data))
        loss, grads, inv_scale = loss_and_grads(
            model, scaler, sstate, state.model, tokens, labels, step_rng
        )
        grads = jax.lax.pmean(grads, data)
        found_inf = ~all_finite(grads)
        sstate2, skip = scaler.update(sstate, found_inf)
        state = opt.step(state, grads, grad_scale=inv_scale, skip=skip)
        return state, sstate2, rng, jax.lax.pmean(loss, data), skip

    rep, split = P(), P(data)
    state = jax.jit(smap(opt.init, (rep,), rep))(params)
    sstate = scaler.init()
    rng = gpt_134m.dropout_key(cfg.hidden_dropout)
    loss0 = float(
        jax.jit(smap(local_eval, (rep, split, split), rep))(
            state, tokens, labels
        )
    )
    say(
        f"[phase 3] eval loss on dp2 x tp2 {loss0:.4f}, on one chip "
        f"{loss_one_chip:.4f}"
    )
    if abs(loss0 - loss_one_chip) > MESH_LOSS_TOL:
        raise AssertionError(
            f"phase 3: dp2 x tp2 loss {loss0} != one-chip loss "
            f"{loss_one_chip} (tolerance {MESH_LOSS_TOL})"
        )

    step = jax.jit(
        smap(local_step, (rep, rep, rep, split, split), (rep,) * 5),
        donate_argnums=(0, 1),
    )
    losses, skipped = [], 0
    for _ in range(1 + TRAIN_STEPS):
        state, sstate, rng, loss, skip = step(
            state, sstate, rng, tokens, labels
        )
        losses.append(float(loss))
        skipped += int(skip)
    check_losses("phase 3", losses, skipped)
    audit.require("phase 3", r"local_step", TRAIN_KERNELS)

    holders = {
        shard.device
        for leaf in jax.tree_util.tree_leaves(state)
        for shard in leaf.addressable_shards
    }
    if holders != set(devices):
        raise AssertionError(f"phase 3: state lives on {holders}")
    if on_chip:
        in_use = {d.id: d.memory_stats()["bytes_in_use"] for d in devices}
        say(f"[phase 3] bytes_in_use per device {in_use}")
        if min(in_use.values()) <= 0:
            raise AssertionError("phase 3: a device holds no buffers")
    say(f"[phase 3] mesh {dict(mesh.shape)} over {[d.id for d in devices]}")
    parallel_state.destroy_model_parallel()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="toy shapes on any backend, no Mosaic checks, no pass line: "
             "for debugging this script off the chip",
    )
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    on_chip = not rehearsal

    device = phase0_device(rehearsal)
    sizes = TOY if rehearsal else FULL
    say(f"[phase 0] compile cache at {enable_compile_cache()}")
    cache = CompileCacheCounters()
    audit = MosaicAudit(enforce=on_chip)
    try:
        loss_one_chip = phase1_trainer(sizes, audit)
        say(f"[phase 1] ok; compile cache so far {cache.counts}")
        phase2_server(sizes, audit, on_chip)
        say(f"[phase 2] ok; compile cache so far {cache.counts}")
        if device["count"] >= 4:
            phase3_mesh(sizes, audit, on_chip, loss_one_chip)
            say(f"[phase 3] ok; compile cache so far {cache.counts}")
        else:
            say(f"[phase 3] not run: {device['count']} device(s) visible")
    finally:
        audit.close()
    say(f"compile cache: {cache.counts}")
    if rehearsal:
        say("rehearsal finished: no pass line off the chip")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
