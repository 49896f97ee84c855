"""Megatron-style GPT pretraining: TP x DP over the device mesh.

The analogue of the reference's transformer bring-up scripts
(reference: tests/L0/run_transformer/run_megatron_gpt_pipeline.py +
apex/transformer/testing/standalone_gpt.py driven by the Megatron
argument system). One process drives the whole mesh: tensor-parallel
layers shard over the ``tensor`` axis inside `shard_map`, gradients
psum over ``data``, the mixed-precision Adam state (bf16 model + fp32
masters) updates under dynamic loss scaling with model-parallel-aware
found_inf sync.

CPU smoke (2-way TP x 4-way DP):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/gpt_train.py --tensor-model-parallel-size 2 \
        --num-layers 2 --hidden-size 64 --num-attention-heads 4 \
        --seq-length 32 --micro-batch-size 2 --train-iters 4
"""

import hashlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import optax

from rocm_apex_tpu.amp import all_finite
from rocm_apex_tpu.checkpoint import CheckpointManager
from rocm_apex_tpu.contrib.optimizers import distributed_fused_adam
from rocm_apex_tpu.models import gpt_134m
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel, gpt_loss_fn
from rocm_apex_tpu.monitor import (
    SLO,
    BurnRule,
    FlightRecorder,
    JsonlWriter,
    MetricRegistry,
    Metrics,
    MetricsLogger,
    RegistryWriter,
    SLOMonitor,
    Tracer,
    group_nonfinite,
    model_flops,
    start_exporter,
    tree_norm,
)
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
from rocm_apex_tpu.optimizers.packed import PackedOptimizerStep
from rocm_apex_tpu.transformer import parallel_state
from rocm_apex_tpu.transformer.amp import GradScaler
from rocm_apex_tpu.transformer.testing import parse_args
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache


def _observability_args(parser):
    g = parser.add_argument_group(title="observability")
    g.add_argument(
        "--trace", type=str, default=None, metavar="PATH",
        help="export a Chrome trace-event JSON of the run's step "
             "spans (monitor.Tracer; load in Perfetto)",
    )
    g.add_argument(
        "--flight-recorder", type=str, default=None, metavar="PATH",
        const="nan_dump.jsonl", nargs="?",
        help="arm the numerics flight recorder: per-param-group "
             "nonfinite probes ride the step metrics and a NaN/Inf "
             "anomaly dumps a jsonl bundle to PATH "
             "(monitor.FlightRecorder)",
    )
    g.add_argument(
        "--metrics-port", type=int, default=None, metavar="PORT",
        help="serve /metrics (Prometheus text), /healthz, /varz on "
             "127.0.0.1:PORT for the run's telemetry registry "
             "(monitor.RegistryWriter mirror of every flushed "
             "scalar); 0 = ephemeral, the bound port prints on the "
             "'metrics:' line",
    )
    g.add_argument(
        "--slo", type=float, default=None, const=-1.0, nargs="?",
        metavar="MS",
        help="arm a step-time SLO (objective: 90%% of steps finish "
             "within MS milliseconds) with Google-SRE multi-window "
             "burn-rate alerting (monitor.SLOMonitor); omit MS to "
             "auto-set the threshold to 3x the first logging "
             "window's mean step time. Alerts print at the end and "
             "ride /varz when --metrics-port is set",
    )
    # the example's own default is no dropout (the schema's is 0.1);
    # --hidden-dropout / --attention-dropout turn it on
    parser.set_defaults(hidden_dropout=0.0, attention_dropout=0.0)
    g0 = parser.add_argument_group(title="model (examples)")
    g0.add_argument(
        "--vocab-size", type=int, default=8192,
        help="vocabulary size (the argument schema takes it from a "
             "tokenizer file; the example trains on random ids)",
    )
    g2 = parser.add_argument_group(title="distributed optimizer")
    g2.add_argument(
        "--dist-opt", action="store_true",
        help="shard the Adam state over the data-parallel axis "
             "(contrib.optimizers.distributed_fused_adam: "
             "reduce-scatter grads -> 1/dp-sharded update -> "
             "allgather params, the reference DistributedFusedAdam "
             "semantics); composes with the dynamic loss scaler — the "
             "unscale + found_inf probe runs fused on the packed grad "
             "buffers before the reduce-scatter, and the scaler's "
             "halve/grow logic reads the optimizer-reported flag",
    )
    g2.add_argument(
        "--comm-dtype", default="fp32", choices=("fp32", "int8"),
        help="wire dtype for the ring collectives: int8 quantizes each "
             "hop with per-row fp32 scale sidecars "
             "(ops/quantized_collectives.py) — under --dist-opt the "
             "ZeRO grad reduce-scatter and param all-gather, under "
             "--collective-matmul the TP-boundary rings; fp32 keeps "
             "the plain full-precision collectives",
    )
    g3 = parser.add_argument_group(title="checkpointing (examples)")
    g3.add_argument(
        "--checkpoint-dir", type=str, default=None, metavar="DIR",
        help="enable stepped checkpoints + autoresume "
             "(checkpoint.CheckpointManager): restore the latest step "
             "in DIR if one exists, save every --save-interval iters "
             "(final iter always), and save-and-exit cleanly on "
             "SIGTERM. The saved tree is the FULL training state — "
             "fp32 masters / Adam moments (incl. the ZeRO shards and "
             "their implicit int8-comm error-feedback residuals under "
             "--dist-opt --comm-dtype int8) and the loss-scaler "
             "counters — so a killed run resumes bitwise",
    )
    g2.add_argument(
        "--packed-update", action="store_true",
        help="run the optimizer step over packed dtype-group buffers "
             "(optimizers.PackedOptimizerStep): one-pass unscale + "
             "found_inf + Adam update per dtype buffer, O(dtype-groups) "
             "traced equations instead of O(leaves); ignored under "
             "--dist-opt (the ZeRO path is always packed)",
    )
    return parser


def main():
    args = parse_args(
        extra_args_provider=_observability_args,
        defaults=dict(
            num_layers=4, hidden_size=256, num_attention_heads=8,
            seq_length=256, max_position_embeddings=256,
            micro_batch_size=4, train_iters=20, lr=1e-4, log_interval=5,
        ),
        ignore_unknown_args=True,
    )
    tp = args.tensor_model_parallel_size
    mesh = parallel_state.initialize_model_parallel(tp, 1)
    dp = parallel_state.get_data_parallel_world_size()
    print(f"mesh: data={dp} x tensor={tp}")

    cfg = GPTConfig(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_attention_heads=args.num_attention_heads,
        max_position_embeddings=args.max_position_embeddings,
        ffn_hidden_size=args.ffn_hidden_size,
        hidden_dropout=args.hidden_dropout,
        attention_dropout=args.attention_dropout,
        tensor_parallel_size=tp,
        init_method_std=args.init_method_std,
        # the argument system migrates --checkpoint-activations to
        # activations_checkpoint_method='uniform' (reference semantics)
        checkpoint_activations=args.activations_checkpoint_method
        is not None,
        # --sequence-parallel shards the inter-boundary activations
        # over the tensor axis; --collective-matmul rides only if the
        # reference's async-allreduce opt-out was not given
        sequence_parallel=args.sequence_parallel,
        collective_matmul=(
            args.collective_matmul
            and args.async_tensor_model_parallel_allreduce
        ),
        comm_dtype=(
            args.comm_dtype if args.collective_matmul else "fp32"
        ),
    )
    model = GPTModel(cfg)
    if args.packed_update and not args.dist_opt:
        opt = PackedOptimizerStep(
            "adam", args.lr, weight_decay=args.weight_decay
        )
    else:
        opt = MixedPrecisionAdam(args.lr, weight_decay=args.weight_decay)
    scaler = GradScaler(axis_names=(parallel_state.TENSOR_AXIS,))
    dist = (
        distributed_fused_adam(
            args.lr, weight_decay=args.weight_decay,
            axis_name=parallel_state.DATA_AXIS,
            # found_inf must agree across TP ranks too: the probe sees
            # only this rank's grad shards
            probe_sync_axes=(parallel_state.TENSOR_AXIS,),
            comm_dtype=args.comm_dtype,
        )
        if args.dist_opt else None
    )

    b_local = args.micro_batch_size
    seq = args.seq_length
    dropout = max(args.hidden_dropout, args.attention_dropout)

    def per_token_losses(p, tokens, labels, drop_rng):
        if dropout == 0.0:
            return model.apply(p, tokens, labels=labels)
        # each data-parallel rank draws its own masks
        drop_rng = jax.random.fold_in(
            drop_rng, jax.lax.axis_index(parallel_state.DATA_AXIS)
        )
        return model.apply(
            p, tokens, labels=labels, deterministic=False,
            rngs={"dropout": drop_rng},
        )

    def local_init(tokens):
        params32 = model.init(jax.random.PRNGKey(args.seed), tokens)
        if dist is not None:
            # ZeRO path: fp32 params beside 1/dp Adam shards; the
            # scaler state stays in the carry only so both paths share
            # one step/init signature
            return (params32, dist.init(params32)), scaler.init()
        return opt.init(params32), scaler.init()

    def local_step_dist(state, sstate, tokens, labels, drop_rng):
        params, ostate = state

        def loss_fn(p):
            losses = per_token_losses(p, tokens, labels, drop_rng)
            return gpt_loss_fn(losses) * scaler.loss_scale(sstate)

        scaled, grads = jax.value_and_grad(loss_fn)(params)
        inv_scale = 1.0 / scaler.loss_scale(sstate)
        # NO grad pmean here: the optimizer's reduce-scatter over the
        # data axis IS the gradient averaging — that is the ZeRO
        # bargain (all-reduce bytes, but the Adam state the result
        # feeds lives 1/dp-sharded). The scaler composes through the
        # optimizer: the inv_scale multiply + found_inf probe run as
        # one fused pass over the PACKED grad buffers before the
        # reduce-scatter (synced over data + tensor axes), and on
        # overflow the kernel freezes masters/moments in place
        updates, ostate2, info = dist.update(
            grads, ostate, params, inv_scale=inv_scale, with_info=True
        )
        params2 = optax.apply_updates(params, updates)
        # host-visible scale bookkeeping (halve/grow/skip counters)
        # unchanged from the non-dist path — the optimizer already
        # applied the skip, so the returned flag only drives the scale
        sstate2, _ = scaler.update(sstate, info["found_inf"])
        loss = scaled * inv_scale
        unscaled = jax.tree_util.tree_map(lambda g: g * inv_scale, grads)
        metrics = (
            Metrics.empty()
            .record("loss", loss)
            .record_norm("grad_norm", unscaled)
            .record_ratio_norms(unscaled, params, prefix="grad_ratio")
            .record("loss_scale", sstate2.loss_scale)
            .record("overflows", sstate2.overflows)
        )
        if args.flight_recorder is not None:
            metrics = metrics.merge(Metrics(group_nonfinite(
                grads, axis_name=parallel_state.TENSOR_AXIS
            )))
        # pre-reduce-scatter grads differ across dp ranks, so every
        # scalar above is rank-local — mean them so the P() out_spec
        # (check_vma=False) carries honest replicated values
        metrics = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, parallel_state.DATA_AXIS),
            metrics,
        )
        return (params2, ostate2), sstate2, metrics

    def local_step(state, sstate, tokens, labels, drop_rng):
        def loss_fn(p):
            losses = per_token_losses(p, tokens, labels, drop_rng)
            return gpt_loss_fn(losses) * scaler.loss_scale(sstate)

        scaled, grads = jax.value_and_grad(loss_fn)(state.model)
        grads = jax.lax.pmean(grads, parallel_state.DATA_AXIS)
        found_inf = ~all_finite(grads)
        sstate2, skip = scaler.update(sstate, found_inf)
        state2 = opt.step(
            state, grads,
            grad_scale=1.0 / scaler.loss_scale(sstate), skip=skip,
        )
        inv_scale = 1.0 / scaler.loss_scale(sstate)
        loss = scaled * inv_scale
        # in-graph telemetry (monitor.Metrics): one pytree of fp32
        # scalars riding the step outputs — the UNSCALED grad norm
        # (grads here still carry the loss scale) over the rank-LOCAL
        # trees (TP shards; identical across dp ranks after the pmean —
        # a spike diagnostic rather than an exact global norm), plus
        # the scaler's own observability counters
        unscaled = jax.tree_util.tree_map(lambda g: g * inv_scale, grads)
        # packed states keep masters as flat buffers — the bf16 model
        # tree is the per-leaf ratio-norm denominator there
        denom = state.model if args.packed_update else state.master
        metrics = (
            Metrics.empty()
            .record("loss", loss)
            .record_norm("grad_norm", unscaled)
            .record_ratio_norms(unscaled, denom, prefix="grad_ratio")
            .record("loss_scale", sstate2.loss_scale)
            .record("overflows", sstate2.overflows)
        )
        if args.flight_recorder is not None:
            # per-group nonfinite probes for the flight recorder —
            # shard-partial grads psum over the tensor axis per the
            # Metrics convention. Gated: the default program carries
            # ZERO extra equations (the recorder-off acceptance bar).
            metrics = metrics.merge(Metrics(group_nonfinite(
                grads, axis_name=parallel_state.TENSOR_AXIS
            )))
        return state2, sstate2, metrics

    data_spec = P(parallel_state.DATA_AXIS)
    init_f = jax.jit(
        shard_map(
            local_init, mesh=mesh,
            in_specs=(data_spec,), out_specs=(P(), P()),
            check_vma=False,
        )
    )
    # the (state, sstate) carry is donated: the loop reassigns both
    # every iteration and the checkpoint gather only reads the current
    # step's output, so the old buffers are dead the moment step_f
    # returns. Halves peak optimizer-state memory; the donation is a
    # standing contract pinned by `tools/graphlint.py` (gpt_train_bf16).
    step_f = jax.jit(
        shard_map(
            local_step_dist if dist is not None else local_step,
            mesh=mesh,
            in_specs=(P(), P(), data_spec, data_spec, P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )

    # per-iteration data keys FOLD IN the iteration index instead of
    # chaining splits, so a resumed run regenerates iteration N's batch
    # bitwise without replaying iterations 0..N-1
    base_rng = jax.random.PRNGKey(args.seed + 1)
    drop_base = gpt_134m.dropout_key(dropout)
    tokens0 = jnp.ones((b_local * dp, seq), jnp.int32)
    state, sstate = init_f(tokens0)

    # --- checkpointing (--checkpoint-dir): rank-stacked host view ----
    # Training state lives at per-rank local shapes behind the P()
    # out_specs (check_vma=False) — the "replicated" claim is false for
    # TP param shards and 1/dp ZeRO shards, so saving the host view of
    # `state` directly would persist rank 0's shard for every rank. The
    # gather jit all-gathers over BOTH mesh axes into a genuinely
    # replicated (tp, dp, ...) stack per leaf; the scatter jit is its
    # bitwise inverse (pure data movement, no arithmetic). Fine at
    # example scale — a production run would hand orbax the sharded
    # arrays directly.
    def local_gather(state, sstate):
        tree = jax.lax.all_gather(
            (state, sstate), parallel_state.DATA_AXIS
        )
        return jax.lax.all_gather(tree, parallel_state.TENSOR_AXIS)

    def local_scatter(tree):
        ti = jax.lax.axis_index(parallel_state.TENSOR_AXIS)
        di = jax.lax.axis_index(parallel_state.DATA_AXIS)
        return jax.tree_util.tree_map(lambda x: x[ti, di], tree)

    mgr = None
    start_it = 0
    if args.checkpoint_dir is not None:
        gather_f = jax.jit(shard_map(
            local_gather, mesh=mesh,
            in_specs=(P(), P()), out_specs=P(), check_vma=False,
        ))
        scatter_f = jax.jit(shard_map(
            local_scatter, mesh=mesh,
            in_specs=(P(),), out_specs=(P(), P()), check_vma=False,
        ))
        # SIGTERM → should_exit(): the loop saves and leaves cleanly
        mgr = CheckpointManager(args.checkpoint_dir)
        latest = mgr.latest_step()
        if latest is not None:
            restored = mgr.restore(
                latest, template=jax.device_get(gather_f(state, sstate))
            )
            state, sstate = scatter_f(restored)
            start_it = latest
            print(
                f"resumed from {args.checkpoint_dir} at iter {latest}",
                file=sys.stderr,
            )
    if dist is not None:
        # sharded leaves exit shard_map at their LOCAL (1/dp) shapes
        # under the P() out_spec, so summing bytes here reads the
        # per-chip optimizer footprint directly
        opt_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(state[1])
        )
        print(
            f"ZeRO optimizer state: {opt_bytes / 2**20:.2f} MiB/chip "
            f"(dp={dp})"
        )

    # host-side pipeline (monitor.MetricsLogger): jsonl metric lines on
    # stdout every log_interval steps — window means of the in-graph
    # Metrics plus step time (Timers sync semantics: end_step fetches
    # the loss), tokens/sec, and MFU from the shared model_flops
    # accounting. Param count via eval_shape of an unsharded replica
    # (abstract — no compute; local leaves are 1/tp shards under TP).
    import dataclasses

    cfg_count = dataclasses.replace(
        cfg, tensor_parallel_size=1, sequence_parallel=False,
        collective_matmul=False,
    )
    raw_count = sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(
            jax.eval_shape(
                lambda t: GPTModel(cfg_count).init(
                    jax.random.PRNGKey(0), t
                ),
                tokens0[:1],
            )
        )
    )
    logger = MetricsLogger(
        writers=[JsonlWriter(stream=sys.stdout)],
        window=args.log_interval,
        tokens_per_step=b_local * dp * seq,
        flops_per_step=model_flops(
            cfg, b_local * dp, seq, raw_param_count=raw_count
        ),
        n_chips=tp * dp,
    )
    # span tracer (--trace): one host span per train step, aligned
    # with any live device capture via StepTraceAnnotation; exported
    # as Perfetto-loadable Chrome trace JSON at the end of the run
    tracer = Tracer(enabled=args.trace is not None)
    # telemetry plane (--metrics-port / --slo): a RegistryWriter
    # mirrors every flushed scalar into a MetricRegistry so the
    # training run exports through the SAME /metrics + SLO surface as
    # the serving engine (docs/observability.md "Telemetry & SLOs")
    registry = None
    slo_monitor = None
    exporter = None
    if args.metrics_port is not None or args.slo is not None:
        registry = MetricRegistry()
        logger.writers.append(RegistryWriter(registry))
        if args.slo is not None:
            slo_monitor = SLOMonitor(registry=registry, tracer=tracer)
        if args.metrics_port is not None:
            exporter = start_exporter(
                registry, port=args.metrics_port,
                slo_monitor=slo_monitor,
            )
            print(f"metrics: {exporter.url}", flush=True)
    # numerics flight recorder (--flight-recorder): the last-k metric
    # snapshots ride a host ring; a NaN/Inf anomaly dumps a jsonl
    # bundle naming the offending param group
    recorder = (
        FlightRecorder(path=args.flight_recorder)
        if args.flight_recorder is not None else None
    )
    # context-managed logger: the trailing partial window (short runs'
    # last < log_interval steps) flushes on exit
    with logger:
        for it in range(start_it, args.train_iters):
            k = jax.random.fold_in(base_rng, it)
            tokens = jax.random.randint(
                k, (b_local * dp, seq), 0, cfg.vocab_size
            )
            labels = jnp.roll(tokens, -1, axis=1)
            logger.start_step()
            with tracer.step_span(it + 1):
                state, sstate, metrics = step_f(
                    state, sstate, tokens, labels,
                    jax.random.fold_in(drop_base, it),
                )
                logger.end_step(sync_on=metrics["loss"])  # fetch = sync
            record = logger.log_step(it + 1, metrics)
            if record is not None and slo_monitor is not None:
                if not slo_monitor.slos:
                    # threshold: the flag's value, or 3x the first
                    # window's mean step time (post-compile steady
                    # state; the compile-heavy first window itself
                    # never enters the histogram ring twice)
                    thresh = (
                        args.slo if args.slo > 0
                        else 3.0 * record["step_time_ms"]
                    )
                    slo_monitor.add(SLO(
                        "train_step_time", 0.9,
                        series=registry.get("train_step_ms"),
                        threshold=thresh,
                        windows=(BurnRule(60.0, 15.0, 2.0),),
                    ))
                slo_monitor.tick()
                slo_monitor.alerts()  # rising edges -> events/tracer
            if recorder is not None:
                bundle = recorder.record(it + 1, metrics)
                if bundle is not None:
                    print(
                        f"iter {it + 1}: NUMERICS ANOMALY in "
                        f"{bundle['offending']} -> "
                        f"{args.flight_recorder}",
                        file=sys.stderr,
                    )
            if record is not None:
                print(
                    f"iter {it + 1}: lm loss {record['loss']:.4f}  "
                    f"{record['tokens_per_sec']:.0f} tokens/s  "
                    f"grad_norm {record['grad_norm']:.3f}  "
                    f"scale {record['loss_scale']:.0f}",
                    file=sys.stderr,
                )
            if mgr is not None:
                if mgr.should_exit():
                    # preemption notice: persist and leave with code 0
                    # — the relaunch resumes at this exact step
                    mgr.save(it + 1, gather_f(state, sstate), force=True)
                    print(
                        f"preemption notice at iter {it + 1}: "
                        f"checkpoint saved, exiting cleanly",
                        file=sys.stderr,
                    )
                    break
                if (
                    args.save_interval
                    and (it + 1) % args.save_interval == 0
                    and (it + 1) < args.train_iters
                ):
                    mgr.save(it + 1, gather_f(state, sstate))
    if mgr is not None:
        if mgr.latest_step() != args.train_iters and not mgr.should_exit():
            mgr.save(
                args.train_iters, gather_f(state, sstate), force=True
            )
        # full-state digest: kill-and-resume is bitwise iff this line
        # matches the uninterrupted run's (masters, moments — incl.
        # ZeRO shards and int8-comm residual state — and the scaler
        # counters all hash in)
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(
            jax.device_get(gather_f(state, sstate))
        ):
            h.update(np.ascontiguousarray(leaf).tobytes())
        print(f"state digest: {h.hexdigest()}")
        mgr.wait_until_finished()
        mgr.close()
    if slo_monitor is not None:
        fired = slo_monitor.events
        print(
            f"slo: {len(fired)} burn-rate alert(s)"
            + (
                " — " + "; ".join(
                    f"{e['slo']} burn={e['burn_long']:.1f}x "
                    f"(factor {e['factor']:.1f})" for e in fired
                ) if fired else ""
            ),
            file=sys.stderr,
        )
    if exporter is not None:
        exporter.close()
    if args.trace is not None:
        n = tracer.export_chrome_trace(args.trace)
        print(f"wrote {n} trace events to {args.trace}", file=sys.stderr)


if __name__ == "__main__":
    enable_compile_cache()
    main()
