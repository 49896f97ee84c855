"""Driver benchmark: one JSON line on stdout.

Measures the flagship config on whatever single chip is available: a
Megatron-style GPT train step under the O5/amp-O2 recipe — bf16 model
params computing with Pallas flash attention + the chunked fused
linear+CE LM head (ops/linear_xentropy.py: the (b·s, vocab) logits
never materialize; `--loss=naive` A/Bs the materialized fp32-logits
optax path, and the stderr line reports the head's share of the step
from a standalone fwd+bwd timing of the same op), fp32 masters
updated by the XLA-tree-fused mixed-precision Adam (optimizers/mixed.py
— see its header for why tree fusion, not buffer packing, is the TPU
fast path), dynamic loss scaling with jit-safe skip-step — reporting
tokens/sec/chip.

The DEFAULT is the TRAINING configuration (dropout 0.1 — attention
dropout in-kernel in the flash kernels, hidden dropout in-kernel in the
residual-LN kernels): the config users train is the config the driver
gate records (round-5 change; `--dropout=0` measures the eval-shaped
config under the un-suffixed metric key).

`--seq-parallel` A/Bs the tp-axis configuration: the model shards over
ALL visible chips on the tensor axis with sequence-parallel
activations between the TP boundaries (GPTConfig.sequence_parallel);
`--collective-matmul` additionally decomposes the boundary collectives
into ppermute-ring matmuls (ops/collective_matmul.py). These emit
`_sp_tpN` / `_spcm_tpN`-suffixed metric keys so the tp-axis step-time
series stays separate from the dp bench above.

`--audit` (gpt bench) additionally prints a static program audit of
one train step to stderr — collective counts/bytes + dot FLOPs from
`rocm_apex_tpu.monitor.audit` (trace-only, no timing impact) — and
emits the estimated per-step collective wire bytes as a
`gpt_comm_payload_mib` jsonl metric.

`--comm-dtype=int8` (gpt bench) quantizes the ring-collective hop
payloads to int8 with fp32 scale sidecars
(ops/quantized_collectives.py): with `--dist-opt` the ZeRO grad
reduce-scatter and param all-gather rings, with `--collective-matmul`
the TP-boundary rings. The `--dist-opt` bench always emits
`gpt_comm_payload_mib` (audit-traced, ~3.5-4x lower at int8) next to
the step-time line; docs/perf.md has the A/B numbers.

`python bench.py serve` measures the SERVING path: the continuous-
batching engine's chunked-prefill token-budget scheduler on a mixed
prompt-length workload, reporting `gpt_serve_tokens_per_sec_per_chip`
and `gpt_serve_ttft_ms` (p95) with the whole-prompt prefill A/B run in
the same invocation as the baseline ratio (docs/inference.md).

Timing notes:
* ITERS steps run inside ONE dispatch via `lax.scan`, so per-dispatch
  host latency is paid once per timed region, as in real multi-step
  training;
* the timed region ends with a scalar value fetch, which waits for the
  device.

Every record names the device it ran on (platform, device_kind,
device_count). On a CPU the benches still run, at small shapes, as a
functional rehearsal, and `_report` writes a ``not_measured`` record:
no CPU timing is ever written under a device metric's name, and no MFU
is computed for a device outside `monitor.flops.CHIP_PEAKS`.

The reference publishes no numbers (SURVEY.md §6, BASELINE.json
"published": {}), so ``vs_baseline`` is the ratio against the
north-star bar (70% MFU): vs_baseline = MFU / 0.70 for the model
benches (gpt / rn50 / bert). The micro-bench subcommands report a
different, per-metric efficiency ratio named on their stderr line:
attn = fraction of bf16 peak FLOP/s, ln = xla_ms / pallas_ms
(speedup), optim = bandwidth_floor_ms / measured_ms.
"""

import sys
import time

import jax
import jax.numpy as jnp

from rocm_apex_tpu import monitor
from rocm_apex_tpu.amp import LossScaler
from rocm_apex_tpu.models import gpt_134m
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache

BATCH = gpt_134m.TRAIN_BATCH
SEQ = gpt_134m.TRAIN_SEQ
# one warmup runN (compile + state settle) then one timed. 50 steps per
# dispatch: the timed region ends with one value fetch, and at N steps
# its latency over-reports each step by 1/N of itself — real training
# fetches nothing per step.
ITERS = 50


# the stdout contract rides the shared observability sink: one
# MetricsLogger with a JsonlWriter on stdout, records passed through
# verbatim (monitor/logger.py `emit`)
_REPORT_LOGGER = monitor.MetricsLogger(
    writers=[monitor.JsonlWriter(stream=sys.stdout)], memory_stats=False
)


def _mfu(flops, dt, n_chips=1):
    """`monitor.mfu` against the local chip's peak; NaN on a device the
    peaks table does not know (a CPU rehearsal), so no utilization can
    be printed for it."""
    try:
        return monitor.mfu(flops, dt, n_chips=n_chips)
    except monitor.UnknownDeviceError:
        return float("nan")


def _report(metric, value, unit, vs_baseline, extra=""):
    """The single choke point for result records. Every record names
    the device; a CPU run's record carries neither the metric's name nor
    its value."""
    print(extra, file=sys.stderr)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    if device["platform"] == "cpu":
        _REPORT_LOGGER.emit(
            {
                "metric": "not_measured",
                "reason": "no accelerator: functional rehearsal only",
                **device,
            }
        )
        return
    _REPORT_LOGGER.emit(
        {
            "metric": metric,
            # sub-10 values keep 4 decimals (a 0.168 ms kernel must
            # not be published as 0.2)
            "value": round(value, 1) if value >= 10 else round(value, 4),
            "unit": unit,
            "vs_baseline": round(vs_baseline, 4),
            **device,
        }
    )


def bench_rn50(fused: bool = False):
    """BASELINE.json config 2: ResNet-50, O5 recipe (bf16 + fp32
    masters via amp.initialize) + FusedAdam, images/sec/chip.
    DDP-equivalent gradient psum degenerates on one chip (the
    multi-chip path is exercised by tests/L0/test_parallel.py).
    `--fused=1` routes the 13 stride-1 blocks through the fused Pallas
    bottleneck kernels (ops/fused_bottleneck.py) and reports under a
    `_fused`-suffixed key; the default XLA chain remains the headline
    because Mosaic's shifted-tap conv lowering measures well below
    XLA's native conv emitter at RN50 channel widths."""
    import optax

    from rocm_apex_tpu import amp, models
    from rocm_apex_tpu.optimizers import FusedAdam

    on_tpu = jax.default_backend() == "tpu"
    batch = 128 if on_tpu else 4  # b128 beats b64 by 16% img/s on v5e
    size = 224 if on_tpu else 32
    iters = 20 if on_tpu else 2
    # the policy's compute dtype threads through the model definition
    # (SURVEY §7: flax-style dtype IS the O-level cast_model_type);
    # without it every conv and feature map runs fp32 — measured 97.7
    # vs 53.1 ms per step on v5e. BN params stay fp32 via amp.initialize
    # (keep_batchnorm_fp32) and flax accumulates BN stats in fp32.
    model = models.resnet50(
        num_classes=1000,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        fused=fused and on_tpu,
    )
    x0 = jnp.zeros((batch, size, size, 3))
    variables = model.init(jax.random.PRNGKey(0), x0)
    params, batch_stats = variables["params"], variables["batch_stats"]
    optimizer = FusedAdam(1e-3, weight_decay=1e-4)
    params, optimizer, amp_state = amp.initialize(
        params, optimizer, opt_level="O5" if on_tpu else "O0"
    )
    opt_state = optimizer.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, size, size, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)

    def one_step(carry, _):
        params, batch_stats, opt_state, scaler_states = carry
        st = amp_state.replace(scaler_states=scaler_states)

        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x.astype(jnp.bfloat16 if on_tpu else jnp.float32),
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
            one_step,
            (params, batch_stats, opt_state, scaler_states),
            None,
            length=iters,
        )
        return carry, ces

    carry, ces = runN(params, batch_stats, opt_state, amp_state.scaler_states)
    float(ces[-1])
    t0 = time.perf_counter()
    carry, ces = runN(*carry)
    loss = float(ces[-1])
    dt = (time.perf_counter() - t0) / iters
    img_s = batch / dt
    # RN50 train ~ 3 x 4.1 GFLOPs fwd per image at 224x224
    # (monitor.resnet50_train_flops — the shared accounting module)
    mfu = _mfu(monitor.resnet50_train_flops(batch), dt)
    # a metric series must never mix configs under one key: the
    # fused-kernel run gets its own metric name
    suffix = "_fused" if (fused and on_tpu) else ""
    _report(
        f"rn50_train_images_per_sec_per_chip{suffix}",
        img_s, "images/s", mfu / 0.70,
        f"rn50: step={dt*1000:.1f}ms loss={loss:.3f} mfu={mfu:.3f}",
    )


def build_bert_train(dropout: float = 0.0, batch: int = 0,
                     remat: bool = False, iters: int = 0):
    """The BERT bench step, importable (`bench_bert` runs it). Returns
    ``(runN, state0, rng0, cfg, batch, seq, params32)``."""
    from rocm_apex_tpu.models import BertConfig, BertModel
    from rocm_apex_tpu.optimizers.mixed import MixedPrecisionLamb
    from rocm_apex_tpu.utils.tree import path_str

    on_tpu = jax.default_backend() == "tpu"
    batch = batch or (8 if on_tpu else 2)
    seq = 512 if on_tpu else 64
    iters = iters or (20 if on_tpu else 2)
    cfg = BertConfig(
        vocab_size=30592 if on_tpu else 1024,
        hidden_size=1024 if on_tpu else 64,
        num_layers=24 if on_tpu else 2,
        num_attention_heads=8 if on_tpu else 4,
        ffn_hidden_size=4096 if on_tpu else 128,
        max_position_embeddings=seq,
        hidden_dropout=dropout,
        attention_dropout=dropout,
        tensor_parallel_size=1,
        checkpoint_activations=remat,
    )
    model = BertModel(cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (batch, seq), 0, cfg.vocab_size
    )
    lm_labels = jnp.roll(tokens, 1, axis=1)
    params32 = model.init(jax.random.PRNGKey(1), tokens[:1])
    flat = jax.tree_util.tree_map_with_path(
        lambda kp, _: not (
            path_str(kp).endswith("bias") or "layernorm" in path_str(kp).lower()
        ),
        params32,
    )
    # store_model=False: the bf16 model copy is cast from the masters
    # in-scan instead of riding the carry — the carried copy would be
    # double-buffered (2 x 0.66 GB), which is exactly the b8 OOM margin
    # on the 16 GB chip
    # bf16 moments: half the m/v traffic and state (the
    # moment_dtype knob, tolerance pinned by
    # test_mixed_precision.py::test_bf16_moments_close_to_fp32);
    # with fp32 moments the b16 config exceeds the 16 GB chip
    opt = MixedPrecisionLamb(
        1e-4, weight_decay=0.01, weight_decay_mask=flat,
        compute_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        moment_dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        store_model=False,
    )
    state = opt.init(params32)

    def one_step(carry, _):
        state, rng = carry
        rng, step_rng = jax.random.split(rng)

        def loss_fn(p):
            losses, _ = model.apply(
                p, tokens, lm_labels=lm_labels,
                deterministic=dropout == 0.0,
                rngs={"dropout": step_rng} if dropout > 0.0 else None,
            )
            return jnp.mean(losses)

        loss, grads = jax.value_and_grad(loss_fn)(opt.model_params(state))
        state2, _ = opt.step_and_probe(state, grads)
        return (state2, rng), loss

    @jax.jit
    def runN(state, rng):
        carry, losses = jax.lax.scan(
            one_step, (state, rng), None, length=iters
        )
        return carry, losses

    return (
        runN, state, gpt_134m.dropout_key(dropout), cfg, batch, seq,
        params32,
    )


def bench_bert(dropout: float = 0.0, batch: int = 0, remat: bool = False):
    """BASELINE.json config 4: BERT-Large-shaped MLM pretrain step with
    the mixed-precision LAMB recipe (bf16 model copy + fp32 masters,
    `MixedPrecisionLamb` — norms fused into the update passes, no
    materialized update buffer) + fused LayerNorm, tokens/sec/chip.
    24L/1024h with head_dim 128 (the TPU-first head shape; see main()).
    ``--batch=16 --remat`` measures the large-batch config with
    per-layer activation checkpointing."""
    on_tpu = jax.default_backend() == "tpu"
    iters = 20 if on_tpu else 2
    runN, state, rng0, cfg, batch, seq, params32 = build_bert_train(
        dropout, batch, remat, iters
    )
    carry, losses = runN(state, rng0)
    float(losses[-1])
    t0 = time.perf_counter()
    carry, losses = runN(*carry)
    loss = float(losses[-1])
    dt = (time.perf_counter() - t0) / iters
    tok_s = batch * seq / dt
    # same Megatron-style crediting as the GPT bench, via the shared
    # monitor.model_flops accounting (+ the tied MLM-head projection
    # trio; see main())
    flops = monitor.model_flops(
        cfg, batch, seq,
        raw_param_count=sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params32)
        ),
    )
    mfu = _mfu(flops, dt)
    # non-default configs get distinct metric names: a metric series
    # must never mix configs under one key
    suffix = "_dropout" if dropout > 0.0 else ""
    if batch != (8 if on_tpu else 2):
        suffix += f"_b{batch}"
    if remat:
        suffix += "_remat"
    _report(
        f"bert_large_train_tokens_per_sec_per_chip{suffix}", tok_s,
        "tokens/s", mfu / 0.70,
        f"bert: step={dt*1000:.1f}ms loss={loss:.3f} mfu={mfu:.3f} "
        f"dropout={dropout} remat={remat}",
    )


def bench_serve(budget: int = 0, whole_prompt: bool = False,
                trace: str = "", paged: bool = False,
                page_size: int = 0, kv_dtype: str = "",
                shared_prefix: bool = False, spec_k: int = -1,
                chaos: int = -1, slo: bool = False,
                metrics_port: int = -1, replicas: int = 0,
                tp: int = 0, disagg: bool = False,
                adapters: int = 0, ranks: str = ""):
    """Serving benchmark: the continuous-batching engine on a MIXED
    prompt-length workload (fixed seed — the raggedness is the point:
    whole-prompt prefill pads every prompt to the longest and stalls
    every decode slot behind each admit; the chunked token-budget
    scheduler streams prompts through the fixed budget while the
    decode grid advances every tick).

    Emits ``gpt_serve_tokens_per_sec_per_chip`` (generated tokens/sec;
    vs_baseline = speedup over the whole-prompt A/B run measured in the
    same invocation) and ``gpt_serve_ttft_ms`` (p95 enqueue→first-token;
    vs_baseline = whole-prompt p95 / chunked p95) through the shared
    MetricsLogger/JsonlWriter stdout contract. ``--whole-prompt``
    instead reports ONLY the legacy path under ``_whole``-suffixed keys
    (its own metric series). ``--budget=N`` overrides the prefill
    token budget (default 256 on TPU, 16 on CPU).

    ``--trace=PATH`` attaches a `monitor.Tracer` to the measured
    engine and writes (a) PATH: Chrome trace-event JSON with one track
    per request (enqueue → queue_wait → prefill_chunk spans → decode →
    finish) plus the engine's mixed/decode tick track — load it in
    Perfetto; and (b) PATH.requests.jsonl: the per-request completion
    records (TTFT, TPOT, tokens, chunks, queue wait) next to the
    aggregate ``stats()``. Tracing is host-side ring-buffer writes on
    timestamps the engine already takes — the compiled programs and
    the one-fetch-per-tick pattern are unchanged.

    ``--paged`` A/Bs the block-table cache against the contiguous
    chunked engine on the same workload: greedy tokens are asserted
    IDENTICAL (the bf16/fp32 paged path is parity-exact), throughput
    reports under ``gpt_serve_tokens_per_sec_per_chip_paged`` with
    vs_baseline = paged/contiguous, and a cache-bytes line contrasts
    the contiguous allocation with the paged pool and its PEAK live
    pages (the memory actually needed). ``--page-size=N`` tunes the
    page (default 16 CPU / 64 TPU); ``--kv-dtype=int8`` stores int8
    pools with per-(page, head) scales (the parity assert relaxes to
    a match-count report; keys gain an ``_int8`` suffix).
    ``--shared-prefix`` switches to the shared-system-prompt workload
    and A/Bs paged+prefix-sharing against plain paged: same tokens,
    ``prefix_hits``/``shared_page_ratio`` > 0, and the TTFT p95 win
    reports under ``gpt_serve_ttft_ms_shared_prefix``.

    ``--chaos=SEED`` runs the mixed workload once under a seeded
    `inference.FaultPlan` (a device-step failure, a NaN-poisoned
    logits row, probabilistic host-fetch failures, a page-allocation
    failure on ``--paged``) with a bounded queue and a mid-run cancel,
    then asserts the ISSUE-12 completion-accounting identity: every
    submitted request yields exactly one completion record —
    completed + shed + quarantined + cancelled + expired ==
    submitted — with the mixed step still traced ONCE and, under
    ``--paged``, every page back in the pool after the drain. Reports
    under ``gpt_serve_chaos_survival`` (vs_baseline = completed
    fraction). Same SEED, same schedule: a failure replays exactly.

    ``--slo`` is the telemetry plane's acceptance rig: the measured
    per-request TTFTs are replayed through a real `monitor.SLOMonitor`
    (latency `SLO` over a ``serve_ttft_ms`` histogram, objective 0.9,
    threshold = 2x the fault-free p95) on an EVENT-INDEX clock — one
    request per tick, so the Google-SRE window math runs over request
    counts and the asserts cannot flake on wall-clock jitter. Alone it
    asserts the fault-free run stays QUIET (zero burn-rate alerts).
    Composed with ``--chaos=SEED`` it first calibrates the threshold
    on a fault-free pass (asserted quiet), then augments the fault
    plan with a burst of retry-backoff device-step faults and asserts
    the TTFT burn-rate alert FIRES. Reports under
    ``gpt_serve_slo_alerts``. ``--metrics-port=N`` stands up the
    telemetry exporter over the measured engine's registry on
    127.0.0.1:N (0 = ephemeral) and self-scrapes ``/metrics`` and
    ``/healthz`` once before exiting.

    ``--replicas=N`` runs the multi-replica fabric
    (`inference.ReplicaRouter`, N >= 2) on the mixed workload and
    reports ``gpt_serve_fleet_tokens_per_sec`` (vs_baseline = fleet
    rate / a single-replica run measured in the same invocation).
    Greedy fleet tokens are asserted bitwise-identical to the
    single-replica reference (placement must never change outputs).
    Composed with ``--chaos=SEED`` the fleet pass runs again under a
    seed-derived replica fault plan (a ``replica_kill`` mid-decode
    plus a ``replica_slow`` latency injection) and asserts the
    ISSUE-15 survival identity: every submitted request accounted
    exactly ONCE, every recovered request's tokens bitwise-identical
    to the undisturbed reference (no token emitted twice), the killed
    replica's pages/slots provably clean after quarantine, each
    replica's mixed step still traced once, and the merged fleet
    registry's TTFT histogram reproducing the combined per-replica
    completion streams. ``--metrics-port=N`` here stands the exporter
    up over the ROUTER (zero-arg merged-registry provider, fleet
    `/healthz`) and self-scrapes it.

    ``--tp=N`` A/Bs the tensor-parallel paged serve at EQUAL CHIP
    COUNT: the same mixed workload runs on a tp=1 engine (1 chip) and
    on a tp=N engine whose params are sliced from the SAME tp=1
    checkpoint (`inference.shard_tp1_params`), each still ONE fused
    mixed trace per tick. Greedy tokens are asserted IDENTICAL and the
    per-chip KV bytes exactly 1/N (the pools shard over heads).
    Reports ``gpt_serve_tokens_per_sec_per_chip_tpN`` (fleet rate / N
    chips; vs_baseline = per-chip ratio over tp=1 — below 1.0 on CPU
    where the simulated mesh buys no real bandwidth, the per-chip KV
    headroom is the win) and ``gpt_serve_ttft_ms_tpN``. Needs N
    visible devices (CPU: ``--xla_force_host_platform_device_count``).

    ``--disagg`` A/Bs disaggregated prefill/decode serving at EQUAL
    CHIP COUNT: a ``replica_classes=["prefill", "decode", ...]`` fleet
    (half prefill, half decode; ``--replicas=N`` sizes it, default 2)
    against an identical-replica fleet on the same workload. Fresh
    prompts chunk on prefill replicas, finished prompts migrate WITH
    their KV pages (page-shipping, no re-prefill) to decode replicas.
    Greedy tokens are asserted IDENTICAL to the uniform fleet, at
    least one handoff must actually ship pages, and both fleets must
    drain leak-free. Reports
    ``gpt_serve_tokens_per_sec_per_chip_disagg`` (vs_baseline =
    disagg / uniform fleet rate) plus per-class TTFT p95 under
    ``gpt_serve_ttft_ms_prefill`` / ``_decode`` (vs_baseline = uniform
    fleet p95 / class p95), attributed to the replica class that
    FINISHED each request — the decode-class line is the
    time-to-first-token the fleet's decode capacity actually delivers.

    ``--adapters=N`` A/Bs batched multi-LoRA serving against the
    single-model engine on the same workload IN ONE INVOCATION: N
    tenant adapters (``--ranks=R1,R2,...`` cycles per-adapter ranks,
    default 2,4,8, rank-padded into one packed `AdapterPool`) are
    striped across the requests next to base traffic, applied as
    segmented gather->bmm deltas inside the ONE fused mixed trace.
    Adapter-0 greedy tokens are asserted bitwise identical to the
    base engine, at least one adapter must visibly change tokens, and
    a park/reclaim churn wave (2N registered adapters over N+1
    residency slots) must neither retrace nor leak refs. Reports
    ``gpt_serve_adapter_tokens_per_sec_per_chip`` (vs_baseline =
    aggregate rate / single-model rate — the ~10% adapter tax
    ceiling). Composed with ``--chaos=SEED`` it runs the
    tenant-isolation scenario instead: a seeded one-tenant burst
    (burster and size derived from SEED) replayed through a real
    `monitor.TenantSLOBoard` on an event-index clock must trip ONLY
    the bursting tenant's TTFT burn-rate monitor — every other
    tenant's monitor stays quiet (structural isolation: each reads
    only its own labeled series) — while the per-tenant
    completion-accounting identity holds exactly. Reports
    ``gpt_serve_tenant_isolation``.

    ``--spec-k=K`` A/Bs speculative decoding (n-gram self-drafting
    through the mixed step, `inference/drafting.py`) against the
    non-speculative chunked engine on a HIGH-ACCEPTANCE workload:
    periodic prompts whose greedy continuations repeat, the regime the
    suffix-matching drafter locks onto. Greedy tokens are asserted
    IDENTICAL (and again on quick paged-bf16 and paged-int8 passes —
    the rollback path must be invisible in tokens on every cache
    layout), throughput reports under
    ``gpt_serve_tokens_per_sec_per_chip_spec{K}`` with vs_baseline =
    spec/non-spec, and the stderr line carries acceptance rate,
    drafted/accepted totals, and TTFT/TPOT p95. ``--spec-k=0`` runs
    only the baseline series."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams

    on_tpu = jax.default_backend() == "tpu"
    default_page = gpt_134m.SERVE_PAGE_SIZE if on_tpu else 16
    req_budget = budget  # pre-default: the spec branch sizes its own
    import numpy as np

    if on_tpu:
        cfg = gpt_134m.serve_config()
        num_slots = gpt_134m.SERVE_SLOTS
        capacity = gpt_134m.SERVE_CAPACITY
        budget = budget or gpt_134m.SERVE_PREFILL_BUDGET
        lens = list(gpt_134m.SERVE_PROMPT_LENS)
        probs = list(gpt_134m.SERVE_PROMPT_PROBS)
        n_requests, max_new = 32, 64
    else:
        # CPU smoke shape: small model, but a LONG-TAILED prompt mix
        # against a real pad width — the regime the scheduler targets
        # (the whole-prompt path pays b*max_prompt_len, chunked pays
        # the actual prompt tokens)
        cfg = GPTConfig(
            vocab_size=512, hidden_size=128, num_layers=2,
            num_attention_heads=4, max_position_embeddings=160,
            hidden_dropout=0.0, attention_dropout=0.0,
            tensor_parallel_size=1, attention_impl="jnp",
        )
        num_slots, capacity = 4, 160
        # swept on this workload: 24 -> 1.08x over whole-prompt, 32 ->
        # ~parity, 48 -> ~1.3x (the 96-token tail absorbs in 2 ticks)
        budget = budget or 48
        lens = [8, 16, 32, 96]
        probs = [0.35, 0.3, 0.2, 0.15]
        n_requests, max_new = 12, 6
    model = GPTModel(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )
    rng = np.random.RandomState(0)

    if spec_k >= 0:
        # ---- speculative-decoding A/B. The workload is periodic on
        # purpose: a tiny greedy model continues a repeating prompt
        # with the same period, so the n-gram drafter's proposals are
        # mostly right and the measured win is the DESIGN's ceiling
        # regime (k accepted tokens per cache sweep). Random-prompt
        # traffic exercises the rollback path instead — covered by the
        # paged parity passes below and the L0 suite.
        # decode-heavy on purpose: speculative decoding amortizes the
        # DECODE tick, so short periodic prompts + a long generation
        # phase isolate the per-token win from prefill fixed costs
        n_req = 16 if on_tpu else 8
        spec_new = 128 if on_tpu else 96
        reps = 8 if on_tpu else 5
        prompts = []
        for i in range(n_req):
            p = 3 + i % 4  # periods 3..6: all hit the 3/2-gram cascade
            cyc = rng.randint(1, cfg.vocab_size, size=p).tolist()
            prompts.append((cyc * (reps + 1))[: p * reps + i % 3])
        # every decoding slot needs k+1 chunk rows per tick for its
        # span (last token + k drafts) — and no more: each extra
        # budget row is dead weight in every spec tick's fused chunk
        sbudget = req_budget or (num_slots * (max(spec_k, 2) + 1))

        def run_spec(k, paged_kv=None, use_paged=False, reqs=None,
                     new_toks=None):
            eng = InferenceEngine(
                model, params, num_slots=num_slots, capacity=capacity,
                sampling=SamplingParams(temperature=0.0), seed=0,
                prefill_token_budget=sbudget, spec_k=k,
                paged=use_paged,
                page_size=(page_size or default_page)
                if use_paged else 16,
                kv_dtype=paged_kv,
            )
            work = reqs if reqs is not None else prompts
            # warmup long enough that accepted spans COMMIT (a span
            # that finishes its request skips the commit program —
            # 3-token warmups would leave that compile in the timed
            # window)
            eng.generate(work[:num_slots], max_new_tokens=10)
            eng.reset_stats()
            t0 = time.perf_counter()
            results = eng.generate(
                work, max_new_tokens=new_toks or spec_new
            )
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in results)
            return eng, [r.tokens for r in results], gen / dt, dt

        eng_b, toks_b, rate_b, dt_b = run_spec(0)
        s_b = eng_b.stats()
        tpot_b = [c["tpot_ms"] for c in eng_b.completions]
        print(
            f"serve[spec0]: {rate_b:.1f} gen tok/s over {dt_b:.2f}s "
            f"(budget={sbudget}) ttft p95={s_b['ttft_ms_p95']:.0f}ms "
            f"tpot p95={np.percentile(tpot_b, 95):.1f}ms",
            file=sys.stderr,
        )
        if spec_k == 0:
            _report("gpt_serve_tokens_per_sec_per_chip_spec0", rate_b,
                    "tokens/s", 1.0, "")
            return
        eng_s, toks_s, rate_s, dt_s = run_spec(spec_k)
        # a throughput win that changes tokens is not a win: greedy
        # speculative output must be TOKEN-IDENTICAL to the baseline
        for i, (tb, ts) in enumerate(zip(toks_b, toks_s)):
            assert tb == ts, f"spec-k={spec_k} changed tokens (req {i})"
        s_s = eng_s.stats()
        tpot_s = [c["tpot_ms"] for c in eng_s.completions]
        assert eng_s.mixed_trace_count == 1, (
            f"spec mixed step traced {eng_s.mixed_trace_count}x"
        )
        # quick parity passes on the paged layouts (reduced workload):
        # the accept/rollback walk must be invisible in tokens whether
        # rejected rows would have landed in bf16 or int8 pages
        sub = prompts[: num_slots + 2]
        for kvd in (None, jnp.int8):
            _, pb, _, _ = run_spec(0, paged_kv=kvd, use_paged=True,
                                   reqs=sub, new_toks=12)
            _, ps_, _, _ = run_spec(spec_k, paged_kv=kvd,
                                    use_paged=True, reqs=sub,
                                    new_toks=12)
            name = "int8" if kvd is not None else "bf16"
            assert pb == ps_, (
                f"spec-k={spec_k} changed tokens on the paged {name} "
                f"cache"
            )
        acc = s_s["acceptance_rate"]
        print(
            f"serve[spec{spec_k}]: {rate_s:.1f} gen tok/s over "
            f"{dt_s:.2f}s vs baseline {rate_b:.1f} "
            f"({rate_s / rate_b:.2f}x); acceptance={acc:.2f} "
            f"({s_s['tokens_accepted']:.0f}/"
            f"{s_s['tokens_drafted']:.0f} drafted, "
            f"{s_s['rollbacks']:.0f} rollbacks) "
            f"ttft p95={s_s['ttft_ms_p95']:.0f}ms "
            f"tpot p95={np.percentile(tpot_s, 95):.1f}ms; tokens "
            f"identical (contiguous + paged bf16/int8)",
            file=sys.stderr,
        )
        _report(
            f"gpt_serve_tokens_per_sec_per_chip_spec{spec_k}", rate_s,
            "tokens/s", rate_s / rate_b,
            f"spec-k={spec_k} {rate_s:.1f} vs non-spec {rate_b:.1f} "
            f"tok/s (speedup = vs_baseline); acceptance {acc:.2f}; "
            f"tokens identical on contiguous/paged/int8",
        )
        _report(
            f"gpt_serve_tpot_ms_spec{spec_k}",
            float(np.percentile(tpot_s, 95)), "ms",
            float(np.percentile(tpot_b, 95))
            / max(float(np.percentile(tpot_s, 95)), 1e-9),
            f"tpot p95: spec {np.percentile(tpot_s, 95):.1f} ms vs "
            f"baseline {np.percentile(tpot_b, 95):.1f} ms "
            f"(ratio = vs_baseline); ttft p95 "
            f"{s_s['ttft_ms_p95']:.0f} vs {s_b['ttft_ms_p95']:.0f} ms",
        )
        return
    if shared_prefix:
        # shared-system-prompt traffic (the millions-of-users regime:
        # most tokens of most requests are the same tokens): one fixed
        # prefix + a short random tail per request. The length is NOT
        # page-aligned on purpose: the tail's first tokens land inside
        # the last shared page, so the A/B also exercises the partial
        # borrow -> copy-on-write fork path
        prefix_len = 250 if on_tpu else 60
        prefix = rng.randint(0, cfg.vocab_size, size=prefix_len).tolist()
        prompts = [
            prefix
            + rng.randint(
                0, cfg.vocab_size, size=int(rng.randint(4, 17))
            ).tolist()
            for _ in range(n_requests)
        ]
    else:
        prompts = [
            rng.randint(
                0, cfg.vocab_size, size=int(rng.choice(lens, p=probs))
            ).tolist()
            for _ in range(n_requests)
        ]
    total_prompt = sum(len(p) for p in prompts)

    def build(chunked, tracer=None):
        return InferenceEngine(
            model, params, num_slots=num_slots, capacity=capacity,
            max_prompt_len=max(lens),
            sampling=SamplingParams(temperature=0.0), seed=0,
            prefill_token_budget=budget if chunked else None,
            tracer=tracer,
        )

    def run(chunked, tracer=None):
        # compile warmup on the SAME engine (its jit caches), then a
        # clean telemetry window for the timed pass — greedy decoding
        # is rng-independent, so the warmup does not perturb tokens
        eng = build(chunked, tracer)
        eng.generate(prompts[: num_slots], max_new_tokens=3)
        eng.reset_stats()
        if tracer is not None:
            tracer.clear()  # the timeline starts at the timed window
        t0 = time.perf_counter()
        results = eng.generate(prompts, max_new_tokens=max_new)
        dt = time.perf_counter() - t0
        gen = sum(len(r.tokens) for r in results)
        return eng, results, gen / dt, dt

    def slo_replay_ttft(completions, threshold_ms):
        # replay the measured per-request TTFTs through the real SLO
        # machinery on an EVENT-INDEX clock (one request = one tick):
        # the burn-rate windows count requests, not seconds, so the
        # assert is deterministic while still exercising
        # Histogram.good_below, the window differencing, and the
        # rising-edge alert path end to end
        from rocm_apex_tpu.monitor import BurnRule, MetricRegistry, SLO, SLOMonitor

        reg = MetricRegistry()
        hist = reg.histogram(
            "serve_ttft_ms",
            "Replayed enqueue->first-token latency (ms).",
        )
        mon = SLOMonitor(registry=reg)
        mon.add(SLO(
            "serve_ttft", 0.9, series=hist, threshold=threshold_ms,
            # request-counted windows: any 6-request span burning the
            # 10% error budget at >= 2x, confirmed by its trailing 3,
            # trips the rule
            windows=(BurnRule(6.0, 3.0, 2.0),),
        ))
        mon.tick(now=0.0)  # pre-traffic baseline sample
        # requests shed/cancelled before their first token carry
        # ttft_ms == 0 — no latency was observed, nothing to judge
        ttfts = [
            c["ttft_ms"] for c in completions if c["ttft_ms"] > 0
        ]
        for i, t in enumerate(ttfts):
            hist.observe(t)
            mon.tick(now=float(i + 1))
            mon.alerts(now=float(i + 1))
        return mon

    def scrape_metrics(eng):
        # --metrics-port: stand the exporter up over the measured
        # engine's registry and self-scrape each endpoint once — the
        # bench proves the surface; a deployment would leave it up
        import http.client
        import json as _json

        srv = monitor.start_exporter(
            eng.registry, port=metrics_port, engine=eng
        )
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", srv.port, timeout=10
            )
            conn.request("GET", "/metrics")
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 200 and b"serve_ttft_ms_count" in body, (
                f"/metrics scrape failed: status={resp.status}"
            )
            conn.request("GET", "/healthz")
            hz = conn.getresponse()
            healthy = _json.loads(hz.read()).get("healthy")
            conn.close()
            print(
                f"serve metrics: {srv.url} — /metrics {len(body)} "
                f"bytes, /healthz status={hz.status} healthy={healthy}",
                file=sys.stderr,
            )
        finally:
            srv.close()

    if adapters > 0:
        # ---- batched multi-LoRA serving A/B: N tenant adapters ride
        # the ONE fused mixed chunk+decode program as segmented
        # gather->bmm deltas over rank-padded packed pool buffers
        # (ops/lora.py, inference/adapters.py). The headline is
        # aggregate tok/s staying within ~10% of the single-model
        # engine on the SAME workload — the adapters must be near-free
        # — with adapter-0 greedy tokens asserted bitwise identical to
        # the base engine. Composed with --chaos=SEED it instead runs
        # the tenant-isolation scenario: a seeded one-tenant burst
        # must burn ONLY that tenant's TTFT SLO (every other tenant's
        # monitor on the `TenantSLOBoard` stays quiet) while the
        # per-tenant completion-accounting identity holds.
        from rocm_apex_tpu.inference import AdapterPool

        rank_list = [int(r) for r in ranks.split(",") if r] or [2, 4, 8]
        if any(r < 1 for r in rank_list):
            raise SystemExit(f"--ranks must be >= 1, got {rank_list}")
        max_rank = max(rank_list)
        # widen the A/B window past the serve default (12 req x 6 tok
        # is ~0.1 s on this box — the ratio drowns in scheduler
        # jitter); both sides run the SAME widened workload
        n_req_a = max(n_requests, 4 * (adapters + 1))
        max_new_a = max(max_new, 24)
        prompts_a = [
            prompts[i % len(prompts)] for i in range(n_req_a)
        ]

        def make_pool(max_resident):
            return AdapterPool(
                cfg.num_layers, cfg.hidden_size,
                max_resident=max_resident, max_rank=max_rank,
            )

        def register_all(pool, n, seed0=100, prefix="tenant"):
            # scale 0.5: big enough that a non-base adapter visibly
            # flips greedy argmax (the delta-took-effect canary)
            rng_a = np.random.RandomState(seed0)
            aids = []
            for i in range(n):
                r = rank_list[i % len(rank_list)]
                ws = [
                    {
                        "qkv": (
                            0.5 * rng_a.randn(cfg.hidden_size, r),
                            0.5 * rng_a.randn(r, 3 * cfg.hidden_size),
                        ),
                        "dense": (
                            0.5 * rng_a.randn(cfg.hidden_size, r),
                            0.5 * rng_a.randn(r, cfg.hidden_size),
                        ),
                    }
                    for _ in range(cfg.num_layers)
                ]
                aids.append(pool.register(
                    f"{prefix}-{i}", ws, rank=r, tier=i % 3,
                ))
            return aids

        def build_lora(pool):
            return InferenceEngine(
                model, params, num_slots=num_slots, capacity=capacity,
                max_prompt_len=max(lens),
                sampling=SamplingParams(temperature=0.0), seed=0,
                prefill_token_budget=budget, adapter_pool=pool,
            )

        def submit_and_drain(eng, work, new_tokens, sink=None):
            ids = [
                eng.add_request(p, new_tokens, adapter_id=a)
                for p, a in work
            ]
            out = {}
            while eng.has_work():
                for r in eng.step():
                    out[r.request_id] = r
            if sink is not None:
                sink.update(out)
            return [out[i] for i in ids]

        if chaos >= 0:
            from rocm_apex_tpu.monitor import (
                BurnRule, MetricRegistry, TenantSLOBoard,
            )

            rng_c = np.random.RandomState(chaos)
            pool = make_pool(adapters + 1)
            aids = register_all(pool, adapters)
            burst_aid = aids[int(rng_c.randint(0, len(aids)))]
            burst_n = 4 * num_slots + int(rng_c.randint(0, num_slots))
            burst_tenant = pool.tenant_of(burst_aid)
            eng = build_lora(pool)
            # warmup compiles the lora mixed + decode programs OUTSIDE
            # the measured window (a compile spike inside phase 1
            # would inflate the calibration p95 past any burst)
            submit_and_drain(
                eng,
                list(zip(prompts_a[:num_slots],
                         ([0] + aids)[:num_slots])),
                3,
            )
            eng.reset_stats()
            # phase 1 (calm): every tenant — including the future
            # burster — trickles requests one slot-wave at a time, so
            # queue wait never builds and the TTFTs calibrate the
            # alert threshold
            wave = [
                (prompts_a[i % len(prompts_a)],
                 ([0] + aids)[i % (adapters + 1)])
                for i in range(2 * (adapters + 1))
            ]
            for w0 in range(0, len(wave), num_slots):
                submit_and_drain(eng, wave[w0:w0 + num_slots], max_new)
            calm = [
                c["ttft_ms"] for c in eng.completions
                if c["ttft_ms"] > 0
            ]
            threshold = max(2.0 * float(np.percentile(calm, 95)), 1.0)
            # phase 2 (burst): the seeded tenant dumps burst_n
            # requests at once — the tail queues behind its own
            # burst, so ITS ttft blows through 2x the calm p95 while
            # no other tenant observes a single slow request
            submit_and_drain(
                eng,
                [(prompts_a[j % len(prompts_a)], burst_aid)
                 for j in range(burst_n)],
                max_new,
            )
            assert eng.mixed_trace_count == 1, (
                f"adapter burst retraced the mixed step "
                f"{eng.mixed_trace_count}x"
            )
            pool.assert_consistent()
            assert pool.snapshot()["refs"] == 1, (
                "adapter refs leaked across the burst"
            )
            # per-tenant completion-accounting identity: the host
            # tenant counters sum EXACTLY to the completion records,
            # per tenant and in aggregate
            ts = eng.tenant_stats()
            by_tenant = {}
            for c in eng.completions:
                t = c.get("tenant") or "base"
                by_tenant[t] = by_tenant.get(t, 0) + 1
            assert {
                t: s["completed"] for t, s in ts.items()
            } == by_tenant, (ts, by_tenant)
            assert sum(
                s["generated_tokens"] for s in ts.values()
            ) == sum(c["new_tokens"] for c in eng.completions)
            # replay the measured TTFTs through a real TenantSLOBoard
            # on an event-index clock: one labeled histogram, one
            # monitor per tenant, each reading ONLY its own series
            reg_b = MetricRegistry()
            hist = reg_b.histogram(
                "serve_ttft_ms",
                "Replayed per-tenant enqueue->first-token (ms).",
                labelnames=("tenant",),
            )
            board = TenantSLOBoard(
                hist, objective=0.9, threshold_ms=threshold,
                windows=(BurnRule(6.0, 3.0, 2.0),),
            )
            for t in sorted(by_tenant):
                board.ensure(t)
            board.tick(now=0.0)
            i = 0
            for c in eng.completions:
                if c["ttft_ms"] <= 0:
                    continue
                i += 1
                hist.observe(
                    c["ttft_ms"], tenant=c.get("tenant") or "base"
                )
                board.tick(now=float(i))
                board.alerts(now=float(i))
            fired = {
                t for t, mon in board.monitors.items() if mon.events
            }
            assert burst_tenant in fired, (
                f"{burst_tenant}'s burst did not trip its TTFT "
                f"burn-rate alert (threshold {threshold:.1f} ms)"
            )
            assert fired == {burst_tenant}, (
                f"the burst bled into other tenants' SLOs: "
                f"{sorted(fired - {burst_tenant})} also fired"
            )
            n_alerts = len(board.monitors[burst_tenant].events)
            print(
                f"serve[adapters={adapters} chaos seed={chaos}]: "
                f"tenant {burst_tenant} burst {burst_n} requests, "
                f"{n_alerts} alert(s) at threshold {threshold:.1f} ms; "
                f"{len(by_tenant) - 1} other tenants quiet; "
                f"accounting identity holds "
                f"({len(eng.completions)} records)",
                file=sys.stderr,
            )
            _report(
                "gpt_serve_tenant_isolation", float(n_alerts),
                "alerts", 1.0,
                f"seeded one-tenant burst (seed={chaos}): only "
                f"{burst_tenant}'s burn-rate monitor fired; "
                f"per-tenant completion accounting exact; mixed step "
                f"traced once; adapter pool leak-free",
            )
            if metrics_port >= 0:
                scrape_metrics(eng)
            return

        # ---- throughput A/B: single-model reference first, then the
        # same workload with requests striped across base + N adapters
        def run_base():
            eng = build(True)
            eng.generate(prompts_a[:num_slots], max_new_tokens=3)
            eng.reset_stats()
            t0 = time.perf_counter()
            results = eng.generate(prompts_a, max_new_tokens=max_new_a)
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in results)
            return eng, results, gen / dt, dt

        eng_b, res_b, rate_b, dt_b = run_base()
        pool = make_pool(adapters + 1)  # all resident: pure serving
        aids = register_all(pool, adapters)
        assign = [
            ([0] + aids)[i % (adapters + 1)] for i in range(n_req_a)
        ]
        eng_a = build_lora(pool)
        submit_and_drain(
            eng_a,
            list(zip(prompts_a[:num_slots], assign[:num_slots])), 3,
        )
        eng_a.reset_stats()
        t0 = time.perf_counter()
        res_a = submit_and_drain(
            eng_a, list(zip(prompts_a, assign)), max_new_a
        )
        dt_a = time.perf_counter() - t0
        rate_a = sum(len(r.tokens) for r in res_a) / dt_a
        assert eng_a.mixed_trace_count == 1, (
            f"{adapters} adapters traced the mixed step "
            f"{eng_a.mixed_trace_count}x — the segmented delta must "
            f"live inside the ONE program"
        )
        # adapter-0 requests are the base model: bitwise identical
        base_reqs = [i for i, a in enumerate(assign) if a == 0]
        for i in base_reqs:
            assert res_a[i].tokens == res_b[i].tokens, (
                f"adapter-0 request {i} diverged from the base engine"
            )
        assert any(
            res_a[i].tokens != res_b[i].tokens
            for i, a in enumerate(assign) if a != 0
        ), "no adapter changed any tokens — deltas not applied?"
        # park/reclaim churn on the SAME engine: register a second
        # wave of adapters past residency and cycle through them —
        # evictions/revivals must not retrace or leak
        extra = register_all(pool, adapters, seed0=200, prefix="late")
        churn = [aids[-1]] + extra + [aids[0]]
        for aid in churn:
            submit_and_drain(eng_a, [(prompts_a[0], aid)], 2)
        snap = pool.snapshot()
        assert snap["evictions"] > 0, snap
        assert eng_a.mixed_trace_count == 1, (
            "adapter park/reclaim retraced the mixed step"
        )
        pool.assert_consistent()
        assert snap["refs"] == 1, "adapter refs leaked"
        ratio = rate_a / rate_b
        s_a = eng_a.stats()
        print(
            f"serve[adapters={adapters}]: {rate_a:.1f} gen tok/s over "
            f"{dt_a:.2f}s vs single-model {rate_b:.1f} "
            f"({ratio:.2f}x); ranks {rank_list} padded to {max_rank}; "
            f"uploads={int(s_a['adapter_uploads'])} "
            f"evictions={int(s_a['adapter_evictions'])} "
            f"revivals={int(s_a['adapter_revivals'])}; adapter-0 "
            f"tokens bitwise identical ({len(base_reqs)} reqs); "
            f"mixed step traced once across {2 * adapters} adapters "
            f"+ churn",
            file=sys.stderr,
        )
        _report(
            "gpt_serve_adapter_tokens_per_sec_per_chip", rate_a,
            "tokens/s", ratio,
            f"{adapters} concurrent adapters (ranks {rank_list}, "
            f"rank-padded to {max_rank}) vs single-model "
            f"{rate_b:.1f} tok/s (ratio = vs_baseline); one mixed "
            f"trace; adapter-0 bitwise identical to base",
        )
        if metrics_port >= 0:
            scrape_metrics(eng_a)
        return

    if tp >= 2:
        # ---- equal-chip-count tensor-parallel A/B: tp=1 on 1 chip vs
        # tp=N on N chips, SAME checkpoint, SAME workload. The tokens
        # must not move; the per-chip KV footprint must drop 1/N.
        import dataclasses

        from rocm_apex_tpu.inference import shard_tp1_params
        from rocm_apex_tpu.transformer import parallel_state

        if len(jax.devices()) < tp:
            raise SystemExit(
                f"--tp={tp} needs {tp} visible devices, have "
                f"{len(jax.devices())} (CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={tp})"
            )
        ekw = dict(
            num_slots=num_slots, capacity=capacity,
            sampling=SamplingParams(temperature=0.0), seed=0,
            prefill_token_budget=budget, paged=True,
            page_size=page_size or default_page,
        )

        def run_tp(m, p):
            eng = InferenceEngine(m, p, **ekw)
            eng.generate(prompts[:num_slots], max_new_tokens=3)
            eng.reset_stats()
            t0 = time.perf_counter()
            results = eng.generate(prompts, max_new_tokens=max_new)
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in results)
            return eng, [r.tokens for r in results], gen / dt, dt

        eng1, toks1, rate1, _ = run_tp(model, params)
        assert eng1.mixed_trace_count == 1
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tp, 1, devices=jax.devices()[:tp]
        )
        model_tp = GPTModel(
            dataclasses.replace(cfg, tensor_parallel_size=tp)
        )
        params_tp = shard_tp1_params(model_tp, params, mesh)
        eng_t, toks_t, rate_t, dt_t = run_tp(model_tp, params_tp)
        assert eng_t.mixed_trace_count == 1, (
            f"tp={tp} mixed step traced {eng_t.mixed_trace_count}x"
        )
        assert toks1 == toks_t, (
            f"tp={tp} serve changed greedy tokens"
        )
        kv1, kvt = eng1.per_chip_kv_bytes(), eng_t.per_chip_kv_bytes()
        assert kvt * tp == kv1, (
            f"per-chip KV bytes {kvt} x{tp} != tp=1 {kv1}"
        )
        s1, s_t = eng1.stats(), eng_t.stats()
        chip_rate = rate_t / tp
        print(
            f"serve[tp{tp}]: {rate_t:.1f} gen tok/s over {dt_t:.2f}s "
            f"= {chip_rate:.1f}/chip vs tp1 {rate1:.1f}/chip "
            f"({chip_rate / rate1:.2f}x); tokens identical; per-chip "
            f"KV {kvt / 2**20:.1f} MiB vs {kv1 / 2**20:.1f} MiB "
            f"(1/{tp}); ttft p95 {s_t['ttft_ms_p95']:.0f} vs "
            f"{s1['ttft_ms_p95']:.0f} ms",
            file=sys.stderr,
        )
        _report(
            f"gpt_serve_tokens_per_sec_per_chip_tp{tp}", chip_rate,
            "tokens/s", chip_rate / rate1,
            f"tp={tp} paged serve at equal chip count vs tp=1 "
            f"{rate1:.1f} tok/s/chip (ratio = vs_baseline); greedy "
            f"tokens identical, mixed step traced once, per-chip KV "
            f"bytes exactly 1/{tp}",
        )
        _report(
            f"gpt_serve_ttft_ms_tp{tp}", s_t["ttft_ms_p95"], "ms",
            s1["ttft_ms_p95"] / max(s_t["ttft_ms_p95"], 1e-9),
            f"enqueue->first-token p95 at tp={tp} vs tp=1 "
            f"{s1['ttft_ms_p95']:.0f} ms (ratio = vs_baseline)",
        )
        parallel_state.destroy_model_parallel()
        return

    if disagg:
        # ---- equal-chip-count disaggregation A/B: a prefill/decode
        # class fleet vs an identical-replica fleet, same chips, same
        # workload. Placement and page-shipping handoffs must be
        # invisible in tokens; the per-class TTFT split is the point.
        from rocm_apex_tpu.inference import ReplicaRouter

        n_rep = replicas if replicas >= 2 else 2
        classes = (
            ["prefill"] * (n_rep // 2)
            + ["decode"] * (n_rep - n_rep // 2)
        )
        # disaggregation amortizes one page-shipping handoff per
        # request over the DECODE phase: measure the decode-heavy
        # regime it exists for (the mixed workload's 6-token CPU tail
        # would be all handoff, no decode)
        dis_new = max_new if on_tpu else max_new * 8
        ekw = dict(
            num_slots=num_slots, capacity=capacity,
            max_prompt_len=max(lens),
            sampling=SamplingParams(temperature=0.0), seed=0,
            prefill_token_budget=budget, paged=True,
            page_size=page_size or default_page,
        )

        def run_fleet(fleet_classes):
            router = ReplicaRouter(
                model, params, replicas=n_rep,
                engine_kwargs=dict(ekw),
                replica_classes=fleet_classes,
            )
            for i in range(router.num_replicas):
                router.replica(i).generate(
                    prompts[:num_slots], max_new_tokens=3
                )
                router.replica(i).reset_stats()
            t0 = time.perf_counter()
            results = router.generate(prompts, max_new_tokens=dis_new)
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in results)
            return router, results, gen / dt, dt

        # throwaway disagg pass: the page-ship import scatters compile
        # lazily on first handoff (one program per shipped-page count)
        # — warm jax's global jit cache so the timed passes measure
        # the serving fabric, not XLA
        run_fleet(classes)
        router_u, res_u, rate_u, _ = run_fleet(None)
        router_d, res_d, rate_d, dt_d = run_fleet(classes)
        assert [r.tokens for r in res_d] == [r.tokens for r in res_u], (
            "disagg fleet tokens diverged from the uniform fleet"
        )
        s_d = router_d.stats()
        assert s_d["handoffs"] >= 1, s_d
        assert s_d["page_migrations"] >= 1, s_d
        ships = 0
        for i in range(n_rep):
            rep = router_d.replica(i)
            ships += int(rep.stats().get("page_ships", 0))
            assert rep.num_active == 0 and rep.pages_used == 0, (
                f"disagg replica {i} leaked slots/pages"
            )
            rep._allocator.assert_consistent()
        assert ships >= 1, "no handoff actually shipped pages"
        # per-class TTFT p95 from the per-replica completion records,
        # attributed (like the router_ttft_ms histogram) to the class
        # of the replica that FINISHED the request
        ttft_all = [
            c["ttft_ms"]
            for i in range(n_rep)
            for c in router_u.replica(i).completions
            if c["ttft_ms"] > 0
        ]
        p95_u = float(np.percentile(ttft_all, 95)) if ttft_all else 0.0
        by_class = {}
        for i, c in enumerate(classes):
            by_class.setdefault(c, []).extend(
                rec["ttft_ms"]
                for rec in router_d.replica(i).completions
                if rec["ttft_ms"] > 0
            )
        chip_u, chip_d = rate_u / n_rep, rate_d / n_rep
        class_p95 = {
            c: float(np.percentile(v, 95))
            for c, v in by_class.items() if v
        }
        per_class = ", ".join(
            f"{c} p95={v:.0f}ms" for c, v in sorted(class_p95.items())
        )
        print(
            f"serve[disagg x{n_rep}]: {rate_d:.1f} gen tok/s "
            f"({chip_d:.1f}/chip) over {dt_d:.2f}s vs uniform "
            f"{rate_u:.1f} ({rate_d / rate_u:.2f}x); tokens identical; "
            f"{int(s_d['handoffs'])} handoffs, {ships} page ships; "
            f"ttft {per_class} vs uniform p95={p95_u:.0f}ms",
            file=sys.stderr,
        )
        _report(
            "gpt_serve_tokens_per_sec_per_chip_disagg", chip_d,
            "tokens/s", rate_d / rate_u,
            f"prefill/decode class fleet ({'+'.join(classes)}) vs "
            f"uniform x{n_rep} at equal chip count "
            f"(ratio = vs_baseline); tokens identical, "
            f"{int(s_d['handoffs'])} handoffs shipped {ships} page "
            f"payloads, both fleets leak-free",
        )
        for c, v in sorted(class_p95.items()):
            _report(
                f"gpt_serve_ttft_ms_{c}", v, "ms",
                p95_u / max(v, 1e-9),
                f"ttft p95 of requests FINISHED by {c}-class replicas "
                f"vs uniform-fleet p95 {p95_u:.0f} ms "
                f"(ratio = vs_baseline)",
            )
        if chaos >= 0:
            # ---- fleet-causal observability pass: the same disagg
            # fleet under a seeded mid-decode replica kill, with a
            # tracer on the router AND every replica, the retrace
            # sentinel armed after warmup, and the sensor ring
            # sampling the router registry every tick. Three
            # acceptance properties: (1) ONE merged Perfetto trace in
            # which every request — handed off, migrated, or failed
            # over — is a single trace_id lifeline with exactly one
            # finish; (2) the /timeseries-style windowed rate and
            # quantile queries agree with the cumulative counters and
            # see the seeded load doubling before the cumulative
            # average moves; (3) zero post-warmup XLA compiles.
            import os
            import tempfile

            from rocm_apex_tpu.inference import Fault, FaultPlan
            from rocm_apex_tpu.monitor.timeseries import TimeSeriesStore
            from rocm_apex_tpu.monitor.trace import Tracer, trace_lifelines

            rng_c = np.random.RandomState(chaos)
            victim = int(rng_c.randint(0, n_rep))
            kill_tick = int(rng_c.randint(4, 9))

            def run_observed(traced):
                # one tick-deterministic driver for both passes: the
                # throwaway pass (traced=False) replays the exact
                # schedule first so every kill-path page-ship gather
                # shape is compiled BEFORE the sentinel arms — the
                # traced pass then proves the serving fabric itself
                # never retraces
                plan = FaultPlan([
                    Fault(site="replica_kill", tick=kill_tick,
                          payload={"replica": victim}),
                ], seed=chaos)
                router = ReplicaRouter(
                    model, params, replicas=n_rep,
                    engine_kwargs=dict(ekw),
                    replica_classes=classes, faults=plan,
                    tracer=Tracer() if traced else None,
                    retrace_policy="count" if traced else None,
                )
                for i in range(router.num_replicas):
                    router.replica(i).generate(
                        prompts[:num_slots], max_new_tokens=3
                    )
                    router.replica(i).reset_stats()
                    if traced:
                        # fresh per-replica tracers AFTER warmup:
                        # merge_traces gives each its own process id
                        router.replica(i).tracer = Tracer()
                ts = None
                if traced:
                    ts = TimeSeriesStore(
                        router.registry, interval=1e-4, capacity=8192,
                    )
                    router.timeseries = ts  # step() ticks it
                    router.arm_retrace_sentinel()
                done = {}

                def tick():
                    for r in router.step():
                        done[r.request_id] = r

                # wave 1: paced arrival, one prompt per two ticks
                # (the kill fires mid-wave); drain to empty
                for p in prompts:
                    router.add_request(p, max_new_tokens=dis_new)
                    tick()
                    tick()
                guard = 0
                while router.has_work():
                    tick()
                    guard += 1
                    assert guard < 20000, "observability pass wedged"
                t2 = time.perf_counter()
                # wave 2: the seeded load doubling — twice the
                # request count offered in one burst
                for p in prompts + prompts:
                    router.add_request(p, max_new_tokens=dis_new)
                while router.has_work():
                    tick()
                    guard += 1
                    assert guard < 20000, "observability pass wedged"
                return router, ts, done, t2, plan

            run_observed(traced=False)
            router_t, ts, done, t2, plan = run_observed(traced=True)
            n_req = 3 * len(prompts)
            s_t = router_t.stats()
            assert plan.fires.get("replica_kill", 0) == 1, (
                f"replica_kill never fired: {dict(plan.fires)}"
            )
            assert len(done) == s_t["submitted"] == n_req, (
                len(done), s_t,
            )
            assert s_t["replica_kills"] >= 1 and s_t["migrations"] >= 1
            assert s_t["handoffs"] >= 1, s_t
            # (1) the merged fleet trace: one lifeline per request,
            # exactly one finish each, and the handed-off / failed-over
            # ones span more than one replica process
            trace_path = os.path.join(
                tempfile.gettempdir(),
                f"rocm_apex_disagg_fleet_trace_{os.getpid()}.json",
            )
            n_events = router_t.export_merged_trace(trace_path)
            lines = trace_lifelines(router_t.merged_trace())
            assert len(lines) == n_req, (len(lines), n_req)
            bad = {
                t: d for t, d in lines.items() if d["finishes"] != 1
            }
            assert not bad, f"lifelines without exactly one finish: {bad}"
            multi = [
                t for t, d in lines.items()
                if len([p for p in d["pids"] if p > 1]) > 1
            ]
            assert len(multi) >= len(prompts), (
                f"{int(s_t['handoffs'])} handoffs + "
                f"{int(s_t['migrations'])} migrations but only "
                f"{len(multi)} of {n_req} lifelines span 2+ replicas"
            )
            # (2) sensor plane vs cumulative counters: the full-ring
            # delta reproduces the cumulative completion count, and
            # the burst-window rate/quantile move while the
            # cumulative average still blends the paced wave
            t_end = time.perf_counter()
            assert int(round(ts.delta("router_ttft_ms"))) == n_req, (
                ts.delta("router_ttft_ms"), n_req,
            )
            w_burst = t_end - t2
            rate_burst = ts.rate("router_ttft_ms", window=w_burst)
            rate_full = ts.rate("router_ttft_ms")
            assert rate_burst > rate_full, (
                f"burst-window finish rate {rate_burst:.2f}/s did not "
                f"exceed the cumulative average {rate_full:.2f}/s"
            )
            q_burst = ts.quantile_over(
                "router_ttft_ms", 0.95, window=w_burst
            )
            q_full = ts.quantile_over("router_ttft_ms", 0.95)
            assert q_burst >= q_full, (q_burst, q_full)
            # (3) the armed sentinel saw no compile anywhere in the
            # process across kill, failover, migration, and handoff
            tripped = int(router_t.retrace_sentinel.tripped)
            assert tripped == 0, (
                f"post-warmup compiles: "
                f"{router_t.retrace_sentinel.status()}"
            )
            print(
                f"serve[disagg x{n_rep} chaos seed={chaos}]: killed "
                f"replica {victim} at tick {kill_tick}; {n_req} "
                f"requests -> {len(lines)} lifelines, every finish "
                f"exactly once, {len(multi)} span 2+ replicas "
                f"({int(s_t['handoffs'])} handoffs, "
                f"{int(s_t['migrations'])} migrations); merged trace "
                f"{n_events} events -> {trace_path}; sensor ring "
                f"{len(ts)} samples: burst rate {rate_burst:.2f}/s vs "
                f"cumulative {rate_full:.2f}/s, ttft p95 "
                f"{q_burst:.0f}ms vs {q_full:.0f}ms; retrace sentinel "
                f"{tripped} post-warmup compiles",
                file=sys.stderr,
            )
            _report(
                "gpt_serve_retrace_sentinel", float(tripped),
                "compiles", 1.0,
                f"post-warmup XLA compiles observed by the armed "
                f"retrace sentinel across the chaos-composed disagg "
                f"pass (seed={chaos}: replica kill, failover "
                f"migration, prefill->decode handoffs, load "
                f"doubling); every request one trace_id lifeline "
                f"with exactly one finish in the merged fleet trace",
            )
        return

    if replicas >= 2:
        from rocm_apex_tpu.inference import Fault, FaultPlan, ReplicaRouter

        ekw = dict(
            num_slots=num_slots, capacity=capacity,
            max_prompt_len=max(lens),
            sampling=SamplingParams(temperature=0.0), seed=0,
            prefill_token_budget=budget,
        )
        if paged:
            ekw.update(
                paged=True,
                page_size=page_size or default_page,
                kv_dtype=jnp.int8 if kv_dtype == "int8" else None,
            )

        # the undisturbed single-replica run is BOTH the rate baseline
        # and the token-parity anchor: placement and recovery must
        # never change greedy outputs
        eng_ref, res_ref, rate_ref, _ = run(True)
        ref_tokens = [r.tokens for r in res_ref]
        assert eng_ref.mixed_trace_count == 1

        def run_fleet(plan):
            router = ReplicaRouter(
                model, params, replicas=replicas,
                engine_kwargs=dict(ekw), faults=plan,
            )
            # per-replica compile warmup (the router's tick counter
            # stays 0, so seeded fault ticks land in the timed window)
            for i in range(router.num_replicas):
                router.replica(i).generate(
                    prompts[:num_slots], max_new_tokens=3
                )
                router.replica(i).reset_stats()
            t0 = time.perf_counter()
            results = router.generate(prompts, max_new_tokens=max_new)
            dt = time.perf_counter() - t0
            gen = sum(len(r.tokens) for r in results)
            return router, results, gen / dt, dt

        def check_fleet(router, results, label):
            # the ISSUE-15 survival identity, asserted on every fleet
            # pass (clean and chaotic alike)
            assert [r.tokens for r in results] == ref_tokens, (
                f"{label}: fleet tokens diverged from the "
                f"single-replica reference"
            )
            rids = [r.request_id for r in results]
            assert len(results) == n_requests == len(set(rids)), (
                f"{label}: {n_requests} submitted, {len(results)} "
                f"delivered ({len(set(rids))} unique)"
            )
            s = router.stats()
            assert s["completed"] == s["submitted"] == n_requests, s
            for i in range(router.num_replicas):
                rep = router.replica(i)
                assert rep.mixed_trace_count == 1, (
                    f"{label}: replica {i} traced the mixed step "
                    f"{rep.mixed_trace_count}x"
                )
                assert rep.num_active == 0 and rep.pages_used == 0, (
                    f"{label}: replica {i} leaked slots/pages"
                )
                if paged:
                    rep._allocator.assert_consistent()
            # the merged scrape reproduces the combined per-replica
            # completion streams (bucket adds are exact)
            merged = router.merged_registry().get("serve_ttft_ms")
            per_rep = sum(
                router.replica(i).registry.get("serve_ttft_ms").count()
                for i in range(router.num_replicas)
            )
            assert merged.count() == per_rep == n_requests, (
                f"{label}: merged ttft count {merged.count()} != "
                f"sum of replicas {per_rep} != {n_requests}"
            )
            return s

        router_f, res_f, rate_f, dt_f = run_fleet(None)
        s_f = check_fleet(router_f, res_f, f"fleet x{replicas}")
        survival = "clean pass"
        if chaos >= 0:
            # seed-derived replica fault plan: one mid-decode kill plus
            # one slow-replica injection — replays bit-for-bit from the
            # same command line
            rng_c = np.random.RandomState(chaos)
            victim = int(rng_c.randint(0, replicas))
            plan = FaultPlan([
                Fault(site="replica_kill",
                      tick=int(rng_c.randint(3, 8)),
                      payload={"replica": victim}),
                Fault(site="replica_slow",
                      tick=int(rng_c.randint(8, 12)),
                      payload={"replica": (victim + 1) % replicas,
                               "seconds": 0.001}),
            ], seed=chaos)
            router_c, res_c, _, _ = run_fleet(plan)
            s_c = check_fleet(router_c, res_c, f"chaos seed={chaos}")
            assert plan.fires.get("replica_kill", 0) == 1, (
                f"replica_kill never fired: {dict(plan.fires)}"
            )
            assert s_c["replica_kills"] >= 1, s_c
            assert s_c["migrations"] >= 1, (
                "kill mid-decode migrated no in-flight work"
            )
            survival = (
                f"chaos seed={chaos}: killed replica {victim}, "
                f"{int(s_c['migrations'])} migrations, "
                f"{int(s_c['replica_rejoins'])} rejoins — recovered "
                f"tokens bitwise-identical, no request lost or "
                f"double-delivered, killed replica's slots/pages clean"
            )
        if metrics_port >= 0:
            # fleet exporter: zero-arg merged-registry provider + the
            # fleet /healthz (503 only when NO replica is healthy)
            import http.client
            import json as _json

            srv = monitor.start_exporter(
                router=router_f, port=metrics_port
            )
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", srv.port, timeout=10
                )
                conn.request("GET", "/metrics")
                resp = conn.getresponse()
                body = resp.read()
                assert resp.status == 200, resp.status
                assert b"serve_ttft_ms_count" in body
                assert b"router_events_total" in body
                conn.request("GET", "/healthz")
                hz = conn.getresponse()
                healthy = _json.loads(hz.read()).get("healthy")
                assert hz.status == 200 and healthy, (hz.status, healthy)
                conn.close()
                print(
                    f"serve fleet metrics: {srv.url} — /metrics "
                    f"{len(body)} bytes (merged per scrape), /healthz "
                    f"200 with {int(s_f['healthy_replicas'])} healthy",
                    file=sys.stderr,
                )
            finally:
                srv.close()
        print(
            f"serve[fleet x{replicas}{'/paged' if paged else ''}]: "
            f"{rate_f:.1f} gen tok/s over {dt_f:.2f}s vs 1-replica "
            f"{rate_ref:.1f} ({rate_f / rate_ref:.2f}x); tokens "
            f"identical to the single-replica reference; {survival}",
            file=sys.stderr,
        )
        _report(
            "gpt_serve_fleet_tokens_per_sec", rate_f, "tokens/s",
            rate_f / rate_ref,
            f"{replicas}-replica ReplicaRouter vs single replica "
            f"{rate_ref:.1f} tok/s (ratio = vs_baseline); every "
            f"request accounted exactly once, fleet tokens "
            f"bitwise-identical to the 1-replica reference, merged "
            f"/metrics ttft count == sum of replicas; {survival}",
        )
        return

    if chaos >= 0:
        from rocm_apex_tpu.inference import FINISH_REASONS, Fault, FaultPlan

        kv = jnp.int8 if kv_dtype == "int8" else None
        ps = page_size or default_page
        ttft_threshold = 0.0
        backoff = 0.0
        if slo:
            # calibration: the same workload fault-free fixes the
            # alert threshold (2x its ttft p95) and must stay quiet
            # against it — the no-false-positive half of the assert
            eng_cal, _, _, _ = run(True)
            p95_cal = eng_cal.stats()["ttft_ms_p95"]
            ttft_threshold = max(2.0 * p95_cal, 1.0)
            mon_quiet = slo_replay_ttft(
                eng_cal.completions, ttft_threshold
            )
            assert not mon_quiet.events, (
                f"fault-free calibration run tripped the TTFT burn "
                f"alert: {mon_quiet.events}"
            )
            backoff = min(1.0, max(0.05, p95_cal / 1000.0))
        # the schedule derives from SEED alone, so a red run replays
        # bit-for-bit with the same command line
        rng_c = np.random.RandomState(chaos)
        faults = [
            Fault(site="device_step", tick=int(rng_c.randint(1, 5))),
            Fault(site="logits", tick=int(rng_c.randint(5, 10)),
                  payload={"slot": int(rng_c.randint(0, num_slots))}),
            Fault(site="host_fetch", p=0.05, times=2),
            # consulted on the paged engine only; 0 fires on contiguous
            Fault(site="page_alloc", nth=int(rng_c.randint(2, 7))),
        ]
        if slo:
            # latency burst: six consecutive mid-run ticks each lose
            # one device-step attempt (distinct ticks, times=1 each —
            # retries cannot exhaust on them) and step_retry_backoff
            # stalls each retry ~one fault-free p95, so the requests
            # queued behind the burst blow through the 2x-p95 alert
            # threshold while the early wave stays under it
            faults.extend(
                Fault(site="device_step", tick=t)
                for t in range(10, 16)
            )
        plan = FaultPlan(faults, seed=chaos)
        eng = InferenceEngine(
            model, params, num_slots=num_slots, capacity=capacity,
            max_prompt_len=max(lens),
            sampling=SamplingParams(temperature=0.0), seed=0,
            prefill_token_budget=budget, faults=plan,
            # p=0.05 times=2 can never out-fire 3 attempts — the plan
            # is chaotic, not unrecoverable (under --slo the burst
            # adds ONE deterministic fire per tick, so the margin
            # needs one more retry)
            max_step_retries=3 if slo else 2,
            step_retry_backoff=backoff,
            # bounded admission: the last 2 submissions shed
            max_queue=n_requests - 2,
            paged=paged, page_size=ps if paged else 16,
            kv_dtype=kv if paged else None,
        )
        baseline = eng._allocator.snapshot() if paged else None
        for p in prompts:
            eng.add_request(p, max_new_tokens=max_new)
        done = {}
        for _ in range(2):
            for r in eng.step():
                done[r.request_id] = r
        victim = next(
            st.req.request_id for st in eng._slots if st is not None
        )
        done[victim] = eng.cancel(victim)
        done.update(
            {r.request_id: r for r in eng.drain()}
        )
        s = eng.stats()
        shed = int(s["shed"])
        quar = int(s["quarantined"])
        canc = int(s["cancelled"])
        dead = int(s["deadline_exceeded"])
        reasons = {}
        for c in eng.completions:
            reasons[c["finish_reason"]] = (
                reasons.get(c["finish_reason"], 0) + 1
            )
        finished_ok = sum(
            n for why, n in reasons.items()
            if why in ("length", "stop", "capacity")
        )
        # the accounting identity: one record per submission, every
        # record a known reason, the teardown counters summing exactly
        assert len(eng.completions) == n_requests, (
            f"{n_requests} submitted, {len(eng.completions)} accounted"
        )
        assert set(reasons) <= set(FINISH_REASONS), reasons
        assert (
            finished_ok + shed + quar + canc + dead == n_requests
        ), (
            f"completion accounting leaked: {finished_ok} completed + "
            f"{shed} shed + {quar} quarantined + {canc} cancelled + "
            f"{dead} expired != {n_requests} submitted ({reasons})"
        )
        assert quar == reasons.get("error", 0)
        assert eng.mixed_trace_count == 1, "chaos retraced the mixed step"
        assert sum(plan.fires.values()) >= 2, (
            f"chaos plan barely fired: {dict(plan.fires)}"
        )
        if paged:
            eng._allocator.assert_consistent()
            assert eng._allocator.snapshot() == baseline, (
                "pages leaked across the chaos run"
            )
        print(
            f"serve[chaos seed={chaos}{'/paged' if paged else ''}]: "
            f"{finished_ok} completed, {shed} shed, {quar} "
            f"quarantined, {canc} cancelled, {dead} expired of "
            f"{n_requests}; retries={int(s['step_retries'])} "
            f"fires={dict(plan.fires)} — accounting identity holds",
            file=sys.stderr,
        )
        _report(
            "gpt_serve_chaos_survival", float(finished_ok), "requests",
            finished_ok / n_requests,
            f"seeded chaos (seed={chaos}): completed + shed + "
            f"quarantined + cancelled + expired == submitted "
            f"({n_requests}); mixed step traced once; "
            f"{'no page leak; ' if paged else ''}"
            f"fault fires {dict(plan.fires)}",
        )
        if slo:
            mon_chaos = slo_replay_ttft(eng.completions, ttft_threshold)
            n_alerts = len(mon_chaos.events)
            assert n_alerts > 0, (
                f"chaos latency burst did not trip the TTFT burn-rate "
                f"alert (threshold {ttft_threshold:.0f} ms, fires "
                f"{dict(plan.fires)})"
            )
            _report(
                "gpt_serve_slo_alerts", float(n_alerts), "alerts", 1.0,
                f"ttft burn-rate: chaos fired {n_alerts} alert(s) at "
                f"threshold {ttft_threshold:.0f} ms (2x fault-free "
                f"p95); fault-free calibration pass stayed quiet",
            )
        if metrics_port >= 0:
            scrape_metrics(eng)
        return

    if paged or shared_prefix:
        kv = jnp.int8 if kv_dtype == "int8" else None
        ps = page_size or default_page
        suffix = "_int8" if kv is not None else ""

        def build_paged(sharing):
            return InferenceEngine(
                model, params, num_slots=num_slots, capacity=capacity,
                sampling=SamplingParams(temperature=0.0), seed=0,
                prefill_token_budget=budget, paged=True, page_size=ps,
                kv_dtype=kv, prefix_sharing=sharing,
            )

        def run_steps(eng):
            # warmup compiles on the same engine; under prefix sharing
            # it ALSO registers the shared prefix, so the timed window
            # measures steady-state serving (warm store). The second
            # tiny pass replays a TRUNCATED first prompt that ends
            # INSIDE a stored page (the prefix is not page-aligned):
            # partial borrow -> the copy-on-write fork program
            # compiles here, not in the timed window
            eng.generate(prompts[:num_slots], max_new_tokens=3)
            if eng.prefix_sharing and shared_prefix:
                eng.generate(
                    [prompts[0][:prefix_len + 2]], max_new_tokens=3
                )
            eng.reset_stats()
            ids = [
                eng.add_request(p, max_new_tokens=max_new)
                for p in prompts
            ]
            done = {}
            peak_pages = 0
            t0 = time.perf_counter()
            while eng.has_work():
                for r in eng.step():
                    done[r.request_id] = r
                if eng.paged:
                    peak_pages = max(
                        peak_pages, int(eng.stats()["pages_used"])
                    )
            dt = time.perf_counter() - t0
            results = [done[i] for i in ids]
            gen = sum(len(r.tokens) for r in results)
            return eng, results, gen / dt, dt, eng.stats(), peak_pages

        if shared_prefix:
            _, res_b, tok_b, dt_b, s_b, _ = run_steps(build_paged(False))
            _, res_s, tok_s, dt_s, s_s, _ = run_steps(build_paged(True))
            # sharing maps the SAME materialized pages a private
            # prefill would have produced — tokens must not move
            for rb, rs in zip(res_b, res_s):
                assert rb.tokens == rs.tokens, (
                    f"prefix sharing changed tokens on request "
                    f"{rs.request_id}"
                )
            assert s_s["prefix_hits"] > 0, "no prefix hits measured"
            for mode, tk, dt, s in (
                ("paged", tok_b, dt_b, s_b),
                ("paged+shared", tok_s, dt_s, s_s),
            ):
                print(
                    f"serve[{mode}{suffix}]: {tk:.1f} gen tok/s over "
                    f"{dt:.2f}s ttft p95={s['ttft_ms_p95']:.0f}ms "
                    f"prefix_hits={s['prefix_hits']:.0f} "
                    f"hit_tokens={s['prefix_hit_tokens']:.0f} "
                    f"cow_forks={s['cow_forks']:.0f}",
                    file=sys.stderr,
                )
            _report(
                f"gpt_serve_tokens_per_sec_per_chip_shared_prefix{suffix}",
                tok_s, "tokens/s", tok_s / tok_b,
                f"prefix sharing {tok_s:.1f} vs plain paged "
                f"{tok_b:.1f} tok/s; {s_s['prefix_hit_tokens']:.0f} "
                f"prompt tokens never re-prefilled; tokens identical",
            )
            _report(
                f"gpt_serve_ttft_ms_shared_prefix{suffix}",
                s_s["ttft_ms_p95"], "ms",
                s_b["ttft_ms_p95"] / max(s_s["ttft_ms_p95"], 1e-9),
                f"ttft p95: shared {s_s['ttft_ms_p95']:.0f} ms vs "
                f"plain paged {s_b['ttft_ms_p95']:.0f} ms "
                f"(ratio = vs_baseline)",
            )
            return

        # plain paged A/B against the contiguous chunked engine
        eng_c, res_c, tok_c, dt_c = run(True)
        s_c = eng_c.stats()
        eng_p, res_p, tok_p, dt_p, s_p, peak = run_steps(
            build_paged(False)
        )
        if kv is None:
            for rc, rp in zip(res_c, res_p):
                assert rc.tokens == rp.tokens, (
                    f"paged/contiguous token mismatch on request "
                    f"{rp.request_id}"
                )
            parity = "tokens identical"
        else:
            same = sum(
                rc.tokens == rp.tokens for rc, rp in zip(res_c, res_p)
            )
            parity = f"int8 greedy match {same}/{len(res_c)} requests"
        cont_bytes = eng_c.cache_bytes()
        pool_bytes = eng_p.cache_bytes()
        num_pages = eng_p.cache.num_pages
        live_bytes = int(pool_bytes * peak / max(num_pages, 1))
        mb = 1.0 / (1024 * 1024)
        print(
            f"serve[paged{suffix}]: {tok_p:.1f} gen tok/s over "
            f"{dt_p:.2f}s (page_size={ps}) vs contiguous {tok_c:.1f}; "
            f"cache bytes: contiguous {cont_bytes*mb:.2f} MiB, paged "
            f"pool {pool_bytes*mb:.2f} MiB, peak LIVE "
            f"{live_bytes*mb:.2f} MiB ({peak}/{num_pages} pages) — "
            f"{parity}",
            file=sys.stderr,
        )
        _report(
            f"gpt_serve_tokens_per_sec_per_chip_paged{suffix}",
            tok_p, "tokens/s", tok_p / tok_c,
            f"paged {tok_p:.1f} vs contiguous {tok_c:.1f} tok/s; "
            f"{parity}; peak live cache {live_bytes*mb:.2f} MiB vs "
            f"contiguous {cont_bytes*mb:.2f} MiB",
        )
        _report(
            f"gpt_serve_ttft_ms_paged{suffix}",
            s_p["ttft_ms_p95"], "ms",
            s_c["ttft_ms_p95"] / max(s_p["ttft_ms_p95"], 1e-9),
            f"ttft p95: paged {s_p['ttft_ms_p95']:.0f} ms vs "
            f"contiguous {s_c['ttft_ms_p95']:.0f} ms "
            f"(ratio = vs_baseline)",
        )
        return

    # --trace instruments the MEASURED mode (chunked, or whole under
    # --whole-prompt) — the A/B contrast numbers stay tracer-free
    tracer = monitor.Tracer() if trace else None
    traced_mode = "whole" if whole_prompt else "chunked"
    modes = ["whole"] if whole_prompt else ["whole", "chunked"]
    out = {}
    for mode in modes:
        eng, results, tok_s, dt = run(
            mode == "chunked",
            tracer if mode == traced_mode else None,
        )
        s = eng.stats()
        out[mode] = (tok_s, s, results)
        if trace and mode == traced_mode:
            n = tracer.export_chrome_trace(trace)
            req_path = trace + ".requests.jsonl"
            with open(req_path, "w") as f:
                w = monitor.JsonlWriter(stream=f)
                for rec in eng.completions:
                    w.emit(rec)
            print(
                f"serve trace: {n} events -> {trace}; "
                f"{len(eng.completions)} request records -> {req_path}",
                file=sys.stderr,
            )
        print(
            f"serve[{mode}]: {tok_s:.1f} gen tok/s over {dt:.2f}s "
            f"(prompt_tokens={total_prompt} budget="
            f"{budget if mode == 'chunked' else 'whole'}) "
            f"ttft p50/p95={s['ttft_ms_p50']:.0f}/"
            f"{s['ttft_ms_p95']:.0f}ms "
            f"queue_wait p95={s['queue_wait_ms_p95']:.0f}ms "
            f"mixed_traces={eng.mixed_trace_count} "
            f"prefill_traces={eng.prefill_trace_count}",
            file=sys.stderr,
        )
    if slo:
        # fault-free serving must not page anyone: replay the measured
        # run's TTFTs against a threshold budgeted off its own p95 —
        # the quiet half of the --chaos --slo acceptance pair
        s_m = out[traced_mode][1]
        thresh = max(2.0 * s_m["ttft_ms_p95"], 1.0)
        mon = slo_replay_ttft(eng.completions, thresh)
        assert not mon.events, (
            f"fault-free serve run tripped the TTFT burn alert: "
            f"{mon.events}"
        )
        _report(
            "gpt_serve_slo_alerts", 0.0, "alerts", 1.0,
            f"ttft burn-rate quiet on the fault-free {traced_mode} "
            f"run (threshold {thresh:.0f} ms = 2x its p95)",
        )
    if metrics_port >= 0:
        scrape_metrics(eng)
    if whole_prompt:
        tok_s, s, _ = out["whole"]
        _report("gpt_serve_tokens_per_sec_per_chip_whole", tok_s,
                "tokens/s", 1.0, "")
        _report("gpt_serve_ttft_ms_whole", s["ttft_ms_p95"], "ms", 1.0,
                "")
        return
    # greedy outputs must be token-identical across the A/B pair — a
    # throughput win that changes tokens is not a win
    for rc, rw in zip(out["chunked"][2], out["whole"][2]):
        assert rc.tokens == rw.tokens, (
            f"chunked/whole token mismatch on request {rc.request_id}"
        )
    tok_c, s_c, _ = out["chunked"]
    tok_w, s_w, _ = out["whole"]
    _report(
        "gpt_serve_tokens_per_sec_per_chip", tok_c, "tokens/s",
        tok_c / tok_w,
        f"chunked {tok_c:.1f} vs whole-prompt {tok_w:.1f} tok/s "
        f"(speedup = vs_baseline); tokens identical",
    )
    _report(
        "gpt_serve_ttft_ms", s_c["ttft_ms_p95"], "ms",
        s_w["ttft_ms_p95"] / max(s_c["ttft_ms_p95"], 1e-9),
        f"ttft p95: chunked {s_c['ttft_ms_p95']:.0f} ms vs whole "
        f"{s_w['ttft_ms_p95']:.0f} ms (ratio = vs_baseline)",
    )


def _lint_head_is_chunked(cfg, batch: int, seq: int) -> bool:
    """True when the fused LM head really tiles (b·s, vocab): with few
    rows the op's default chunk covers them all and the single tile IS
    logits-shaped by design, so the no-materialization probe would
    flag a non-violation."""
    from rocm_apex_tpu.ops.linear_xentropy import _chunk_rows

    rows = batch * seq
    return _chunk_rows(rows, cfg.vocab_size, cfg.lm_head_chunk_size) < rows


def _timed_scan(step, init, iters):
    """ms per iteration of `step` (carry -> carry) inside one dispatch.

    The carry must make each iteration depend on the last or XLA hoists
    the body out of the loop. Per-dispatch overhead (launch plus the
    scalar fetch that ends the region, which swamps sub-ms kernels) is
    cancelled exactly by timing scans of length N and 2N and taking
    (T(2N) - T(N)) / N; each is timed 3x and the minima are differenced
    (min is the low-noise duration estimator). Syncs are scalar
    fetches."""

    def sync(tree):
        leaf = jax.tree_util.tree_leaves(tree)[0]
        float(leaf.reshape(-1)[0].astype(jnp.float32))

    def make(n):
        @jax.jit
        def many(c):
            return jax.lax.scan(
                lambda c, _: (step(c), None), c, None, length=n
            )[0]

        return many

    many_n, many_2n = make(iters), make(2 * iters)
    c = many_n(init)
    sync(c)
    c2 = many_2n(init)
    sync(c2)

    def best(f):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            sync(f(init))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    dt = best(many_2n) - best(many_n)
    if dt <= 0:
        # host jitter exceeded the device time at this scan length:
        # re-measure at 4x before giving up (never silently report
        # noise as an absurdly fast kernel)
        many_4n, many_8n = make(4 * iters), make(8 * iters)
        sync(many_4n(init))
        sync(many_8n(init))
        dt = (best(many_8n) - best(many_4n)) / 4.0
        if dt <= 0:
            raise RuntimeError(
                "timing noise exceeded device time even at 8x iters; "
                "raise `iters` for this bench"
            )
    return dt / iters * 1000.0


def bench_attn():
    """Long-context flash attention sweep (the reference's perf-test
    analogue is
    apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py —
    its kernels cap at seqlen 512/2048, this sweep runs to 32k)."""
    from rocm_apex_tpu.ops.flash_attention import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    bh, hd = 8, 128
    seqs = (8192, 16384, 32768) if on_tpu else (256,)
    rows = []
    for s in seqs:
        # enough iterations that RTT jitter (±~15 ms across dispatches)
        # stays well under the per-iter signal
        iters = max(10, 400_000 // s) if on_tpu else 2
        q, k, v = (
            jax.random.normal(jax.random.PRNGKey(i), (bh, s, hd), jnp.bfloat16)
            for i in range(3)
        )

        def step(carry, q=q, k=k, v=v):
            q2, acc = carry

            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, None, True).astype(jnp.float32)
                    ** 2
                )

            l, grads = jax.value_and_grad(loss, (0, 1, 2))(q2, k, v)
            g = sum(jnp.sum(t.astype(jnp.float32)) for t in grads)
            # feed the loss back into q at 1e-30 scale: numerically a
            # no-op in bf16, but it defeats loop-invariant hoisting
            return q2 + (l * 1e-30).astype(q2.dtype), acc + l + g

        ms = _timed_scan(step, (q, jnp.float32(0)), iters)
        # 7 block-matmuls (2 fwd + 5 merged bwd) x 2*hd MAC-FLOPs per
        # score position, over the causal half: 7 * 2*hd * bh * s^2/2
        flops = 7.0 * bh * s * s * hd
        tf = flops / (ms / 1000.0) / 1e12
        rows.append((s, ms, tf))
        print(f"attn s={s}: {ms:.1f} ms/iter  {tf:.1f} eff TFLOP/s",
              file=sys.stderr)
    s, ms, tf = rows[-1]
    _report(
        "flash_attention_fwd_bwd_ms_s32k" if on_tpu else "flash_attention_ms",
        ms, "ms",
        _mfu(flops, ms / 1000.0),
        f"sweep: {', '.join(f's={s}: {m:.1f}ms' for s, m, _ in rows)}",
    )


def bench_fmha():
    """Packed-native vs padded-batch varlen attention at high
    raggedness (reference design point:
    apex/contrib/fmha packed kernels). 64 sequences drawn from a
    long-tailed length mix padding to max_s=2048: the padded path pays
    b*max_s, the packed path pays O(total)."""
    import numpy as np

    from rocm_apex_tpu.contrib.fmha import fmha

    on_tpu = jax.default_backend() == "tpu"
    h, d = 8, 64
    if on_tpu:
        rng = np.random.RandomState(0)
        lens = rng.choice(
            [64, 128, 256, 512, 2048], size=64, p=[0.3, 0.3, 0.2, 0.15, 0.05]
        ).tolist()
        iters = 20
    else:
        lens = [32, 64, 8]
        iters = 2
    max_s = max(lens)
    cu = jnp.asarray(np.cumsum([0] + lens), jnp.int32)
    total = int(cu[-1])
    qkv = 0.5 * jax.random.normal(
        jax.random.PRNGKey(0), (total, 3, h, d), jnp.bfloat16
    )
    print(
        f"fmha raggedness: b={len(lens)} total={total} "
        f"b*max_s={len(lens) * max_s}",
        file=sys.stderr,
    )

    results = {}
    for name, packed in (("packed", True), ("padded", False)):
        def step(carry, packed=packed):
            x, acc = carry

            def loss(x):
                return jnp.sum(
                    fmha(
                        x, cu, max_s, causal=True, packed=packed
                    ).astype(jnp.float32) ** 2
                )

            l, g = jax.value_and_grad(loss)(x)
            tot = l + jnp.sum(g.astype(jnp.float32))
            return x + (tot * 1e-30).astype(x.dtype), acc + tot

        results[name] = _timed_scan(step, (qkv, jnp.float32(0)), iters)
        print(f"fmha {name}: {results[name]:.2f} ms fwd+bwd", file=sys.stderr)
    _report(
        "fmha_packed_native_fwd_bwd_ms", results["packed"], "ms",
        results["padded"] / results["packed"],
        f"packed {results['packed']:.2f} ms vs padded "
        f"{results['padded']:.2f} ms (speedup = vs_baseline)",
    )


def bench_optim():
    """Optimizer micro-bench on the 134M-param GPT tree: parity
    `fused_adam` (XLA-tree-fused) vs
    `MixedPrecisionAdam.step_and_probe`."""
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
    from rocm_apex_tpu.optimizers import fused_adam
    from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam

    on_tpu = jax.default_backend() == "tpu"
    iters = 50 if on_tpu else 2
    cfg = GPTConfig(
        vocab_size=32768 if on_tpu else 512,
        hidden_size=1024 if on_tpu else 64,
        num_layers=8 if on_tpu else 2,
        num_attention_heads=8 if on_tpu else 4,
        max_position_embeddings=1024 if on_tpu else 64,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=1,
    )
    tokens = jnp.zeros((1, 64), jnp.int32)
    params = GPTModel(cfg).init(jax.random.PRNGKey(0), tokens)
    # runtime-derived grads (a constant tree would let XLA fold the
    # moment updates below their real bandwidth cost)
    grads = jax.tree_util.tree_map(
        lambda p: (p * 1e-3 + 1e-5).astype(jnp.bfloat16), params
    )
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))

    opt = fused_adam(1e-4, weight_decay=0.01)
    o_state = opt.init(params)

    import optax

    def step_parity(carry):
        p, s, g = carry
        updates, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, updates), s2, g

    ms_parity = _timed_scan(step_parity, (params, o_state, grads), iters)

    mp = MixedPrecisionAdam(1e-4, weight_decay=0.01)
    m_state = mp.init(params)

    def step_mixed(carry):
        state, g = carry
        state2, _ = mp.step_and_probe(state, g, grad_scale=1.0)
        return state2, g

    ms_mixed = _timed_scan(step_mixed, (m_state, grads), iters)
    print(
        f"optim ({n/1e6:.0f}M tree): fused_adam {ms_parity:.2f} ms, "
        f"MixedPrecisionAdam.step_and_probe {ms_mixed:.2f} ms",
        file=sys.stderr,
    )
    # fp32 p/m/v read+write + bf16 grads read ≈ 26 bytes/param
    floor_ms = 26.0 * n / 819e9 * 1000 if on_tpu else None
    _report(
        "mixed_precision_adam_step_ms", ms_mixed, "ms",
        (floor_ms / ms_mixed) if floor_ms else 0.0,
        f"vs bandwidth floor {floor_ms:.2f} ms" if floor_ms else "",
    )


def bench_ln():
    """Fused LayerNorm micro-bench (reference perf scaffolding:
    apex/contrib/test fast LN tests). Measures the
    Pallas LN fwd+bwd on GPT-bench-shaped rows vs the jnp composition."""
    from rocm_apex_tpu.normalization.fused_layer_norm import (
        fused_layer_norm_affine,
    )

    on_tpu = jax.default_backend() == "tpu"
    rows, hidden = (16384, 1024) if on_tpu else (64, 32)
    iters = 100 if on_tpu else 2
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden), jnp.bfloat16)
    g = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    def jnp_ln(x, g, b):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        return ((xf - mu) * jax.lax.rsqrt(var + 1e-5) * g + b).astype(x.dtype)

    results = {}

    def pallas_ln(x, g, b):
        return fused_layer_norm_affine(x, g, b, (hidden,), 1e-5)

    for name, fn in (("pallas", pallas_ln), ("xla", jnp_ln)):
        def step(carry, fn=fn):
            x2, acc = carry
            l, (gx, gg, gb) = jax.value_and_grad(
                lambda x, g, b: jnp.sum(fn(x, g, b).astype(jnp.float32) ** 2),
                (0, 1, 2),
            )(x2, g, b)
            tot = l + sum(
                jnp.sum(t.astype(jnp.float32)) for t in (gx, gg, gb)
            )
            return x2 + (tot * 1e-30).astype(x2.dtype), acc + tot

        results[name] = _timed_scan(step, (x, jnp.float32(0)), iters)
        print(f"ln {name}: {results[name]:.3f} ms fwd+bwd", file=sys.stderr)
    _report(
        "fused_layer_norm_fwd_bwd_ms", results["pallas"], "ms",
        results["xla"] / results["pallas"],
        f"pallas {results['pallas']:.3f} ms vs xla {results['xla']:.3f} ms",
    )


def main(dropout: float = 0.1, seq: int = 0, batch: int = 0,
         remat: bool = False, loss: str = "fused",
         seq_parallel: bool = False, collective_matmul: bool = False,
         audit: bool = False, lint: bool = False, dist_opt: bool = False,
         packed_update: bool = False, comm_dtype: str = "fp32"):
    if loss not in ("fused", "naive"):
        raise SystemExit(f"--loss must be 'fused' or 'naive', got {loss!r}")
    if collective_matmul and not seq_parallel:
        raise SystemExit("--collective-matmul requires --seq-parallel")
    if comm_dtype not in ("fp32", "int8"):
        raise SystemExit(
            f"--comm-dtype must be 'fp32' or 'int8', got {comm_dtype!r}"
        )
    if comm_dtype != "fp32" and not (dist_opt or collective_matmul):
        raise SystemExit(
            "--comm-dtype=int8 quantizes ring collectives; it needs "
            "--dist-opt (ZeRO grad/param rings) or --collective-matmul "
            "(TP-boundary rings) to have a ring to quantize"
        )
    if dist_opt and seq_parallel:
        raise SystemExit(
            "--dist-opt does not compose with --seq-parallel"
        )
    if dist_opt and loss != "fused":
        raise SystemExit("--dist-opt measures the fused-loss path")
    if packed_update and (dist_opt or seq_parallel):
        raise SystemExit(
            "--packed-update A/Bs the replicated optimizer step; the "
            "ZeRO path (--dist-opt) is always packed and the tp series "
            "keys on the model sharding"
        )
    if lint and dist_opt:
        raise SystemExit(
            "--lint checks the replicated train step; the ZeRO path's "
            "contracts live in tools/graphlint.py (zero_int8 config)"
        )
    on_tpu = jax.default_backend() == "tpu"
    # tp-axis A/B: shard the model over ALL visible chips on the
    # tensor axis with sequence-parallel activations between the TP
    # boundaries; --collective-matmul additionally fuses the boundary
    # collectives into ppermute-ring matmuls (ops/collective_matmul).
    # On a one-chip host the flags still run (identity collectives) so
    # the code path and the distinct metric key are exercised.
    tp = len(jax.devices()) if seq_parallel else 1
    default_seq = SEQ if on_tpu else 128
    seq = min(seq or default_seq, default_seq if not on_tpu else 1 << 20)
    # long-context configs shrink the batch to fit and pay ITERS down
    # (the S^2 attention makes each step long enough to amortize RTT)
    default_batch = (
        BATCH if seq <= 2048 else max(1, BATCH * SEQ // (4 * seq))
    )
    batch = batch or default_batch
    iters = ITERS if seq <= 2048 else max(8, ITERS * SEQ // seq)
    parallelism = dict(
        tensor_parallel_size=tp,
        sequence_parallel=seq_parallel,
        collective_matmul=collective_matmul,
        comm_dtype=comm_dtype if collective_matmul else "fp32",
        checkpoint_activations=remat,
    )
    if on_tpu:
        cfg = gpt_134m.train_config(seq, dropout, **parallelism)
    else:
        cfg = GPTConfig(
            vocab_size=1024, hidden_size=128, num_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            hidden_dropout=dropout, attention_dropout=dropout,
            **parallelism,
        )
    seq = min(seq, cfg.max_position_embeddings)

    mesh = None
    if tp > 1:
        from rocm_apex_tpu.transformer import parallel_state

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(tp, 1)

    model = GPTModel(cfg)
    opt = MixedPrecisionAdam(1e-4, weight_decay=0.01)
    scaler = LossScaler(loss_scale="dynamic")

    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        # sharded init: each rank draws its own weight shards (rank-
        # folded init); the batch is replicated over the tensor axis
        def local_init(tokens):
            return model.init(jax.random.PRNGKey(1), tokens)

        params32 = jax.jit(
            shard_map(
                local_init, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False,
            )
        )(tokens[:1])
    else:
        params32 = model.init(jax.random.PRNGKey(1), tokens[:1])

    if dist_opt:
        # ---- ZeRO-sharded data-parallel training (--dist-opt): the
        # contrib DistributedFusedAdam replaces the replicated
        # MixedPrecisionAdam — each rank feeds its UNREDUCED local
        # grads straight into the transform (no pre-pmean: the
        # reduce-scatter IS the gradient averaging), updates only its
        # 1/dp master/moment shards, and all-gathers fresh params.
        # Optimizer state per chip shrinks by dp; the metric line
        # reports the measured bytes next to step time.
        import numpy as np
        import optax
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from rocm_apex_tpu.contrib.optimizers import (
            distributed_fused_adam,
        )

        dp = len(jax.devices())
        batch = max(dp, (batch // dp) * dp)
        tokens = tokens[:batch]
        labels = labels[:batch]
        dmesh = Mesh(np.array(jax.devices()), ("data",))
        dist = distributed_fused_adam(
            1e-4, weight_decay=0.01, allgather_dtype="fp32",
            axis_name="data", comm_dtype=comm_dtype,
        )
        ostate = jax.jit(
            shard_map(
                dist.init, mesh=dmesh, in_specs=(P(),),
                out_specs=P(), check_vma=False,
            )
        )(params32)

        def local_runN_zero(params, ostate, rng, tok_l, lab_l):
            def one(carry, _):
                params, ostate, rng = carry
                rng, step_rng = jax.random.split(rng)

                def loss_fn(p):
                    rngs = (
                        {"dropout": step_rng} if dropout > 0.0 else None
                    )
                    return model.apply(
                        p, tok_l, labels=lab_l, loss_reduction="mean",
                        deterministic=dropout == 0.0, rngs=rngs,
                    )

                loss, grads = jax.value_and_grad(loss_fn)(params)
                updates, ostate2 = dist.update(grads, ostate, params)
                return (
                    optax.apply_updates(params, updates), ostate2, rng
                ), loss

            (params, ostate, rng), losses = jax.lax.scan(
                one, (params, ostate, rng), None, length=iters,
                unroll=2,
            )
            return params, ostate, rng, losses

        # params/ostate are DONATED: the scan consumes and returns them,
        # so the executable updates in place instead of holding both
        # generations live (the donation lint pins this). Only metadata
        # reads of params32 (`.size` for the param count) happen after
        # the first call — those survive buffer deletion.
        runN_z = jax.jit(
            shard_map(
                local_runN_zero, mesh=dmesh,
                in_specs=(P(), P(), P(), P("data"), P("data")),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )
        rng0 = gpt_134m.dropout_key(dropout)
        params_z, ostate, rng0, losses = runN_z(
            params32, ostate, rng0, tokens, labels
        )
        float(losses[-1])  # warmup + sync
        t0 = time.perf_counter()
        params_z, ostate, rng0, losses = runN_z(
            params_z, ostate, rng0, tokens, labels
        )
        loss_val = float(losses[-1])
        dt = (time.perf_counter() - t0) / iters

        n_params = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params32)
        ) - cfg.vocab_size * cfg.hidden_size
        raw_params = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params32)
        )
        # sharded leaves leave the shard_map with local (1/dp) shapes:
        # summing them IS the per-chip optimizer footprint. The
        # replicated MixedPrecisionAdam reference holds fp32 master +
        # m + v on every chip (12 bytes/param).
        opt_bytes = sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(ostate)
        )
        repl_bytes = 12 * raw_params
        mb = 1.0 / (1024 * 1024)
        step_flops = monitor.model_flops(
            cfg, batch, seq, n_params=n_params
        )
        mfu = _mfu(step_flops, dt, n_chips=dp)
        suffix = "_dropout" if dropout > 0.0 else ""
        if seq != default_seq:
            suffix += f"_s{seq}"
        if batch != default_batch:
            suffix += f"_b{batch}"
        if remat:
            suffix += "_remat"
        suffix += f"_zero_dp{dp}"
        if comm_dtype != "fp32":
            suffix += f"_{comm_dtype}comm"
        _report(
            f"gpt_train_tokens_per_sec_per_chip{suffix}",
            batch * seq / dt / dp, "tokens/s", mfu / 0.70,
            f"step={dt*1000:.1f}ms loss={loss_val:.4f} mfu={mfu:.3f} "
            f"optimizer state {opt_bytes*mb:.2f} MiB/chip (ZeRO "
            f"dp={dp}; replicated fp32 master+m+v would be "
            f"{repl_bytes*mb:.2f} MiB/chip) dropout={dropout} "
            f"b={batch} s={seq} remat={remat} "
            f"backend={jax.default_backend()}",
        )
        # static comm audit (monitor/audit.py): trace ONE ZeRO step
        # abstractly — no compile, no timing impact — and land the
        # estimated collective wire bytes in the jsonl BENCH output so
        # the --comm-dtype A/B is a first-class metric, not a stderr
        # footnote.
        def _one_zero(params, ostate, rng, tok_l, lab_l):
            rng, step_rng = jax.random.split(rng)

            def loss_fn(p):
                rngs = {"dropout": step_rng} if dropout > 0.0 else None
                return model.apply(
                    p, tok_l, labels=lab_l, loss_reduction="mean",
                    deterministic=dropout == 0.0, rngs=rngs,
                )

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, _ = dist.update(grads, ostate, params)
            return loss

        rep = monitor.audit(
            shard_map(
                _one_zero, mesh=dmesh,
                in_specs=(P(), P(), P(), P("data"), P("data")),
                out_specs=P(), check_vma=False,
            ),
            params_z, ostate, rng0, tokens, labels,
        )
        comm_mib = rep.collective_wire_bytes * mb
        _report(
            f"gpt_comm_payload_mib{suffix}", comm_mib, "MiB", 1.0,
            f"estimated per-step collective wire bytes (ZeRO dp={dp}, "
            f"comm_dtype={comm_dtype}; monitor/audit.py conventions) "
            f"ppermute={rep.count('ppermute')} "
            f"backend={jax.default_backend()}",
        )
        if audit:
            print("audit: one gpt ZeRO train step", file=sys.stderr)
            print(rep.summary(), file=sys.stderr)
        return

    state = opt.init(params32)
    sstate = scaler.init()
    rng0 = gpt_134m.dropout_key(dropout)

    def make_one_step(opt):
        # parameterized over the optimizer so --packed-update can run
        # the identical step with PackedOptimizerStep (same
        # init/model/step_and_probe surface as MixedPrecisionAdam)
        def one_step(carry, _):
            state, sstate, rng = carry
            rng, step_rng = jax.random.split(rng)

            def loss_fn(params):
                rngs = {"dropout": step_rng} if dropout > 0.0 else None
                if loss == "naive":
                    # A/B reference: materialize the full (b, s, vocab)
                    # logits, cast fp32, optax CE — the path the model
                    # no longer ships (fused_lm_head + in-op mean
                    # reduction)
                    import optax

                    logits = model.apply(
                        params, tokens,
                        deterministic=dropout == 0.0, rngs=rngs,
                    )
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        logits.astype(jnp.float32), labels
                    ).mean()
                    return ce * scaler.loss_scale(sstate)
                # fused linear-CE head, mean reduction inside the op:
                # the loss cotangent is a scalar, so the head's dx/dW
                # finish in the forward pass and no logits ever hit HBM
                mean = model.apply(
                    params, tokens, labels=labels, loss_reduction="mean",
                    deterministic=dropout == 0.0, rngs=rngs,
                )
                return mean * scaler.loss_scale(sstate)

            scaled, grads = jax.value_and_grad(loss_fn)(state.model)
            inv_scale = 1.0 / scaler.loss_scale(sstate)
            # probe rides the update pass (and fuses into the dW
            # matmuls); a standalone all_finite(grads) would re-read
            # every gradient
            state2, found_inf = opt.step_and_probe(
                state, grads, grad_scale=inv_scale
            )
            sstate2, _ = scaler.update(sstate, found_inf)
            return (state2, sstate2, rng), scaled * inv_scale

        return one_step

    one_step = make_one_step(opt)

    def local_runN(state, sstate, rng):
        # unroll=2 halves the while-loop bookkeeping between steps
        # (measured -0.9 ms/step) at the cost of one extra body compile
        (state, sstate, rng), losses = jax.lax.scan(
            one_step, (state, sstate, rng), None, length=iters, unroll=2
        )
        return state, sstate, rng, losses

    # (state, sstate) are DONATED into the loop: the optimizer carry is
    # the largest resident buffer set in the program and an un-donated
    # step holds two generations of it live (the donation lint pins
    # this). state.master ALIASES params32 (fp32→fp32 astype is a
    # no-copy view), so every VALUE read of params32 must happen before
    # the first runN call — see the hoist block below; `.size`-only
    # metadata reads survive buffer deletion.
    if mesh is not None:
        runN = jax.jit(
            shard_map(
                local_runN, mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=(P(), P(), P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )
    else:
        runN = jax.jit(local_runN, donate_argnums=(0, 1))

    if audit:
        # static program audit (monitor/audit.py): trace ONE train step
        # abstractly — no compile, no timing impact — and report the
        # collective counts/bytes and dot FLOPs to stderr. The jsonl
        # stdout contract is untouched.
        def _one(state, sstate, rng):
            (_, _, _), scaled = one_step((state, sstate, rng), None)
            return scaled

        target = _one
        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            target = shard_map(
                _one, mesh=mesh, in_specs=(P(), P(), P()),
                out_specs=P(), check_vma=False,
            )
        report = monitor.audit(target, state, sstate, rng0)
        print("audit: one gpt train step", file=sys.stderr)
        print(report.summary(), file=sys.stderr)

    if lint:
        # graph-contract lint (monitor/lint.py): the train-step ruleset
        # on ONE abstractly traced step — precision policy for the
        # active compute dtype, no materialized (b·s, vocab) logits on
        # the fused-head path (--loss=naive fails this by design: the
        # naive reference IS the materialization), donated carries,
        # trace stability. Exit 1 on any violation.
        def _one_lint(state, sstate, rng):
            (state, sstate, rng), scaled = one_step(
                (state, sstate, rng), None
            )
            return state, sstate, scaled

        target = _one_lint
        if mesh is not None:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            target = shard_map(
                _one_lint, mesh=mesh, in_specs=(P(), P(), P()),
                out_specs=(P(), P(), P()), check_vma=False,
            )
        subject = monitor.LintSubject.from_fn(
            "gpt_train_step", target, state, sstate, rng0,
            donate_argnums=(0, 1),
        )
        rules = [
            monitor.PrecisionPolicy(
                compute_dtype=str(jnp.dtype(cfg.dtype))
            ),
            monitor.NoMaterialization(
                forbidden_shapes=((batch * seq, cfg.vocab_size),)
                if loss == "fused" and _lint_head_is_chunked(cfg, batch, seq)
                else ()
            ),
            monitor.DonationContract(min_bytes=float(64 << 10)),
            monitor.TraceStability(),
        ]
        lint_report = monitor.run_lint(subject, rules)
        print(lint_report.summary(), file=sys.stderr)
        if not lint_report.ok:
            raise SystemExit(1)

    # ---- donation hoists: state.master aliases params32 (no-copy
    # astype), and the first runN call donates state — so everything
    # below that reads params32 VALUES is computed here, before any
    # donating call. (`.size` reads for the param count are metadata
    # and stay where they are.)
    w_emb = hidden0 = None
    if loss == "fused" and tp == 1:
        from rocm_apex_tpu.ops.linear_xentropy import (
            linear_cross_entropy_mean,
        )

        w_emb = jnp.array(
            params32["params"]["embedding"]["word_embeddings"]["weight"],
            dtype=cfg.dtype,  # forced copy: must outlive the donation
        )
        hidden0 = jax.random.normal(
            jax.random.PRNGKey(3), (batch, seq, cfg.hidden_size),
            cfg.dtype,
        )
    if packed_update:
        from rocm_apex_tpu.optimizers.packed import PackedOptimizerStep

        popt = PackedOptimizerStep("adam", 1e-4, weight_decay=0.01)
        # packed init packs masters into FRESH flat buffers — no alias
        pstate = popt.init(params32)
        grads_fix = jax.tree_util.tree_map(
            lambda p: (p * 1e-3 + 1e-5).astype(cfg.dtype), params32
        )
        # the tree-optimizer master tree aliases params32; deep-copy so
        # the update-phase timing below survives the donating runN calls
        upd_state_tree = jax.tree_util.tree_map(
            jnp.array, opt.init(params32)
        )
        upd_state_packed = popt.init(params32)

    state, sstate, rng0, losses = runN(state, sstate, rng0)
    float(losses[-1])  # warmup + sync (value fetch, not block_until_ready)

    t0 = time.perf_counter()
    state, sstate, rng0, losses = runN(state, sstate, rng0)
    loss_val = float(losses[-1])
    dt = (time.perf_counter() - t0) / iters

    tokens_per_sec = batch * seq / dt
    count_tree = params32
    if tp > 1:
        # sharded leaves report local shapes; count the full model
        # from an abstract tp=1 init (eval_shape: no compute)
        import dataclasses
        import math

        cfg_count = dataclasses.replace(
            cfg, tensor_parallel_size=1, sequence_parallel=False,
            collective_matmul=False,
        )
        count_tree = jax.eval_shape(
            lambda t: GPTModel(cfg_count).init(jax.random.PRNGKey(1), t),
            tokens[:1],
        )
        n_params = sum(
            int(math.prod(x.shape))
            for x in jax.tree_util.tree_leaves(count_tree)
        ) - cfg.vocab_size * cfg.hidden_size
    else:
        n_params = sum(
            int(x.size) for x in jax.tree_util.tree_leaves(count_tree)
        ) - cfg.vocab_size * cfg.hidden_size
    # Model FLOPs, Megatron-style, via the shared accounting module
    # (monitor/flops.py — the one copy of the formula; its docstring
    # carries the Narayanan/PaLM crediting discussion). The tied-head
    # projection trio is real dense MXU work (17.3 ms/step of
    # 94-98%-of-peak on this config); the record carries the
    # head-inclusive MFU, with the sans-head figure on stderr.
    step_flops = monitor.model_flops(cfg, batch, seq, n_params=n_params)
    mfu = _mfu(step_flops, dt, n_chips=tp)
    mfu_sans_head = _mfu(
        monitor.model_flops(cfg, batch, seq, n_params=n_params,
                            include_head=False),
        dt, n_chips=tp,
    )
    # per-chip normalization: the tp-sharded step spreads the same
    # global batch over tp chips
    tokens_per_sec = tokens_per_sec / tp
    # a metric series must never mix configs under one key. The
    # dropout suffix keys on the VALUE, not the default:
    # dropout 0.1 became the default in round 5, and its rows must
    # stay series-comparable with the round-4 `_dropout` side rows
    # (and the un-suffixed key must keep meaning dropout=0.0).
    suffix = "_dropout" if dropout > 0.0 else ""
    if seq != default_seq:
        suffix += f"_s{seq}"
    if batch != default_batch:
        suffix += f"_b{batch}"
    if remat:
        suffix += "_remat"
    if loss != "fused":
        suffix += f"_loss_{loss}"
    if seq_parallel:
        # the tp-axis series gets its own keys: _sp (blocking
        # sequence-parallel collectives) vs _spcm (ring collective
        # matmuls), never mixed with the dp series above
        suffix += ("_spcm" if collective_matmul else "_sp") + f"_tp{tp}"
    if comm_dtype != "fp32":
        suffix += f"_{comm_dtype}comm"

    # head share: fwd+bwd of the fused LM head + CE alone, on a bench-
    # shaped hidden batch against the real tied table — the number the
    # in-model `jax.named_scope("lm_head_loss")` annotation attributes
    # in profiles, measured here so a run can track it without a
    # profiler. Skipped under --seq-parallel: the tied
    # table is then a vocab shard per rank and the standalone replay
    # would measure a different (1/tp) head.
    head_ms = None
    if loss == "fused" and tp == 1:
        # w_emb/hidden0 were hoisted above the first donating runN call

        def head_step(carry):
            h, acc = carry
            l, (gh, gw) = jax.value_and_grad(
                lambda h, w: linear_cross_entropy_mean(
                    h, w, labels, None, cfg.label_smoothing,
                    cfg.ignore_index, cfg.lm_head_chunk_size,
                ),
                (0, 1),
            )(h, w_emb)
            # single-column reads force both grads without paying a
            # full extra sweep inside the timed region
            tot = (
                l
                + jnp.sum(gh[..., 0].astype(jnp.float32))
                + jnp.sum(gw[:, 0].astype(jnp.float32))
            )
            return h + (tot * 1e-30).astype(h.dtype), acc + tot

        head_ms = _timed_scan(head_step, (hidden0, jnp.float32(0)), iters)
        print(
            f"lm_head_loss: {head_ms:.2f} ms fwd+bwd "
            f"({100.0 * head_ms / (dt * 1000):.1f}% of step)",
            file=sys.stderr,
        )
    _report(
        f"gpt_train_tokens_per_sec_per_chip{suffix}", tokens_per_sec,
        "tokens/s", mfu / 0.70,
        f"step={dt*1000:.1f}ms loss={loss_val:.4f} mfu={mfu:.3f} "
        f"(sans-head crediting: {mfu_sans_head:.3f}) "
        + (f"head={head_ms:.2f}ms " if head_ms is not None else "")
        + f"dropout={dropout} b={batch} s={seq} remat={remat} "
        f"loss_impl={loss} backend={jax.default_backend()}"
        + (
            f" seq_parallel=True collective_matmul={collective_matmul} "
            f"tp={tp}"
            if seq_parallel
            else ""
        ),
    )
    if audit:
        # the same traced report that printed to stderr, landed in the
        # jsonl output: estimated per-step collective wire bytes
        _report(
            f"gpt_comm_payload_mib{suffix}",
            report.collective_wire_bytes / (1024 * 1024), "MiB", 1.0,
            f"estimated per-step collective wire bytes "
            f"(comm_dtype={comm_dtype}; monitor/audit.py conventions) "
            f"ppermute={report.count('ppermute')} "
            f"backend={jax.default_backend()}",
        )

    if packed_update:
        # ---- packed-buffer optimizer A/B (--packed-update): rerun the
        # IDENTICAL train loop with PackedOptimizerStep (one fused
        # unscale+probe+Adam pass per dtype buffer, masters/moments
        # held packed in the carry) against the MixedPrecisionAdam
        # baseline just measured, then isolate the update phase and the
        # traced program size so the three claims — step time, update
        # share, O(dtype-groups) equations — each get their own number.
        # popt/pstate/grads_fix/upd states were hoisted above the first
        # donating runN call (they read params32 values)
        one_step_p = make_one_step(popt)

        def local_runN_p(state, sstate, rng):
            (state, sstate, rng), losses = jax.lax.scan(
                one_step_p, (state, sstate, rng), None, length=iters,
                unroll=2,
            )
            return state, sstate, rng, losses

        runN_p = jax.jit(local_runN_p, donate_argnums=(0, 1))
        pstate, psstate, prng, plosses = runN_p(
            pstate, scaler.init(), rng0
        )
        ploss_val = float(plosses[-1])  # warmup + sync
        # interleaved best-of-5: tree and packed alternate inside the
        # same wall-clock window so host-load drift (which dominates a
        # ~600 ms CPU step, observed +-10% run to run against a true
        # per-step delta under 0.1%) cancels instead of landing on one
        # side; both sides get the same sample count from the same
        # window, and best-of estimates each program's quiet-host time
        dt_tree = float("inf")
        dt_packed = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            state, sstate, rng0, losses = runN(state, sstate, rng0)
            float(losses[-1])
            dt_tree = min(dt_tree, (time.perf_counter() - t0) / iters)
            t0 = time.perf_counter()
            pstate, psstate, prng, plosses = runN_p(
                pstate, psstate, prng
            )
            ploss_val = float(plosses[-1])
            dt_packed = min(dt_packed, (time.perf_counter() - t0) / iters)

        # update-phase share: the bare optimizer step on fixed grads
        # (bench_optim idiom), tree vs packed, outside the fwd/bwd

        def upd_tree(carry):
            s, g = carry
            s2, _ = opt.step_and_probe(s, g, grad_scale=1.0)
            return s2, g

        def upd_packed(carry):
            s, g = carry
            s2, _ = popt.step_and_probe(s, g, grad_scale=1.0)
            return s2, g

        ms_upd_tree = _timed_scan(
            upd_tree, (upd_state_tree, grads_fix), iters
        )
        ms_upd_packed = _timed_scan(
            upd_packed, (upd_state_packed, grads_fix), iters
        )

        # traced-program size of the bare update (monitor/audit.py
        # equation count): the packed step is O(dtype-groups), the
        # tree step O(leaves) — the fusion-granularity claim, printed
        # here and pinned by tests/L0/test_packed_optimizers.py
        rep_tree = monitor.audit(
            lambda s, g: opt.step_and_probe(s, g, grad_scale=1.0),
            upd_state_tree, grads_fix,
        )
        rep_packed = monitor.audit(
            lambda s, g: popt.step_and_probe(s, g, grad_scale=1.0),
            upd_state_packed, grads_fix,
        )
        n_leaves = len(jax.tree_util.tree_leaves(params32))
        print(
            f"packed A/B: step {dt_packed*1000:.1f} ms vs tree "
            f"{dt_tree*1000:.1f} ms; update phase {ms_upd_packed:.2f} ms "
            f"({100.0 * ms_upd_packed / (dt_packed * 1000):.1f}% of "
            f"step) vs tree {ms_upd_tree:.2f} ms "
            f"({100.0 * ms_upd_tree / (dt_tree * 1000):.1f}%); update "
            f"equations {int(rep_packed.eqn_count)} (packed, "
            f"{n_leaves}-leaf tree) vs {int(rep_tree.eqn_count)} "
            f"(tree-fused)",
            file=sys.stderr,
        )
        _report(
            f"gpt_train_tokens_per_sec_per_chip{suffix}_packed",
            batch * seq / dt_packed, "tokens/s", dt_tree / dt_packed,
            f"step={dt_packed*1000:.1f}ms loss={ploss_val:.4f} "
            f"update={ms_upd_packed:.2f}ms "
            f"(tree {ms_upd_tree:.2f}ms) eqns={int(rep_packed.eqn_count)} "
            f"(tree {int(rep_tree.eqn_count)}, {n_leaves} leaves) "
            f"vs_baseline = tree_step/packed_step "
            f"backend={jax.default_backend()}",
        )


if __name__ == "__main__":
    enable_compile_cache()
    # plain `python bench.py` = the flagship GPT line; `python bench.py
    # rn50|bert` measures the other BASELINE.json configs. `--dropout=R`
    # on the gpt/bert benches measures the TRAINING config (attention
    # dropout through the in-kernel flash dropout, hidden dropout
    # through the fused LN-dropout path).
    benches = {
        "gpt": main,
        "serve": bench_serve,
        "rn50": bench_rn50,
        "bert": bench_bert,
        "attn": bench_attn,
        "fmha": bench_fmha,
        "optim": bench_optim,
        "ln": bench_ln,
    }
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    kwargs = {}
    for a in sys.argv[1:]:
        if a.startswith("--dropout="):
            kwargs["dropout"] = float(a.split("=", 1)[1])
        elif a.startswith("--batch="):
            kwargs["batch"] = int(a.split("=", 1)[1])
        elif a.startswith("--seq="):
            kwargs["seq"] = int(a.split("=", 1)[1])
        elif a == "--remat":
            kwargs["remat"] = True
        elif a == "--seq-parallel":
            kwargs["seq_parallel"] = True
        elif a == "--collective-matmul":
            kwargs["collective_matmul"] = True
        elif a == "--audit":
            kwargs["audit"] = True
        elif a == "--lint":
            kwargs["lint"] = True
        elif a.startswith("--loss="):
            kwargs["loss"] = a.split("=", 1)[1]
        elif a.startswith("--budget="):
            kwargs["budget"] = int(a.split("=", 1)[1])
        elif a == "--whole-prompt":
            kwargs["whole_prompt"] = True
        elif a.startswith("--trace="):
            kwargs["trace"] = a.split("=", 1)[1]
        elif a == "--paged":
            kwargs["paged"] = True
        elif a.startswith("--page-size="):
            kwargs["page_size"] = int(a.split("=", 1)[1])
        elif a.startswith("--kv-dtype="):
            kwargs["kv_dtype"] = a.split("=", 1)[1]
        elif a == "--shared-prefix":
            kwargs["shared_prefix"] = True
        elif a.startswith("--spec-k="):
            kwargs["spec_k"] = int(a.split("=", 1)[1])
        elif a.startswith("--chaos="):
            kwargs["chaos"] = int(a.split("=", 1)[1])
        elif a == "--slo":
            kwargs["slo"] = True
        elif a.startswith("--metrics-port="):
            kwargs["metrics_port"] = int(a.split("=", 1)[1])
        elif a.startswith("--replicas="):
            kwargs["replicas"] = int(a.split("=", 1)[1])
        elif a.startswith("--tp="):
            kwargs["tp"] = int(a.split("=", 1)[1])
        elif a == "--disagg":
            kwargs["disagg"] = True
        elif a.startswith("--adapters="):
            kwargs["adapters"] = int(a.split("=", 1)[1])
        elif a.startswith("--ranks="):
            kwargs["ranks"] = a.split("=", 1)[1]
        elif a == "--dist-opt":
            kwargs["dist_opt"] = True
        elif a.startswith("--comm-dtype="):
            kwargs["comm_dtype"] = a.split("=", 1)[1]
        elif a == "--packed-update":
            kwargs["packed_update"] = True
        elif a.startswith("--fused="):
            kwargs["fused"] = bool(int(a.split("=", 1)[1]))
        elif a.startswith("--"):
            # a typoed flag must not silently measure the wrong config
            raise SystemExit(f"unknown flag {a!r}")
    which = args[0] if args else "gpt"
    if which not in benches:
        raise SystemExit(
            f"unknown benchmark {which!r}; choose from {sorted(benches)}"
        )
    if "dropout" in kwargs and which not in ("gpt", "bert"):
        raise SystemExit(f"--dropout applies to gpt/bert, not {which!r}")
    if ("batch" in kwargs or "remat" in kwargs) and which not in (
        "gpt", "bert"
    ):
        raise SystemExit("--batch/--remat apply to the gpt/bert benches")
    if "seq" in kwargs and which != "gpt":
        raise SystemExit("--seq applies to the gpt bench")
    if "loss" in kwargs and which != "gpt":
        raise SystemExit("--loss applies to the gpt bench")
    if "audit" in kwargs and which != "gpt":
        raise SystemExit("--audit applies to the gpt bench")
    if "lint" in kwargs and which != "gpt":
        raise SystemExit("--lint applies to the gpt bench")
    if (
        "seq_parallel" in kwargs or "collective_matmul" in kwargs
    ) and which != "gpt":
        raise SystemExit(
            "--seq-parallel/--collective-matmul apply to the gpt bench"
        )
    if (
        "budget" in kwargs or "whole_prompt" in kwargs
        or "trace" in kwargs or "paged" in kwargs
        or "page_size" in kwargs or "kv_dtype" in kwargs
        or "shared_prefix" in kwargs or "spec_k" in kwargs
        or "chaos" in kwargs or "slo" in kwargs
        or "metrics_port" in kwargs or "replicas" in kwargs
        or "tp" in kwargs or "disagg" in kwargs
        or "adapters" in kwargs or "ranks" in kwargs
    ) and which != "serve":
        raise SystemExit(
            "--budget/--whole-prompt/--trace/--paged/--page-size/"
            "--kv-dtype/--shared-prefix/--spec-k/--chaos/--slo/"
            "--metrics-port/--replicas/--tp/--disagg/--adapters/"
            "--ranks apply to the serve bench"
        )
    if kwargs.get("adapters", 1) < 1:
        raise SystemExit("--adapters takes a pool size N >= 1")
    if "ranks" in kwargs and "adapters" not in kwargs:
        raise SystemExit("--ranks requires --adapters")
    if "adapters" in kwargs and any(
        k in kwargs
        for k in ("whole_prompt", "shared_prefix", "spec_k", "paged",
                  "kv_dtype", "page_size", "replicas", "tp", "disagg",
                  "slo", "trace")
    ):
        raise SystemExit(
            "--adapters runs its own single-model A/B (or, with "
            "--chaos, the tenant-isolation scenario); it composes "
            "with --chaos/--budget/--metrics-port only"
        )
    if kwargs.get("tp", 2) < 2:
        raise SystemExit("--tp takes a tensor-parallel width N >= 2")
    if "tp" in kwargs and any(
        k not in ("tp", "budget", "page_size") for k in kwargs
    ):
        raise SystemExit(
            "--tp runs its own equal-chip-count paged A/B; it "
            "composes with --budget/--page-size only"
        )
    if kwargs.get("disagg") and any(
        k in kwargs
        for k in ("whole_prompt", "shared_prefix", "spec_k",
                  "slo", "metrics_port", "trace", "paged", "kv_dtype",
                  "tp")
    ):
        raise SystemExit(
            "--disagg runs its own equal-chip-count fleet A/B; it "
            "composes with --replicas/--budget/--page-size/--chaos "
            "only (--chaos adds the fleet-trace observability pass)"
        )
    if kwargs.get("spec_k", 0) < 0:
        raise SystemExit("--spec-k must be >= 0")
    if kwargs.get("chaos", 0) < 0:
        raise SystemExit("--chaos takes a seed >= 0")
    if kwargs.get("metrics_port", 0) < 0:
        raise SystemExit("--metrics-port takes a port >= 0 (0 = ephemeral)")
    if kwargs.get("replicas", 2) < 2:
        raise SystemExit("--replicas takes a fleet size N >= 2")
    if "replicas" in kwargs and (
        kwargs.get("whole_prompt") or kwargs.get("shared_prefix")
        or "spec_k" in kwargs or kwargs.get("slo")
    ):
        raise SystemExit(
            "--replicas runs the fleet pass on the mixed workload; it "
            "composes with --chaos/--paged/--metrics-port, not with "
            "--whole-prompt/--shared-prefix/--spec-k/--slo"
        )
    if ("slo" in kwargs or "metrics_port" in kwargs) and (
        kwargs.get("shared_prefix") or "spec_k" in kwargs
        or (
            kwargs.get("paged") and "chaos" not in kwargs
            and "replicas" not in kwargs
        )
    ):
        raise SystemExit(
            "--slo/--metrics-port instrument the mixed-workload serve "
            "pass (plain or --chaos); they do not compose with "
            "--shared-prefix/--spec-k/--paged-without-chaos"
        )
    if "chaos" in kwargs and (
        kwargs.get("shared_prefix") or "spec_k" in kwargs
        or kwargs.get("whole_prompt")
    ):
        raise SystemExit(
            "--chaos runs its own serving pass; it does not compose "
            "with --whole-prompt/--shared-prefix/--spec-k"
        )
    if "dist_opt" in kwargs and which != "gpt":
        raise SystemExit("--dist-opt applies to the gpt bench")
    if "comm_dtype" in kwargs and which != "gpt":
        raise SystemExit("--comm-dtype applies to the gpt bench")
    if "packed_update" in kwargs and which != "gpt":
        raise SystemExit("--packed-update applies to the gpt bench")
    if kwargs.get("dist_opt") and kwargs.get("seq_parallel"):
        raise SystemExit(
            "--dist-opt shards the optimizer over the data axis; it "
            "does not compose with --seq-parallel (tensor axis)"
        )
    if kwargs.get("kv_dtype") not in (None, "int8"):
        raise SystemExit(
            f"--kv-dtype={kwargs['kv_dtype']!r}: only int8 is a "
            "quantized cache dtype (omit the flag for the model dtype)"
        )
    if (
        "page_size" in kwargs or "kv_dtype" in kwargs
    ) and not (kwargs.get("paged") or kwargs.get("shared_prefix")):
        raise SystemExit(
            "--page-size/--kv-dtype require --paged (or --shared-prefix)"
        )
    if "fused" in kwargs and which != "rn50":
        raise SystemExit("--fused applies to the rn50 bench")
    if kwargs.get("fused") and jax.default_backend() != "tpu":
        # a flag must not silently measure the wrong config: the fused
        # kernel path is TPU-only (interpret mode would measure noise)
        raise SystemExit("--fused=1 requires the TPU backend")
    benches[which](**kwargs)
