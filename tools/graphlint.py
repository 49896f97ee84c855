#!/usr/bin/env python
"""Graph-contract CI gate: lint a registry of named configs and diff
their traced-program fingerprints against a checked-in manifest.

Every policy claim the repo has shipped — ring collectives instead of
blocking gathers (PR 3), a found_inf skip branch that pays no comm
(PR 9/11), a fused head that never materializes logits (PR 2), packed
optimizer programs that stay O(dtype-groups) (PR 9), donated step
carries — is a property of a TRACED PROGRAM, not of any one test's
wall clock. This tool re-traces six representative configs
abstractly (`jax.make_jaxpr` / AOT `.trace`: zero compiles), runs the
`monitor/lint.py` rule sets against them, and compares a structural
fingerprint (collective counts, wire-byte estimates, equation/dot
counts, donation totals) against ``tools/graph_contracts.json``:

    python tools/graphlint.py --check     # CI gate: exit 1 on any
                                          # rule violation or drift
    python tools/graphlint.py --update    # re-baseline the manifest
                                          # (reviewed, intended change)
    python tools/graphlint.py --configs   # list registry entries

Registered configs (each mirrors shapes an L0 test already traces, so
nothing here compiles and the suite's compile cache stays warm):

* ``gpt_train_bf16`` — the bf16 (O4/O5-style) GPT train step with
  dynamic loss scaling and the fused chunked LM head: precision
  policy, no full-logits intermediate, donated (state, scaler) carry.
* ``packed_opt`` — the PR-9 packed-buffer fused optimizer step:
  donation of the packed state, and the manifest pins ``eqn_count``
  (the O(dtype-groups) fusion-granularity claim).
* ``serve_mixed`` — the serving engine's fused prefill+decode mixed
  step lowered with donated cache buffers: KV-cache donation verified
  from the executable's own ``args_info``, no whole-batch logits.
* ``serve_mixed_lora`` — the multi-LoRA variant of the same step
  (packed `AdapterPool` buffers + per-token adapter ids): segmented
  gather->bmm deltas proven to never materialize a dense per-token
  delta weight or an every-adapter broadcast; cache AND adapter
  buffers donated.
* ``serve_mixed_tp2`` — the same mixed step under shard_map at tp=2
  (sequence-parallel chunk + collective-matmul rings, head-sharded
  paged pools): exactly 8 ppermute ring hops, no full-seq full-width
  FFN activation, cache still donated.
* ``spcm_tp2`` — the tp=2 sequence-parallel + collective-matmul
  transformer stack (init+fwd+bwd): exactly 16 ppermute ring hops, no
  all_gather/reduce_scatter, no full (b, s, h) gathered activation.
* ``zero_int8`` — the ZeRO ``distributed_fused_adam`` int8 update:
  the all-gather-free quantized-ring contract plus the found_inf cond
  proof (the skip branch is collective-free).

`--check` fails loudly with messages naming the rule, scope, and
offending shape/dtype; manifest drift prints field-level before/after
pairs. See docs/observability.md "Static analysis & graph contracts".
"""

import argparse
import json
import os
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
MANIFEST_PATH = REPO / "tools" / "graph_contracts.json"

# Env bootstrap BEFORE the first jax import (tests/conftest.py does the
# same): the tp2/dp4 registry configs need simulated devices. When jax
# is already imported (in-process use from the test suite) the
# conftest has already provided 8 devices.
if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from rocm_apex_tpu import monitor  # noqa: E402
from rocm_apex_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)
from rocm_apex_tpu.monitor import (  # noqa: E402
    CollectiveContract,
    DonationContract,
    LintSubject,
    NoMaterialization,
    PrecisionPolicy,
    TraceStability,
    run_lint,
)


def _mesh(n: int, axis: str) -> Mesh:
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"graphlint needs {n} simulated devices for axis {axis!r} "
            f"(got {len(devs)}): run via `python tools/graphlint.py` so "
            "the XLA_FLAGS bootstrap applies"
        )
    return Mesh(np.array(devs[:n]), (axis,))


# ---------------------------------------------------------------------------
# registry: name -> builder() -> (LintSubject, [rules])
# ---------------------------------------------------------------------------


def _build_gpt_train_bf16():
    """The bf16 train step on tests/L0/test_monitor.py's exact model
    shapes (vocab 64, hidden 32, 2 layers) with dynamic loss scaling
    and the chunked fused head (chunk 8 < 32 rows: the head really
    tiles)."""
    from rocm_apex_tpu.amp import LossScaler
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
    from rocm_apex_tpu.optimizers.mixed import MixedPrecisionAdam

    b, s = 2, 16
    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2,
        num_attention_heads=2, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=1, params_dtype=jnp.float32,
        dtype=jnp.bfloat16, attention_impl="jnp",
        use_pallas_softmax=False, lm_head_chunk_size=8,
    )
    model = GPTModel(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (b, s), 0, 64)
    labels = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(1), tokens)
    opt = MixedPrecisionAdam(1e-3)
    scaler = LossScaler(loss_scale="dynamic")
    state = opt.init(params)
    sstate = scaler.init()

    def step(state, sstate):
        def loss_fn(p):
            mean = model.apply(
                p, tokens, labels=labels, loss_reduction="mean"
            )
            return mean * scaler.loss_scale(sstate)

        scaled, grads = jax.value_and_grad(loss_fn)(state.model)
        inv = 1.0 / scaler.loss_scale(sstate)
        state2, found_inf = opt.step_and_probe(
            state, grads, grad_scale=inv
        )
        sstate2, _ = scaler.update(sstate, found_inf)
        return state2, sstate2, scaled * inv

    subject = LintSubject.from_fn(
        "gpt_train_bf16", step, state, sstate, donate_argnums=(0, 1)
    )
    rules = [
        # calibrated on the real trace: every model dot is bf16 (the
        # attention-score and dW dots carry fp32 accumulators via
        # preferred_element_type, which the rule permits) and the fp32
        # optimizer is dot-free, so no allowlist is needed
        PrecisionPolicy(compute_dtype="bfloat16"),
        # chunk 8 < 32 rows: the (b·s, vocab) logits must never exist
        NoMaterialization(forbidden_shapes=((b * s, 64),)),
        # every large carry leaf (the 8 KiB embedding masters/moments
        # and up) rides the donated (state, sstate) argnums
        DonationContract(min_bytes=8192.0),
        TraceStability(),
    ]
    return subject, rules


def _build_packed_opt():
    """The PR-9 packed-buffer step on test_packed_optimizers' exact
    param tree; the manifest's eqn_count IS the O(dtype-groups)
    fusion claim."""
    from rocm_apex_tpu.optimizers.packed import PackedOptimizerStep

    params = {
        "w": jnp.zeros((33, 65), jnp.float32),
        "b": jnp.zeros((65,), jnp.float32),
        "deep": {"k": jnp.zeros((7, 3, 11), jnp.float32)},
    }
    popt = PackedOptimizerStep("adam", 1e-3)
    state = popt.init(params)
    # grads arrive in the model's compute dtype (bf16 by default),
    # exactly as autodiff against state.model would produce them
    grads = jax.tree_util.tree_map(jnp.ones_like, state.model)

    def step(state, grads):
        state2, found_inf = popt.step_and_probe(
            state, grads, grad_scale=1.0
        )
        return state2, found_inf

    subject = LintSubject.from_fn(
        "packed_opt", step, state, grads, donate_argnums=(0,)
    )
    rules = [
        PrecisionPolicy(compute_dtype="float32"),
        # the packed carry (masters/moments/model) is donated wholesale;
        # grads arrive from autodiff and are consumed in place by XLA
        DonationContract(min_bytes=float("inf"), require=("args[0]",)),
        TraceStability(),
    ]
    return subject, rules


def _build_serve_mixed():
    """The engine's fused mixed prefill+decode step, lowered with
    donate_buffers=True on test_inference's exact fp32 engine config —
    donation read back from the executable's own args_info."""
    from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=1, params_dtype=jnp.float32,
        dtype=jnp.float32,
    )
    model = GPTModel(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), toks)
    eng = InferenceEngine(
        model, params, num_slots=2, max_prompt_len=8, capacity=24,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=16, donate_buffers=True,
    )
    budget, ns = eng.prefill_token_budget, eng.num_slots
    i32 = lambda shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    subject = LintSubject.from_jit(
        "serve_mixed", eng.programs.mixed,
        eng.params, eng.cache,
        i32((budget,)), i32((budget,)), i32((budget,)),   # tokens/slots/pos
        i32((ns,)), i32((ns,)),                           # lengths before/after
        -jnp.ones((ns,), jnp.int32),                      # completion_idx
        i32((ns,)), jnp.zeros((ns,), bool),               # dec tokens/active
        jnp.zeros((budget,), jnp.float32),                # chunk poison
        jnp.zeros((ns,), jnp.float32),                    # dec poison
        jax.random.PRNGKey(0),
    )
    rules = [
        PrecisionPolicy(compute_dtype="float32"),
        # chunked scheduler: logits exist per chunk row and per decode
        # slot, never for the whole (slots, capacity) batch at once
        NoMaterialization(forbidden_shapes=((ns, 24, 96),)),
        # the KV cache (arg 1) is the resident pool: donated in place
        DonationContract(min_bytes=float("inf"), require=("args[0][1]",)),
        TraceStability(),
    ]
    return subject, rules


def _build_serve_mixed_lora():
    """The multi-LoRA fused mixed step (ISSUE 18): the serve_mixed
    geometry plus an `AdapterPool`'s packed rank-padded buffers as
    donated argument 2 and per-token adapter ids next to the slot
    ids/positions. The NoMaterialization rule is the segmented-delta
    proof: no per-token DENSE delta weight (budget, h, out) and no
    all-adapters broadcast (P, budget, h) may appear — the delta must
    stay contracted through the (budget, r) bottleneck. Cache AND
    adapter buffers are donated (the host re-binds `pool.buffers`
    each tick exactly like the cache)."""
    from rocm_apex_tpu.inference import (
        AdapterPool, InferenceEngine, SamplingParams,
    )
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel

    cfg = GPTConfig(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=1, params_dtype=jnp.float32,
        dtype=jnp.float32,
    )
    model = GPTModel(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), toks)
    pool = AdapterPool(
        cfg.num_layers, cfg.hidden_size, max_resident=4, max_rank=4,
    )
    eng = InferenceEngine(
        model, params, num_slots=2, max_prompt_len=8, capacity=24,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=16, donate_buffers=True,
        adapter_pool=pool,
    )
    budget, ns = eng.prefill_token_budget, eng.num_slots
    h, pp = cfg.hidden_size, 4
    i32 = lambda shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    subject = LintSubject.from_jit(
        "serve_mixed_lora", eng.programs.mixed,
        eng.params, eng.cache, pool.buffers,
        i32((budget,)), i32((budget,)), i32((budget,)),   # tokens/slots/pos
        i32((budget,)),                                   # chunk adapter ids
        i32((ns,)), i32((ns,)),                           # lengths before/after
        -jnp.ones((ns,), jnp.int32),                      # completion_idx
        i32((ns,)), jnp.zeros((ns,), bool),               # dec tokens/active
        i32((ns,)),                                       # dec adapter ids
        jnp.zeros((budget,), jnp.float32),                # chunk poison
        jnp.zeros((ns,), jnp.float32),                    # dec poison
        jax.random.PRNGKey(0),
    )
    rules = [
        PrecisionPolicy(compute_dtype="float32"),
        NoMaterialization(forbidden_shapes=(
            (ns, 24, 96),          # whole-batch logits (serve_mixed)
            (budget, h, 3 * h),    # dense per-token qkv delta weight
            (budget, h, h),        # dense per-token proj delta weight
            (pp, budget, h),       # every-adapter broadcast of the chunk
        )),
        # cache (arg 1) AND adapter buffers (arg 2) donated in place
        DonationContract(
            min_bytes=float("inf"),
            require=("args[0][1]", "args[0][2]"),
        ),
        TraceStability(),
    ]
    return subject, rules


def _build_serve_mixed_tp2():
    """The tp=2 fused mixed step under shard_map (PR-17 disaggregated
    serving rung 1): sequence-parallel chunk with collective-matmul
    rings, head-sharded paged pools, replicated host control arrays,
    and the vocab gather before sampling. Ring hops are pinned
    exactly; all_gather is NOT forbidden here — the sp-exit gather
    before attend and the vocab-parallel logits gather are the two
    legitimate blocking collectives of the serving forward."""
    from rocm_apex_tpu.inference import (
        InferenceEngine, SamplingParams, shard_tp1_params,
    )
    from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
    from rocm_apex_tpu.transformer import parallel_state

    mesh = parallel_state.initialize_model_parallel(
        2, 1, devices=jax.devices()[:2]
    )
    kw = dict(
        vocab_size=96, hidden_size=32, num_layers=2,
        num_attention_heads=4, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        params_dtype=jnp.float32, dtype=jnp.float32,
        attention_impl="jnp",
    )
    toks = jnp.zeros((1, 8), jnp.int32)
    model1 = GPTModel(GPTConfig(tensor_parallel_size=1, **kw))
    params1 = model1.init(jax.random.PRNGKey(1), toks)
    model = GPTModel(GPTConfig(tensor_parallel_size=2, **kw))
    params = shard_tp1_params(model, params1, mesh)
    eng = InferenceEngine(
        model, params, num_slots=2, capacity=24,
        paged=True, page_size=4,
        sampling=SamplingParams(temperature=0.0),
        prefill_token_budget=16, donate_buffers=True,
    )
    budget, ns = eng.prefill_token_budget, eng.num_slots
    i32 = lambda shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    subject = LintSubject.from_jit(
        "serve_mixed_tp2", eng.programs.mixed,
        eng.params, eng.cache,
        i32((budget,)), i32((budget,)), i32((budget,)),   # tokens/slots/pos
        i32((ns,)), i32((ns,)),                           # lengths before/after
        -jnp.ones((ns,), jnp.int32),                      # completion_idx
        i32((ns,)), jnp.zeros((ns,), bool),               # dec tokens/active
        jnp.zeros((budget,), jnp.float32),                # chunk poison
        jnp.zeros((ns,), jnp.float32),                    # dec poison
        jax.random.PRNGKey(0),
    )
    rules = [
        PrecisionPolicy(compute_dtype="float32"),
        # the sp+cm chunk rides ppermute rings: 4 TP-edge matmuls
        # (qkv, attn out, fc, proj) x 2 layers x (tp-1)=1 hop = 8
        CollectiveContract(expect={"ppermute": 8}),
        # the full-seq, full-width FFN activation must never exist:
        # under sp+cm it lives either seq-sharded (1, budget/2, 4h) or
        # width-sharded (1, budget, 4h/2), never (1, budget, 4h)
        NoMaterialization(
            forbidden_shapes=((1, budget, 4 * 32),)
        ),
        # the head-sharded paged cache (arg 1) is donated in place
        DonationContract(min_bytes=float("inf"), require=("args[0][1]",)),
        TraceStability(),
    ]
    return subject, rules


def _build_spcm_tp2():
    """tests/L0/test_monitor.py's SP/CM tp=2 stack (init+fwd+bwd):
    the PR-3 ring contract as a standing CI gate."""
    from rocm_apex_tpu.models.gpt import GPTConfig, ParallelTransformer

    B, S, H = 2, 32, 64
    mesh = _mesh(2, "tensor")
    cfg = GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=1,
        num_attention_heads=4, max_position_embeddings=32,
        ffn_hidden_size=256, hidden_dropout=0.0, attention_dropout=0.0,
        tensor_parallel_size=2, dtype=jnp.float32,
        sequence_parallel=True, collective_matmul=True,
    )
    stack = ParallelTransformer(cfg)
    x_loc = jnp.ones((B, S // 2, H), jnp.float32)

    def step(x):
        params = stack.init(jax.random.PRNGKey(0), x)

        def loss(p, x):
            y = stack.apply(p, x, deterministic=True)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.grad(loss, (0, 1))(params, x)

    f = shard_map(
        step, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False,
    )
    subject = LintSubject.from_fn("spcm_tp2", f, x_loc)
    rules = [
        # 4 TP-edge ops x (init fwd + grad fwd + 2x bwd) at tp=2 = 16
        # ring hops; the blocking edge collectives must be GONE
        CollectiveContract(
            expect={"ppermute": 16},
            forbid=("all_gather", "reduce_scatter"),
        ),
        # no full-sequence (b, s, h) gathered activation anywhere
        NoMaterialization(forbidden_shapes=((B, S, H),)),
        PrecisionPolicy(compute_dtype="float32"),
    ]
    return subject, rules


def _build_zero_int8():
    """test_quantized_collectives' ZeRO int8 update at dp=4: the
    quantized rings carry everything (no plain all_gather/
    reduce_scatter) and the found_inf cond proves a comm-free skip."""
    from rocm_apex_tpu.contrib.optimizers import distributed_fused_adam

    mesh = _mesh(4, "data")
    params = {
        "w": jnp.zeros((24, 33), jnp.float32),
        "b": jnp.zeros((33,), jnp.float32),
    }
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    dist = distributed_fused_adam(
        1e-3, axis_name="data", comm_dtype="int8"
    )

    def local(params, grads):
        state = dist.init(params)
        updates, _, info = dist.update(
            grads, state, params, inv_scale=0.5, with_info=True
        )
        return updates

    f = shard_map(
        local, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False,
    )
    subject = LintSubject.from_fn("zero_int8", f, params, grads)
    rules = [
        CollectiveContract(
            forbid=("all_gather", "reduce_scatter"),
            skip_branches_collective_free=True,
            require_skip_cond=True,
        ),
        PrecisionPolicy(compute_dtype="float32"),
    ]
    return subject, rules


REGISTRY = {
    "gpt_train_bf16": _build_gpt_train_bf16,
    "packed_opt": _build_packed_opt,
    "serve_mixed": _build_serve_mixed,
    "serve_mixed_lora": _build_serve_mixed_lora,
    "serve_mixed_tp2": _build_serve_mixed_tp2,
    "spcm_tp2": _build_spcm_tp2,
    "zero_int8": _build_zero_int8,
}


# ---------------------------------------------------------------------------
# fingerprints and the manifest diff
# ---------------------------------------------------------------------------


def fingerprint(subject: LintSubject) -> dict:
    """The structural identity of one traced config: what drifts when
    someone changes the program shape without meaning to."""
    r = subject.report
    fp = {
        "counts": {k: int(v) for k, v in sorted(r.counts.items())},
        "wire_bytes": {
            k: int(round(v))
            for k, v in sorted(r.wire_bytes_moved.items())
        },
        "eqn_count": int(r.eqn_count),
        "dot_count": int(r.dot_count),
    }
    if subject.args is not None:
        fp["arg_leaves"] = len(subject.args)
        fp["donated_leaves"] = sum(a.donated for a in subject.args)
        fp["donated_bytes"] = int(
            sum(a.nbytes for a in subject.args if a.donated)
        )
    return fp


def _diff(name: str, baseline: dict, current: dict):
    """Field-level drift lines between two fingerprints."""
    lines = []
    keys = sorted(set(baseline) | set(current))
    for k in keys:
        b, c = baseline.get(k), current.get(k)
        if isinstance(b, dict) or isinstance(c, dict):
            subkeys = sorted(set(b or {}) | set(c or {}))
            for sk in subkeys:
                bv = (b or {}).get(sk)
                cv = (c or {}).get(sk)
                if bv != cv:
                    lines.append(
                        f"  {name}.{k}[{sk}]: manifest {bv} != traced {cv}"
                    )
        elif b != c:
            lines.append(f"  {name}.{k}: manifest {b} != traced {c}")
    return lines


def load_manifest(path: pathlib.Path) -> dict:
    if not path.exists():
        return {"configs": {}}
    with open(path) as f:
        return json.load(f)


def write_manifest(path: pathlib.Path, configs: dict):
    doc = {
        "_about": (
            "Traced-program fingerprints per registered graphlint "
            "config (tools/graphlint.py). CI fails on drift; "
            "re-baseline intended changes with "
            "`python tools/graphlint.py --update`."
        ),
        "configs": {k: configs[k] for k in sorted(configs)},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true",
                    help="lint + manifest diff (the default action)")
    ap.add_argument("--update", action="store_true",
                    help="re-baseline the manifest from fresh traces "
                         "(still fails on rule violations)")
    ap.add_argument("--configs", action="store_true",
                    help="list registered configs and exit")
    ap.add_argument("--only", action="append", default=None,
                    metavar="NAME", help="restrict to config NAME "
                    "(repeatable)")
    ap.add_argument("--manifest", default=str(MANIFEST_PATH),
                    help="manifest path (default: the checked-in one)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.configs:
        for name, builder in REGISTRY.items():
            doc = (builder.__doc__ or "").split(".")[0].strip()
            print(f"{name}: {doc}")
        return 0

    names = list(REGISTRY)
    if args.only:
        unknown = [n for n in args.only if n not in REGISTRY]
        if unknown:
            print(f"unknown config(s): {unknown}; choose from {names}",
                  file=sys.stderr)
            return 2
        names = [n for n in names if n in set(args.only)]

    manifest_path = pathlib.Path(args.manifest)
    manifest = load_manifest(manifest_path)
    baseline = dict(manifest.get("configs", {}))

    failed = False
    fresh = {}
    for name in names:
        subject, rules = REGISTRY[name]()
        report = run_lint(subject, rules)
        fp = fingerprint(subject)
        fresh[name] = fp
        if not report.ok:
            failed = True
            print(report.summary(), file=sys.stderr)
        drift = []
        if name not in baseline:
            drift = [f"  {name}: not in manifest (new config?)"]
        else:
            drift = _diff(name, baseline[name], fp)
        if drift and not args.update:
            failed = True
            print(f"graphlint[{name}]: manifest drift vs "
                  f"{manifest_path.name}:", file=sys.stderr)
            for line in drift:
                print(line, file=sys.stderr)
        if report.ok and not (drift and not args.update):
            print(f"graphlint[{name}]: OK "
                  f"(eqns={fp['eqn_count']}, dots={fp['dot_count']}, "
                  f"collectives={sum(fp['counts'].values())})")

    if args.update:
        if failed:
            print("refusing to --update: rule violations above must be "
                  "fixed first (the manifest records compliant programs)",
                  file=sys.stderr)
            return 1
        baseline.update(fresh)
        write_manifest(manifest_path, baseline)
        print(f"wrote {manifest_path} ({len(fresh)} config(s))")
        return 0

    if failed:
        print("graphlint: FAILED — fix the violations or, for an "
              "intended program change, re-baseline with "
              "`python tools/graphlint.py --update`", file=sys.stderr)
        return 1
    print(f"graphlint: all {len(names)} config(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
