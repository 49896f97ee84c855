"""KV-cached GPT generation through the continuous-batching engine.

The serving-side counterpart of `examples/gpt_train.py`: builds a GPT,
leases cache slots to a queue of mixed-length requests, and drives the
engine's admit → prefill-chunk → decode → evict loop, printing
per-request outputs and aggregate serving throughput. With random init
the tokens are noise — the point is the serving machinery: the
token-budget chunked-prefill scheduler packs pending prompt tokens
into ONE compiled mixed chunk+decode step per tick (the trace counters
printed at the end must stay at 1), prompts longer than any pad width
stream through in budget-sized pieces, and decodes never stall behind
a prefill. ``--token-budget 0`` selects the legacy whole-prompt
prefill (the A/B baseline, pad width ``--max-prompt-len``).

CPU smoke:
    JAX_PLATFORMS=cpu python examples/generate_gpt.py \
        --num-layers 2 --hidden-size 64 --num-attention-heads 4 \
        --max-seq-len 64 --num-slots 2 --num-requests 6 \
        --max-new-tokens 8 --token-budget 6
"""

import argparse
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from rocm_apex_tpu.inference import InferenceEngine, SamplingParams
from rocm_apex_tpu.models.gpt import GPTConfig, GPTModel
from rocm_apex_tpu.monitor import JsonlWriter, Tracer
from rocm_apex_tpu.utils.compile_cache import enable_compile_cache


def _install_sigterm_drain() -> threading.Event:
    """SIGTERM → graceful drain instead of a mid-tick kill.

    Same shape as CheckpointManager's preemption hook: flip an Event
    from the (async-signal-safe) handler and let the serving loop act
    on it at the next tick boundary; chain any previously installed
    handler so we compose with outer supervisors.
    """
    stop = threading.Event()
    if threading.current_thread() is not threading.main_thread():
        return stop  # signal.signal is main-thread-only
    prev = signal.getsignal(signal.SIGTERM)

    def _handler(signum, frame):
        stop.set()
        if callable(prev):
            prev(signum, frame)

    try:
        signal.signal(signal.SIGTERM, _handler)
    except (ValueError, OSError):
        pass
    return stop


def main():
    stop = _install_sigterm_drain()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--hidden-size", type=int, default=64)
    p.add_argument("--num-attention-heads", type=int, default=4)
    p.add_argument("--vocab-size", type=int, default=512)
    p.add_argument("--max-seq-len", type=int, default=64,
                   help="cache capacity == max_position_embeddings")
    p.add_argument("--max-prompt-len", type=int, default=16,
                   help="prompt-length cap for the RANDOM workload "
                        "below; also the pad width of the legacy "
                        "whole-prompt path (--token-budget 0)")
    p.add_argument("--token-budget", type=int, default=16,
                   help="prefill tokens absorbed per engine tick "
                        "(chunked-prefill scheduler); 0 = legacy "
                        "whole-prompt prefill")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="optional cap on tokens taken from ONE "
                        "request per tick (fairness inside the budget)")
    p.add_argument("--num-slots", type=int, default=2)
    p.add_argument("--replicas", type=int, default=1,
                   help="serve through a ReplicaRouter fleet of N "
                        "identical engines (N >= 2): prefix-affinity "
                        "+ least-loaded placement, failover with "
                        "token-identical recovery, rolling drain; "
                        "needs a token budget (migration recomputes "
                        "through chunked prefill); 1 = single engine")
    p.add_argument("--num-requests", type=int, default=6)
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: up to K tokens per slot "
                        "drafted by the n-gram self-drafter and "
                        "verified in the same mixed step (0 = off; "
                        "requires a token budget >= num_slots*(K+1) "
                        "for full-rate drafting)")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trace", type=str, default=None, metavar="PATH",
                   help="write a Chrome trace-event JSON of per-request"
                        " serving timelines to PATH (load in Perfetto)"
                        " and per-request completion records to"
                        " PATH.requests.jsonl")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="serve /metrics (Prometheus text), /healthz "
                        "(engine liveness), /varz (JSON) on "
                        "127.0.0.1:PORT while the loop runs; 0 = "
                        "ephemeral (the bound port is printed on the "
                        "'metrics:' line)")
    args = p.parse_args()

    cfg = GPTConfig(
        vocab_size=args.vocab_size,
        hidden_size=args.hidden_size,
        num_layers=args.num_layers,
        num_attention_heads=args.num_attention_heads,
        max_position_embeddings=args.max_seq_len,
        hidden_dropout=0.0,
        attention_dropout=0.0,
        tensor_parallel_size=1,
    )
    model = GPTModel(cfg)
    params = model.init(
        jax.random.PRNGKey(args.seed),
        jnp.zeros((1, args.max_prompt_len), jnp.int32),
    )
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(params)
    )
    chunked = args.token_budget > 0
    # flush: supervisors watch this banner to know the serving loop
    # (and its SIGTERM drain handler) is up, even through a pipe
    print(f"model: {n_params / 1e6:.1f}M params, "
          f"{jax.default_backend()} backend, "
          f"prefill={'budget %d' % args.token_budget if chunked else 'whole-prompt'}",
          flush=True)

    tracer = Tracer(enabled=args.trace is not None)
    engine_kwargs = dict(
        num_slots=args.num_slots,
        max_prompt_len=args.max_prompt_len,
        capacity=args.max_seq_len,
        sampling=SamplingParams(
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
        ),
        seed=args.seed,
        prefill_token_budget=args.token_budget if chunked else None,
        prefill_chunk=args.prefill_chunk,
        tracer=tracer,
        spec_k=args.spec_k,
    )
    router = None
    if args.replicas >= 2:
        if not chunked:
            raise SystemExit(
                "--replicas needs --token-budget > 0: replica "
                "failover recomputes migrated requests through the "
                "chunked prefill"
            )
        if args.trace is not None or args.spec_k > 0:
            raise SystemExit(
                "--replicas does not compose with --trace/--spec-k "
                "in this example (single-engine instrumentation)"
            )
        from rocm_apex_tpu.inference import ReplicaRouter

        router = ReplicaRouter(
            model, params, replicas=args.replicas,
            engine_kwargs=engine_kwargs,
        )
        serve = router
        print(f"fleet: {args.replicas} replicas behind one router",
              flush=True)
    else:
        serve = eng = InferenceEngine(model, params, **engine_kwargs)

    exporter = None
    if args.metrics_port is not None:
        from rocm_apex_tpu.monitor import start_exporter

        if router is not None:
            # merged-per-scrape registry + fleet /healthz (503 only
            # when no replica is healthy); replica detail on /varz
            exporter = start_exporter(
                router=router, port=args.metrics_port
            )
        else:
            exporter = start_exporter(
                eng.registry, port=args.metrics_port, engine=eng
            )
        # flush: the L1 smoke scrapes this address mid-run
        print(f"metrics: {exporter.url}", flush=True)

    rng = np.random.RandomState(args.seed)
    prompts = [
        rng.randint(0, args.vocab_size,
                    size=rng.randint(1, args.max_prompt_len + 1)).tolist()
        for _ in range(args.num_requests)
    ]

    t0 = time.perf_counter()
    for prompt in prompts:
        serve.add_request(prompt, args.max_new_tokens)
    results = []
    drained = False
    while serve.has_work():
        if stop.is_set():
            # SIGTERM: shed the queue, let in-flight requests finish,
            # exit 0 — never kill a request mid-token
            results.extend(serve.drain(shed_queue=True))
            drained = True
            break
        results.extend(serve.step())
    results.sort(key=lambda r: r.request_id)
    dt = time.perf_counter() - t0

    n_gen = sum(len(r.tokens) for r in results)
    if drained:
        shed = sum(1 for r in results if r.finish_reason == "cancelled")
        print(f"SIGTERM: drained gracefully — "
              f"{len(results) - shed} requests completed, "
              f"{shed} shed from the queue")
    for r in results:
        print(f"req {r.request_id}: prompt[{len(r.prompt)}] -> "
              f"{r.tokens} ({r.finish_reason})")
    s = serve.stats()
    if router is not None:
        hist = router.merged_registry().get("serve_ttft_ms")
        traces = [
            router.replica(i).mixed_trace_count
            for i in range(router.num_replicas)
        ]
        print(f"generated {n_gen} tokens across {len(results)} "
              f"requests in {dt:.2f}s ({n_gen / dt:.1f} tok/s) | "
              f"ttft p50/p95={hist.percentile(50):.0f}/"
              f"{hist.percentile(95):.0f}ms (merged fleet) | "
              f"migrations={s['migrations']:.0f} "
              f"quarantines={s['replica_quarantines']:.0f} | "
              f"traces: mixed={traces} (one per replica)")
    else:
        print(f"generated {n_gen} tokens across {len(results)} requests "
              f"in {dt:.2f}s ({n_gen / dt:.1f} tok/s) | "
              f"ttft p50/p95={s['ttft_ms_p50']:.0f}/{s['ttft_ms_p95']:.0f}ms | "
              f"traces: mixed={eng.mixed_trace_count} "
              f"decode={eng.decode_trace_count} "
              f"prefill={eng.prefill_trace_count}")
    if args.spec_k > 0:
        print(f"speculative: k={args.spec_k} "
              f"drafted={s['tokens_drafted']:.0f} "
              f"accepted={s['tokens_accepted']:.0f} "
              f"(acceptance={s['acceptance_rate']:.2f}) "
              f"rollbacks={s['rollbacks']:.0f}")
    if exporter is not None:
        # completion accounting: the registry counters, the delivered
        # results, and stats() must tell one story (the L1 smoke
        # asserts this line says "consistent")
        reg = (
            router.merged_registry() if router is not None
            else eng.registry
        )
        c_done = reg.get("serve_completions_total").total()
        c_gen = reg.get(
            "serve_tokens_total"
        ).value(phase="generated")
        if router is not None:
            # router-shed requests (drain cancels the global queue)
            # never reached an engine, so they are absent from the
            # per-replica completion counters by design
            n_router_shed = len(results) - int(
                sum(
                    router.replica(i).stats()["evicted"]
                    + router.replica(i).stats()["shed"]
                    for i in range(router.num_replicas)
                )
            ) if drained else 0
            ok_acct = (
                c_done == len(results) - n_router_shed
                and c_gen == n_gen
                and s["completed"] == s["submitted"] == len(results)
            )
        else:
            ok_acct = c_done == len(results) and c_gen == n_gen
            if not drained:
                ok_acct = ok_acct and c_done == s["evicted"] + s["shed"]
        print(f"telemetry: completions={c_done:.0f}/{len(results)} "
              f"generated_tokens={c_gen:.0f}/{n_gen} "
              f"({'consistent' if ok_acct else 'MISMATCH'})",
              flush=True)
        exporter.close()
        if not ok_acct:
            raise SystemExit(
                "telemetry counters disagree with results/stats()"
            )
    if args.trace is not None:
        n = tracer.export_chrome_trace(args.trace)
        req_path = args.trace + ".requests.jsonl"
        with open(req_path, "w") as f:
            w = JsonlWriter(stream=f)
            for rec in eng.completions:
                w.emit(rec)
        print(f"trace: {n} events -> {args.trace}; "
              f"{len(eng.completions)} request records -> {req_path}")
    if drained:
        return  # a drained run may stop before every program traced
    if router is not None:
        # host-only fabric: every replica still compiled ONE mixed
        # program; the router never adds a trace
        ok = all(
            router.replica(i).mixed_trace_count == 1
            and router.replica(i).decode_trace_count <= 1
            for i in range(router.num_replicas)
        )
    elif chunked:
        # the fixed-shape contract: ONE mixed program for the whole
        # run regardless of the prompt mix (+ at most one decode-only
        # fast-path program)
        ok = eng.mixed_trace_count == 1 and eng.decode_trace_count <= 1
    else:
        ok = eng.decode_trace_count == 1 and eng.prefill_trace_count == 1
    if not ok:
        raise SystemExit("serving programs retraced — scheduler broken")


if __name__ == "__main__":
    enable_compile_cache()
    main()
