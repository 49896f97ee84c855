"""The three pipeline schedules, as SPMD scan-over-ppermute programs.

TPU-native redesign of the reference's schedule trio
(reference: apex/transformer/pipeline_parallel/schedules/ — dispatcher
`__init__.py:16-34`, `fwd_bwd_no_pipelining.py:29`, 1F1B
`fwd_bwd_pipelining_without_interleaving.py:22-170`, interleaved
`fwd_bwd_pipelining_with_interleaving.py:41-308`). The reference runs a
*per-rank asymmetric* program: warmup = P−rank−1 forwards, a steady
1F1B phase of paired send_forward_recv_backward, and a cooldown of
backwards, all over NCCL P2P. Single-controller JAX cannot (and should
not) express per-rank control flow; instead each schedule here is one
SPMD program in which every stage runs the same `lax.scan` and
activations hop stages via `lax.ppermute`:

* tick ``t``: stage ``s`` computes microbatch ``t−s`` (when valid) and
  the permute hands its output to ``s+1`` — exactly the reference's
  pipeline diagram, with warmup/steady/cooldown appearing as the
  triangular valid-regions of the scan rather than as python phases;
* training runs the TRUE 1F1B: ONE non-differentiated scan interleaves
  a forward and a backward unit per tick (`_one_pass_interleaved`),
  building gradients inside the scan via per-tick `jax.vjp` — stage
  inputs wait in an O(P)-slot ring, activation cotangents ride a
  reverse ppermute, and live activations are bounded by the pipeline
  depth, not the microbatch count (differentiating the forward scan —
  the previous design — saved the carry at every tick: O(M));
* `forward_only` keeps the plain forward scan, whose transpose is
  never taken;
* the interleaved schedule is the same program over a *circular*
  pipeline: each stage holds ``vp`` model chunks, the permute wraps
  P−1 → 0, and crossing the wrap advances the chunk index — same unit
  ordering as the reference's `num_warmup` doubling / chunk-id
  scheduling, derived from the closed-form tick formula instead of
  bookkeeping. The linear schedule is its vp = 1 degenerate case.

All schedule functions share one signature (the reference's share theirs
via `forward_step_func`):

    schedule(stage_fn, loss_fn, params, inputs, targets, ...)
      stage_fn(stage_params, x) -> y        uniform stage body (x, y same
                                            shape — the reference has the
                                            same constraint, tensor_shape)
      loss_fn(y_last, target) -> scalar     applied on the final stage
      params:  leaves stacked over stages — local shard inside shard_map
               has leading dim 1 (non-interleaved) or vp (interleaved);
               no leading axis for no-pipelining
      inputs:  (M, micro_batch, ...) microbatched inputs, replicated
               across the pipe axis
      targets: (M, ...) per-microbatch targets

    returns (per_microbatch_losses, grads) — grads of mean loss w.r.t.
    params (None when forward_only), loss replicated on every stage.

Shared non-stage parameters (the reference's pre_process/post_process
stages: embedding on the first stage, tied LM head on the last —
schedules/common.py build_model) ride the optional ``extra_params`` /
``pre_fn`` arguments: ``pre_fn(extra, microbatch_input)`` produces the
stage-0 activation (embedding lookup) and ``loss_fn`` becomes
``loss_fn(extra, y_last, target)`` (head + loss). The return value is
then ``(losses, (stage_grads, extra_grads))`` with extra grads summed
over the pipe axis — the reference's embedding-group allreduce.

Pipelined schedules must run inside shard_map with the ``pipe`` axis
bound; `forward_backward_no_pipelining` runs anywhere.
"""

import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import core as _jax_core
from jax.lax import axis_size

from rocm_apex_tpu.transformer import parallel_state


def _start_timer(timers, forward_only, tracer=None, microbatches=0):
    """Observability hook (rocm_apex_tpu.monitor): every schedule takes
    ``timers=`` (a `transformer._timers.Timers`) and times the whole
    schedule call under ``pipeline/forward`` / ``pipeline/fwd-bwd``.
    Called eagerly the stop syncs on the losses (a value fetch — true
    device wall time); called under jit the outputs are tracers, so the
    stop records trace/build time only and the in-graph phase
    attribution comes from the ``pp_fwd``/``pp_bwd``/``pp_comm``/
    ``pp_head`` named scopes instead (visible in a device trace — one
    fused scan admits no host-side phase timers).

    ``tracer=`` (a `monitor.Tracer`) records the same region as a span
    on the host timeline (and an ``apex/`` `monitor.trace.phase` scope,
    so a live device capture shows the schedule boundary); the shared
    disabled tracer makes the default free."""
    name = "pipeline/forward" if forward_only else "pipeline/fwd-bwd"
    span = None
    if tracer is not None and tracer.enabled:
        span = tracer.span(name, track="pipeline",
                           microbatches=int(microbatches))
        span.__enter__()
    if timers is None:
        return None, span
    t = timers(name)
    t.start()
    return t, span


def _finish_timer(obs, out):
    t, span = obs
    if t is not None:
        leaves = [
            x for x in jax.tree_util.tree_leaves(out) if x is not None
        ]
        sync = None
        if leaves and not any(
            isinstance(x, _jax_core.Tracer) for x in leaves
        ):
            sync = leaves[0]
        t.stop(sync_on=sync)
    if span is not None:
        span.__exit__(None, None, None)
    return out


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _replicate_masked(x, maskf, axis):
    """Broadcast masked values across the axis:
    out = psum(where(maskf, x, 0)).

    Explicit VJP because the raw psum's transpose depends on shard_map
    replication tracking: with check_vma=False it degenerates to a psum
    of cotangents and every gradient through the loss replication comes
    back axis-size times too large. The true transpose of "replicate
    from the masked rank" keeps the cotangent only where the mask is
    set — correct under either check_vma setting.

    Masking is a select, not a multiply: non-exit ranks run the head on
    zero activation buffers, and a NaN/Inf produced there would survive
    ``NaN * 0`` and poison the psum for every rank. ``where`` discards
    the non-exit value outright."""
    return jax.lax.psum(jnp.where(maskf != 0, x, jnp.zeros_like(x)), axis)


def _replicate_masked_fwd(x, maskf, axis):
    return (
        jax.lax.psum(jnp.where(maskf != 0, x, jnp.zeros_like(x)), axis),
        maskf,
    )


def _replicate_masked_bwd(axis, maskf, ct):
    return (
        jnp.where(maskf != 0, ct, jnp.zeros_like(ct)),
        jnp.zeros_like(maskf),
    )


_replicate_masked.defvjp(_replicate_masked_fwd, _replicate_masked_bwd)


def _pcast_varying(x, axis):
    """Make `x` varying over `axis` by adding a varying zero.

    Idempotent, and — unlike a raw `pcast(to='varying')`, whose
    transpose is a psum over the axis — the add's transpose passes the
    cotangent through per-rank, so no hidden collective appears in the
    backward (the schedules do their cross-stage grad sums explicitly)."""
    z = jax.lax.pcast(jnp.zeros((), jnp.result_type(x)), (axis,), to="varying")
    return x + z


def _stage0_inputs(pre_fn, extra, inputs, axis):
    """(M, ...) stage-0 activations: every microbatch embedded ONCE
    before the scan (instead of once per tick inside it). SPMD runs the
    embedding on every rank; only stage 0 consumes the result, and the
    unused copies carry zero cotangents through the stage-0 select."""
    if pre_fn is None:
        return inputs, jax.eval_shape(lambda x: x[0], inputs)
    x0_all = _pcast_varying(
        jax.vmap(lambda xi: pre_fn(extra, xi))(inputs), axis
    )
    return x0_all, jax.eval_shape(lambda x: x[0], x0_all)


def _head_losses(loss_fn, has_extra, extra, y_buf, targets, axis, is_last):
    """(M,) per-microbatch losses: the post_process head applied ONCE
    per microbatch after the scan (not per tick), and ONLY on the exit
    stage. The `cond` (not a select) matters twice over: non-exit ranks
    skip the head's M vmapped applications entirely, and — since
    `cond`'s VJP differentiates only the taken branch — a user loss_fn
    that produces Inf/NaN on zero activation buffers cannot leak NaN
    into non-exit gradients via the 0·Inf of a masked-output transpose.
    The predicate depends only on the pipe rank, so any collective
    inside loss_fn (e.g. the vocab-parallel CE's tensor-axis psum) sees
    a uniform decision within its device group.

    NOTE: the predicate VARIES over the pipe axis, so this `cond` (and
    the per-tick head in `_one_pass_interleaved`) is only legal under
    `shard_map(..., check_vma=False)` — every current caller. A future
    caller with replication checking enabled would see this rejected;
    it would need `check_vma=False` or a select-based head."""

    def one(y, t):
        loss = loss_fn(extra, y, t) if has_extra else loss_fn(y, t)
        return loss.astype(jnp.float32)

    m = y_buf.shape[0]

    def _real():
        return _pcast_varying(jax.vmap(one)(y_buf, targets), axis)

    def _zero():
        # the zero branch must carry the same varying-over-axis type as
        # the real branch or cond rejects the branch pair
        return _pcast_varying(jnp.zeros((m,), jnp.float32), axis)

    return jax.lax.cond(is_last, _real, _zero)


__all__ = [
    "get_forward_backward_func",
    "forward_backward_no_pipelining",
    "forward_backward_pipelining_without_interleaving",
    "forward_backward_pipelining_with_interleaving",
]

StageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]
LossFn = Callable[[jnp.ndarray, Any], jnp.ndarray]


def get_forward_backward_func(
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_size: Optional[int] = None,
):
    """Pick the schedule (reference: schedules/__init__.py:16-34)."""
    if pipeline_model_parallel_size is None:
        pipeline_model_parallel_size = (
            parallel_state.get_pipeline_model_parallel_world_size()
        )
    if pipeline_model_parallel_size > 1:
        if virtual_pipeline_model_parallel_size is not None:
            return forward_backward_pipelining_with_interleaving
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining


def _maybe_checkpoint(fn: StageFn, on: bool) -> StageFn:
    return jax.checkpoint(fn) if on else fn


def forward_backward_no_pipelining(
    stage_fn: StageFn,
    loss_fn: LossFn,
    params: Any,
    inputs: jnp.ndarray,
    targets: Any,
    *,
    forward_only: bool = False,
    checkpoint_stages: bool = False,
    axis_name: Optional[str] = None,
    extra_params: Any = None,
    pre_fn=None,
    timers=None,
    tracer=None,
    **unused_kw,
):
    """Sequential microbatch loop with gradient accumulation.

    reference: fwd_bwd_no_pipelining.py:29-84 — grads accumulate across
    the microbatch loop and sync once (the reference suppresses DDP
    hooks until the last microbatch; here accumulation is explicit and
    the caller psums afterwards). Loss is divided by the number of
    microbatches, as the reference does inside forward_step
    (schedules/common.py:158-166).
    """
    del axis_name
    m = inputs.shape[0]
    body = _maybe_checkpoint(stage_fn, checkpoint_stages)
    has_extra = extra_params is not None
    tmr = _start_timer(timers, forward_only, tracer, m)

    def one_loss(p, extra, x, t):
        with jax.named_scope("pp_fwd"):
            x0 = pre_fn(extra, x) if pre_fn is not None else x
            y = body(p, x0)
        with jax.named_scope("pp_head"):
            return loss_fn(extra, y, t) if has_extra else loss_fn(y, t)

    if forward_only:
        losses = jax.lax.map(
            lambda xt: one_loss(params, extra_params, xt[0], xt[1]),
            (inputs, targets),
        )
        return _finish_timer(tmr, (losses, None))

    argnums = (0, 1) if has_extra else 0

    def step(acc, xt):
        x, t = xt
        accp, acce = acc
        loss, g_all = jax.value_and_grad(one_loss, argnums=argnums)(
            params, extra_params, x, t
        )
        g, ge = g_all if has_extra else (g_all, None)
        accp = jax.tree_util.tree_map(lambda a, b: a + b / m, accp, g)
        if has_extra:
            acce = jax.tree_util.tree_map(lambda a, b: a + b / m, acce, ge)
        return (accp, acce), loss

    zero = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )
    zero_e = (
        jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), extra_params
        )
        if has_extra
        else None
    )
    (grads, egrads), losses = jax.lax.scan(
        step, (zero, zero_e), (inputs, targets)
    )
    if has_extra:
        return _finish_timer(tmr, (losses, (grads, egrads)))
    return _finish_timer(tmr, (losses, grads))


def _tree_idx(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _one_pass_1f1b(
    stage_fn, loss_fn, local_params, inputs, targets, axis,
    extra, pre_fn, has_extra,
):
    """True 1F1B with O(P) live activations: ONE non-differentiated
    scan interleaving a forward and a backward unit per tick.

    Differentiating a forward scan (the previous implementation) saves
    the carried activation at EVERY tick for the transpose — O(M)
    memory, defeating 1F1B's point. The linear pipeline is exactly the
    vp = 1 case of the circular one (`_one_pass_interleaved`: tick
    algebra degenerates to forward of microbatch t−s and backward of
    t−(2(P−1)−s); the ring's wrap edges carry only data masked off by
    the entry/exit selects), so it delegates there with a singleton
    chunk axis. Gradients accumulate in fp32 and are cast to the param
    dtype; returns (losses (M,), grads, extra_grads | None).
    """
    stacked = jax.tree_util.tree_map(lambda x: x[None], local_params)
    losses, grads, egrads = _one_pass_interleaved(
        stage_fn, loss_fn, stacked, inputs, targets, axis,
        extra, pre_fn, has_extra, 1,
    )
    grads = jax.tree_util.tree_map(lambda g: jnp.squeeze(g, 0), grads)
    return losses, grads, egrads


def forward_backward_pipelining_without_interleaving(
    stage_fn: StageFn,
    loss_fn: LossFn,
    params: Any,
    inputs: jnp.ndarray,
    targets: Any,
    *,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
    axis_name: Optional[str] = None,
    extra_params: Any = None,
    pre_fn=None,
    timers=None,
    tracer=None,
    **unused_kw,
):
    """The 1F1B linear pipeline.

    reference: fwd_bwd_pipelining_without_interleaving.py:22-170. Tick
    ``t`` has stage ``s`` working on microbatch ``t−s``; with M
    microbatches the forward spans M+P−1 ticks. Training runs the
    one-pass interleaved schedule (`_one_pass_1f1b` — O(P) live
    activations, gradients built inside the scan); `forward_only`
    keeps the plain forward scan. ``checkpoint_stages`` is accepted
    for API compatibility: the one-pass backward always rematerializes
    the stage from its saved input, which is the same recompute the
    checkpointed transpose performed — passing ``False`` with training
    enabled cannot disable the recompute, and warns once.
    """
    if not checkpoint_stages and not forward_only:
        warnings.warn(
            "checkpoint_stages=False has no effect on the training "
            "path: the one-pass 1F1B backward always rematerializes "
            "each stage from its saved input (O(P) live activations). "
            "There is no store-all-activations fast path.",
            stacklevel=2,
        )
    axis = axis_name or parallel_state.PIPE_AXIS
    p = axis_size(axis)
    m = inputs.shape[0]
    ticks = m + p - 1
    rank = jax.lax.axis_index(axis)
    is_first = rank == 0
    is_last = rank == p - 1
    # checkpoint_stages never wraps here: training runs the one-pass
    # backward (always remats), and the forward_only scan below is
    # never differentiated, so jax.checkpoint would be a no-op
    body = stage_fn
    perm = [(i, i + 1) for i in range(p - 1)]

    local_params = jax.tree_util.tree_map(
        lambda x: jnp.squeeze(x, 0) if x.shape[:1] == (1,) else x, params
    )
    has_extra = extra_params is not None

    def run(local_params, extra):
        # pre_process: every microbatch embedded once, on stage 0 only
        x0_all, a0 = _stage0_inputs(pre_fn, extra, inputs, axis)

        def tick(carry, t):
            act_recv, y_buf = carry
            mb_in = jnp.clip(t, 0, m - 1)
            x = jnp.where(is_first, x0_all[mb_in], act_recv)
            with jax.named_scope("pp_fwd"):
                y = body(local_params, x)
            # Output collection on the last stage: tick t completes
            # microbatch t-(P-1). The head/loss is NOT applied here —
            # outputs buffer up and post_process runs once after the
            # scan (the where gates cotangents of invalid ticks to zero)
            mb_out = t - (p - 1)
            valid = (mb_out >= 0) & is_last
            mb_out_c = jnp.clip(mb_out, 0, m - 1)
            y_buf = y_buf.at[mb_out_c].set(
                jnp.where(valid, y, y_buf[mb_out_c])
            )
            with jax.named_scope("pp_comm"):
                sent = jax.lax.ppermute(y, axis, perm)
            return (sent, y_buf), None

        act0 = jax.lax.pcast(
            jnp.zeros(a0.shape, a0.dtype), (axis,), to="varying"
        )
        ybuf0 = jax.lax.pcast(
            jnp.zeros((m,) + a0.shape, a0.dtype), (axis,), to="varying"
        )
        (_, y_buf), _ = jax.lax.scan(tick, (act0, ybuf0), jnp.arange(ticks))
        # post_process on the last stage, once per microbatch
        loss_buf = _head_losses(
            loss_fn, has_extra, extra, y_buf, targets, axis, is_last
        )
        # Replicate the last stage's losses to every stage so the caller
        # sees one logical value (reference keeps losses on the last
        # stage only and broadcasts out-of-band).
        loss_buf = _replicate_masked(
            loss_buf, is_last.astype(loss_buf.dtype), axis
        )
        return jnp.mean(loss_buf), loss_buf

    tmr = _start_timer(timers, forward_only, tracer, m)
    if forward_only:
        _, losses = run(local_params, extra_params)
        return _finish_timer(tmr, (losses, None))
    losses, grads, egrads = _one_pass_1f1b(
        stage_fn, loss_fn, local_params, inputs, targets, axis,
        extra_params, pre_fn, has_extra,
    )
    grads = jax.tree_util.tree_map(
        lambda g, x: g[None] if x.shape[:1] == (1,) else g, grads, params
    )
    if has_extra:
        # egrads are per-stage partials summed over the axis inside
        # _one_pass_1f1b — the reference's embedding-group allreduce
        # (parallel_state embedding group = first + last stage)
        return _finish_timer(tmr, (losses, (grads, egrads)))
    return _finish_timer(tmr, (losses, grads))


def _one_pass_interleaved(
    stage_fn, loss_fn, params, inputs, targets, axis,
    extra, pre_fn, has_extra, vp,
):
    """One-pass interleaved 1F1B: the circular pipeline with gradients
    built inside a single non-differentiated scan (the `_one_pass_1f1b`
    scheme generalized to vp model chunks per rank).

    Geometry (global stage ``g = v·P + s``, ``G = vp·P``,
    ``L = P·vp``): forward of unit (m, v) runs on rank s at
    ``t_f = (m//P)·L + v·P + m%P + s`` (the round-robin order of the
    forward-only schedule) and its backward at
    ``t_b = t_f + 2·(G−1−g)``, i.e. ``t_b − 2(G−1) + s =
    (m//P)·L + m%P − v·P`` — decoded per tick by the same mod-L
    arithmetic. Cotangents ride ONE reverse ring permute
    ``i → (i−1) mod P``: a step within a chunk moves g+1 → g on the
    next rank down, and the wrap P−1 ← 0 decrements the chunk — the
    mirror image of the forward's wrap-around hand-off.

    Stage inputs wait in a ``2(G−1)+1``-slot ring keyed by forward
    tick (one unit per rank per tick, lifetime ≤ 2(G−1)); the exit
    unit (g = G−1) backwards the tick it forwards, so live activations
    are bounded by the schedule depth O(P·vp) — the interleaved
    1F1B's documented in-flight profile — instead of the O(M·vp)
    carry history of a differentiated scan.
    """
    p = axis_size(axis)
    m = inputs.shape[0]
    rank = jax.lax.axis_index(axis)
    is_first = rank == 0
    is_last = rank == p - 1
    L = p * vp
    G = vp * p
    ring = [(i, (i + 1) % p) for i in range(p)]
    rring = [(i, (i - 1) % p) for i in range(p)]
    nslots = 2 * (G - 1) + 1
    ticks = ((m - 1) // p) * L + (m - 1) % p + 2 * (G - 1) + 1

    in0 = jax.eval_shape(lambda x: x[0], inputs)
    a0 = in0 if pre_fn is None else jax.eval_shape(pre_fn, extra, in0)

    def varying(x):
        return jax.tree_util.tree_map(lambda v: _pcast_varying(v, axis), x)

    def zeros_of(shape_tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, dtype or s.dtype), shape_tree
        )

    def chunk_at(tree, v):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, v, 0, keepdims=False),
            tree,
        )

    def decode_bwd(t):
        """tick -> (m_b, v_b, valid): invert t_b's round-robin form."""
        r = t - 2 * (G - 1) + rank
        rnd = jnp.floor_divide(r, L)
        rr = r - rnd * L  # in [0, L)
        # rr = m%p - v*p (v=0 branch) or L + m%p - v*p (v>0 branch)
        in_v0 = rr < p
        v_pos = jnp.floor_divide(L - rr + p - 1, p)
        v_b = jnp.where(in_v0, 0, v_pos)
        mp = jnp.where(in_v0, rr, v_pos * p - (L - rr))
        rnd_b = jnp.where(in_v0, rnd, rnd + 1)
        m_b = rnd_b * p + mp
        # r itself may be negative for early microbatches of higher
        # chunks (m%p - v*p < 0); the mb bound is the real validity
        valid = (m_b >= 0) & (m_b < m) & (v_b < vp)
        return m_b, v_b, valid

    def tick(carry, t):
        act_recv, ct_recv, x_buf, g_acc, eg_acc, losses = carry

        # ---- forward unit (current schedule's decomposition) -----------
        r = t - rank
        rnd, rr = r // L, r % L
        v_f = rr // p
        m_f = rnd * p + rr % p
        fwd_valid = (r >= 0) & (m_f >= 0) & (m_f < m)
        v_fc = jnp.clip(v_f, 0, vp - 1)
        m_fc = jnp.clip(m_f, 0, m - 1)
        chunk = chunk_at(params, v_fc)
        inp_j = _tree_idx(inputs, m_fc)
        is_entry = is_first & (v_fc == 0)
        if pre_fn is None:
            x0 = _pcast_varying(inp_j, axis)
        else:
            # embedding only on the entry rank's valid v=0 ticks: the
            # cond skips a full vocab-gather per tick on every other
            # rank (its result would be discarded by the select below)
            x0 = jax.lax.cond(
                is_entry & fwd_valid,
                lambda: _pcast_varying(pre_fn(extra, inp_j), axis),
                lambda: _pcast_varying(
                    jnp.zeros(a0.shape, a0.dtype), axis
                ),
            )
        x_in = jnp.where(is_entry, x0, act_recv)
        with jax.named_scope("pp_fwd"):
            y = stage_fn(chunk, x_in)

        # exit-unit post_process (global stage G-1)
        is_exit = is_last & (v_fc == vp - 1) & fwd_valid
        tgt_j = _tree_idx(targets, m_fc)
        ct1 = _pcast_varying(jnp.asarray(1.0 / m, jnp.float32), axis)

        def _head():
            if has_extra:
                def lf(e, yy):
                    return loss_fn(e, yy, tgt_j).astype(jnp.float32)

                loss, pull = jax.vjp(lf, extra, y)
                de, dy = pull(ct1)
                eg2 = jax.tree_util.tree_map(
                    lambda a, d: a + d.astype(jnp.float32), eg_acc, de
                )
                return varying((loss, dy)), eg2

            def lf(yy):
                return loss_fn(yy, tgt_j).astype(jnp.float32)

            loss, pull = jax.vjp(lf, y)
            (dy,) = pull(ct1)
            return varying((loss, dy)), eg_acc

        def _nohead():
            return (
                varying(
                    (
                        jnp.zeros((), jnp.float32),
                        jnp.zeros(y.shape, y.dtype),
                    )
                ),
                eg_acc,
            )

        with jax.named_scope("pp_head"):
            (loss_j, dy), eg_acc = jax.lax.cond(is_exit, _head, _nohead)
        losses = losses.at[m_fc].set(
            jnp.where(is_exit, loss_j, losses[m_fc])
        )

        # ---- backward unit --------------------------------------------
        m_b, v_b, bwd_valid = decode_bwd(t)
        v_bc = jnp.clip(v_b, 0, vp - 1)
        m_bc = jnp.clip(m_b, 0, m - 1)
        g_b = v_bc * p + rank
        t_f_b = t - 2 * (G - 1 - g_b)
        slot_b = jnp.clip(t_f_b, 0, None) % nslots
        bwd_is_exit = is_last & (v_bc == vp - 1)
        x_saved = jnp.where(bwd_is_exit, x_in, x_buf[slot_b])
        ct_in = jnp.where(bwd_is_exit, dy.astype(y.dtype), ct_recv)
        bchunk = chunk_at(params, v_bc)
        with jax.named_scope("pp_bwd"):
            _, pull = jax.vjp(stage_fn, bchunk, x_saved)
            dp_j, dx_j = pull(ct_in)
        g_acc = jax.tree_util.tree_map(
            lambda a, d: jax.lax.dynamic_update_index_in_dim(
                a,
                jax.lax.dynamic_index_in_dim(a, v_bc, 0, keepdims=False)
                + jnp.where(bwd_valid, d.astype(jnp.float32), 0.0),
                v_bc,
                0,
            ),
            g_acc,
            dp_j,
        )

        if has_extra and pre_fn is not None:
            inp_b = _tree_idx(inputs, m_bc)

            def _pre_bwd():
                _, pullE = jax.vjp(lambda e: pre_fn(e, inp_b), extra)
                (deE,) = pullE(dx_j)
                return jax.tree_util.tree_map(
                    lambda a, d: a + d.astype(jnp.float32), eg_acc, deE
                )

            eg_acc = jax.lax.cond(
                is_first & (v_bc == 0) & bwd_valid,
                _pre_bwd,
                lambda: eg_acc,
            )

        # ---- buffer + ring transfers (slots keyed by forward tick) ----
        slot_f = t % nslots
        x_buf = x_buf.at[slot_f].set(
            jnp.where(
                fwd_valid & ~(is_last & (v_fc == vp - 1)), x_in,
                x_buf[slot_f],
            )
        )
        with jax.named_scope("pp_comm"):
            act_send = jax.lax.ppermute(y, axis, ring)
            ct_send = jax.lax.ppermute(
                jnp.where(bwd_valid, dx_j, jnp.zeros_like(dx_j)),
                axis, rring,
            )
        return (act_send, ct_send, x_buf, g_acc, eg_acc, losses), None

    act0 = varying(jnp.zeros(a0.shape, a0.dtype))
    ct0 = varying(jnp.zeros(a0.shape, a0.dtype))
    xbuf0 = varying(jnp.zeros((nslots,) + a0.shape, a0.dtype))
    g0 = varying(zeros_of(params, jnp.float32))
    eg0 = varying(zeros_of(extra, jnp.float32)) if has_extra else ()
    losses0 = varying(jnp.zeros((m,), jnp.float32))

    (_, _, _, g_acc, eg_acc, losses), _ = jax.lax.scan(
        tick,
        (act0, ct0, xbuf0, g0, eg0, losses0),
        jnp.arange(ticks),
    )
    grads = jax.tree_util.tree_map(
        lambda g, pp: g.astype(pp.dtype), g_acc, params
    )
    losses = _replicate_masked(losses, is_last.astype(losses.dtype), axis)
    if has_extra:
        egrads = jax.tree_util.tree_map(
            lambda g, e: jax.lax.psum(g, axis).astype(e.dtype),
            eg_acc,
            extra,
        )
        return losses, grads, egrads
    return losses, grads, None


def forward_backward_pipelining_with_interleaving(
    stage_fn: StageFn,
    loss_fn: LossFn,
    params: Any,
    inputs: jnp.ndarray,
    targets: Any,
    *,
    forward_only: bool = False,
    checkpoint_stages: bool = True,
    axis_name: Optional[str] = None,
    extra_params: Any = None,
    pre_fn=None,
    timers=None,
    tracer=None,
    **unused_kw,
):
    """Interleaved virtual stages as a circular pipeline.

    reference: fwd_bwd_pipelining_with_interleaving.py:41-308. Each stage
    holds ``vp`` model chunks (params leaves: (vp, ...) locally); global
    stage ``g = v·P + s``. Work unit (microbatch m, chunk v) runs on
    stage s at tick

        t(m, v, s) = (m // P)·P·vp + v·P + (m % P) + s

    which is exactly the reference's round-robin chunk order (rounds of
    P microbatches sweep all chunks before the next round). Consecutive
    global stages differ by one tick, so a single wrap-around ring
    permute carries every transfer, including the chunk hand-off
    P−1 → 0. Requires M % P == 0, like the reference
    (fwd_bwd_pipelining_with_interleaving.py asserts the same).
    ``checkpoint_stages=False`` with training enabled warns, as in the
    linear schedule: the one-pass backward always rematerializes.
    """
    if not checkpoint_stages and not forward_only:
        warnings.warn(
            "checkpoint_stages=False has no effect on the training "
            "path: the one-pass interleaved backward always "
            "rematerializes each chunk from its saved input.",
            stacklevel=2,
        )
    axis = axis_name or parallel_state.PIPE_AXIS
    p = axis_size(axis)
    m = inputs.shape[0]
    if m % p != 0:
        raise ValueError(
            f"interleaved schedule requires num_microbatches ({m}) divisible "
            f"by pipeline size ({p})"
        )
    vp_sizes = {
        leaf.shape[0] for leaf in jax.tree_util.tree_leaves(params)
    }
    if len(vp_sizes) != 1:
        raise ValueError(
            f"all param leaves must share the leading (vp) axis; got sizes "
            f"{sorted(vp_sizes)}"
        )
    vp = vp_sizes.pop()
    ticks = m * vp + p - 1
    rank = jax.lax.axis_index(axis)
    body = stage_fn  # same no-op rationale as the linear schedule
    ring = [(i, (i + 1) % p) for i in range(p)]
    round_len = p * vp

    has_extra = extra_params is not None
    is_first = rank == 0
    is_last = rank == p - 1

    def run(params, extra):
        x0_all, a0 = _stage0_inputs(pre_fn, extra, inputs, axis)

        def tick(carry, t):
            act_recv, y_buf = carry
            r = t - rank
            rnd, rr = r // round_len, r % round_len
            v = rr // p
            mb = rnd * p + rr % p
            valid = (r >= 0) & (mb >= 0) & (mb < m)
            v_c = jnp.clip(v, 0, vp - 1)
            mb_c = jnp.clip(mb, 0, m - 1)
            chunk = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, v_c, 0, keepdims=False),
                params,
            )
            is_entry = is_first & (v_c == 0)
            x = jnp.where(is_entry, x0_all[mb_c], act_recv)
            with jax.named_scope("pp_fwd"):
                y = body(chunk, x)
            is_exit = is_last & (v_c == vp - 1) & valid
            y_buf = y_buf.at[mb_c].set(jnp.where(is_exit, y, y_buf[mb_c]))
            with jax.named_scope("pp_comm"):
                sent = jax.lax.ppermute(y, axis, ring)
            return (sent, y_buf), None

        act0 = jax.lax.pcast(
            jnp.zeros(a0.shape, a0.dtype), (axis,), to="varying"
        )
        ybuf0 = jax.lax.pcast(
            jnp.zeros((m,) + a0.shape, a0.dtype), (axis,), to="varying"
        )
        (_, y_buf), _ = jax.lax.scan(tick, (act0, ybuf0), jnp.arange(ticks))
        loss_buf = _head_losses(
            loss_fn, has_extra, extra, y_buf, targets, axis, is_last
        )
        loss_buf = _replicate_masked(
            loss_buf, is_last.astype(loss_buf.dtype), axis
        )
        return jnp.mean(loss_buf), loss_buf

    tmr = _start_timer(timers, forward_only, tracer, m)
    if forward_only:
        _, losses = run(params, extra_params)
        return _finish_timer(tmr, (losses, None))
    losses, grads, egrads = _one_pass_interleaved(
        stage_fn, loss_fn, params, inputs, targets, axis,
        extra_params, pre_fn, has_extra, vp,
    )
    if has_extra:
        return _finish_timer(tmr, (losses, (grads, egrads)))
    return _finish_timer(tmr, (losses, grads))
