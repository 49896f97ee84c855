"""Mixed-precision training state: bf16 model params + fp32 masters.

The reference's performance architecture for mixed precision keeps TWO
copies of the model — low-precision params the model computes with and
fp32 masters the optimizer updates, the update writing the low-precision
copy out in the same kernel (reference:
apex/amp/_process_optimizer.py:28-90 master-weight management,
apex/optimizers/fused_sgd.py depth-3 lists with fp16 copy-out,
apex/contrib/optimizers/distributed_fused_adam.py fp32 shards +
all-gathered fp16 params). This module is that architecture as a
functional train state:

    opt    = MixedPrecisionAdam(...)
    state  = opt.init(params_fp32)
    ...
    loss, grads = value_and_grad(loss_fn)(state.model)   # bf16 tree
    state = opt.step(state, grads, grad_scale=1/S, skip=skip)

**Why the update is XLA-fused tree math, not the packed Pallas kernel.**
The CUDA reference packs tensor lists into flat buffers because a kernel
launch per tensor dominates there (csrc/multi_tensor_apply.cuh). On TPU
the measured reality is the opposite: (8,128)-tiled 2-D arrays do NOT
linearize for free, so every pack/unpack of the parameter set is a
physical relayout — profiled at ~20 ms/step on a 134M-param GPT (the
gradient-pack loop fusion ran at 27 GB/s against an >800 GB/s chip),
while XLA fuses the whole per-leaf Adam update into a handful of
bandwidth-bound fusions with zero packing traffic. XLA fusion IS the
multi-tensor-apply of this hardware. The packed Pallas kernels remain
the substrate where packing is structurally required — the row-sharded
ZeRO optimizers (contrib/optimizers/distributed.py) and the
multi_tensor parity layer (ops/multi_tensor.py).

Skip-step (dynamic loss scaling) folds into the update as a select on
every buffer being written anyway — the jit-safe analogue of the
reference's optimizer.step no-op patch (apex/amp/handle.py:128-154).

Every update (and the master-to-model cast) traces under
``jax.named_scope("optimizer")``: its operations carry the scope in
their HLO ``op_name`` and its Pallas kernels are NAMED after it
(``%optimizer.<n>`` where they were ``%train_step.<n>``). A TPU trace
of this JAX keeps only the names, so `optimizer.kernels_device_ms`
(`benchmarks/layer_metrics/`) totals the kernels and cannot see the
update's fusions.
"""

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from rocm_apex_tpu.optimizers import _common as c

#: the scope a device trace finds the update under
OPTIMIZER_SCOPE = "optimizer"

__all__ = [
    "MixedPrecisionAdam",
    "MixedPrecisionState",
    "MixedPrecisionLamb",
]


class MixedPrecisionState(NamedTuple):
    count: jnp.ndarray
    model: Any   # compute-dtype param tree (feed to model.apply)
    master: Any  # fp32 master tree
    m: Any
    v: Any


class MixedPrecisionAdam:
    """Fused Adam/AdamW over mixed-precision train state.

    Hyperparameters match `fused_adam` / the reference
    (apex/optimizers/fused_adam.py:20-60); `compute_dtype` is the model
    params' dtype (bf16 = the O5/O2 recipe). `weight_decay_mask` is a
    bool pytree (True = decay), the functional stand-in for torch param
    groups.
    """

    def __init__(
        self,
        learning_rate: c.ScalarOrSchedule = 1e-3,
        *,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        weight_decay_mask: Optional[Any] = None,
        compute_dtype: jnp.dtype = jnp.bfloat16,
    ):
        self.learning_rate = learning_rate
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.weight_decay_mask = weight_decay_mask
        self.compute_dtype = compute_dtype

    def init(self, params) -> MixedPrecisionState:
        """`params` may be fp32 (preferred: they seed the masters
        exactly) or already in compute dtype."""
        master = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params
        )
        model = jax.tree_util.tree_map(
            lambda x: x.astype(self.compute_dtype), master
        )
        zeros = jax.tree_util.tree_map(jnp.zeros_like, master)
        return MixedPrecisionState(
            count=jnp.zeros((), jnp.int32),
            model=model,
            master=master,
            m=zeros,
            v=jax.tree_util.tree_map(jnp.zeros_like, master),
        )

    def model_params(self, state: MixedPrecisionState):
        """The compute-dtype tree for `model.apply` (== state.model)."""
        return state.model

    @jax.named_scope(OPTIMIZER_SCOPE)
    def step(
        self,
        state: MixedPrecisionState,
        grads,
        *,
        grad_scale=None,
        skip=None,
    ) -> MixedPrecisionState:
        """One fused update. `grads` are w.r.t. the compute-dtype params
        (`state.model`); `grad_scale` (1/loss_scale) fuses the unscale;
        `skip` freezes every buffer when True."""
        b1, b2, eps = self.beta1, self.beta2, self.eps
        live_t = (state.count + 1).astype(jnp.float32)
        lr = c.resolve_lr(self.learning_rate, state.count + 1)
        if self.bias_correction:
            bc1 = 1.0 - b1**live_t
            bc2 = 1.0 - b2**live_t
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        gs = jnp.asarray(
            1.0 if grad_scale is None else grad_scale, jnp.float32
        )
        if skip is None:
            live = jnp.asarray(1.0, jnp.float32)
            count = state.count + 1
        else:
            live = 1.0 - jnp.asarray(skip, jnp.float32)
            count = state.count + live.astype(jnp.int32)

        wd_tree = c.wd_tree(
            state.master, self.weight_decay, self.weight_decay_mask
        )

        def upd(p, g, m, v, wd):
            gf = g.astype(jnp.float32) * gs
            if not self.adam_w_mode:  # L2 mode: decay into the gradient
                gf = gf + wd * p
            m2 = b1 * m + (1.0 - b1) * gf
            v2 = b2 * v + (1.0 - b2) * gf * gf
            u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if self.adam_w_mode:  # AdamW: decoupled decay
                u = u + wd * p
            p2 = p - lr * u
            # jnp.where, not an arithmetic blend: skipped steps carry
            # inf/nan in p2, and inf * 0.0 == nan would poison p
            on = live > 0.0
            return (
                jnp.where(on, p2, p),
                jnp.where(on, m2, m),
                jnp.where(on, v2, v),
            )

        out = jax.tree_util.tree_map(
            upd, state.master, grads, state.m, state.v, wd_tree
        )
        master2, m2, v2 = c.unzip_tree(state.master, out, 3)
        return MixedPrecisionState(
            count=count,
            model=jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype), master2
            ),
            master=master2,
            m=m2,
            v=v2,
        )

    @jax.named_scope(OPTIMIZER_SCOPE)
    def step_and_probe(
        self,
        state: MixedPrecisionState,
        grads,
        *,
        grad_scale=None,
    ):
        """`step` with the overflow probe fused into the update pass.

        Returns ``(new_state, found_inf)``. A standalone
        `all_finite(grads)` probe costs a full extra pass over the
        gradients as dozens of separate reduce kernels (~18 ms/step
        measured on the 134M GPT); here each leaf's fp32 sum rides the
        update fusion that already reads the gradient, and the
        skip-select applies to the provisional outputs afterwards —
        overflow semantics identical to probe-then-skip (reference:
        the in-kernel noop_flag of multi_tensor_scale,
        csrc/multi_tensor_scale_kernel.cu:30-136)."""
        b1, b2, eps = self.beta1, self.beta2, self.eps
        live_t = (state.count + 1).astype(jnp.float32)
        lr = c.resolve_lr(self.learning_rate, state.count + 1)
        if self.bias_correction:
            bc1 = 1.0 - b1**live_t
            bc2 = 1.0 - b2**live_t
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        gs = jnp.asarray(
            1.0 if grad_scale is None else grad_scale, jnp.float32
        )
        wd_tree = c.wd_tree(
            state.master, self.weight_decay, self.weight_decay_mask
        )

        def upd(p, g, m, v, wd):
            gf = g.astype(jnp.float32) * gs
            probe = jnp.sum(gf)  # fused with the pass that reads gf
            if not self.adam_w_mode:
                gf = gf + wd * p
            m2 = b1 * m + (1.0 - b1) * gf
            v2 = b2 * v + (1.0 - b2) * gf * gf
            u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if self.adam_w_mode:
                u = u + wd * p
            return (p - lr * u, m2, v2, probe)

        out = jax.tree_util.tree_map(
            upd, state.master, grads, state.m, state.v, wd_tree
        )
        new_master, new_m, new_v, probes = c.unzip_tree(
            state.master, out, 4
        )
        found_inf = ~jnp.isfinite(
            sum(jax.tree_util.tree_leaves(probes))
        )
        ok = ~found_inf

        def sel(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(ok, n, o), new, old
            )

        master2 = sel(new_master, state.master)
        new_state = MixedPrecisionState(
            count=state.count + ok.astype(jnp.int32),
            model=jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype), master2
            ),
            master=master2,
            m=sel(new_m, state.m),
            v=sel(new_v, state.v),
        )
        return new_state, found_inf


class MixedPrecisionLamb:
    """Fused LAMB over mixed-precision train state — the BERT-Large
    recipe (reference: apex/optimizers/fused_lamb.py:4-215 semantics on
    the apex master-weight architecture, and
    fused_mixed_precision_lamb.py:8-256 which is the same marriage on
    the CUDA side).

    Same state shape as `MixedPrecisionAdam` (bf16 model copy + fp32
    masters + moments), with LAMB's extra structure arranged for HBM
    bandwidth — on a 330M-param BERT the naive tree-LAMB costs
    ~15 ms/step in optimizer machinery (round-5 profile: 202 standalone
    per-tensor reduce kernels + the materialized update-direction
    buffers and their scan-carry copies):

    * the overflow probe IS the global grad-norm pass — LAMB must read
      every gradient for the clip anyway, so `found_inf` falls out of
      the same per-leaf sum-of-squares (non-finite gsq == overflow);
    * the update direction ``u`` is NEVER materialized: pass A updates
      the moments and emits the (psq, usq) trust-ratio partials from
      registers; pass B recomputes ``u`` from (m2, v2, master) and
      applies ``p − lr·ratio·u`` with the bf16 model copy emitted from
      the same fusion. Recomputing u costs re-reading m2/v2 (8 B/param)
      and saves writing+re-reading a 4 B/param u buffer — net −4 B and
      one fewer kernel boundary;
    * ``moment_dtype=bf16`` (optional) halves the m/v traffic and
      state, the analogue of the reference's fp16-moment modes. Numerics
      caveat — trust-ratio skew: pass A emits ``usq`` (the ratio
      denominator) from the PRE-rounding fp32 moments in-register,
      while pass B recomputes the applied ``u`` from the STORED
      bf16-rounded moments — so with bf16 moments the update direction
      and the ratio scaling it are ~2⁻⁹-tier inconsistent with each
      other (and with an fp32-moment run). Accepted as designed: the
      ratio is one scalar per tensor and checkpoint-replay consistency
      anchors on pass B's stored moments; runs that must be bitwise-
      comparable against an fp32-moment baseline need
      ``moment_dtype=fp32``.

    Trust-ratio semantics match `fused_lamb` exactly: ratio =
    ||master||/||u|| for decayed tensors (all tensors with
    `use_nvlamb`), identity otherwise; the clip divides grads by
    max(||g||/max_grad_norm, 1).
    """

    def __init__(
        self,
        learning_rate: c.ScalarOrSchedule = 1e-3,
        *,
        bias_correction: bool = True,
        betas: Tuple[float, float] = (0.9, 0.999),
        eps: float = 1e-6,
        weight_decay: float = 0.01,
        grad_averaging: bool = True,
        adam_w_mode: bool = True,
        max_grad_norm: float = 1.0,
        use_nvlamb: bool = False,
        weight_decay_mask: Optional[Any] = None,
        compute_dtype: jnp.dtype = jnp.bfloat16,
        moment_dtype: jnp.dtype = jnp.float32,
        store_model: bool = True,
    ):
        self.learning_rate = learning_rate
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.beta3 = 1.0 - self.beta1 if grad_averaging else 1.0
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.weight_decay_mask = weight_decay_mask
        self.compute_dtype = compute_dtype
        self.moment_dtype = moment_dtype
        # store_model=False keeps state.model EMPTY (None) and
        # `model_params` casts from the masters on demand: the cast is
        # the same 6 B/param of traffic either way, but a scan-carried
        # model copy is double-buffered by XLA — on a 330M BERT that is
        # 2 x 0.66 GB of the 16 GB chip (the b8 OOM margin)
        self.store_model = store_model

    def init(self, params) -> MixedPrecisionState:
        master = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params
        )
        model = (
            jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype), master
            )
            if self.store_model
            else None
        )
        zeros = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, self.moment_dtype), master
        )
        return MixedPrecisionState(
            count=jnp.zeros((), jnp.int32),
            model=model,
            master=master,
            m=zeros,
            v=jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape, self.moment_dtype), master
            ),
        )

    def model_params(self, state: MixedPrecisionState):
        if state.model is not None:
            return state.model
        with jax.named_scope(OPTIMIZER_SCOPE):
            return jax.tree_util.tree_map(
                lambda x: x.astype(self.compute_dtype), state.master
            )

    @jax.named_scope(OPTIMIZER_SCOPE)
    def step_and_probe(
        self,
        state: MixedPrecisionState,
        grads,
        *,
        grad_scale=None,
    ):
        """One fused update; returns ``(new_state, found_inf)``.

        `grads` are w.r.t. `state.model`; `grad_scale` (1/loss_scale)
        fuses the unscale. On overflow every buffer (and the count)
        freezes — the skip-step contract of the reference's
        `_step_supports_amp_scaling` path
        (fused_mixed_precision_lamb.py:140-256)."""
        b1, b2, b3, eps = self.beta1, self.beta2, self.beta3, self.eps
        live_t = (state.count + 1).astype(jnp.float32)
        lr = c.resolve_lr(self.learning_rate, state.count + 1)
        if self.bias_correction:
            bc1 = 1.0 - b1**live_t
            bc2 = 1.0 - b2**live_t
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        gs = jnp.asarray(
            1.0 if grad_scale is None else grad_scale, jnp.float32
        )
        wd_tree = c.wd_tree(
            state.master, self.weight_decay, self.weight_decay_mask
        )

        # global grad norm = the overflow probe (one read of g)
        gsq = sum(
            jnp.sum((g.astype(jnp.float32) * gs) ** 2)
            for g in jax.tree_util.tree_leaves(grads)
        )
        found_inf = ~jnp.isfinite(gsq)
        gnorm = jnp.sqrt(gsq)
        if self.max_grad_norm and self.max_grad_norm > 0:
            clip = jnp.where(
                gnorm > self.max_grad_norm, self.max_grad_norm / gnorm, 1.0
            )
        else:
            clip = jnp.asarray(1.0, jnp.float32)

        ok = ~found_inf
        live = ok.astype(jnp.float32)

        def _u(m2, v2, p, wd):
            u = (m2.astype(jnp.float32) / bc1) / (
                jnp.sqrt(v2.astype(jnp.float32) / bc2) + eps
            )
            if self.adam_w_mode:
                u = u + wd * p
            return u

        # Leaf routing: large lane-aligned leaves run the per-leaf
        # Pallas kernel pair (ops/optim_kernels.lamb_leaf_stage1/2 —
        # norms emitted from the update pass, u never materialized);
        # the rest (biases, LN params: negligible bytes) keep the
        # XLA tree math. The tree formulation leaves the trust-ratio
        # norms as standalone reduce kernels re-reading every buffer —
        # ~16 ms/step on a 330M BERT (round-5 profile).
        from rocm_apex_tpu.ops import optim_kernels as _ok

        def _leaf_view(x):
            """(rows, cols) 2-D view for the kernel path, or None."""
            if x.ndim == 0 or x.size < (1 << 16):
                return None
            cols = x.shape[-1]
            if cols % 128 != 0:
                return None
            rows = int(np.prod(x.shape[:-1]))
            return rows, cols

        def _padded(x, rows, cols, rows_p):
            x2 = x.reshape(rows, cols)
            if rows_p != rows:
                x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
            return x2

        # pass A: moment update + trust-ratio partials, u in-register
        def stage_a(p, g, m, v, wd):
            view = _leaf_view(p)
            if view is not None:
                rows, cols = view
                block = _ok._leaf_block(rows, cols, 6)
                rows_p = -(-rows // block) * block
                m2, v2, psq, usq = _ok.lamb_leaf_stage1(
                    _padded(p, rows, cols, rows_p),
                    _padded(g, rows, cols, rows_p),
                    _padded(m, rows, cols, rows_p),
                    _padded(v, rows, cols, rows_p),
                    [b1, b2, b3, eps, bc1, bc2, gs * clip, live],
                    float(wd), self.adam_w_mode,
                )
                return (
                    m2[:rows].reshape(p.shape).astype(m.dtype),
                    v2[:rows].reshape(p.shape).astype(v.dtype),
                    psq,
                    usq,
                )
            gf = g.astype(jnp.float32) * gs * clip
            pf = p  # master, already fp32
            if not self.adam_w_mode:
                gf = gf + wd * pf
            m2f = b1 * m.astype(jnp.float32) + b3 * gf
            v2f = b2 * v.astype(jnp.float32) + (1.0 - b2) * gf * gf
            u = _u(m2f, v2f, pf, wd)
            return (
                jnp.where(ok, m2f, m.astype(jnp.float32)).astype(m.dtype),
                jnp.where(ok, v2f, v.astype(jnp.float32)).astype(v.dtype),
                jnp.sum(pf * pf),
                jnp.sum(u * u),
            )

        out_a = jax.tree_util.tree_map(
            stage_a, state.master, grads, state.m, state.v, wd_tree
        )
        new_m, new_v, psq, usq = c.unzip_tree(state.master, out_a, 4)

        # per-tensor ratio (scalar math on the reduction results)
        def ratio_of(psq, usq, wd):
            r = jnp.where(
                (psq > 0.0) & (usq > 0.0),
                jnp.sqrt(psq) / jnp.sqrt(usq),
                1.0,
            )
            if not self.use_nvlamb and wd == 0.0:
                r = jnp.asarray(1.0, jnp.float32)
            return r

        ratios = jax.tree_util.tree_map(ratio_of, psq, usq, wd_tree)

        # pass B: recompute u (from the NEW moments) and apply; the
        # compute-dtype model copy rides the same kernel/fusion. NOTE
        # pass B uses the pass-A moment values as STORED (after any
        # moment_dtype rounding) so a reloaded checkpoint reproduces
        # the same params
        def stage_b(p, m2, v2, wd, r):
            view = _leaf_view(p)
            if view is not None:
                rows, cols = view
                block = _ok._leaf_block(rows, cols, 5)
                rows_p = -(-rows // block) * block
                # model_dtype=None with store_model=False: emitting
                # the model copy here would be a dead ~2 B/param write
                p2, c2 = _ok.lamb_leaf_stage2(
                    _padded(p, rows, cols, rows_p),
                    _padded(m2, rows, cols, rows_p),
                    _padded(v2, rows, cols, rows_p),
                    [eps, bc1, bc2, lr * r, live],
                    float(wd), self.adam_w_mode,
                    self.compute_dtype if state.model is not None else None,
                )
                return (
                    p2[:rows].reshape(p.shape),
                    c2[:rows].reshape(p.shape) if c2 is not None else None,
                )
            u = _u(m2, v2, p, wd)
            p2 = p - lr * r * u
            p2 = jnp.where(ok, p2, p)
            return (
                p2,
                p2.astype(self.compute_dtype)
                if state.model is not None
                else None,
            )

        out_b = jax.tree_util.tree_map(
            stage_b, state.master, new_m, new_v, wd_tree, ratios
        )
        master2, model2 = c.unzip_tree(state.master, out_b, 2)

        new_state = MixedPrecisionState(
            count=state.count + ok.astype(jnp.int32),
            model=model2 if state.model is not None else None,
            master=master2,
            m=new_m,
            v=new_v,
        )
        return new_state, found_inf
