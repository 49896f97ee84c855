"""The serving engine's compiled step programs, defined once.

An engine tick runs ONE of two programs: the fused **mixed** step (a
packed prefill chunk plus the whole decode grid) or the **decode**
step alone. The mixed step has two bodies, and the MODEL's class says
which (`StepPrograms.one_pass`): a model that declares
``mixed_in_one_pass`` (`models/hybrid.py::ServedDecoder`) is applied
ONCE to the chunk's rows and the grid's together, and a prompt the chunk
completes emits its first token in this tick and decodes from the next;
every other model (`GPTModel`) is applied to the chunk and then to the
grid, which takes the completed prompts' first tokens in the same tick.
`StepPrograms` holds the single definition of each, built
from what the traced graphs bake in — the model, the `SamplingParams`,
the cache's type and geometry, the tp mesh, the donation flag — and a
feature set the ENGINE derives from its configuration, never a user's
flag: ``spec`` (``spec_k > 0``) and ``lora`` (an adapter pool). A
feature adds OPERANDS to the one body (`_mixed` says which and
why); it never forks the body. `mixed_operands` / `decode_operands`
are the positional signatures; every program returns ``(*fetched, key,
cache[, adapters][, chunk_kv])``, the `FETCHED` leading values being
the tick's one `device_get` and what follows them the state the engine
re-binds on success.

The sampling key is such a state. Every program takes the engine's key
where a host-made subkey would go, begins with ``key, rng =
jax.random.split(key)`` and returns the new ``key``: the stream is the
one a host-side ``key, rng = split(key)`` per call gives, bit for bit,
with no eager dispatch on the host. The programs hold no key: engines
sharing them pass each its own, and a call that fails has advanced
nothing.

Engines sharing a `StepPrograms` (``step_source=``) share its jitted
callables and its trace counters: a fleet traces each program once,
and a retrace anywhere shows in every replica's ``mixed_trace_count``.
"""

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from rocm_apex_tpu.inference.paging import PagedKVCache
from rocm_apex_tpu.inference.sampling import sample

__all__ = ["StepPrograms", "FETCHED"]

#: how many leading outputs of each tick program the host fetches (the
#: sampled tokens and the per-row nonfinite flags); what follows is the
#: state the engine re-binds on success, the sampling key first
FETCHED = {"prefill": 1, "decode": 2, "mixed": 4}


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: (
            tuple(getattr(a, "shape", ())),
            str(getattr(a, "dtype", type(a).__name__)),
        ),
        tree,
    )


class StepPrograms:
    """The jitted programs of one engine configuration.

    ``cache`` and ``adapter_buffers`` are read for their type and
    shapes only; no buffer is kept. ``budget`` (the prefill token
    budget; None on the whole-prompt path) and ``spec_k`` shape nothing
    beyond ``spec``, but engines that differ in them would retrace or
    schedule differently, so `compatible_with` compares them.
    ``mesh`` / ``cache_pspec`` are the tensor-parallel mesh and the
    cache's `PartitionSpec` pytree (tp > 1 only).
    """

    def __init__(
        self,
        model,
        sampling,
        cache,
        *,
        budget: Optional[int],
        spec_k: int = 0,
        adapter_buffers: Any = None,
        mesh=None,
        cache_pspec=None,
        donate_buffers: bool = False,
    ):
        cfg = model.cfg
        spec = self.spec = spec_k > 0
        lora = self.lora = adapter_buffers is not None
        #: the mixed step applies the model once to all of a tick's rows
        #: (the model's class declares it; `_mixed_one_pass` says what
        #: the engine then does differently)
        self.one_pass = bool(getattr(model, "mixed_in_one_pass", False))
        paged = isinstance(cache, PagedKVCache)
        donate_buffers = bool(donate_buffers)
        # What `compatible_with` compares, by the name it reports:
        # everything the traced graphs close over or specialize on.
        self._built_for = {
            # (the closures below keep the model alive: its id is it)
            "model (must be the SAME object)": id(model),
            "sampling": sampling,
            "prefill_token_budget": budget,
            "spec_k": spec_k,
            "paged": paged,
            "donate_buffers": donate_buffers,
            "cache layout": type(cache),
            "cache geometry (num_slots/capacity/page_size/dtype)":
                _shapes(cache),
            "adapter_pool presence": lora,
            "adapter pool geometry (max_resident/max_rank)":
                _shapes(adapter_buffers),
        }
        # Trace counters live in ONE mutable cell, incremented as a
        # trace-time side effect: they count COMPILES, not calls.
        traces = self.traces = {
            "prefill": 0, "decode": 0, "mixed": 0, "commit": 0}

        # tp > 1: the CHUNK apply rides the sequence-parallel +
        # collective-matmul layout ((1, budget/tp, h) rows per chip,
        # TP-edge collectives fused into ppermute rings), the DECODE
        # apply plain tensor parallelism (a width-1 seq axis cannot be
        # sequence-sharded). sequence_parallel changes ZERO parameter
        # shapes, so both variants consume the same params pytree.
        decode_model = chunk_model = model
        if mesh is not None:
            from rocm_apex_tpu.transformer.tensor_parallel import mappings

            chunk_model = type(model)(
                cfg=dataclasses.replace(
                    cfg, sequence_parallel=True, collective_matmul=True
                )
            )
            if cfg.sequence_parallel:
                decode_model = type(model)(
                    cfg=dataclasses.replace(
                        cfg, sequence_parallel=False,
                        collective_matmul=False,
                    )
                )

        def _full_logits(logits):
            # tp > 1: the tied head returns VOCAB-PARALLEL logits
            # (..., vocab/tp); sampling needs the full vocab row. The
            # gather is replicated-in, replicated-out, so the sample
            # is bit-identical on every rank.
            if mesh is None:
                return logits
            return mappings.gather_from_tensor_model_parallel_region(
                logits, cfg.tensor_axis
            )

        def _sample(rng, logits):
            return sample(
                rng,
                logits,
                temperature=sampling.temperature,
                top_k=sampling.top_k,
                top_p=sampling.top_p,
            )

        def _prefill(params, cache, tokens, slot, length, key):
            traces["prefill"] += 1
            key, rng = jax.random.split(key)
            sub = cache.slot_view(slot)
            sub = sub.replace(lengths=jnp.zeros((1,), jnp.int32))
            logits, sub = decode_model.apply(params, tokens, cache=sub)
            # the model advanced by the PADDED width; the live prefix
            # is the real prompt — decode overwrites the pad positions
            # one by one and never attends past `lengths`
            sub = sub.replace(
                lengths=jnp.reshape(length, (1,)).astype(jnp.int32)
            )
            cache = cache.write_back(slot, sub)
            last = jax.lax.dynamic_index_in_dim(
                logits[0], length - 1, 0, keepdims=False
            )
            first_tok = _sample(rng, _full_logits(last)[None, :])[0]
            return first_tok, key, cache

        dev_capacity = cache.capacity

        def _start_tick(cache):
            # the paged cache's counters of what its layers do in a tick
            # start from zero; the sums ride the tick's one fetch. A
            # cache that keeps none comes back as it is, so this runs
            # under every feature set and adds no equation to any.
            return cache.start_tick() if paged else cache

        def _with_ids(adapters, ids):
            # ``active`` (any id != 0, computed in-trace) arms the
            # `apply_lora` skip branch: a pure-base tick runs zero
            # adapter FLOPs in this same program
            if not lora:
                return None
            return dict(adapters, ids=ids, active=jnp.any(ids != 0))

        def _decode_body(params, cache, tokens, active, poison, rng,
                         adapters=None):
            # `poison` is a per-slot fp32 addend on the logits — zeros
            # on the fault-free path (x + 0.0 leaves the greedy argmax
            # and the sampling distribution untouched), NaN/Inf when
            # the chaos harness poisons one slot. The nonfinite flag is
            # computed IN-GRAPH and rides the tick's one fetch: fault
            # isolation costs no extra device sync and no extra trace,
            # and catches a genuine model blow-up for free.
            lengths0 = cache.lengths
            if paged:
                # dead rows write at the device capacity sentinel: the
                # paged scatter DROPS the write (a contiguous cache
                # tolerates dead-row junk because the next prefill
                # overwrites it, but a paged junk write could land in
                # a live — even SHARED — page, and under int8 would
                # inflate that page's running scale)
                cache = cache.replace(
                    lengths=jnp.where(
                        active, lengths0,
                        jnp.full_like(lengths0, dev_capacity),
                    )
                )
            logits, new_cache = decode_model.apply(
                params, tokens[:, None], cache=cache, adapters=adapters
            )
            # pin inactive slots' lengths (their dead-row writes drop
            # (paged) or land in junk the next prefill overwrites
            # (contiguous), but unbounded drift would saturate the
            # clamp)
            new_cache = new_cache.replace(
                lengths=jnp.where(
                    active, new_cache.lengths, lengths0
                )
            )
            last = _full_logits(logits[:, -1, :]) + poison[:, None]
            bad = jnp.any(~jnp.isfinite(last), axis=-1)
            tok = _sample(rng, last)
            return jnp.where(active, tok, 0), bad, new_cache

        # The positional signatures, in one place (a tuple times a bool:
        # there or not). An engine with neither feature passes 6 and 13
        # operands, its raw key state last.
        self.decode_operands = (
            ("params", "cache") + ("adapters",) * lora
            + ("tokens", "active") + ("dec_adp",) * lora
            + ("poison", "key")
        )
        self.mixed_operands = (
            ("params", "cache") + ("adapters",) * lora
            + ("chunk_tokens", "chunk_slots", "chunk_pos")
            + ("commit_slots",) * spec + ("chunk_adp",) * lora
            + ("lengths_before", "lengths_after", "completion_idx",
               "dec_tokens", "dec_active")
            + ("dec_adp",) * lora
            + ("chunk_poison", "dec_poison", "key")
        )

        def _decode(params, cache, tokens, active, poison, key,
                    adapters=None, dec_adp=None):
            traces["decode"] += 1
            key, rng = jax.random.split(key)
            tok, bad, cache = _decode_body(
                params, _start_tick(cache), tokens, active, poison, rng,
                adapters=_with_ids(adapters, dec_adp),
            )
            return (tok, bad, key, cache) + (adapters,) * lora

        def _mixed(
            params, cache, chunk_tokens, chunk_slots, chunk_pos,
            lengths_before, lengths_after, completion_idx,
            dec_tokens, dec_active, chunk_poison, dec_poison, key,
            commit_slots=None, adapters=None, chunk_adp=None,
            dec_adp=None,
        ):
            """ONE compiled program per tick: packed prefill chunk +
            the whole decode grid. The host is the source of truth for
            per-slot lengths (a freed slot's stale device length must
            never bound a successor's reads), so the cursor vectors
            ride in as arguments. ``completion_idx[slot]`` is the chunk
            index of the slot's LAST prompt token when its prefill
            completes this tick (else -1): its sampled first token is
            fed STRAIGHT into the decode grid, so a completing request
            gets its second token in the same tick — exactly the
            whole-prompt path's admit-tick cadence, with no padded
            prefill.

            With ``spec`` the chunk may carry, per decoding slot, that
            slot's last generated token plus up to k drafted
            continuations. Those rows score against the slot's
            committed prefix in the SAME fused trace (they are just
            budget tokens — no per-k shapes), but their K/V must NOT
            commit in-trace: a rejected draft can never be unwound
            from a shared page or an int8 scale that only grows, and
            the contiguous decode grid's dead-row write would clobber
            an eagerly-committed row. So every speculative row carries
            the pad sentinel in ``commit_slots`` (the scatter drops
            it), the model hands back the packed per-layer chunk K/V,
            and the host commits exactly the accepted prefix afterwards
            (`commit`). ``mixed_trace_count`` stays 1 at any k.

            With ``lora``, ``chunk_adp`` (budget,) maps each packed
            prompt token to its pool buffer slot and ``dec_adp`` (S,)
            each decode row."""
            traces["mixed"] += 1
            key, rng = jax.random.split(key)
            rng_c, rng_d = jax.random.split(rng)
            cache = _start_tick(cache).replace(lengths=lengths_before)
            chunk_adapters = _with_ids(adapters, chunk_adp)
            logits_c, cache, *chunk_kv = chunk_model.apply(
                params,
                chunk_tokens[None, :],
                cache=cache,
                chunk=(chunk_slots, chunk_pos) + (commit_slots,) * spec,
                adapters=chunk_adapters,
            )
            logits_c = _full_logits(logits_c)
            # sample EVERY chunk position (fixed shape); the host keeps
            # only the positions that completed a prompt this tick. For
            # a draft row the sample IS the verifier's token — greedy
            # accepts on equality, and under temperature the
            # sample-vs-draft equality test is exact rejection sampling
            # for a point-mass drafter.
            # `chunk_poison` follows the decode-grid poison contract:
            # zeros normally, NaN/Inf on a quarantine-test row — the
            # per-row nonfinite flags share the tick's one fetch.
            logits_p = logits_c[0] + chunk_poison[:, None]
            chunk_bad = jnp.any(~jnp.isfinite(logits_p), axis=-1)
            chunk_tok = _sample(rng_c, logits_p)
            # commit the chunk: cursors advance by what was packed
            cache = cache.replace(lengths=lengths_after)
            budget = chunk_tokens.shape[0]
            has_comp = completion_idx >= 0
            first_tok = chunk_tok[
                jnp.clip(completion_idx, 0, budget - 1)
            ]
            dec_tokens = jnp.where(has_comp, first_tok, dec_tokens)
            dec_active = dec_active | has_comp
            dec_tok, dec_bad, cache = _decode_body(
                params, cache, dec_tokens, dec_active, dec_poison, rng_d,
                adapters=_with_ids(adapters, dec_adp),
            )
            return (
                (chunk_tok, dec_tok, chunk_bad, dec_bad, key, cache)
                + (adapters,) * lora + tuple(chunk_kv)
            )

        def _mixed_one_pass(
            params, cache, chunk_tokens, chunk_slots, chunk_pos,
            lengths_before, lengths_after, completion_idx,
            dec_tokens, dec_active, chunk_poison, dec_poison, key,
            **features,
        ):
            """`_mixed`'s operands and outputs, ONE apply of the model
            over the chunk's rows and the grid's together. The grid
            takes no token from the chunk, so a prompt the chunk
            completes emits its first token here and decodes from the
            next tick, and no slot has rows in both parts. The head and
            the ONE sampler call see ``2 x slots`` rows: per slot the
            chunk row ``completion_idx`` names (some row where it names
            none: the host reads a slot's first token only where it
            packed a completion) and the grid's. The chunk's two fetched
            values are therefore per SLOT (first token, its nonfinite
            flag), and ``chunk_poison`` is read at the rows gathered.
            A feature's operands are refused: one apply verifies no
            drafts and rides no adapter ids."""
            if features:
                raise ValueError(
                    f"{type(model).__name__}'s mixed tick is one apply "
                    f"of the model: it takes no {sorted(features)}")
            traces["mixed"] += 1
            key, rng = jax.random.split(key)
            slots, budget = dec_tokens.shape[0], chunk_tokens.shape[0]
            logits, cache = model.apply(
                params, chunk_tokens[None, :],
                cache=_start_tick(cache).replace(lengths=lengths_before),
                chunk=(chunk_slots, chunk_pos),
                grid=(
                    dec_tokens,
                    # dead rows write at the capacity sentinel, as in
                    # `_decode_body`
                    jnp.where(dec_active, lengths_after, dev_capacity),
                    completion_idx),
            )
            # the chunk commits (cursors advance by what was packed) and
            # the grid's live rows by their one token
            cache = cache.replace(lengths=jnp.where(
                dec_active, jnp.minimum(lengths_after + 1, dev_capacity),
                lengths_after))
            has_comp = completion_idx >= 0
            poison = jnp.concatenate([
                chunk_poison[jnp.clip(completion_idx, 0, budget - 1)],
                dec_poison])
            last = logits + poison[:, None]
            bad = jnp.any(~jnp.isfinite(last), axis=-1)
            tok = _sample(rng, last)
            return (
                jnp.where(has_comp, tok[:slots], 0),
                jnp.where(dec_active, tok[slots:], 0),
                bad[:slots], bad[slots:], key, cache,
            )

        def _positional(name, step, names):
            def program(*operands):
                return step(**dict(zip(names, operands, strict=True)))
            # the compiled program is named after the step, whichever
            # body it has (`jit__mixed`, `jit__decode`): trace readers
            # group executions by it
            program.__name__ = program.__qualname__ = name
            return program

        _decode = _positional("_decode", _decode, self.decode_operands)
        _mixed = _positional(
            "_mixed", _mixed_one_pass if self.one_pass else _mixed,
            self.mixed_operands)

        n_layers = len(cache.k)

        def _commit(cache, chunk_kv, slots, positions):
            """Post-verification commit: write the accepted rows'
            packed chunk K/V into the cache (`write_at` drops the pad
            sentinel rows). Fixed (budget,) shapes — ONE compiled
            commit program per engine run."""
            traces["commit"] += 1
            ck, cv = chunk_kv
            for i in range(n_layers):
                cache = cache.write_at(i, slots, positions, ck[i], cv[i])
            return cache

        #: the programs as plain functions, for an audit or a lowering
        #: for a described device (no jit, no shard_map)
        self.prefill_fn = _prefill
        self.decode_fn = _decode
        self.mixed_fn = _mixed

        if mesh is not None:
            # One shard_map per step program, jitted around the whole
            # region: replicated host inputs (token buffers, masks,
            # cursors, rng) ride in with P(); the cache rides its
            # head-sharded spec; params are the repo's fake-replicated
            # idiom (global shape == local shape, per-rank contents),
            # so P() hands each rank its own shard. check_vma=False:
            # the sampled tokens are replicated by construction (the
            # vocab gather), not by anything the rep checker can see.
            from jax import shard_map

            P = jax.sharding.PartitionSpec
            rep = P()
            kv_spec = tuple(
                P(None, cfg.tensor_axis, None) for _ in range(n_layers)
            )
            kv_specs = (kv_spec, kv_spec)

            def _shmap(f, names, name):
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(rep, cache_pspec) + (rep,) * (len(names) - 2),
                    out_specs=(
                        (rep,) * (FETCHED[name] + 1) + (cache_pspec,)
                        + (rep,) * lora
                        + (kv_specs,) * (spec and name == "mixed")
                    ),
                    check_vma=False,
                )

            _decode = _shmap(_decode, self.decode_operands, "decode")
            _mixed = _shmap(_mixed, self.mixed_operands, "mixed")
            _commit = shard_map(
                _commit, mesh=mesh,
                in_specs=(cache_pspec, kv_specs, rep, rep),
                out_specs=cache_pspec,
                check_vma=False,
            )

        # The cache is DONATED (the step updates it in place; the
        # engine says when), and so are the adapter buffers beside it:
        # returned pass-through, the output aliases the input allocation.
        def _jit(f, *state):
            return jax.jit(
                f, donate_argnums=state if donate_buffers else ())

        self.prefill = _jit(_prefill, 1)
        self.decode = _jit(_decode, 1, *(2,) * lora)
        self.mixed = _jit(_mixed, 1, *(2,) * lora)
        self.commit = _jit(_commit, 0)
        #: copy-on-write fork of one page (paged caches only)
        self.fork = jax.jit(
            lambda cache, src, dst: cache.fork_page(src, dst)
        ) if paged else None

    def compatible_with(self, other: "StepPrograms") -> None:
        """Refuse (ValueError) unless an engine that would have built
        ``other`` can run THESE programs instead: a mismatch would
        silently retrace per call or, worse, run the wrong geometry."""
        mismatches = [
            name for name, mine in self._built_for.items()
            if other._built_for[name] != mine
        ]
        if mismatches:
            raise ValueError(
                "step_source engine is incompatible; differs in: "
                + ", ".join(mismatches)
            )
