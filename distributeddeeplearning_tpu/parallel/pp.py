"""Pipeline parallelism — GPipe schedule over the ``pp`` mesh axis.

TPU-native replacement for rank-per-stage pipeline frameworks: the reference
family of trainers places each stage in its own process and moves activations
with NCCL send/recv; here the whole pipeline is ONE SPMD program. Stage
parameters are stacked on a leading ``stage`` axis sharded over ``pp``; a
``shard_map`` body runs the classic GPipe time loop as a ``lax.scan`` where
every tick computes one stage-application per device and hands activations to
the next stage with a single-neighbor ``lax.ppermute`` (an ICI hop). XLA's
latency-hiding scheduler overlaps the permute with the next tick's compute.

Schedule (GPipe, SURVEY.md §7 "hard parts" #1 — 1F1B is future work):

- ``M`` microbatches, ``S`` stages, ``T = M + S - 1`` ticks;
- at tick ``t`` stage ``s`` processes microbatch ``t - s`` (garbage compute
  in the ``(S-1)/T`` bubble fraction, as in any GPipe);
- the last stage's outputs are collected per-microbatch and broadcast to all
  ``pp`` ranks with a masked ``psum`` so downstream (loss) code is ordinary
  SPMD.

Autodiff: ``scan`` + ``ppermute`` are differentiable; the backward pass is
automatically the reverse pipeline (cotangents ppermute stage ``s+1 -> s``),
i.e. GPipe's synchronous backward schedule falls out of ``jax.grad``.

Schedules:

- ``gpipe`` — forward tick loop differentiated by ``jax.grad``: the scan's
  autodiff stores every per-tick intermediate of every stage body (attention
  scores, MLP hiddens, ...) for the whole M+S-1 ticks. Simple, memory-heavy.
- ``1f1b`` (:func:`one_f_one_b`) — same forward schedule, but a
  ``jax.custom_vjp`` whose residuals are ONLY each stage's per-microbatch
  *inputs*; the backward runs the 1F1B reverse pipeline (stage ``s`` does
  the backward of microbatch ``m`` as soon as stage ``s+1`` hands it the
  cotangent, recomputing the stage forward from the stashed input). This is
  1F1B-with-remat's backward ordering and memory profile under plain
  ``jax.grad``, and it composes with PP×TP. Peak-memory win vs gpipe is
  asserted by ``tests/test_pipeline.py`` via compiled memory analysis.
- ``1f1b_interleaved`` (:func:`interleaved_1f1b`) — TRUE 1F1B: the engine
  owns loss AND differentiation, every tick runs one forward and one
  backward unit, and the activation stash is a circular buffer of depth
  ``2S`` (pipeline depth) instead of ``M`` (microbatch count). The Trainer
  dispatches to ``model.pipeline_value_and_grad`` for this schedule.

Composability: batch axes (``dp``/``fsdp``) pass straight through the
``shard_map`` specs, so PP x DP works out of the box. PP x TP runs tensor
parallelism *inside* each stage (tp-sliced stage params + boundary psums);
see ``models/pipeline.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..mesh import BATCH_AXES


def check_pipeline_shapes(
    local_batch: int, num_microbatches: int, num_layers: int, num_stages: int
) -> None:
    if num_layers % num_stages:
        raise ValueError(
            f"pipeline: num_layers={num_layers} not divisible by "
            f"num_stages={num_stages}"
        )
    if local_batch % num_microbatches:
        raise ValueError(
            f"pipeline: per-device batch {local_batch} not divisible by "
            f"num_microbatches={num_microbatches}"
        )


def _microbatch(t, num_microbatches):
    """Reshape a [local_batch, ...] array to [M, local_batch/M, ...]."""
    return t.reshape(
        (num_microbatches, t.shape[0] // num_microbatches) + t.shape[1:]
    )


def _stage_apply(stage_fn, params, x, extra_mb, m_idx):
    """Run one stage on one microbatch's activations. ``extra_mb`` is the
    microbatched per-sample side input (key-padding mask) replicated over
    ``pp`` — every device holds ALL microbatches' rows, so the stage just
    gathers slot ``m_idx`` (the microbatch it is processing this tick)
    locally; unlike activations, the mask never rides the ppermute ring."""
    if extra_mb is None:
        return stage_fn(params, x)
    return stage_fn(params, x, jax.tree.map(lambda e: e[m_idx], extra_mb))


def _gpipe_local(
    stage_fn, params, x, *, axis_name: str, num_microbatches: int, extra=None
):
    """Per-device GPipe time loop (runs inside shard_map).

    params: this device's stage slice, leading dim 1 (squeezed here).
    x: [local_batch, ...] — the full local batch (replicated over ``pp``).
    extra: optional pytree of [local_batch, ...] per-sample side inputs
    (key-padding mask) handed to ``stage_fn(params, x, extra_mb)``.
    Returns the last stage's outputs for every microbatch, [local_batch, ...].
    """
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = num_microbatches
    params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
    mb = _microbatch(x, M)
    emb = None if extra is None else jax.tree.map(
        lambda t: _microbatch(t, M), extra
    )

    # Activation shape/dtype are stage-invariant (residual blocks), so one
    # rotating buffer + one output accumulator suffice. x is replicated over
    # pp but the loop makes them stage-varying — pcast the initial carries so
    # the scan carry type is stable.
    buf0 = jax.lax.pcast(jnp.zeros_like(mb[0]), (axis_name,), to="varying")
    out0 = jax.lax.pcast(jnp.zeros_like(mb), (axis_name,), to="varying")
    # Stage s -> s+1 handoff; stage 0 receives nothing (gets zeros, unused).
    perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        state_in, outputs = carry
        x_in = jnp.where(stage == 0, mb[jnp.minimum(t, M - 1)], state_in)
        # Microbatch this stage processes at tick t (clipped in the bubble,
        # where the compute is garbage anyway).
        m_idx = jnp.clip(t - stage, 0, M - 1)
        y = _stage_apply(stage_fn, params, x_in, emb, m_idx)
        out_t = t - (S - 1)  # which microbatch the LAST stage just finished
        # Single-slot masked write keeps the scan carry in place.
        out_i = jnp.clip(out_t, 0, M - 1)
        out_ok = (stage == S - 1) & (out_t >= 0)
        outputs = outputs.at[out_i].set(
            jnp.where(out_ok, y, outputs[out_i])
        )
        state_next = jax.lax.ppermute(y, axis_name, perm)
        return (state_next, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (buf0, out0), jnp.arange(M + S - 1))
    # Only the last stage holds real outputs; masked psum = broadcast to the
    # whole pp ring so the loss is computed as ordinary SPMD code.
    outputs = jax.lax.psum(
        jnp.where(stage == S - 1, outputs, jnp.zeros_like(outputs)), axis_name
    )
    return outputs.reshape(x.shape)


def gpipe_bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    """Fraction of schedule ticks a stage spends idle: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def _batch_sharded_call(local, mesh, param_specs, x_spec, stacked_params,
                        x, extra):
    """The one shard_map construction every pipeline engine shares.

    ``local(params, x, extra)`` always takes three operands: ``extra=None``
    is an empty pytree, so ``tree.map`` produces an empty spec subtree for
    it and the mask-less and masked arities go through the SAME call —
    review r5: the previous per-arity shard_map arms (four near-identical
    blocks across gpipe/one_f_one_b) could drift apart silently."""
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            param_specs, x_spec, jax.tree.map(lambda _: x_spec, extra)
        ),
        out_specs=x_spec,
    )
    return fn(stacked_params, x, extra)


def _pp_local_fwd(
    stage_fn, params, x, *, axis_name, num_microbatches, extra=None
):
    """GPipe forward tick loop that ALSO stashes each stage's per-microbatch
    input (the 1F1B backward residuals). Returns (outputs, stash)."""
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = num_microbatches
    params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
    mb = _microbatch(x, M)
    emb = None if extra is None else jax.tree.map(
        lambda t: _microbatch(t, M), extra
    )

    buf0 = jax.lax.pcast(jnp.zeros_like(mb[0]), (axis_name,), to="varying")
    out0 = jax.lax.pcast(jnp.zeros_like(mb), (axis_name,), to="varying")
    stash0 = jax.lax.pcast(jnp.zeros_like(mb), (axis_name,), to="varying")
    perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        state_in, outputs, stash = carry
        m = t - stage  # microbatch this stage processes at tick t
        valid = (m >= 0) & (m < M)
        m_idx = jnp.clip(m, 0, M - 1)
        x_in = jnp.where(stage == 0, mb[jnp.minimum(t, M - 1)], state_in)
        # Single-slot masked writes (not whole-buffer selects) keep the scan
        # carry updating in place.
        stash = stash.at[m_idx].set(jnp.where(valid, x_in, stash[m_idx]))
        y = _stage_apply(stage_fn, params, x_in, emb, m_idx)
        out_i = jnp.clip(t - (S - 1), 0, M - 1)
        out_ok = (stage == S - 1) & (t - (S - 1) >= 0)
        outputs = outputs.at[out_i].set(
            jnp.where(out_ok, y, outputs[out_i])
        )
        state_next = jax.lax.ppermute(y, axis_name, perm)
        return (state_next, outputs, stash), None

    (_, outputs, stash), _ = jax.lax.scan(
        tick, (buf0, out0, stash0), jnp.arange(M + S - 1)
    )
    # NOTE: outputs are returned pp-varying (real data only on the last
    # stage, zeros elsewhere); the caller psums OUTSIDE the custom_vjp so
    # the vma checker types the broadcast and its transpose delivers the
    # full output cotangent to every device.
    return outputs.reshape(x.shape), stash


def _pp_local_bwd(
    stage_fn, params, stash, g, *, axis_name, num_microbatches, extra=None
):
    """Reverse (1F1B-ordered) pipeline: stage ``s`` runs the backward of
    microbatch ``m`` at tick ``(S-1-s) + (M-1-m)``, recomputing the stage
    forward from the stashed input and handing the input-cotangent one hop
    backwards (``s+1 -> s``). Param grads accumulate locally per stage.
    Returns (dparams [1, ...] leaves, dx)."""
    S = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = num_microbatches
    params_sq = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
    gmb = _microbatch(g, M)
    emb = None if extra is None else jax.tree.map(
        lambda t: _microbatch(t, M), extra
    )

    # params/stash/g are all already pp-varying here (params via in_specs,
    # stash as a fwd residual, g via the psum transpose), so plain zeros_like
    # carries the right vma typing.
    dparams0 = jax.tree.map(lambda a: jnp.zeros_like(a), params_sq)
    dx0 = jnp.zeros_like(gmb)
    recv0 = jnp.zeros_like(gmb[0])
    perm_back = [(i + 1, i) for i in range(S - 1)]

    def tick(carry, u):
        dparams, dx_out, recv = carry
        k = u - (S - 1 - stage)  # position in this stage's backward sequence
        m = (M - 1) - k  # microbatch whose cotangent is handled now
        valid = (k >= 0) & (k < M)
        m_idx = jnp.clip(m, 0, M - 1)
        g_in = jnp.where(stage == S - 1, gmb[m_idx], recv)
        x_in = stash[m_idx]
        # Recompute the stage forward (1F1B-with-remat): the vjp sees only
        # one microbatch's activations at a time. The mask (if any) is a
        # non-differentiated side input — closed over, not a vjp operand.
        _, vjp_fn = jax.vjp(
            lambda p, xx: _stage_apply(stage_fn, p, xx, emb, m_idx),
            params_sq, x_in,
        )
        dp, dxi = vjp_fn(g_in)
        dparams = jax.tree.map(
            lambda a, b: a + jnp.where(valid, b, jnp.zeros_like(b)),
            dparams, dp,
        )
        dx_out = dx_out.at[m_idx].set(
            jnp.where((stage == 0) & valid, dxi, dx_out[m_idx])
        )
        send = jnp.where(valid, dxi, jnp.zeros_like(dxi))
        recv = jax.lax.ppermute(send, axis_name, perm_back)
        return (dparams, dx_out, recv), None

    (dparams, dx_out, _), _ = jax.lax.scan(
        tick, (dparams0, dx0, recv0), jnp.arange(M + S - 1)
    )
    dparams = jax.tree.map(lambda a: jnp.expand_dims(a, 0), dparams)
    # x entered replicated over pp, so its cotangent must leave the body
    # pp-invariant: only stage 0 holds real input-cotangents, the psum is
    # the broadcast (and satisfies the vma transpose typing).
    dx_out = jax.lax.psum(dx_out, axis_name)
    return dparams, dx_out.reshape(g.shape)


def one_f_one_b(
    stage_fn,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    param_specs=None,
    extra=None,
):
    """Drop-in for :func:`gpipe` with the 1F1B backward schedule.

    Same stacked-params interface and forward semantics; the difference is
    entirely in ``jax.grad``: residuals are each stage's per-microbatch
    inputs only (one activation tensor per microbatch instead of every
    intermediate of every tick), and the backward runs the reverse pipeline
    with per-microbatch recompute.

    ``param_specs``: optional per-leaf PartitionSpecs for the stacked params
    (default ``P('pp')`` on the leading stage dim). PP×TP passes specs that
    additionally shard heads/mlp dims over ``tp``; the stage_fn is then
    responsible for the tp boundary psums (see ``models/pipeline.py``).

    ``extra``: optional pytree of per-sample side inputs ([local_batch, ...],
    e.g. a key-padding mask) passed through to ``stage_fn(params, x, extra)``
    per microbatch; not differentiated (its cotangent is zero).
    """
    S = mesh.shape[axis_name]
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    x_spec = P(BATCH_AXES)
    if S == 1:
        return sequential(stage_fn, stacked_params, x, extra=extra)

    # ``e`` rides through the custom_vjp as an operand pytree (None when
    # unused — an empty pytree, so both arities share one code path) with a
    # zero cotangent: masks are data, not parameters.
    @jax.custom_vjp
    def core(params, x, e):
        out, _ = _pp_local_fwd(
            stage_fn, params, x,
            axis_name=axis_name, num_microbatches=num_microbatches, extra=e,
        )
        return out

    def core_fwd(params, x, e):
        out, stash = _pp_local_fwd(
            stage_fn, params, x,
            axis_name=axis_name, num_microbatches=num_microbatches, extra=e,
        )
        return out, (params, stash, e)

    def core_bwd(res, g):
        params, stash, e = res
        dparams, dx = _pp_local_bwd(
            stage_fn, params, stash, g,
            axis_name=axis_name, num_microbatches=num_microbatches, extra=e,
        )
        return dparams, dx, jax.tree.map(jnp.zeros_like, e)

    core.defvjp(core_fwd, core_bwd)

    def local(params, x, e=None):
        # core's output is pp-varying (last stage real, zeros elsewhere);
        # psum here — outside the custom_vjp — is the broadcast, and its
        # transpose hands the full output cotangent to every stage.
        return jax.lax.psum(core(params, x, e), axis_name)

    return _batch_sharded_call(
        local, mesh, param_specs, x_spec, stacked_params, x, extra
    )


def interleaved_1f1b(
    embed_fn,
    stage_fn,
    head_fn,
    stacked_params,
    shared_params,
    batch,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    param_specs=None,
):
    """TRUE interleaved 1F1B: loss inside the schedule, grads out.

    Unlike :func:`one_f_one_b` (a custom_vjp whose backward replays the
    reverse pipeline after ``jax.grad`` calls it), this engine owns the whole
    training step's differentiation: at every tick each stage runs one
    forward unit AND one backward unit, the last stage computes the
    microbatch loss + output cotangent the same tick its forward finishes,
    and cotangents chase activations down the ring with a lag of one tick
    per stage. Consequences:

    - activation stash is a CIRCULAR buffer of depth ``2S`` (pipeline
      depth), not ``M`` (microbatch count) — the memory bound that defines
      1F1B; an input's lifetime is at most ``2(S-1)+1`` ticks, so slots
      recycle safely for any ``M``;
    - total ticks ``M + 2(S-1)``: the steady state really is
      one-forward-one-backward per tick.

    Schedule (stage ``s``, microbatch ``m``):
      forward at tick ``s + m``; last stage's loss/cotangent at
      ``(S-1) + m`` (same tick as its forward); backward of stage ``s`` at
      ``(S-1) + m + (S-1-s)``.

    Contracts:
      ``embed_fn(shared, batch_mb) -> x_mb`` (per microbatch, differentiable
      in ``shared``); ``stage_fn(stage_params, x) -> x``;
      ``head_fn(shared, y_mb, batch_mb) -> loss_mb`` — the MICROBATCH's
      scalar loss; the engine reports (and differentiates) the mean over
      microbatches. ``batch`` is a pytree of ``[local_batch, ...]`` arrays.
      Embed/head compute runs under ``lax.cond`` so only the stages that own
      it pay for it; ``shared`` params are replicated inside the body
      (boundary all-gather per step — the storage stays sharded, e.g. the
      pp-sharded embedding table).

    Returns ``(loss, (dstacked, dshared))`` — plug straight into the
    optimizer; not differentiated from outside.
    """
    S = mesh.shape[axis_name]
    M = num_microbatches
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    x_spec = P(BATCH_AXES)
    batch_specs = jax.tree.map(lambda _: x_spec, batch)
    shared_specs = jax.tree.map(lambda _: P(), shared_params)

    if S == 1:
        def loss_fn(stacked, shared):
            mb = jax.tree.map(
                lambda t: t.reshape((M, t.shape[0] // M) + t.shape[1:]), batch
            )
            def body(acc, m):
                bm = jax.tree.map(lambda t: t[m], mb)
                y = sequential(stage_fn, stacked, embed_fn(shared, bm))
                return acc + head_fn(shared, y, bm) / M, None
            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                  jnp.arange(M))
            return acc
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            stacked_params, shared_params
        )
        return loss, grads

    def local(stacked, shared, batch):
        stage = jax.lax.axis_index(axis_name)
        mb = jax.tree.map(
            lambda t: t.reshape((M, t.shape[0] // M) + t.shape[1:]), batch
        )
        take = lambda m: jax.tree.map(lambda t: t[m], mb)  # noqa: E731
        params_sq = jax.tree.map(lambda p: jnp.squeeze(p, 0), stacked)

        batch_axes = tuple(a for a in BATCH_AXES if a in mesh.shape)
        # Shapes: probe one microbatch's activation abstractly.
        x0_shape = jax.eval_shape(lambda: embed_fn(shared, take(0)))
        zeros_x = jnp.zeros(x0_shape.shape, x0_shape.dtype)

        depth = 2 * S  # > max input lifetime 2(S-1)+1, so slots never clash
        carry0 = dict(
            recv_fwd=zeros_x,
            recv_bwd=jnp.zeros_like(zeros_x),
            stash=jnp.zeros((depth,) + zeros_x.shape, zeros_x.dtype),
            loss=jnp.zeros((), jnp.float32),
            dstacked=jax.tree.map(jnp.zeros_like, params_sq),
            dshared=jax.tree.map(jnp.zeros_like, shared),
        )
        perm_fwd = [(i, i + 1) for i in range(S - 1)]
        perm_bwd = [(i + 1, i) for i in range(S - 1)]

        def tick(c, t):
            # ---- forward unit: stage s, microbatch mf = t - s ------------
            mf = t - stage
            valid_f = (mf >= 0) & (mf < M)
            mf_i = jnp.clip(mf, 0, M - 1)
            bm_f = take(mf_i)
            x_embed = jax.lax.cond(
                stage == 0,
                lambda: embed_fn(shared, bm_f),
                lambda: zeros_x,
            )
            x_in = jnp.where(stage == 0, x_embed, c["recv_fwd"])
            y = stage_fn(params_sq, x_in)
            # Single-slot masked write (NOT a whole-buffer select): keeps
            # the scan carry's in-place dynamic-update-slice. Equivalent:
            # an invalid tick's clipped index rewrites its slot with the
            # slot's own value.
            slot = mf_i % depth
            stash = c["stash"].at[slot].set(
                jnp.where(valid_f, x_in, c["stash"][slot])
            )

            # Last stage: loss + output cotangent for mf, THIS tick.
            def head_vjp():
                loss_m, vjp = jax.vjp(
                    lambda sh, yy: head_fn(sh, yy, bm_f), shared, y
                )
                dsh, dy = vjp(jnp.ones((), loss_m.dtype) / M)
                return loss_m, dsh, dy

            def head_zero():
                return (
                    jnp.zeros((), jnp.float32),
                    jax.tree.map(jnp.zeros_like, shared),
                    jnp.zeros_like(y),
                )

            loss_m, dsh_head, g_y = jax.lax.cond(
                stage == S - 1, head_vjp, head_zero
            )
            loss = c["loss"] + jnp.where(valid_f, loss_m, 0.0)
            dshared = jax.tree.map(
                lambda a, b: a + jnp.where(valid_f, b, jnp.zeros_like(b)),
                c["dshared"], dsh_head,
            )

            # ---- backward unit: stage s, microbatch mb = t-2(S-1)+s ------
            mb_idx = t - 2 * (S - 1) + stage
            valid_b = (mb_idx >= 0) & (mb_idx < M)
            mb_i = jnp.clip(mb_idx, 0, M - 1)
            g_in = jnp.where(stage == S - 1, g_y, c["recv_bwd"])
            x_b = stash[mb_i % depth]
            # PP×TP needs no boundary fix-ups here: the stage body brackets
            # its tensor-parallel regions with comms.identity_fwd_psum_bwd /
            # psum_identity_bwd (Megatron f/g), so this vjp already yields
            # full input-cotangents and per-rank-correct param grads
            # (owned slices for tp-sharded leaves, identical full grads for
            # replicated ones).
            _, svjp = jax.vjp(stage_fn, params_sq, x_b)
            dp, dx = svjp(g_in)
            dstacked = jax.tree.map(
                lambda a, b: a + jnp.where(valid_b, b, jnp.zeros_like(b)),
                c["dstacked"], dp,
            )

            # Stage 0: cotangent leaves the pipeline into the embed params.
            bm_b = take(mb_i)

            def embed_vjp():
                _, evjp = jax.vjp(lambda sh: embed_fn(sh, bm_b), shared)
                (dsh,) = evjp(dx)
                return dsh

            dsh_embed = jax.lax.cond(
                stage == 0,
                embed_vjp,
                lambda: jax.tree.map(jnp.zeros_like, shared),
            )
            dshared = jax.tree.map(
                lambda a, b: a + jnp.where(valid_b, b, jnp.zeros_like(b)),
                dshared, dsh_embed,
            )

            recv_fwd = jax.lax.ppermute(y, axis_name, perm_fwd)
            recv_bwd = jax.lax.ppermute(
                jnp.where(valid_b, dx, jnp.zeros_like(dx)),
                axis_name, perm_bwd,
            )
            return dict(
                recv_fwd=recv_fwd, recv_bwd=recv_bwd, stash=stash,
                loss=loss, dstacked=dstacked, dshared=dshared,
            ), None

        c, _ = jax.lax.scan(tick, carry0, jnp.arange(M + 2 * (S - 1)))
        # Reductions. Over pp: loss lives on the last stage, embed-grads on
        # stage 0, head-grads on the last stage — psum = combine + broadcast
        # (everything else is 0). Over the batch axes: each dp/fsdp replica
        # saw only its batch shard, so the global mean-loss gradient is the
        # replica-mean — this psum is THE data-parallel gradient sync (the
        # reference's NCCL all-reduce), emitted here explicitly because the
        # engine owns differentiation instead of jax.grad.
        nrep = 1
        for a in batch_axes:
            nrep *= mesh.shape[a]
        loss = jax.lax.psum(c["loss"], (axis_name,) + batch_axes) / (M * nrep)
        dshared = jax.tree.map(
            lambda g: jax.lax.psum(g, (axis_name,) + batch_axes) / nrep,
            c["dshared"],
        )

        dstacked = jax.tree.map(
            lambda g: jnp.expand_dims(
                jax.lax.psum(g, batch_axes) / nrep, 0
            ),
            c["dstacked"],
        )
        return loss, dstacked, dshared

    # check_vma=False: turning the checker ON deadlocks the CPU collectives
    # runtime on this engine's cond/scan structure (measured: devices split
    # between an all-reduce and a collective-permute rendezvous). The
    # protection the checker would give is provided instead by (a) the
    # compiled collective-count assert (tests/test_pipeline.py) and (b) the
    # PP×TP rule that every psum inside the differentiated stage body must
    # be comms.psum_identity_bwd — under check_vma=False a RAW lax.psum's
    # transpose is psum, which double-counts every cotangent crossing it
    # (the identity transpose is the correct one for row-parallel outputs).
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, shared_specs, batch_specs),
        out_specs=(P(), param_specs, shared_specs),
        check_vma=False,
    )
    loss, dstacked, dshared = fn(stacked_params, shared_params, batch)
    return loss, (dstacked, dshared)


def gpipe(
    stage_fn,
    stacked_params,
    x,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    param_specs=None,
    extra=None,
):
    """Apply ``S`` stages to ``x`` as a GPipe pipeline over ``axis_name``.

    stage_fn: ``(stage_params, activations) -> activations`` for ONE stage
        (shape/dtype-preserving); with ``extra``,
        ``(stage_params, activations, extra_mb) -> activations``.
    stacked_params: pytree with leaves ``[S, ...]`` — stage-stacked weights,
        sharded ``P('pp')`` on the leading dim (logical axis ``stage``).
    x: ``[global_batch, ...]`` sharded over ``BATCH_AXES``.
    param_specs: optional per-leaf specs (PP×TP; see :func:`one_f_one_b`).
    extra: optional pytree of per-sample side inputs ([global_batch, ...],
        e.g. a key-padding mask), batch-sharded like ``x`` and microbatched
        in lockstep with it (see :func:`_stage_apply`).

    Returns stage_{S-1}(... stage_0(x)), sharded like ``x``.
    """
    S = mesh.shape[axis_name]
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    x_spec = P(BATCH_AXES)
    if S == 1:
        # Degenerate ring: identical math to the sequential oracle.
        return sequential(stage_fn, stacked_params, x, extra=extra)
    return _batch_sharded_call(
        lambda p, x, e: _gpipe_local(
            stage_fn, p, x,
            axis_name=axis_name, num_microbatches=num_microbatches, extra=e,
        ),
        mesh, param_specs, x_spec, stacked_params, x, extra,
    )


def sequential(stage_fn, stacked_params, x, extra=None):
    """The pipeline's correctness oracle: the same stacked stages applied
    back-to-back with a ``lax.scan`` (the idiomatic single-device execution
    of stage-stacked weights). ``extra`` (key-padding mask) is identical for
    every stage — no microbatching in this path."""

    def body(y, stage_params):
        if extra is None:
            return stage_fn(stage_params, y), None
        return stage_fn(stage_params, y, extra), None

    y, _ = jax.lax.scan(body, x, stacked_params)
    return y


def stack_stage_axis(params_tree):
    """Re-box a vmapped-over-stages param tree so every leaf's leading dim
    carries the ``stage`` logical axis (mapped to ``pp`` by the rules table).

    ``jax.vmap`` over a flax ``init`` adds the stage dim to each
    ``nn.Partitioned`` leaf's value but cannot know to extend ``names`` —
    this fixes the metadata up.
    """

    def fix(leaf):
        if isinstance(leaf, nn.Partitioned):
            return leaf.replace(names=("stage",) + leaf.names)
        return nn.Partitioned(leaf, ("stage",) + (None,) * (leaf.ndim - 1))

    return jax.tree.map(
        fix, params_tree, is_leaf=lambda l: isinstance(l, nn.Partitioned)
    )
