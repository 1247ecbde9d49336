"""Pipeline parallelism over the ``pp`` mesh axis.

The reference framework has **no** pipeline parallelism (SURVEY.md §2.8 —
its only scaling axis is the batch); this module is part of the TPU-native
multi-axis extension promised in ``models/transformer.py``.

Design — GPipe microbatch pipelining, built from the same primitives as the
rest of the stack:

* The transformer stacks layers on a leading axis and iterates them with
  ``lax.scan`` (models/transformer.py) — so pipelining is a *sharding
  decision on that axis*: each ``pp`` stage holds ``n_layers / pp``
  contiguous layers (its "cell").
* The batch is split into M microbatches; a ``lax.scan`` over
  ``M + P - 1`` ticks advances the pipeline.  Every tick each stage
  applies its cell, then activations rotate one stage down the ring via
  ``lax.ppermute`` — the same neighbor-exchange primitive ring attention
  uses.  Stage 0 feeds microbatches in; stage P-1 collects outputs.  The
  classic GPipe bubble is the ``(P-1) / (M+P-1)`` idle fraction.
* Backward is ``jax.grad`` straight through the schedule (GPipe
  semantics: all forwards, then all backwards, with per-cell activation
  rematerialization via ``jax.checkpoint``).  A hand-interleaved 1F1B
  schedule trades peak memory for the same bubble; under XLA the remat
  scan gives most of that back without a second schedule.
* Only ``pp`` is a *manual* axis (``shard_map(axis_names={'pp'})``);
  ``dp``/``tp``/``ep`` stay in GSPMD "auto" mode, so Megatron tensor
  sharding and MoE expert all-to-alls compose with pipelining unchanged.
  (``sp`` ring attention runs its own shard_map and is used in
  non-pipelined steps; inside a pipeline cell attention is GSPMD-dense.)

Numerics: with dense FFN the pipelined forward is exactly the layer scan
re-bracketed, so outputs match the non-pipelined ``tfm.apply`` to float
round-off (the test pins this).  MoE aux-loss and capacity are computed
per *microbatch* when pipelined — the standard semantic shift of
microbatching, documented here rather than hidden.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import filter_spec
from horovod_tpu.parallel.shard import shard_map
from horovod_tpu.parallel.train import _step0


def pipeline_param_specs(cfg: tfm.TransformerConfig):
    """``tfm.param_specs`` with the stacked-layer axis sharded over ``pp``."""
    specs = tfm.param_specs(cfg)

    def reshard(spec: P) -> P:
        return P("pp", *spec[1:])

    specs["layers"] = jax.tree.map(
        reshard, specs["layers"], is_leaf=lambda x: isinstance(x, P))
    return specs


def gpipe(stage_fn, x_mb, *, axis: str = "pp"):
    """Run ``stage_fn`` over microbatches through the ``axis`` ring.

    Call inside a shard_map body where ``axis`` is manual.  ``x_mb`` is
    ``[M, ...]`` microbatched input, present on every stage (only stage
    0's copy is consumed).  ``stage_fn(x) -> (y, aux)`` applies this
    stage's cell.  Returns ``([M, ...] outputs, total_aux)``, both
    replicated across the ``axis`` ring.
    """
    n_stages = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    n_micro = x_mb.shape[0]
    ticks = n_micro + n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        buf, out, aux_sum = carry
        feed = x_mb[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(stage == 0, feed, buf)
        y, aux = stage_fn(inp)
        # Stage P-1 finished microbatch t-(P-1) this tick.
        mb = t - (n_stages - 1)
        write = (stage == n_stages - 1) & (mb >= 0)
        out = jnp.where(write, out.at[jnp.clip(mb, 0, n_micro - 1)].set(y),
                        out)
        # Rotate activations one stage down the ring.  Bubble ticks carry
        # garbage that the feed/write gating above keeps out of results.
        buf = lax.ppermute(y, axis, ring)
        valid = (t >= stage) & (t - stage < n_micro)
        aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        return (buf, out, aux_sum), None

    # The carry becomes pp-varying after one tick (each stage holds its
    # own activations), so it must *start* varying for scan's type check.
    carry0 = jax.tree.map(
        lambda a: lax.pcast(a, axis, to="varying"),
        (jnp.zeros_like(x_mb[0]), jnp.zeros_like(x_mb),
         jnp.zeros((), jnp.float32)))
    (_, out, aux_sum), _ = lax.scan(tick, carry0, jnp.arange(ticks))
    # Results live on the last stage; replicate them ring-wide (masked
    # psum — the same lowering ops.collective.broadcast uses).
    out = lax.psum(jnp.where(stage == n_stages - 1, out,
                             jnp.zeros_like(out)), axis)
    aux = lax.psum(aux_sum, axis)
    return out, aux


def pipeline_apply(params, tokens, cfg: tfm.TransformerConfig, mesh,
                   *, n_microbatches: Optional[int] = None,
                   remat: bool = True):
    """Pipelined forward of the stacked-layer transformer.

    ``params`` laid out per :func:`pipeline_param_specs` (stacked-layer
    axis over ``pp``).  Returns ``(logits_fp32, aux)`` like ``tfm.apply``.
    """
    pp = mesh.shape.get("pp", 1)
    if pp <= 1:
        return tfm.apply(params, tokens, cfg, mesh=mesh, remat=remat)
    if cfg.n_layers % pp:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide over pp={pp}")
    M = n_microbatches or pp
    B = tokens.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    if cfg.attn_impl == "flash" and mesh.shape.get("dp", 1) > 1:
        # Inside the pipeline body dp stays GSPMD-auto, and a pallas_call
        # cannot be partitioned by GSPMD — use dense attention there.
        import dataclasses

        tfm.warn_flash_runs_dense("dp", mesh.shape["dp"],
                                  "the pipeline body (pp > 1)")
        cfg = dataclasses.replace(cfg, attn_impl="dense")

    layer_fn = tfm.remat_layer() if remat else tfm._layer

    def body(params, tokens):
        dtype = cfg.compute_dtype
        # Embedding runs replicated on every stage (cheap next to a cell).
        x = params["embed"].astype(dtype)[tokens]
        S, D = x.shape[1], x.shape[2]
        x_mb = x.reshape(M, B // M, S, D)

        def stage_fn(h):
            def layer_body(carry, lp):
                h, aux_sum = carry
                h, aux, _ = layer_fn(h, lp, cfg, None)
                return (h, aux_sum + aux), None

            (h, aux), _ = lax.scan(
                layer_body, (h, jnp.zeros((), jnp.float32)),
                params["layers"])
            return h, aux

        out, aux = gpipe(stage_fn, x_mb, axis="pp")
        # gpipe sums aux over microbatches; the per-microbatch MoE
        # load-balance statistic is scale-free (~the full-batch value), so
        # average to keep the loss independent of the n_microbatches
        # throughput knob.
        aux = aux / M
        x = out.reshape(B, S, D)
        x = tfm._rmsnorm(x, params["ln_f"])
        return tfm.vocab_projection(x, params["embed"]), aux

    specs = pipeline_param_specs(cfg)
    # Only pp placement is named here; dp/tp/ep stay GSPMD-auto.
    pp_only = jax.tree.map(
        lambda s: P(*[ax if ax == "pp" else None for ax in s]),
        specs, is_leaf=lambda x: isinstance(x, P))
    # check_vma stays ON (unlike the full-manual collectives wrapper):
    # partial-manual shard_map only admits unmentioned-axis out_specs when
    # replication over pp is provable, which the masked-psum broadcast at
    # the end of gpipe() establishes.
    sharded = shard_map(
        body, mesh=mesh, axis_names=frozenset({"pp"}),
        in_specs=(pp_only, P()), out_specs=(P(), P()), check_vma=True)
    return sharded(params, tokens)


def pipeline_loss_fn(params, tokens, targets, cfg, mesh,
                     *, n_microbatches=None, aux_weight: float = 0.01):
    logits, aux = pipeline_apply(params, tokens, cfg, mesh,
                                 n_microbatches=n_microbatches)
    return tfm.softmax_xent(logits, targets) + aux_weight * aux


class PipelineTrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def make_pipeline_train_step(
    cfg: tfm.TransformerConfig,
    mesh,
    optimizer: Optional[optax.GradientTransformation] = None,
    *,
    n_microbatches: Optional[int] = None,
):
    """Pipelined twin of ``train.make_transformer_train_step``: params are
    born sharded over pp (stacked-layer axis) × tp/ep; the whole GPipe
    schedule jits as one program and autodiff provides the backward
    pipeline."""
    if optimizer is None:
        optimizer = optax.adamw(1e-3, weight_decay=0.01)
    from horovod_tpu.parallel.train import _opt_shardings

    specs = pipeline_param_specs(cfg)
    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, filter_spec(s, mesh)), specs,
        is_leaf=lambda x: isinstance(x, P))
    data_sharding = NamedSharding(mesh, filter_spec(P("dp", None), mesh))

    def init_fn(rng) -> PipelineTrainState:
        params = jax.jit(lambda k: tfm.init(k, cfg),
                         out_shardings=param_shardings)(rng)
        opt_state = jax.jit(
            optimizer.init,
            out_shardings=_opt_shardings(optimizer, params,
                                         param_shardings))(params)
        return PipelineTrainState(params, opt_state,
                                  _step0(mesh))

    def _step(state: PipelineTrainState, tokens, targets):
        loss, grads = jax.value_and_grad(pipeline_loss_fn)(
            state.params, tokens, targets, cfg, mesh,
            n_microbatches=n_microbatches)
        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return PipelineTrainState(params, opt_state, state.step + 1), loss

    step_fn = jax.jit(
        _step,
        in_shardings=(None, data_sharding, data_sharding),
        donate_argnums=(0,),
    )
    return step_fn, init_fn
