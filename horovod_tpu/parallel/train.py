"""Sharded training-step builders.

Where the reference bolts distribution onto framework optimizers
(``_DistributedOptimizer`` re-running allreduce per gradient,
``tensorflow/__init__.py:266-311``), the TPU-native shape is: declare
parameter/data shardings over a ``Mesh``, jit the whole step, and let XLA
insert the gradient all-reduces — they come out fused and overlapped with
the backward pass, which is what Horovod's background thread + fusion
buffer worked hard to approximate.

Two regimes are exposed:

* ``make_*_train_step(mesh=...)`` — GSPMD/pjit: params replicated over
  ``dp``/``dcn`` and sharded over ``tp``/``ep`` per the model's
  ``param_specs``; batch sharded over ``dp`` (and ``sp`` for sequences).
  Gradient reduction is implicit.
* the optimizer wrappers in ``horovod_tpu.parallel.optimizer`` — explicit
  Horovod-style allreduce, for code that wants the classic contract.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import mnist as mnist_model
from horovod_tpu.models import resnet as resnet_model
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import filter_spec
from horovod_tpu.telemetry import programs


def _sharding(mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, filter_spec(spec, mesh))


def _replicated(mesh):
    return NamedSharding(mesh, P())


def _batch_spec(mesh, *axes) -> P:
    """P over whichever of ``axes`` exist in the mesh (rest None)."""
    return filter_spec(P(*axes), mesh)


def _step0(mesh):
    """Mesh-replicated zero step counter.  A plain ``jnp.zeros(())`` is an
    uncommitted single-device array — fine until a checkpoint restore
    commits it, at which point jit rejects the mixed device sets; placing
    it on the mesh up front keeps init and restored states identical."""
    return jax.device_put(jnp.zeros((), jnp.int32), _replicated(mesh))


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


# ---------------------------------------------------------------------------
# Transformer (flagship: dp × tp × sp × ep)
# ---------------------------------------------------------------------------


def make_transformer_train_step(
    cfg: tfm.TransformerConfig,
    mesh,
    optimizer: Optional[optax.GradientTransformation] = None,
    *,
    zero1: bool = False,
):
    """Returns ``(step_fn, init_fn)``.

    ``init_fn(rng) -> TrainState`` places params with tp/ep shardings;
    ``step_fn(state, tokens, targets) -> (state, loss)`` is jit-compiled
    over the mesh.  Batch layout: tokens/targets ``[B, S]`` sharded
    ``P('dp', 'sp')``.

    ``zero1=True`` additionally shards the optimizer state over the
    ``dp`` axis (ZeRO stage 1, GSPMD-style: the moments' shardings get
    ``dp`` on their first free dimension and XLA turns the gradient
    sync into reduce-scatter + sharded update + allgather instead of
    allreduce + replicated update — same math, 1/dp the adam-moment
    memory per chip).  The reference has no optimizer-state sharding
    (DP replicates everything); this is TPU-native headroom for large
    models.
    """
    if optimizer is None:
        optimizer = optax.adamw(1e-3, weight_decay=0.01)
    specs = tfm.param_specs(cfg)
    param_shardings = jax.tree.map(
        lambda s: _sharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    data_sharding = NamedSharding(mesh, _batch_spec(mesh, "dp", "sp"))
    zero_axis = "dp" if zero1 and mesh.shape.get("dp", 1) > 1 else None
    abstract_params = jax.eval_shape(
        lambda: tfm.init(jax.random.PRNGKey(0), cfg))
    opt_shardings = _opt_shardings(optimizer, abstract_params,
                                   param_shardings, zero_axis=zero_axis)
    if zero1:
        # The degradation cases must be loud: asking for ZeRO-1 and
        # getting replicated state is a silent 0x memory saving.
        from horovod_tpu.utils.logging import get_logger

        if zero_axis is None:
            get_logger().warning(
                "zero1=True but the mesh has no dp axis > 1; optimizer "
                "state stays replicated")
        else:
            n_sharded = sum(
                zero_axis in (s.spec or ())
                for s in jax.tree.leaves(
                    opt_shardings,
                    is_leaf=lambda x: isinstance(x, NamedSharding)))
            if n_sharded == 0:
                get_logger().warning(
                    "zero1=True but no optimizer-state dimension is "
                    "divisible by dp=%d; state stays replicated",
                    mesh.shape["dp"])

    def init_fn(rng) -> TrainState:
        # Params are born sharded: jit-with-out_shardings means no device
        # ever holds the full unsharded model (tp/ep exist because it
        # wouldn't fit).
        params = jax.jit(
            lambda k: tfm.init(k, cfg),
            out_shardings=param_shardings)(rng)
        opt_state = jax.jit(
            optimizer.init, out_shardings=opt_shardings)(params)
        return TrainState(params, opt_state, _step0(mesh))

    def _step(state: TrainState, tokens, targets):
        loss, grads = jax.value_and_grad(tfm.loss_fn)(
            state.params, tokens, targets, cfg, mesh=mesh)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    if zero_axis is not None:
        # Pin the ZeRO placement through the step so the sharded
        # moments never silently collapse back to replicated (XLA's
        # propagation would otherwise be free to choose).
        rep = NamedSharding(mesh, P())
        state_shardings = TrainState(param_shardings, opt_shardings, rep)
        step_fn = programs.named_jit(
            _step, "train_step_lm",
            in_shardings=(state_shardings, data_sharding, data_sharding),
            out_shardings=(state_shardings, rep),
            donate_argnums=(0,),
        )
    else:
        step_fn = programs.named_jit(
            _step, "train_step_lm",
            in_shardings=(None, data_sharding, data_sharding),
            donate_argnums=(0,),
        )
    return step_fn, init_fn


def _zero1_augment(sharding, shape, axis):
    """Put ``axis`` on the first free, divisible dimension of a
    param-mirroring leaf's sharding (ZeRO-1: shard the moments over
    data-parallel replicas).  Leaves with no eligible dimension keep the
    param's sharding (replicated over ``axis``)."""
    mesh = sharding.mesh
    n = mesh.shape[axis]
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    for i, (ax, dim) in enumerate(zip(spec, shape)):
        if ax is None and dim % n == 0 and dim >= n:
            spec[i] = axis
            return NamedSharding(mesh, P(*spec))
    return sharding


def _opt_shardings(optimizer, params, param_shardings, zero_axis=None):
    """Optimizer-state shardings: state leaves that mirror a param (adam
    moments — their tree path ends with the param's path and the shape
    matches) get that param's sharding; everything else is replicated.
    Path-suffix matching is exact per position, so two params with equal
    shapes but different specs can't collide.  ``zero_axis`` additionally
    shards the param-mirroring leaves over that mesh axis (ZeRO-1)."""
    from jax.tree_util import keystr, tree_flatten_with_path

    shapes = jax.eval_shape(optimizer.init, params)
    param_paths = tree_flatten_with_path(params)[0]
    flat_shard = jax.tree.flatten(param_shardings)[0]
    suffixes = [(keystr(path), leaf.shape, s)
                for (path, leaf), s in zip(param_paths, flat_shard)]
    mesh_rep = flat_shard[0].mesh if flat_shard else None

    def pick(path, leaf):
        ps = keystr(path)
        for suf, shape, s in suffixes:
            if ps.endswith(suf) and leaf.shape == shape:
                if zero_axis is not None:
                    return _zero1_augment(s, shape, zero_axis)
                return s
        return NamedSharding(mesh_rep, P())

    return jax.tree_util.tree_map_with_path(pick, shapes)


# ---------------------------------------------------------------------------
# ResNet / MNIST (pure data parallel over dp [+ dcn])
# ---------------------------------------------------------------------------


class ResNetState(NamedTuple):
    params: Any
    batch_stats: Any
    opt_state: Any
    step: jnp.ndarray


def _resnet_init_fn(cfg, mesh, optimizer):
    """``init_fn(rng) -> ResNetState`` as one jitted program whose outputs
    are born replicated over the mesh (like the transformer's), so no leaf
    is made op by op on the first device and copied out from there."""
    def build(rng) -> ResNetState:
        params, stats = resnet_model.init(rng, cfg)
        return ResNetState(params, stats, optimizer.init(params),
                           jnp.zeros((), jnp.int32))

    return jax.jit(build, out_shardings=_replicated(mesh))


def make_resnet_train_step(
    cfg: resnet_model.ResNetConfig,
    mesh,
    optimizer: Optional[optax.GradientTransformation] = None,
):
    """Data-parallel ResNet step: params replicated, batch over dp (+dcn).

    BN statistics are cross-replica-averaged like the reference's
    examples do with ``hvd.allreduce`` on metrics — here it's a psum XLA
    inserts from the replicated out-sharding of ``batch_stats``.
    """
    if optimizer is None:
        optimizer = optax.sgd(0.1, momentum=0.9)
    data_sharding = NamedSharding(mesh, _batch_spec(mesh, "dp"))
    init_fn = _resnet_init_fn(cfg, mesh, optimizer)

    def _step(state: ResNetState, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            resnet_model.loss_fn, has_aux=True)(
                state.params, state.batch_stats, images, labels, cfg)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return ResNetState(params, new_stats, opt_state,
                           state.step + 1), loss

    step_fn = programs.named_jit(
        _step, "train_step_resnet",
        in_shardings=(None, data_sharding, data_sharding),
        donate_argnums=(0,),
    )
    return step_fn, init_fn


def make_resnet_train_step_hvd(
    cfg: resnet_model.ResNetConfig,
    mesh,
    optimizer: Optional[optax.GradientTransformation] = None,
    *,
    axis=("dp",),
):
    """Classic-Horovod-contract ResNet step: the whole step runs inside
    ``shard_map`` and gradient reduction is an *explicit*
    ``grouped_allreduce`` (via ``DistributedOptimizer``), not a sharding
    XLA infers — the analog of the reference benchmark always training
    through ``hvd.DistributedOptimizer``
    (examples/tensorflow2_synthetic_benchmark.py:119-130).

    Pass ``optimizer`` already wrapped in
    :func:`horovod_tpu.parallel.optimizer.DistributedOptimizer` (with
    matching ``axis``) to control op/compression; a default SGD wrapper is
    built otherwise.  BN statistics and the reported loss are
    cross-replica averaged.
    """
    from horovod_tpu.ops import collective as C
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel.shard import shard_map

    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    axes = tuple(a for a in axes if a in mesh.axis_names)
    if optimizer is None:
        optimizer = opt_mod.DistributedOptimizer(
            optax.sgd(0.1, momentum=0.9), axis=axes)
    # All data-parallel axes gang up on dim 0 (batch).  P(*axes) would
    # instead spread them across dims — sharding image height over the
    # second axis (caught by the hier-ici-dcn dryrun mesh).
    batch_p = filter_spec(P(axes), mesh) if axes else P()
    init_fn = _resnet_init_fn(cfg, mesh, optimizer)

    def body(state: ResNetState, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            resnet_model.loss_fn, has_aux=True)(
                state.params, state.batch_stats, images, labels, cfg)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        if axes:
            with jax.named_scope("stats_reduce"):
                new_stats = jax.tree.map(
                    lambda s: C.allreduce(s, axis=axes), new_stats)
                loss = C.allreduce(loss, axis=axes)
        return ResNetState(params, new_stats, opt_state,
                           state.step + 1), loss

    sharded = shard_map(
        body, mesh,
        in_specs=(P(), batch_p, batch_p),
        out_specs=(P(), P()),
    )
    step_fn = programs.named_jit(sharded, "train_step_resnet_hvd",
                                 donate_argnums=(0,))
    return step_fn, init_fn


def make_mnist_train_step(mesh, optimizer=None):
    if optimizer is None:
        optimizer = optax.adam(1e-3)
    data_sharding = NamedSharding(mesh, _batch_spec(mesh, "dp"))

    def build(rng) -> TrainState:
        params = mnist_model.init(rng)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    # Born replicated, as in _resnet_init_fn.
    init_fn = jax.jit(build, out_shardings=_replicated(mesh))

    def _step(state: TrainState, images, labels):
        loss, grads = jax.value_and_grad(mnist_model.loss_fn)(
            state.params, images, labels)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    step_fn = programs.named_jit(
        _step, "train_step_mnist",
        in_shardings=(None, data_sharding, data_sharding),
        donate_argnums=(0,),
    )
    return step_fn, init_fn
