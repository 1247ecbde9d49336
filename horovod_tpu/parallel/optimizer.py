"""Distributed optimizer / gradient wrappers for JAX (optax).

Parity: ``horovod/tensorflow/__init__.py:266-311`` (_DistributedOptimizer),
``:474-531`` (DistributedGradientTape) and the torch hook-based optimizer
(``torch/__init__.py:127-221``), re-imagined for JAX's functional style:

* ``DistributedOptimizer(inner)`` returns an ``optax.GradientTransformation``
  that all-reduces gradients before applying the inner transformation.
* ``distributed_grad(fun)`` is the DistributedGradientTape analog: the
  returned grad function all-reduces the gradients it produces.

Both work in two regimes:
* **in-graph** (default, TPU path): pass ``axis=`` mesh axis name(s); the
  allreduce lowers to one fused XLA all-reduce inside the jitted step
  (tensor fusion via ``grouped_allreduce`` — one collective per dtype).
* **eager**: ``axis=None`` outside jit uses the process-group engine
  (host-network collectives, the classic Horovod regime).

``backward_passes_per_step`` accumulates gradients locally and reduces only
every Nth step (parity: torch/__init__.py:100-125); in-graph it uses a
counter in the optimizer state with ``lax.cond``-free arithmetic gating so
the program stays trace-stable.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.common.types import ReduceOp
from horovod_tpu.ops import collective as C
from horovod_tpu.ops.compression import Compression


def _allreduce_grads_ingraph(grads, op, axis, compression,
                             hierarchical=False, outer_axis="dcn"):
    # Fuse across leaves: compress first, group by dtype inside
    # grouped_allreduce, decompress after.  All of it carries the name
    # ``grad_reduce`` (telemetry/programs.py: the phase ``reduce``).
    leaves, treedef = jax.tree.flatten(grads)
    with jax.named_scope("grad_reduce"):
        comp = [compression.compress(g) for g in leaves]
        reduced = C.grouped_allreduce([c for c, _ in comp], op=op,
                                      axis=axis, hierarchical=hierarchical,
                                      outer_axis=outer_axis)
        out = [compression.decompress(r, ctx)
               for r, (_, ctx) in zip(reduced, comp)]
    return jax.tree.unflatten(treedef, out)


def _allreduce_grads_eager(grads, op, compression):
    from horovod_tpu.ops import eager

    leaves, treedef = jax.tree.flatten(grads)
    if any(eager._is_traced(g) for g in leaves):
        # Inside jit: one host callback enqueues the whole group into
        # the engine (controller fusion on the compiled path) — the
        # bridge regime, ops/bridge.py.
        from horovod_tpu.ops import bridge

        return jax.tree.unflatten(treedef, list(bridge.grouped_allreduce(
            tuple(leaves), name="grad", op=op, compression=compression)))
    handles = []
    for i, g in enumerate(leaves):
        handles.append(eager.allreduce_async(
            g, name=f"grad.{i}", op=op, compression=compression))
    return jax.tree.unflatten(
        treedef, [eager.synchronize(h) for h in handles])


def allreduce_gradients(grads, *, op: ReduceOp = ReduceOp.AVERAGE,
                        axis=("dp",), compression=Compression.none,
                        hierarchical: bool = False,
                        outer_axis: str = "dcn"):
    """All-reduce a pytree of gradients (in-graph when ``axis`` given).

    ``hierarchical=True`` routes the fused buffers through
    RS(ICI)→AR(DCN)→AG(ICI) — requires both the ``axis`` (inner) and a
    ``dcn`` outer axis in the active mesh (the in-graph analog of
    ``HVD_HIERARCHICAL_ALLREDUCE``)."""
    if axis is None:
        if hierarchical:
            raise ValueError(
                "hierarchical=True is an in-graph (mesh-axis) option; "
                "the eager regime's two-level mode is the engine-side "
                "HVD_HIERARCHICAL_ALLREDUCE knob")
        return _allreduce_grads_eager(grads, op, compression)
    return _allreduce_grads_ingraph(grads, op, axis, compression,
                                    hierarchical, outer_axis)


class _AccumState(NamedTuple):
    counter: jnp.ndarray
    acc: Any
    inner: Any


def _is_float(leaf) -> bool:
    return jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)


def _guarded_ingraph(inner, *, op, axis, compression, hierarchical,
                     outer_axis, policy):
    """In-graph non-finite guard: the flag agreement and the gradient
    allreduce both execute unconditionally (XLA collectives cannot be
    data-dependent); the *application* is masked.  With policy ``skip``
    a bad step leaves params and inner state bit-identical; with
    ``zero`` non-finite entries reduce as zeros.  Counters ride the
    optimizer state (integrity.nonfinite.GuardState / stats())."""
    from horovod_tpu.integrity import nonfinite as _nf

    # The flag agreement must span the FULL gradient-reduction set: with
    # hierarchical=True the gradients reduce across the inner axes AND
    # outer_axis (DCN), and a NaN agreed only within one slice would
    # skip the step there while the other slices apply it — silently
    # forking the replicas.
    flag_axes = (axis,) if isinstance(axis, str) else tuple(axis)
    if hierarchical and outer_axis not in flag_axes:
        flag_axes = flag_axes + (outer_axis,)

    def init_fn(params):
        return _nf.GuardState(jnp.zeros((), jnp.int32),
                              jnp.zeros((), jnp.int32),
                              inner.init(params))

    def update_fn(grads, state, params=None, **extra):
        finite = jnp.array(True)
        for leaf in jax.tree.leaves(grads):
            if _is_float(leaf):
                finite = jnp.logical_and(finite,
                                         jnp.all(jnp.isfinite(leaf)))
        flag = jnp.where(finite, 0, 1).astype(jnp.int32)
        bad = C.allreduce(flag, op=ReduceOp.MAX, axis=flag_axes)
        is_bad = bad > 0

        def reduce_and_apply(tree, inner_state):
            reduced = allreduce_gradients(
                tree, op=op, axis=axis, compression=compression,
                hierarchical=hierarchical, outer_axis=outer_axis)
            return inner.update(reduced, inner_state, params, **extra)

        nonfinite_steps = state.nonfinite_steps + bad
        consecutive = jnp.where(is_bad, state.consecutive + 1, 0)

        if policy == "zero":
            safe = jax.tree.map(
                lambda g: jnp.where(jnp.isfinite(g), g, jnp.zeros_like(g))
                if _is_float(g) else g, grads)
            updates, inner_state = reduce_and_apply(safe, state.inner)
            return updates, _nf.GuardState(nonfinite_steps, consecutive,
                                           inner_state)

        # skip: zero the whole tree on a bad step (jnp.where, never a
        # multiply — NaN * 0 is NaN) so the unconditional reduce and
        # inner update stay finite, then discard their results.
        safe = jax.tree.map(
            lambda g: jnp.where(is_bad, jnp.zeros_like(g), g), grads)
        updates, inner_state = reduce_and_apply(safe, state.inner)
        gated = jax.tree.map(
            lambda u: jnp.where(is_bad, jnp.zeros_like(u), u), updates)
        picked = jax.tree.map(
            lambda new, old: jnp.where(is_bad, old, new),
            inner_state, state.inner)
        return gated, _nf.GuardState(nonfinite_steps, consecutive, picked)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedOptimizer(
    inner: optax.GradientTransformation,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    axis: Union[str, Sequence[str], None] = ("dp",),
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    hierarchical: bool = False,
    outer_axis: str = "dcn",
    nonfinite_policy: Optional[str] = None,
    nonfinite_guard=None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally-reduced gradients.

    ``hierarchical=True`` (in-graph regime only) reduces the fused
    gradient buffers RS(inner/ICI)->AR(outer/DCN)->AG(inner/ICI);
    ``axis`` must name exactly the inner and ``outer_axis`` axes.

    ``nonfinite_policy`` (default: ``HVD_NONFINITE_POLICY``, then
    ``off``) arms the non-finite gradient guard: a 1-element
    MAX-allreduce agrees a per-step any-NaN/Inf flag so every rank
    skips (``skip``), sanitizes (``zero``) or — eager regime only —
    raises on (``raise``) the *same* step.  ``off`` adds zero extra
    collectives.  Pass ``nonfinite_guard`` (a
    :class:`~horovod_tpu.integrity.nonfinite.NonFiniteGuard`) to keep a
    handle on the eager guard's counters.  Composes with
    ``backward_passes_per_step == 1`` only.  The eager guard inspects
    gradients host-side: call the guarded step outside ``jit`` (the
    bridge's traced-leaf path does not compose with a guard; the guard
    raises a clear error on traced leaves).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    from horovod_tpu.integrity import nonfinite as _nf

    guard = nonfinite_guard
    policy = guard.policy if guard is not None \
        else _nf.resolve_policy(nonfinite_policy)
    if policy != "off":
        if backward_passes_per_step != 1:
            raise ValueError(
                "the non-finite gradient guard composes with "
                "backward_passes_per_step == 1 only; accumulate at the "
                "data-loader level to combine them")
        if axis is not None:
            if policy == "raise":
                raise ValueError(
                    "nonfinite_policy 'raise' needs host control flow "
                    "and is eager-only (axis=None); in-graph use 'skip' "
                    "and watch integrity.nonfinite_stats(opt_state)")
            if guard is not None:
                raise ValueError(
                    "nonfinite_guard is the eager-regime (axis=None) "
                    "hook; in-graph counters live in the optimizer "
                    "state (integrity.nonfinite_stats)")
        elif guard is None:
            guard = _nf.NonFiniteGuard(policy)

    if backward_passes_per_step == 1:
        if policy != "off" and axis is not None:
            return _guarded_ingraph(
                inner, op=op, axis=axis, compression=compression,
                hierarchical=hierarchical, outer_axis=outer_axis,
                policy=policy)

        def init_fn(params):
            return inner.init(params)

        def update_fn(grads, state, params=None, **extra):
            if guard is not None:
                grads, skip = guard.intercept(grads)
                if skip:
                    return jax.tree.map(jnp.zeros_like, grads), state
            reduced = allreduce_gradients(
                grads, op=op, axis=axis, compression=compression,
                hierarchical=hierarchical, outer_axis=outer_axis)
            return inner.update(reduced, state, params, **extra)

        return optax.GradientTransformation(init_fn, update_fn)

    n = backward_passes_per_step

    def init_fn(params):
        zeros = jax.tree.map(jnp.zeros_like, params)
        return _AccumState(jnp.zeros((), jnp.int32), zeros,
                           inner.init(params))

    def update_fn(grads, state, params=None, **extra):
        counter = state.counter + 1
        acc = jax.tree.map(lambda a, g: a + g, state.acc, grads)
        do_reduce = counter >= n

        def reduce_branch(acc_tree):
            scaled = jax.tree.map(lambda a: a / n, acc_tree)
            return allreduce_gradients(
                scaled, op=op, axis=axis, compression=compression,
                hierarchical=hierarchical, outer_axis=outer_axis)

        if axis is None:
            # Eager regime: python control flow is fine.
            if bool(do_reduce):
                reduced = reduce_branch(acc)
                updates, inner_state = inner.update(
                    reduced, state.inner, params, **extra)
                new_state = _AccumState(
                    jnp.zeros((), jnp.int32),
                    jax.tree.map(jnp.zeros_like, acc), inner_state)
                return updates, new_state
            zero_updates = jax.tree.map(jnp.zeros_like, grads)
            return zero_updates, _AccumState(counter, acc, state.inner)

        # In-graph: both branches must trace; collective ops must execute
        # unconditionally (XLA collectives cannot be data-dependent), so we
        # reduce every step but only *apply* on the Nth — the reduce of a
        # masked accumulator is the price of trace stability.  For real
        # skip-step savings use backward_passes_per_step at the data-loader
        # level or run the eager regime.
        reduced = reduce_branch(acc)
        updates, inner_state = inner.update(
            reduced, state.inner, params, **extra)
        gate = (counter >= n).astype(jnp.float32)
        gated = jax.tree.map(lambda u: u * gate.astype(u.dtype), updates)
        new_counter = jnp.where(do_reduce, 0, counter)
        new_acc = jax.tree.map(
            lambda a: a * (1.0 - gate).astype(a.dtype), acc)
        # Inner optimizer state advances only on apply steps.
        def pick(new, old):
            return jax.tree.map(
                lambda x, y: jnp.where(do_reduce, x, y), new, old)
        return gated, _AccumState(new_counter, new_acc,
                                  pick(inner_state, state.inner))

    return optax.GradientTransformation(init_fn, update_fn)


def distributed_grad(fun, *, op: ReduceOp = ReduceOp.AVERAGE,
                     axis: Union[str, Sequence[str], None] = ("dp",),
                     compression=Compression.none,
                     argnums=0, has_aux: bool = False):
    """DistributedGradientTape analog: grad-of-``fun`` with the gradients
    all-reduced across ``axis`` (parity: tensorflow/__init__.py:474-531)."""
    gfun = jax.grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        if has_aux:
            grads, aux = gfun(*args, **kwargs)
            return allreduce_gradients(
                grads, op=op, axis=axis, compression=compression), aux
        grads = gfun(*args, **kwargs)
        return allreduce_gradients(
            grads, op=op, axis=axis, compression=compression)

    return wrapped


def distributed_value_and_grad(fun, *, op: ReduceOp = ReduceOp.AVERAGE,
                               axis: Union[str, Sequence[str], None] = ("dp",),
                               compression=Compression.none,
                               argnums=0, has_aux: bool = False):
    vgfun = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        val, grads = vgfun(*args, **kwargs)
        return val, allreduce_gradients(
            grads, op=op, axis=axis, compression=compression)

    return wrapped
