"""Adasum: scale-invariant gradient combination.

Parity (math only): ``horovod/common/ops/adasum/adasum.h`` — the pairwise
combination of gradients a, b is

    a' = (1 - dot(a,b) / (2·‖a‖²)) · a  +  (1 - dot(a,b) / (2·‖b‖²)) · b

applied recursively over pairs of ranks (vector-halving distance-doubling,
adasum.h:167-338).  The result is invariant to per-rank gradient scale and
behaves like an average for orthogonal gradients and like a sum for
identical ones.

TPU-native design: the reference implements VHDD with MPI point-to-point
send/recv because NCCL has no pairwise primitive.  On a TPU mesh we express
each VHDD round as an in-graph ``ppermute`` partner exchange, so the whole
recursion compiles into one XLA program over the ICI ring — no host round
trips.  Dot products and norms accumulate in fp32 regardless of input dtype,
matching the reference's fp16 path (adasum.h:404-520 promotes to float).
"""

from __future__ import annotations

from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax



def adasum_pair(a, b, dot, anorm_sq, bnorm_sq):
    """Combine two gradients given precomputed <a,b>, ‖a‖², ‖b‖².

    Scalar guard behavior matches adasum.h:367-391: if either norm is zero
    the corresponding coefficient contribution degenerates to a plain sum.
    """
    acoef = jnp.where(anorm_sq > 0, 1.0 - dot / (2.0 * anorm_sq), 1.0)
    bcoef = jnp.where(bnorm_sq > 0, 1.0 - dot / (2.0 * bnorm_sq), 1.0)
    return acoef.astype(a.dtype) * a + bcoef.astype(b.dtype) * b


def adasum_pair_numpy(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Eager pairwise combine used by the CPU data plane."""
    dot = float(np.dot(a.ravel(), b.ravel()))
    an = float(np.dot(a.ravel(), a.ravel()))
    bn = float(np.dot(b.ravel(), b.ravel()))
    acoef = 1.0 - dot / (2.0 * an) if an > 0 else 1.0
    bcoef = 1.0 - dot / (2.0 * bn) if bn > 0 else 1.0
    return acoef * a + bcoef * b


def adasum_reduce_numpy(grads: Sequence[np.ndarray]) -> np.ndarray:
    """Reference (oracle) implementation over a list of per-rank gradients,
    recursing over rank pairs exactly like VHDD's distance-doubling order.
    Used by tests as the golden model (the reference validates against a
    NumPy model the same way, test_adasum_tensorflow.py).
    """
    grads = [np.asarray(g, np.float64) for g in grads]
    n = len(grads)
    assert n & (n - 1) == 0, "adasum oracle requires power-of-two ranks"
    if n == 1:
        return grads[0]
    half = n // 2
    a = adasum_reduce_numpy(grads[:half])
    b = adasum_reduce_numpy(grads[half:])
    dot = float(np.dot(a.ravel(), b.ravel()))
    an = float(np.dot(a.ravel(), a.ravel()))
    bn = float(np.dot(b.ravel(), b.ravel()))
    acoef = 1.0 - dot / (2.0 * an) if an > 0 else 1.0
    bcoef = 1.0 - dot / (2.0 * bn) if bn > 0 else 1.0
    return acoef * a + bcoef * b


def adasum_allreduce(x, axis: Union[str, Sequence[str]] = "dp"):
    """In-graph Adasum allreduce over one mesh axis (or axis tuple treated
    as its linearization).

    Implementation: recursive halving by partner exchange.  At round k the
    partner is ``index XOR 2^k``; both sides compute the pairwise statistics
    with an fp32 psum over the *pair* — but since XLA collectives span the
    whole axis, we instead exchange the partner's full vector with
    ``ppermute`` and compute the statistics locally in fp32.  log2(n)
    rounds, each one ppermute of the full vector: same bytes on the wire as
    the reference's VHDD recursive halving+doubling combined.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    # Linearize multi-axis reductions by reshaping into one logical axis:
    # do Adasum over the first axis, then again over the next, which equals
    # the VHDD recursion order (local pairs first).
    out = x
    for ax in reversed(axes):
        out = _adasum_one_axis(out, ax)
    return out


def _adasum_one_axis(x, axis: str):
    n = lax.axis_size(axis)
    if n == 1:
        return x
    assert n & (n - 1) == 0, "adasum requires power-of-two axis size"
    acc = x
    k = 1
    while k < n:
        # Partner exchange: everyone sends acc to (index XOR k).
        perm = [(i, i ^ k) for i in range(n)]
        partner = lax.ppermute(acc, axis, perm)
        a32 = acc.astype(jnp.float32)
        b32 = partner.astype(jnp.float32)
        dot = jnp.vdot(a32, b32)
        an = jnp.vdot(a32, a32)
        bn = jnp.vdot(b32, b32)
        # The pairwise combine is symmetric in (a, b), so both partners
        # compute the identical value and no second exchange is needed.
        acc = adasum_pair(a32, b32, dot, an, bn).astype(x.dtype)
        k *= 2
    return acc
