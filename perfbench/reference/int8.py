"""The control's arithmetic: int8 in place of bfloat16.

Values, and what flows back through them, are rounded to 255 levels; the
scale is the absmax along the given axes (the whole tensor for ``None``).
Accumulation stays float32.  Used only by the references' control path.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _round8(x, axes):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def int8(x, axes):
    return _round8(x, axes)


def _int8_fwd(x, axes):
    return _round8(x, axes), None


def _int8_bwd(axes, _, g):
    # an int8 training path rounds what flows back as well
    return (_round8(g, axes),)


int8.defvjp(_int8_fwd, _int8_bwd)


def fake_quant(x, axes, quant: bool):
    return int8(x, axes) if quant else x
