"""Per-rank continuous-batching decode state.

One :class:`DecodeEngine` lives on every rank of the serving gang and
holds the slot-batched KV caches ([L, max_batch, cache_len, H, HD]) and
the per-slot current token and position vectors.  The caches are ONE
resident pair of buffers: the two programs that write them, the jit-ed
step (models/transformer.decode_step: B new rows a layer) and the
install that ends a prefill (``_install``: one slot's lane), take them
donated and update them in place, so neither a turn nor an admission
copies the cache or holds a second one (pinned on the compiled programs
by tests/test_serving_cache.py, and on the chip by the benchmark's
``peak_hbm_gb.serve`` and op breakdown).  The per-slot math is
bit-identical to the single-request ``generate`` path, so a slot's
output never depends on what its neighbors are decoding (pinned by
tests/test_serving.py oracles).

Long-context KV shards over the mesh via the model's KV_CACHE_SPEC
(heads over ``tp``) — the same ``parallel/`` mesh-spec plumbing training
uses, applied with ``filter_spec`` so a spec axis missing from the mesh
degrades to replication.

Prefill compiles once per distinct prompt length (the serving analogue
of generate()'s per-shape compile); the install takes the slot as a
traced scalar and compiles once.  Greedy sampling only: determinism
is what lets every rank step without exchanging tokens and lets a
re-formed gang replay a request to the identical completion.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as T


def _install(ks, vs, tok, pos, slot, logits, k1, v1, length):
    """End of a prefill: write the request's K/V ([L, 1, cache_len, H,
    HD], zero past the prompt) into lane ``slot`` of the caches and set
    the slot's token (greedy, from the prefill's ``logits`` [V]) and
    position.  Returns (first token, ks, vs, tok, pos)."""
    first = jnp.argmax(logits).astype(jnp.int32)
    at = (0, slot, 0, 0, 0)
    ks = jax.lax.dynamic_update_slice(ks, k1, at)
    vs = jax.lax.dynamic_update_slice(vs, v1, at)
    return (first, ks, vs, tok.at[slot].set(first),
            pos.at[slot].set(length))


class DecodeEngine:
    def __init__(self, params, cfg: T.TransformerConfig, *,
                 max_batch: int, cache_len: Optional[int] = None,
                 mesh=None):
        if cfg.n_experts:
            raise NotImplementedError(
                "serving supports dense-FFN configs (same contract as "
                "models.transformer.generate)")
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len or cfg.max_seq_len
        self.mesh = mesh
        L, H, HD = cfg.n_layers, cfg.n_heads, cfg.head_dim
        shape = (L, max_batch, self.cache_len, H, HD)
        self.ks = jnp.zeros(shape, cfg.compute_dtype)
        self.vs = jnp.zeros(shape, cfg.compute_dtype)
        sharding = None
        if mesh is not None:
            from horovod_tpu.parallel.mesh import sharding_for

            sharding = sharding_for(mesh, T.KV_CACHE_SPEC)
            self.ks = jax.device_put(self.ks, sharding)
            self.vs = jax.device_put(self.vs, sharding)
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        # The caches leave both programs as they entered them: the same
        # buffers (donated), under the same sharding.
        self._step = jax.jit(
            partial(T.decode_step, cfg=cfg), donate_argnums=(3, 4),
            out_shardings=(None, sharding, sharding))
        self._install = jax.jit(
            _install, donate_argnums=(0, 1),
            out_shardings=(None, sharding, sharding, None, None))
        self._prefills: Dict[int, object] = {}  # prompt len -> jit fn

    def prefill(self, slot: int, prompt: List[int]) -> int:
        """Run the prompt through the model, install its K/V into the
        slot's cache lane, and return the first sampled (greedy) token.
        The slot is live from the next step() on."""
        fn = self._prefills.get(len(prompt))
        if fn is None:
            fn = jax.jit(partial(T.prefill_request, cfg=self.cfg,
                                 cache_len=self.cache_len))
            self._prefills[len(prompt)] = fn
        logits, k1, v1 = fn(self.params, jnp.asarray(prompt, jnp.int32))
        first, self.ks, self.vs, self.tok, self.pos = self._install(
            self.ks, self.vs, self.tok, self.pos, np.int32(slot),
            logits, k1, v1, np.int32(len(prompt)))
        return int(first)

    def clear(self, slot: int) -> None:
        """Retire a slot.  The cache lane is left as-is — the position
        mask hides it, and the next admission's install overwrites it."""
        self.tok = self.tok.at[slot].set(0)
        self.pos = self.pos.at[slot].set(0)

    def step(self) -> np.ndarray:
        """One decode step for the whole batch; returns the [max_batch]
        greedy next-token vector (free slots compute harmless garbage —
        rows are independent)."""
        logits, self.ks, self.vs = self._step(
            self.params, self.tok, self.pos, self.ks, self.vs)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.tok = nxt
        # Clamp so an idle slot parked at the cap can never scatter out
        # of bounds; an active slot retires before reaching it.
        self.pos = jnp.minimum(self.pos + 1, self.cache_len - 1)
        return np.asarray(nxt)
