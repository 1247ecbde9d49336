"""Per-rank continuous-batching decode state.

One :class:`DecodeEngine` lives on every rank of the serving gang and
holds the state of ``max_batch`` slots and the per-slot current token and
position vectors.  What that state IS belongs to the model: one pytree
that the model's module makes, fills from a prompt and steps.  This file
has no code per model: every served module presents the same names (see
``MODELS``), and :func:`slot_model` binds them to a config through one
builder.  The state's top-level keys are the kinds of state a slot holds:

* ``"kv"``: position-indexed keys and values.  A step attends a slot's
  lane as far as the slot's position and no further, so a retired lane
  is never read; the next install overwrites it.
* ``"recurrent"``: fixed-size state with no mask (a state-space layer's).
  The install overwrites ALL of a slot's, so nothing of its last tenant
  reaches the next (pinned by tests/test_serving_cache.py for every
  served form, and by tests/test_jamba.py on the served tokens).
* ``"index"``: position-indexed keys of a learned indexer, one a position
  a layer beside the ``"kv"`` lane it selects from; read as far as the
  slot's position, like a lane.
* ``"counters"``: a dict of the registry's counter names to uint32
  scalars that the model's step adds to ON THE DEVICE (what it really
  routed; what its attention read of the lanes).  No slot owns them, an
  install passes them through, and a turn never reads them: the engine
  brings them to the host beside the read an admission makes anyway (the
  prefill's first token) and adds what they grew by to the registry, when
  the registry is on.  A request's state may hold ``"counted"``
  (what its prefill counted, by counter name): the install adds it to the
  batch's counters.
  Which two of them make a share on ``GET /stats``
  is declared with them (``telemetry/registry.py``), not here.

The install is ONE function for every model (``models/layers.py``:
every slot-kind leaf of the request over the slot's, whole), told by the
model's ``SLOT_AXES`` along which axis of each leaf the slots lie.

Donation.  The state is ONE resident set of buffers: the two programs
that write it, the jit-ed step (B new rows, or one recurrent update, a
layer) and the install that ends a prefill (one slot's share), take it
donated and update it in place, so neither a turn nor an admission copies
it or holds a second one (pinned on the compiled programs by
tests/test_serving_cache.py and tests/test_jamba.py, and on the chip by
the benchmark's ``peak_hbm_gb.serve`` and op breakdown).  The per-slot
math is that of the model's single-request path, so a slot's output never
depends on what its neighbors are decoding (pinned by
tests/test_serving.py and tests/test_jamba.py oracles).

The parameters an engine holds are the model's ``serving_params``, made
once when it is built, or the given ones where the module has none.  The
dense decoder's: every leaf that its forward casts to the compute type at
its use, in that type (the norm gains, used in float32, stay float32), so
that the cast at the use is a no-op and every matmul takes the rounding
of its weight that it took before: the programs' results are equal to the
bit (pinned on the CPU by tests/test_serving_cache.py).  Given float32
weights and left to cast at the use, the step's layer scan has the casts
of ALL layers' weights hoisted out of the loop by XLA and run whole on
every turn.  And why ``wqkv``: its attention's three input projections
``[L, D, H, HD]`` are held as ONE leaf ``[L, D, 3 H HD]`` (the same
bytes), because ``"bsd,dhk->bshk"`` lowers on the chip as a convolution
with a window over the heads, into which XLA does not fold the layer
scan's slice of the stack: each layer's three weights were staged in fast
memory by an op of their own, while the plain product ``"bsd,df->bsf"``
reads its layer out of the stack in place (pinned on the step and the
prefill compiled for the chip by tests/test_chip_smoke.py; PERF.md
sections 5 and 6 hold the readings).  Where the given ``wq`` lies split
over its heads (``tp`` > 1) joined columns would not split by heads and
the three are held as given.  A leaf already in its use's type is held as
the same buffer, a sharded leaf keeps its sharding, and the engine keeps
no reference to a leaf it replaced.  ``hvd_serve_param_bytes{dtype}``
says what is held.

Under a mesh the state shards by the model's ``STATE_SPEC`` (the dense
decoder's: KV_CACHE_SPEC, heads over ``tp``), applied with ``filter_spec``
so a spec axis missing from the mesh degrades to replication; a module
that has none refuses a mesh.

Dispatch and read.  A step has two halves.  ``dispatch()`` queues the
jit-ed step and the three lazy ops after it (the greedy ``argmax``, the
token and the position advance) and waits for nothing: the next step's
inputs are device arrays.  ``read()`` brings the oldest unread token
vector to the host, and waits for that step only.  ``ServingLoop``
dispatches step k before it reads step k-1, so the chip is never idle for
the host's part of a turn; ``step()`` is the two in order.  A slot cleared
between a step's dispatch and its read has had one more row computed: the
row stays in bounds (the position clamp), reaches no other row, and the
next install overwrites what it wrote.

Prefill compiles once per distinct prompt length (the serving analogue
of generate()'s per-shape compile); the install takes the slot as a
traced scalar and compiles once.  The three programs carry names of the
engine's own, the same for every model behind the seam (``STEP_PROGRAM``,
``PREFILL_PROGRAM`` with the prompt length, ``INSTALL_PROGRAM``): a
profile's ``XLA Modules`` line reads ``jit_serve_step(...)``,
``jit_serve_prefill_s1024(...)`` and ``jit_serve_install(...)``, and the
benchmark's readers of the device's own times match them
(``perfbench/readers/module_ms.py``).  Greedy sampling only: determinism
is what lets every rank step without exchanging tokens and lets a
re-formed gang replay a request to the identical completion.
"""

from __future__ import annotations

from collections import Counter, deque
from functools import partial
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from horovod_tpu.models import conv_moe as C
from horovod_tpu.models import jamba as J
from horovod_tpu.models import latent_moe as X
from horovod_tpu.models import retention as R
from horovod_tpu.models import ssd_moe as S
from horovod_tpu.models import transformer as T
from horovod_tpu.models.layers import install_request
from horovod_tpu.telemetry import registry as _tmx

STATE_KINDS = ("kv", "recurrent", "index")

# What the engine's three compiled programs are called, in HLO
# (``module @jit_serve_step``) and on a profile's ``XLA Modules`` line.  A
# prefill program's name ends in its prompt length: ``serve_prefill_s1024``.
STEP_PROGRAM = "serve_step"
PREFILL_PROGRAM = "serve_prefill"
INSTALL_PROGRAM = "serve_install"


def named(name: str, fn: Callable) -> Callable:
    """``fn`` as a function called ``name``.  jax names a jit-ed program
    after its function's ``__name__``, and a ``functools.partial``, which
    is what :func:`slot_model` binds, has none: every program of every
    model would be ``jit__unknown``.  Nothing else of the program
    changes."""
    def program(*args):
        return fn(*args)

    program.__name__ = program.__qualname__ = name
    return program


class SlotModel(NamedTuple):
    """A model's side of the seam.  ``state`` is its pytree, keyed by kind
    at the top; a request's state is what ``install`` takes.

    * ``init_state(max_batch)`` -> state, zeros
    * ``prefill(params, prompt [S])`` -> (logits [V], request state)
    * ``install(state, slot, request state)`` -> state
    * ``step(params, tok [B], pos [B], state)`` -> (logits [B, V], state)
    * ``spec``: the state's PartitionSpec pytree, or None (no mesh)
    * ``held(params)`` -> the ``params`` that ``prefill`` and ``step``
      take: each leaf the forward casts at its use, in the type of that
      cast and the form its products read in place; a leaf already so is
      the given buffer
    """
    init_state: Callable
    prefill: Callable
    install: Callable
    step: Callable
    spec: Any
    held: Callable


# Config type -> the module that serves it; a further model is its module
# and a line here.  A module MUST present ``init_state(cfg, max_batch,
# cache_len)`` with ``SLOT_AXES`` beside it (for each slot kind of the
# state, the axis of each leaf that the slots lie along),
# ``prefill_request(params, prompt, cfg, cache_len)`` and
# ``decode_step(params, tok, pos, state, cfg)``.  It MAY omit
# ``STATE_SPEC`` (the state's sharding: without one a mesh is refused, and
# with one ``decode_step`` takes ``mesh=``) and ``serving_params(params,
# cfg)`` (the form the engine holds the parameters in: without it, as
# given).  Its device counters are declared in ``telemetry/registry.py``.
MODELS = {T.TransformerConfig: T, J.JambaConfig: J, X.LatentMoEConfig: X,
          R.RetentionConfig: R, S.SsdMoEConfig: S, C.ConvMoEConfig: C}


def slot_model(cfg, cache_len: int, mesh=None) -> SlotModel:
    """The model is chosen by the type of its config.  A module whose
    state can be sharded (``STATE_SPEC``) is told the ``mesh`` its step
    runs under, if there is one."""
    module = MODELS.get(type(cfg))
    if module is None:
        raise TypeError(f"no serving path for a {type(cfg).__name__}")
    spec = getattr(module, "STATE_SPEC", None)
    held = getattr(module, "serving_params", None)
    under = {} if mesh is None or spec is None else {"mesh": mesh}
    return SlotModel(
        partial(module.init_state, cfg, cache_len=cache_len),
        partial(module.prefill_request, cfg=cfg, cache_len=cache_len),
        partial(install_request, axes=module.SLOT_AXES),
        partial(module.decode_step, cfg=cfg, **under), spec,
        (lambda params: params) if held is None else partial(held, cfg=cfg))


def install(model: SlotModel, state, tok, pos, slot, logits, request,
            length):
    """End of a prefill: write the request's state into slot ``slot`` of
    the batch's and set the slot's token (greedy, from the prefill's
    ``logits`` [V]) and position.  Returns (first token, state, tok,
    pos)."""
    first = jnp.argmax(logits).astype(jnp.int32)
    return (first, model.install(state, slot, request),
            tok.at[slot].set(first), pos.at[slot].set(length))


class DecodeEngine:
    def __init__(self, params, cfg, *, max_batch: int,
                 cache_len: Optional[int] = None, mesh=None):
        self.cfg = cfg
        self.max_batch = max_batch
        self.cache_len = cache_len or cfg.max_seq_len
        self.mesh = mesh
        self.model = slot_model(cfg, self.cache_len, mesh)
        self.params = self.model.held(params)
        held_bytes: Counter = Counter()
        for leaf in jax.tree.leaves(self.params):
            held_bytes[str(leaf.dtype)] += leaf.nbytes
        for dtype, nbytes in held_bytes.items():
            _tmx.set_gauge("hvd_serve_param_bytes", nbytes, labels=(dtype,))
        self.state = self.model.init_state(max_batch)
        sharding = None
        if mesh is not None:
            if self.model.spec is None:
                raise NotImplementedError(
                    f"serving a {type(cfg).__name__} under a mesh: its "
                    "state has no sharding spec (recurrent state under tp, "
                    "routed experts under ep are not written); serve it "
                    "with mesh=None")
            from horovod_tpu.parallel.mesh import sharding_for

            sharding = jax.tree.map(
                partial(sharding_for, mesh), self.model.spec,
                is_leaf=lambda s: isinstance(s, PartitionSpec))
            self.state = jax.device_put(self.state, sharding)
        for kind in STATE_KINDS:
            _tmx.set_gauge(
                "hvd_serve_state_bytes",
                sum(a.nbytes for a in jax.tree.leaves(
                    self.state.get(kind, ()))), labels=(kind,))
        # The device counters' values when the registry last heard of them.
        self._published: Dict[str, int] = {}
        self.tok = jnp.zeros((max_batch,), jnp.int32)
        self.pos = jnp.zeros((max_batch,), jnp.int32)
        # The state leaves both programs as it entered them: the same
        # buffers (donated), under the same sharding.
        self._step = jax.jit(named(STEP_PROGRAM, self.model.step),
                             donate_argnums=(3,),
                             out_shardings=(None, sharding))
        self._install = jax.jit(
            named(INSTALL_PROGRAM, partial(install, self.model)),
            donate_argnums=(0,), out_shardings=(None, sharding, None, None))
        # A live slot moves one position on, clamped so that it can never
        # scatter out of bounds (it retires before the cap); a free slot
        # stays at 0, which is how a model's step can tell it is free.
        cap = self.cache_len - 1
        self._advance = jax.jit(lambda pos: jnp.where(
            pos > 0, jnp.minimum(pos + 1, cap), 0))
        self._prefills: Dict[int, object] = {}  # prompt len -> jit fn
        # Token vectors of steps dispatched and not read yet, oldest
        # first (device arrays; ServingLoop keeps at most one).
        self._unread: Deque[jax.Array] = deque()
        # Steps dispatched so far: the ordinal (from 0) of the step the
        # next dispatch() queues; the next read() is of step
        # ``steps - unread``.
        self.steps = 0

    def prefill(self, slot: int, prompt: List[int]) -> int:
        """Run the prompt through the model, install its state into the
        slot, and return the first sampled (greedy) token.  The slot is
        live from the next step() on."""
        fn = self._prefills.get(len(prompt))
        if fn is None:
            fn = self._prefills[len(prompt)] = jax.jit(named(
                f"{PREFILL_PROGRAM}_s{len(prompt)}", self.model.prefill))
        logits, request = fn(self.params, jnp.asarray(prompt, jnp.int32))
        first, self.state, self.tok, self.pos = self._install(
            self.state, self.tok, self.pos, np.int32(slot), logits,
            request, np.int32(len(prompt)))
        first = int(first)          # waits for the install: so is the state
        if _tmx.enabled():
            self.publish_counters()
        return first

    def counters(self) -> Dict[str, int]:
        """The state's device counters, read to the host (waits for the
        steps dispatched); {} for a model that counts nothing."""
        return {name: int(v) for name, v in jax.device_get(
            self.state.get("counters", {})).items()}

    def publish_counters(self) -> None:
        """Add to the registry what the device counters grew by since the
        last call (uint32: they wrap, the difference does not)."""
        for name, value in self.counters().items():
            grown = (value - self._published.get(name, 0)) % (1 << 32)
            self._published[name] = value
            if grown:
                _tmx.inc_counter(name, grown)

    def clear(self, slot: int) -> None:
        """Retire a slot: its position goes to 0 and stays there.  Its
        state is left as-is — a key/value lane is read no further than
        the slot's position, an idle slot's recurrent state reaches no
        other row, and the next admission's install overwrites both."""
        self.tok = self.tok.at[slot].set(0)
        self.pos = self.pos.at[slot].set(0)

    def dispatch(self) -> None:
        """Queue one decode step for the whole batch.  Free slots, at
        position 0, compute harmless garbage: rows are independent, and a
        model leaves them out of work that is not harmless
        (models/latent_moe.py: out of its routing).  Nothing here waits
        for the chip: the step's token vector joins the unread ones, and
        is the next step's input already."""
        logits, self.state = self._step(
            self.params, self.tok, self.pos, self.state)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.tok = nxt
        self.pos = self._advance(self.pos)
        self._unread.append(nxt)
        self.steps += 1

    @property
    def unread(self) -> int:
        """Steps dispatched whose token vector has not been read."""
        return len(self._unread)

    def read(self) -> np.ndarray:
        """The oldest unread step's [max_batch] greedy next-token vector,
        on the host: waits for that step, not for those queued behind."""
        return np.asarray(self._unread.popleft())

    def step(self) -> np.ndarray:
        """One decode step, dispatched and read."""
        self.dispatch()
        return self.read()
