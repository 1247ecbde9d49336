"""horovod_tpu.serving — continuous-batching LM inference on the gang.

The north star serves heavy traffic, not just training throughput: this
package turns a model's single-request decode into a served workload
with a latency SLO.  The model is chosen by the type of the config handed
to :class:`ServingLoop`: a :class:`TransformerConfig` (the dense decoder,
models/transformer.py: a K/V cache a slot) or a :class:`JambaConfig`
(models/jamba.py: Mamba layers beside attention, so a slot holds
recurrent state beside a small K/V lane) or a :class:`LatentMoEConfig`
(models/latent_moe.py: latent attention, so a slot's lane is a latent and
one rotary key a position, and routed experts that drop no row); ``init``
of the same module makes its weights.

Shape of the system (docs/serving.md):

* Rank 0 runs the HTTP front door (``POST /generate`` / ``GET /stats``,
  server.py) and the admission :class:`Scheduler` (scheduler.py), which
  packs prompts into the running batch at token boundaries —
  join-at-prefill, retire-at-EOS/max-len, per-slot position tracking.
* Each decode step, rank 0 broadcasts the batch delta over the control
  channel (TAG_SERVE, runtime_py.serve_broadcast) so EVERY rank steps
  the same jit-ed decode function (:class:`DecodeEngine`, decode.py) in
  lockstep; decode is greedy, so all ranks compute identical tokens and
  retire identical slots without further coordination.
* Robustness composes with the existing machinery instead of being
  rebuilt: each step's token-agreement allreduce gives the PR-6
  collective deadline a data-plane op to bound and feeds the straggler
  detector; on a gang abort the loop re-forms via ``@hvd.elastic.run``
  and replays in-flight requests from their prompts (at-least-once,
  loop.py).
"""

from horovod_tpu.models.jamba import JambaConfig
from horovod_tpu.models.latent_moe import LatentMoEConfig
from horovod_tpu.models.transformer import TransformerConfig
from horovod_tpu.serving.decode import DecodeEngine, SlotModel, slot_model
from horovod_tpu.serving.loop import ServingLoop
from horovod_tpu.serving.scheduler import QueueFull, Request, Scheduler
from horovod_tpu.serving.server import FrontDoor

__all__ = [
    "DecodeEngine",
    "FrontDoor",
    "JambaConfig",
    "LatentMoEConfig",
    "QueueFull",
    "Request",
    "Scheduler",
    "ServingLoop",
    "SlotModel",
    "TransformerConfig",
    "slot_model",
]
