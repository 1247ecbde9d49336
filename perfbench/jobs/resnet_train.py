"""ResNet through the Horovod-contract step: ``DistributedOptimizer`` over
the ``dp`` axis -> ``make_resnet_train_step_hvd``, one synthetic batch
resident on the chips, as in the reference's synthetic benchmark."""

from __future__ import annotations

from typing import Dict

from perfbench import compare, ops_count
from perfbench.jobs import _train
from perfbench.reference import resnet as ref


def program_step(run, cfg: Dict, mesh, opt):
    """The system under test: ``(jitted step, ResNetState class)``."""
    from horovod_tpu.models import resnet
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod

    rcfg = resnet.ResNetConfig(blocks=tuple(cfg["blocks"]),
                               width=cfg["width"],
                               num_classes=cfg["num_classes"])
    dist = opt_mod.DistributedOptimizer(opt, axis=("dp",))
    step, _ = train_mod.make_resnet_train_step_hvd(rcfg, mesh, dist)
    return step, dist, train_mod.ResNetState


def build(run) -> _train.TrainSetup:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import mesh as mesh_mod

    cfg = dict(run.cell.config)
    p = run.cell.params("train")
    image = run.size("image_size", "train")
    cfg["image_size"] = image
    if run.rehearsal:
        cfg.update(p["rehearsal"].get("config", {}))
    per_chip = run.size("per_chip_batch", "train")
    n = len(run.devices)
    mesh = mesh_mod.make_mesh({"dp": n}, devices=run.devices)
    o = p["optimizer"]
    opt = optax.sgd(o["learning_rate"], momentum=o["momentum"])
    step, dist, State = program_step(run, cfg, mesh, opt)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("dp"))
    key, data_key = run.rng_key(0), run.rng_key(1)

    def make_state(k):
        params, stats = ref.make_weights(k, cfg)
        return State(params, stats, dist.init(params),
                     jnp.zeros((), jnp.int32))

    make_state = jax.jit(make_state, out_shardings=rep)

    def make_batch(k):
        a, b = jax.random.split(k)
        return (jax.random.uniform(a, (per_chip * n, image, image, 3),
                                   jnp.float32),
                jax.random.randint(b, (per_chip * n,), 0,
                                   cfg["num_classes"], jnp.int32))

    batch = jax.jit(make_batch, out_shardings=(rows, rows))(data_key)
    state = make_state(key)
    compiled = step.lower(state, *batch).compile()
    if n > 1 and "all-reduce" not in compiled.as_text():
        raise SystemExit("no all-reduce in the data-parallel step")
    sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))

    def first_grad_norms(st):
        # SGD with momentum: after one step the trace IS the gradient the
        # optimizer was given (after the all-reduce).
        return compare.leaf_norms(_train_mirror(st.opt_state, st.params))

    def delta_norms(st):
        return compare.leaf_norms(sub(st.params, make_state(key).params))

    def reference(quant: bool = False):
        dev = run.devices[0]
        with jax.default_device(dev):
            images = jax.device_put(batch[0], dev)
            labels = jax.device_put(batch[1], dev)
            mk = jax.jit(lambda k: ref.make_weights(k, cfg)[0])
            return ref.Trainer(cfg, o, quant=quant).run(
                lambda: mk(jax.device_put(key, dev)), images, labels, n,
                _train.N_FIRST_STEPS)

    return _train.TrainSetup(
        compiled=compiled, state=state, batch=batch,
        items_per_step=per_chip * n, rate_metric=p["rate_metric"],
        first_grad_norms=first_grad_norms, delta_norms=delta_norms,
        reference=reference, limits=p["limits"],
        flops_per_item=ops_count.resnet_train_flops_per_image(cfg))


def _train_mirror(opt_state, params):
    """The one subtree of ``opt_state`` shaped like ``params``."""
    import jax

    want = jax.tree.structure(params)
    found = []

    def visit(node):
        try:
            if jax.tree.structure(node) == want:
                found.append(node)
                return
        except Exception:
            pass
        if isinstance(node, (tuple, list)):
            for c in node:
                visit(c)
        elif isinstance(node, dict):
            for c in node.values():
                visit(c)

    visit(opt_state)
    if len(found) != 1:
        raise SystemExit(f"expected one momentum trace in the optimizer "
                         f"state, found {len(found)}")
    return found[0]


def run(run) -> None:
    _train.run_training(run, build)
