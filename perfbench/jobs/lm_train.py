"""The decoder LM through ``make_transformer_train_step`` with the Pallas
flash-attention kernel and AdamW, one batch of token rows resident on the
chips."""

from __future__ import annotations

from typing import Dict

from perfbench import compare, ops_count
from perfbench.jobs import _train
from perfbench.reference import lm as ref


def model_sizes(run, section: str) -> Dict:
    """The configuration's published keys under the names the model code
    and the reference use."""
    cfg = dict(run.cell.config)
    if run.rehearsal:
        cfg.update(run.cell.params(section)["rehearsal"].get("config", {}))
    return {"vocab_size": cfg["vocab_size"], "d_model": cfg["hidden_size"],
            "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"],
            "d_ff": cfg["intermediate_size"],
            "rope_theta": cfg["rope_theta"]}


def transformer_config(sizes: Dict, max_seq_len: int, attn_impl: str):
    from horovod_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq_len=max_seq_len,
        rope_theta=sizes["rope_theta"], attn_impl=attn_impl)


def build(run) -> _train.TrainSetup:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod

    p = run.cell.params("train")
    sizes = model_sizes(run, "train")
    seq = run.size("seq_len", "train")
    per_chip = run.size("seqs_per_chip", "train")
    n = len(run.devices)
    tcfg = transformer_config(sizes, seq, p["attn_impl"])
    mesh = mesh_mod.make_mesh({"dp": n}, devices=run.devices)
    o = p["optimizer"]
    opt = optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                      eps=o["eps"], weight_decay=o["weight_decay"])
    step, _ = train_mod.make_transformer_train_step(tcfg, mesh, opt)
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("dp"))
    key = run.rng_key(0)
    make_params = jax.jit(lambda k: ref.make_weights(k, sizes),
                          out_shardings=rep)
    params = make_params(key)
    state = train_mod.TrainState(
        params, jax.jit(opt.init, out_shardings=rep)(params),
        jax.device_put(jnp.zeros((), jnp.int32), rep))
    del params
    toks_np = run.numpy_rng(1).integers(
        1, sizes["vocab_size"], size=(per_chip * n, seq)).astype(np.int32)
    tgts_np = np.roll(toks_np, -1, axis=1)
    batch = (jax.device_put(toks_np, rows), jax.device_put(tgts_np, rows))
    compiled = step.lower(state, *batch).compile()
    if run.devices[0].platform == "tpu" and p["attn_impl"] == "flash" \
            and "tpu_custom_call" not in compiled.as_text():
        raise SystemExit("no tpu_custom_call in the flash LM step")
    scale = 1.0 / (1.0 - o["b1"])
    sub = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))

    def first_grad_norms(st):
        # AdamW: after one step mu = (1 - b1) * g.
        mu = st.opt_state[0].mu
        return {k: v * scale for k, v in compare.leaf_norms(mu).items()}

    def delta_norms(st):
        return compare.leaf_norms(sub(st.params, make_params(key)))

    def reference(quant: bool = False):
        dev = run.devices[0]
        with jax.default_device(dev):
            mk = jax.jit(lambda k: ref.make_weights(k, sizes))
            return ref.Trainer(sizes, o, quant=quant).run(
                lambda: mk(jax.device_put(key, dev)),
                jax.device_put(toks_np, dev), jax.device_put(tgts_np, dev),
                _train.N_FIRST_STEPS)

    heads, hd = sizes["n_heads"], sizes["d_model"] // sizes["n_heads"]
    run.facts["flash_needed"] = ops_count.flash_attention_needed(
        per_chip, heads, seq, hd, sizes["n_layers"])
    return _train.TrainSetup(
        compiled=compiled, state=state, batch=batch,
        items_per_step=per_chip * n * seq, rate_metric=p["rate_metric"],
        first_grad_norms=first_grad_norms, delta_norms=delta_norms,
        reference=reference, limits=p["limits"],
        flops_per_item=ops_count.lm_train_flops_per_token(sizes, seq))


def run(run) -> None:
    _train.run_training(run, build)
