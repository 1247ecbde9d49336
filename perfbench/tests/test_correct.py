"""``correct`` fails when it should.

* The control: the reference's int8 path in the program's place comes out
  as not correct under the cells' own limits (a size a test run can hold).
* The timed path broken underneath the harness (a step that returns its
  state unchanged; a served token altered where it is produced): the rest
  of a run is driven, the chip look-up skipped (``--rehearsal``), and
  ``correct`` comes out false.
"""

import importlib
import json

import pytest

from perfbench import compare, harness


def _run_cell(cell, capsys, job=None, seconds="1"):
    harness.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                  seconds, "--trace", "0", "--rehearsal"], job=job)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# Sizes a test run can hold at which the int8 control still separates from
# the bfloat16 program (rounding noise averages out of short contractions):
# laid over the cells' rehearsal blocks.
TEST_SIZES = {
    "olmo-1b_train_s2048": {
        "seq_len": 256, "seqs_per_chip": 2,
        "config": {"hidden_size": 1024, "intermediate_size": 4096,
                   "num_hidden_layers": 2, "num_attention_heads": 8,
                   "vocab_size": 4096}},
    "resnet50_b128_1chip": {
        "image_size": 64, "per_chip_batch": 32,
        "config": {"blocks": [1, 1, 1, 1], "width": 32,
                   "num_classes": 100}},
}


TELLS_APART = {"olmo-1b_train_s2048": "first_grad_norm_gap",
               "resnet50_b128_1chip": "median_leaf_grad_norm_gap"}


@pytest.mark.parametrize("cell", sorted(TEST_SIZES))
def test_int8_control_is_not_correct_and_the_program_is_training(cell):
    from perfbench.jobs import _train

    run = harness.Run(workload=cell, seed=1, seconds=0, trace=False,
                      rehearsal=True, t_start=0.0)
    block = run.cell.config["train"]["rehearsal"]
    patch = TEST_SIZES[cell]
    block.update({k: v for k, v in patch.items() if k != "config"})
    block["config"].update(patch["config"])
    run.open_devices()
    job = importlib.import_module(
        f"perfbench.jobs.{run.cell.config['family']}_train")
    s = job.build(run)
    state, prog, _ = _train.first_steps(s)
    del state
    ref = s.reference()
    sound = compare.training_checks(prog, ref, s.limits).as_dict()
    control = compare.training_checks(s.reference(quant=True), ref,
                                      s.limits)
    assert not control.correct, control.report()
    # the number that tells the two apart, under the cell's own limit (the
    # others are set for the chip's sizes and are wider apart there)
    name = TELLS_APART[cell]
    assert sound[name]["value"] <= sound[name]["limit"] < \
        control.as_dict()[name]["value"], (sound, control.report())


def test_int8_control_is_not_correct_serving():
    """At each position of seeded token rows, the token the int8 forward
    pass puts first, read on the float32 reference: the widest gap is over
    the serving cell's limit.  Full width, two layers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.jobs import lm_serve
    from perfbench.reference import lm as ref

    cell = harness.Cell("olmo-1b_serve_chat")
    limit = cell.params("serve")["limits"]["logit_gap"]
    c = cell.config
    sizes = {"vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
             "n_layers": 2, "n_heads": c["num_attention_heads"],
             "d_ff": c["intermediate_size"], "rope_theta": c["rope_theta"]}
    weights = ref.make_weights(jax.random.PRNGKey(1), sizes)
    f32, int8 = ref.Forward(sizes), ref.Forward(sizes, quant=True)
    rng = np.random.default_rng(0)
    worst = 0.0
    for _ in range(2):
        seq = jnp.asarray(rng.integers(1, sizes["vocab_size"], (1, 384)),
                          jnp.int32)
        exact = f32.logits(weights, seq)[0]
        first = jnp.argmax(int8.logits(weights, seq)[0], axis=-1)
        assert float(lm_serve.logit_gaps(
            exact, jnp.argmax(exact, axis=-1)).max()) == 0.0
        worst = max(worst, float(lm_serve.logit_gaps(exact, first).max()))
    assert worst > limit


def test_a_rehearsal_prints_no_device_metric_name(capsys):
    out = _run_cell("olmo-1b_train_s2048", capsys)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"rehearsal_setup_s",
                                   "rehearsal_tokens_per_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["olmo-1b_train_s2048",
                                  "resnet50_b128_1chip"])
def test_step_that_returns_its_state_unchanged_is_not_correct(cell, capsys):
    from perfbench.jobs import _train

    job = importlib.import_module(
        "perfbench.jobs." + ("lm_train" if "olmo" in cell
                             else "resnet_train"))

    def broken_build(run):
        import jax
        import jax.numpy as jnp

        s = job.build(run)
        copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

        def step(state, *batch):
            _, loss = s.compiled(copy(state), *batch)
            return state, loss

        return s._replace(compiled=step)

    out = _run_cell(cell, capsys,
                    job=lambda run: _train.run_training(run, broken_build))
    assert out["correct"] is False
    assert out["checks"]["param_delta_norm_gap"]["value"] > \
        out["checks"]["param_delta_norm_gap"]["limit"]


def test_served_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from horovod_tpu.serving.decode import DecodeEngine

    real = DecodeEngine.step

    def altered(self):
        import jax.numpy as jnp

        toks = real(self)
        toks = (toks + 1) % self.cfg.vocab_size
        self.tok = jnp.asarray(toks)
        return toks

    sound = _run_cell("olmo-1b_serve_chat", capsys, seconds="3")
    assert sound["correct"] is True and sound["attempted"] > 0
    monkeypatch.setattr(DecodeEngine, "step", altered)
    out = _run_cell("olmo-1b_serve_chat", capsys, seconds="3")
    assert out["correct"] is False and out["failed"] == 0
    gap = out["checks"]["served_token_logit_gap"]
    assert gap["value"] > gap["limit"]
