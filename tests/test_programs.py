"""telemetry/programs.py: the program names its phases and scopes, the
step makers remember what they compiled, and a compiled text's
instructions are joined to those names.  All on the CPU, at tiny sizes."""

import re
import tracemalloc
from collections import Counter

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu.models import resnet
from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import mesh as mesh_mod
from horovod_tpu.parallel import optimizer as opt_mod
from horovod_tpu.parallel import train as train_mod
from horovod_tpu.telemetry import programs


@pytest.fixture(autouse=True)
def _empty_table():
    programs.forget()
    yield
    programs.forget()


# ---------------------------------------------------------------------------
# phase() and scope(): names as jax writes them
# ---------------------------------------------------------------------------

LAYER = "jit(train_step_lm)/transpose(jvp())/while/body/closed_call/"


@pytest.mark.parametrize("op_name, phase, scope", [
    ("jit(_step)/jvp(ffn)/dot_general", "forward", "ffn"),
    ("jit(_step)/transpose(jvp(ffn))/mul", "backward", "ffn"),
    ("jit(_step)/transpose(jvp(head))/jvp(head)/checkpoint/"
     "rematted_computation/cos", "recompute", "head"),
    ("jit(_step)/optimizer/sub", "optimizer", "optimizer"),
    ("jit(train_step_lm)/jvp()/while/body/closed_call/attention/"
     "bsd,dhk->bshk/dot_general", "forward", "attention"),
    (LAYER + "checkpoint/rematted_computation/ffn/jit(silu)/div",
     "recompute", "ffn"),
    (LAYER + "checkpoint/attention/cond/branch_0_fun/flash_bwd_dq/"
     "pallas_call", "backward", "attention/flash_bwd_dq"),
    ("jit(train_step_resnet_hvd)/shard_map/jvp(stage3)/jvp(norm)/"
     "reduce_sum", "forward", "stage3/norm"),
    ("jit(train_step_resnet_hvd)/shard_map/transpose(jvp(stage3))/"
     "transpose(jvp(norm))/mul", "backward", "stage3/norm"),
    ("jit(train_step_resnet_hvd)/optimizer/grad_reduce/psum", "reduce",
     "optimizer/grad_reduce"),
    ("jit(train_step_resnet_hvd)/stats_reduce/psum", "reduce",
     "stats_reduce"),
    ("jit(train_step_lm)/attention/cos", "other", "attention"),
    ("jit(train_step_lm)/add", "other", ""),
    ("args[0].params['embed']", "other", ""),
    ("", "other", ""),
])
def test_phase_and_scope_of_an_op_name(op_name, phase, scope):
    assert programs.phase(op_name) == phase
    assert programs.scope(op_name) == scope


def test_phase_asks_for_the_reduction_before_the_optimizer():
    """``DistributedOptimizer``'s update holds the reduction
    (``optimizer/grad_reduce``): it is never the optimizer's time."""
    name = "jit(s)/optimizer/grad_reduce/convert_element_type"
    assert programs.phase(name) == "reduce"
    assert programs.scope(name, depth=1) == "optimizer"
    assert "reduce" in programs.PHASES and "mixed" not in programs.PHASES


# ---------------------------------------------------------------------------
# scopes(): a hand-written optimized module
# ---------------------------------------------------------------------------

M = 'metadata={op_name="jit(train_step_x)/%s" stack_frame_id=3}'
HLO = "\n".join([
    "HloModule jit_train_step_x, is_scheduled=true",
    "",
    "FileNames",
    '1 "x.py"',
    "",
    "%fused_wgrad (p0: bf16[8,8], p1: f32[8,8]) -> f32[8,8] {",
    "  %p0 = bf16[8,8]{1,0} parameter(0)",
    "  %p1 = f32[8,8]{1,0:T(8,128)S(1)} parameter(1)",
    "  %conv.1 = f32[8,8]{1,0} convolution(%p0, %p0), dim_labels=bf_io->bf, "
    + M % "transpose(jvp(stage1))/conv_general_dilated",
    "  ROOT %sub.1 = f32[8,8]{1,0} subtract(%p1, %conv.1), "
    + M % "optimizer/sub",
    "}",
    "",
    "%fused_apply (p0: f32[8]) -> f32[8] {",
    "  %p0.1 = f32[8]{0} parameter(0)",
    "  %mul.1 = f32[8]{0} multiply(%p0.1, %p0.1), " + M % "jvp(stem)/mul",
    "  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0.1), "
    + M % "jvp(stem)/jvp(norm)/add",
    "}",
    "",
    "%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {",
    "  %t = (s32[], f32[8]{0}) parameter(0)",
    "  %gte = f32[8]{0} get-tuple-element(%t), index=1",
    "  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, calls=%fused_apply, "
    + M % "jvp()/while/body/closed_call/ffn/add",
    "  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte, %fusion.7)",
    "}",
    "",
    "%cond (t.1: (s32[], f32[8])) -> pred[] {",
    "  %t.1 = (s32[], f32[8]{0}) parameter(0)",
    "  ROOT %lt = pred[] constant(true)",
    "}",
    "",
    "ENTRY %main.9 (a: bf16[8,8], b: f32[8,8]) -> f32[8,8] {",
    '  %a = bf16[8,8]{1,0} parameter(0), metadata={op_name="a"}',
    '  %b = f32[8,8]{1,0} parameter(1), metadata={op_name="b"}',
    "  %multiply_add_fusion.3 = f32[8,8]{1,0} fusion(%a, %b), kind=kOutput, "
    "calls=%fused_wgrad, " + M % "optimizer/sub",
    "  %fusion.2 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fused_apply, "
    + M % "jvp(stem)/jvp(norm)/add",
    "  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) "
    "copy-start(%fusion.2)",
    "  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)",
    "  %while.4 = (s32[], f32[8]{0}) while(%copy-done.1), condition=%cond, "
    "body=%body, " + M % "jvp()/while",
    "  ROOT %out = f32[8,8]{1,0} copy(%multiply_add_fusion.3)",
    "}",
])


def test_scopes_of_a_hand_written_module():
    got = programs.parse(HLO)
    # a two-phase fusion: the convolution's phase, marked mixed
    wgrad = got["multiply_add_fusion.3"]
    assert (wgrad.phase, wgrad.mixed) == ("backward", True)
    assert programs.scope(wgrad.op_name) == "stage1"
    # a fusion without a matmul: its own (its root's), one phase
    assert got["fusion.2"] == programs.Scope(
        "jit(train_step_x)/jvp(stem)/jvp(norm)/add", "forward", False)
    assert programs.scope(got["fusion.2"].op_name) == "stem/norm"
    # a while's body is walked, and the while itself has a name
    assert got["fusion.7"].phase == "forward"
    assert programs.scope(got["fusion.7"].op_name) == "ffn"
    assert got["while.4"].phase == "forward"
    # no metadata: other
    assert got["copy-done.1"] == programs.Scope("", "other", False)
    assert got["out"].phase == "other"
    # a fused computation's own instructions are not the trace's events
    assert "conv.1" not in got and "mul.1" not in got


def test_a_text_without_any_op_name_is_an_empty_map():
    bare = re.sub(r",? ?metadata=\{[^}]*\}", "", HLO)
    assert programs.parse(bare) == {}
    assert programs.scopes("jit_never_compiled") == {}


# ---------------------------------------------------------------------------
# the table: one program a name, the newest
# ---------------------------------------------------------------------------


def test_the_table_keeps_the_newest_program_of_a_name():
    def f(x):
        with jax.named_scope("optimizer"):
            return x * 2.0

    def g(x):
        return x + 1.0

    first = programs.named_jit(f, "train_step_t")
    assert programs.remembered() == ()      # made, called: not remembered
    first(jnp.ones(4))
    assert programs.remembered() == ()
    lowered = first.lower(jnp.ones(4))
    assert "stablehlo" in lowered.as_text()             # passes through
    compiled = lowered.compile()
    assert isinstance(compiled, jax.stages.Compiled)    # jax's own
    assert programs.remembered() == ("jit_train_step_t",)
    assert {s.phase for s in programs.scopes("jit_train_step_t").values()} \
        >= {"optimizer"}
    programs.named_jit(g, "train_step_t").lower(jnp.ones(4)).compile()
    assert programs.remembered() == ("jit_train_step_t",)
    assert "optimizer" not in {
        s.phase for s in programs.scopes("jit_train_step_t").values()}
    programs.forget()
    assert programs.remembered() == ()
    assert programs.scopes("jit_train_step_t") == {}


def test_the_seam_is_the_jit_object_and_costs_a_call_nothing():
    """What ``named_jit`` returns is ``jax.jit``'s own object (donation,
    ``trace``, ``eval_shape`` are its), and a warm call allocates nothing
    in ``telemetry/programs.py``: the zero-cost pin ``tests/test_trace.py``
    keeps for ``span``."""
    def f(state, x):
        return state + x, jnp.sum(x)

    step = programs.named_jit(f, "train_step_t", donate_argnums=(0,))
    assert type(step) is type(jax.jit(f))
    assert step.eval_shape(jnp.ones(4), jnp.ones(4))[0].shape == (4,)
    assert step.trace(jnp.ones(4), jnp.ones(4)).jaxpr is not None
    state = jnp.ones(4)
    state, _ = step(state, jnp.ones(4))
    donated = state
    state, _ = step(state, jnp.ones(4))
    assert donated.is_deleted()
    x = jnp.ones(4)
    tracemalloc.start()
    state, _ = step(state, x)
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    ours = [st for st in snap.statistics("filename")
            if st.traceback[0].filename.endswith("telemetry/programs.py")]
    assert ours == []
    assert programs.remembered() == ()


# ---------------------------------------------------------------------------
# the step makers: every phase has its ops, and the program is the parent's
# ---------------------------------------------------------------------------


def _mesh():
    return mesh_mod.make_mesh({"dp": 1}, devices=jax.devices()[:1])


def _lm(remat):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                                n_heads=2, d_ff=64, max_seq_len=16,
                                remat=remat)
    step, init = train_mod.make_transformer_train_step(
        cfg, _mesh(), optax.adamw(1e-3))
    toks = jnp.zeros((2, 16), jnp.int32)
    return step, (init(jax.random.PRNGKey(0)), toks, toks)


def _resnet():
    cfg = resnet.ResNetConfig(blocks=(1, 1, 1, 1), width=8, num_classes=10)
    dist = opt_mod.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                        axis=("dp",))
    step, init = train_mod.make_resnet_train_step_hvd(cfg, _mesh(), dist)
    return step, (init(jax.random.PRNGKey(0)), jnp.zeros((2, 32, 32, 3)),
                  jnp.zeros((2,), jnp.int32))


def _compiled_phases(make, module):
    step, args = make()
    step.lower(*args).compile()
    got = programs.scopes(module)
    return got, Counter(s.phase for s in got.values())


def test_transformer_step_names_every_phase():
    got, phases = _compiled_phases(lambda: _lm(True), "jit_train_step_lm")
    for phase in ("forward", "recompute", "backward", "optimizer", "other"):
        assert phases[phase], phase
    update = [s for s in got.values() if s.phase == "optimizer"]
    assert all("transpose(" not in s.op_name for s in update)
    assert {programs.scope(s.op_name) for s in update} == {"optimizer"}
    scoped = {programs.scope(s.op_name, 1) for s in got.values()}
    assert scoped >= {"embed", "attention", "ffn", "head_loss", "optimizer"}
    # AdamW's update of a 2-D leaf is one of them
    assert any(s.op_name.endswith(("/add", "/mul", "/sub", "/div"))
               for s in update)


def test_recompute_appears_with_remat_and_vanishes_without():
    _, without = _compiled_phases(lambda: _lm(False), "jit_train_step_lm")
    assert without["recompute"] == 0 and without["backward"]
    _, with_remat = _compiled_phases(lambda: _lm(True), "jit_train_step_lm")
    assert with_remat["recompute"] > 0


def test_resnet_hvd_step_names_every_phase_and_scope():
    got, phases = _compiled_phases(_resnet, "jit_train_step_resnet_hvd")
    for phase in ("forward", "backward", "optimizer", "other"):
        assert phases[phase], phase
    assert phases["recompute"] == 0
    scoped = {programs.scope(s.op_name) for s in got.values()}
    assert scoped >= {"stem", "stem/norm", "stage1", "stage4/norm",
                      "head_loss", "optimizer"}
    # The reduction lies inside the optimizer's update and is not its time.
    inside = [s for s in got.values() if "/grad_reduce/" in s.op_name]
    assert all(s.phase == "reduce" for s in inside)
    assert all(s.phase == "reduce" for s in got.values()
               if "/stats_reduce/" in s.op_name)


def stripped(text):
    """An optimized module's text without what only names it: the
    ``metadata={...}`` of its instructions, the frame tables above its
    computations, the module's own name, and the number the compiler's
    uniquer gave an instruction's or a computation's name (``convert.142``
    is ``convert`` and the order it first appears in: the base comes from
    the ``op_name``'s primitive, the number from how many such names were
    asked for before, which one differing ``op_name`` moves)."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    lines = text.splitlines()
    lines[0] = re.sub(r"^HloModule \S+", "HloModule X", lines[0])
    text = "\n".join(line for line in lines if not re.match(
        r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ )", line))
    order = {}

    def renumbered(m):
        base = re.sub(r"[.\d]+$", "", m.group(1))
        return f"%{base}#{order.setdefault(m.group(1), len(order))}"

    return re.sub(r"%([\w.\-]+)", renumbered, text)


@pytest.mark.parametrize("make", [lambda: _lm(True), _resnet, "mnist"],
                         ids=["lm", "resnet_hvd", "mnist"])
def test_the_optimized_step_is_the_bare_jits(make, monkeypatch):
    """Scopes are metadata and the seam is ``jax.jit``: the optimized
    text equals the one the same ``_step`` gives under a bare ``jax.jit``
    (the parent's form), instruction for instruction."""
    if make == "mnist":
        def make():
            step, init = train_mod.make_mnist_train_step(_mesh())
            return step, (init(jax.random.PRNGKey(0)),
                          jnp.zeros((4, 28, 28, 1)),
                          jnp.zeros((4,), jnp.int32))

    step, args = make()
    named = step.lower(*args).compile().as_text()
    monkeypatch.setattr(train_mod.programs, "named_jit",
                        lambda fn, name, **kw: jax.jit(fn, **kw))
    step, args = make()
    bare = step.lower(*args).compile().as_text()
    assert named.splitlines()[0].startswith("HloModule jit_train_step_")
    assert not bare.splitlines()[0].startswith("HloModule jit_train_step_")
    assert stripped(named) == stripped(bare)
