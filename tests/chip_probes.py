"""Fresh-interpreter probes behind ``tests/test_chip_smoke.py``.

Each probe needs an interpreter of its own (``jax.config`` is process-wide;
libtpu admits one process at a time), so it runs as
``python chip_probes.py <probe> [args]``.  :class:`Probes` starts them all
at once; ``tests/conftest.py`` does so while collection is still importing
the remaining test modules on one core, so by the time the tests ask for
the results the ~14 s of libtpu start-up and compiles have cost the suite
nothing.
"""

import json
import math
import os
import re
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Mesh shapes a flash LM step must lower for: plain data parallel, and the
# two whose automatic axes (ep, dcn) Mosaic used to refuse to partition.
MESHES = ({"dp": 4}, {"dp": 2, "ep": 2}, {"dcn": 2, "dp": 2})


def probe_cache():
    """Where the compile cache went, and that something was written."""
    import jax

    from horovod_tpu.utils.platform import enable_compile_cache

    path = enable_compile_cache()
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0)).block_until_ready()
    print(json.dumps([path, jax.config.jax_compilation_cache_dir]))


def probe_bare_init():
    """Which JAX backends a bare ``hvd.init()`` left initialised."""
    import horovod_tpu as hvd

    hvd.init()
    import jax

    print("BACKENDS", sorted(jax._src.xla_bridge._backends))
    hvd.shutdown()


def serve_cache_programs(cfg, slots, min_elems, sharding=None,
                         weight_elems=None):
    """The two programs that write the serving slots' state (the decode
    step and the install that ends a prefill) as ``DecodeEngine`` builds
    them for ``cfg``'s model from the parameters it HOLDS of those its
    ``init`` makes, compiled from shapes alone for the default device or
    for ``sharding``'s: what each produces of ``min_elems`` elements or
    more (:func:`big_ops`), its temporaries, its aliased bytes and what it
    converts to the compute type (:func:`converts_to`).  With
    ``weight_elems``, the size of one layer's weight matrix, also the
    prefill of a ``PROMPT``-token request, and under ``"weight_ops"``
    what each program produces of that size or more: a layer's weight
    cut out of its stack before the product that reads it."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from horovod_tpu.serving import decode

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def specs(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    init = decode.MODELS[type(cfg)].init
    model = decode.slot_model(cfg, cfg.max_seq_len)
    params = specs(jax.eval_shape(lambda k: model.held(init(k, cfg)),
                                  jax.random.PRNGKey(0)))
    state = specs(jax.eval_shape(lambda: model.init_state(slots)))
    request = specs(jax.eval_shape(lambda: model.init_state(1)))
    lowered = {
        "step": jax.jit(model.step, donate_argnums=(3,)).lower(
            params, spec((slots,)), spec((slots,)), state),
        "install": jax.jit(partial(decode.install, model),
                           donate_argnums=(0,)).lower(
            state, spec((slots,)), spec((slots,)), spec(()),
            spec((cfg.vocab_size,), jnp.float32), request, spec(()))}
    if weight_elems is not None:
        lowered["prefill"] = jax.jit(model.prefill).lower(
            params, spec((PROMPT,)))
    out = {}
    for name, program in lowered.items():
        compiled = program.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        # the Mosaic calls' instruction names, less their number
        calls = [n.rsplit(".", 1)[0] for n in re.findall(
            r"^\s*%?([\w.\-]+) = .*custom_call_target="
            r"\"tpu_custom_call\"", text, re.M)]
        out[name] = {"big_ops": big_ops(text, min_elems),
                     "converts": converts_to(
                         text, jnp.dtype(cfg.compute_dtype).name),
                     "temp_bytes": mem.temp_size_in_bytes,
                     "alias_bytes": mem.alias_size_in_bytes,
                     "kernels": sorted(set(calls)),
                     "kernel_calls": {k: calls.count(k) for k in set(calls)}}
        if weight_elems is not None:
            out[name]["weight_ops"] = big_ops(text, weight_elems)
    return out


# A prompt short enough that no activation of its prefill is as large as a
# layer's projection at SERVE_CACHE's widths (128 x 3 x 2048 elements
# against 2048 x 2048).
PROMPT = 128


# The benchmark's cache shape (32 slots x 1536 x 16 heads of 128) with two
# layers and a narrow feed-forward, so that the weights are smaller than
# one layer's lane of the cache.
SERVE_CACHE = dict(slots=32, vocab_size=1024, d_model=2048, n_layers=2,
                   n_heads=16, d_ff=512, max_seq_len=1536)


# The new cell's state shapes (64 slots x 1536; d_inner 5120, 16 states,
# one key/value head of 128) with two short periods of layers and a
# narrow feed-forward and vocabulary, so that the probe compiles in
# seconds.
SERVE_STATE = dict(slots=64, vocab_size=1024, hidden_size=2560,
                   intermediate_size=512, num_hidden_layers=8,
                   attn_layer_period=4, attn_layer_offset=1,
                   max_seq_len=1536)


# The glm-4.7-flash cell's lanes (64 slots x 4608 positions x a latent
# of 512 and a rotary key of 64) and its attention's widths, with three
# layers (one dense, two of experts), eight narrow experts and a narrow
# vocabulary, so that the probe compiles in seconds.
SERVE_LATENT = dict(slots=64, vocab_size=1024, intermediate_size=512,
                    moe_intermediate_size=256, num_hidden_layers=3,
                    n_routed_experts=8, max_seq_len=4608)


# The deepseek-v3.2 cell's lanes (24 slots x 18432 positions x a latent
# of 512, a rotary key of 64 and an index key of 128), its attention's and
# its indexer's widths and its routing (256 outputs in 8 groups, 16 experts
# held), with three layers, a narrow feed-forward and a narrow vocabulary,
# so that the probe compiles in seconds.
SERVE_SPARSE = dict(slots=24, vocab_size=1024, hidden_size=7168,
                    intermediate_size=512, moe_intermediate_size=256,
                    num_hidden_layers=3, num_attention_heads=128,
                    q_lora_rank=1536, qk_nope_head_dim=128, v_head_dim=128,
                    n_routed_experts=256, num_experts_per_tok=8, n_group=8,
                    topk_group=4, experts_held=16, index_n_heads=64,
                    index_head_dim=128, index_topk=2048, rope_theta=10000.0,
                    rope_scaling={"type": "yarn", "factor": 40,
                                  "beta_fast": 32, "beta_slow": 1,
                                  "mscale": 1, "mscale_all_dim": 1,
                                  "original_max_position_embeddings": 4096},
                    max_seq_len=18432)


# The brumby-14b cell's state (32 slots x 8 key/value heads x a
# [128, 8320] float32 matrix a layer) and its retention's widths, with two
# layers and a narrow feed-forward and vocabulary, so that the probe
# compiles in seconds.
SERVE_RETENTION = dict(slots=32, vocab_size=1024, intermediate_size=512,
                       num_hidden_layers=2, max_seq_len=4736)


# The nemotron-3-nano-30b-a3b cell's state and weights as it serves them
# (96 slots x 4096; four Mamba-2 layers' [64, 64, 128] float32 states, one
# attention layer's two key/value heads of 128, 64 held experts of 2688 x
# 1856 in stacks padded to whole tiles) with a narrow vocabulary.
SERVE_SSD = dict(slots=96, vocab_size=1024, num_hidden_layers=9,
                 hybrid_override_pattern="EMEMEMEM*", experts_held=64,
                 max_seq_len=4096)


# The lfm2-8b-a1b cell's state as it serves it (192 slots x 2048; key/value
# lanes of 8 heads x 64 held side by side, 512 a position; a window of two
# rows of 2048 a short-convolution layer) at the published operator widths,
# with seven layers (one dense, two of them attention), eight experts and a
# narrow vocabulary, so that the probe compiles in seconds.
SERVE_CONV = dict(slots=192, vocab_size=1024, num_hidden_layers=7,
                  layer_types=("conv", "conv", "full_attention", "conv",
                               "conv", "conv", "full_attention"),
                  num_dense_layers=1, num_experts=8, max_seq_len=2048)


# Sequence lengths at which the attention kernel's gradient is compiled
# alone: the training cell's (four blocks of 512 a row) and one whose block
# falls under the chip's 128 lanes (2112 = 33 x 64).
FLASH_ALONE = (2048, 2112)


def flash_grad_calls(seq_len, sharding):
    """``jax.grad`` of ``flash_attention`` alone, compiled for one ``v5e``
    chip at the training cell's block structure (2 heads of 128,
    bfloat16): each Mosaic call's instruction as ``[name, text]``, the
    text its results' and its operands' types (the instruction up to its
    ``backend_config``, which is the kernel itself)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, seq_len, 2, 128), jnp.bfloat16,
                             sharding=sharding)
    text = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    return re.findall(
        r"^\s*%?(\S+) = (.*custom_call_target=\"tpu_custom_call\".*?)"
        r"(?:, backend_config=.*)?$", text, re.M)


# Result shapes a transposition around the attention kernel's calls would
# have at the LM training cell's widths: [B, H, S, D] (with [B, S, H, D] in
# a layout of its own), the folded [B * H, S, D], and [B, S, H * D].
FLASH_LAYOUT_SHAPES = ("[8,16,2048,128]", "[128,2048,128]", "[8,2048,2048]")


def flash_step_layout_copies(device):
    """The flash LM step at ``olmo-1b_train_s2048``'s widths (two layers;
    8 rows of 2048 tokens, 16 heads of 128) compiled for one ``v5e`` chip:
    the names of its Mosaic calls, and every ``copy`` or ``transpose``
    INSIDE THE TWO SCANS' BODIES (fusions they call included) whose result
    has one of :data:`FLASH_LAYOUT_SHAPES`, as ``[name, result type]``: what
    XLA turns round to feed the kernel's calls or to take their results
    on.  The step's copies of ``[8,2048,2048]`` outside the scans (the
    embedding's, the last layer's) are not the kernel's."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod

    cfg = tfm.TransformerConfig(
        vocab_size=50304, d_model=2048, n_layers=2, n_heads=16, d_ff=8192,
        max_seq_len=2048, attn_impl="flash")
    mesh = mesh_mod.make_mesh({"dp": 1}, devices=[device])
    step, init = train_mod.make_transformer_train_step(
        cfg, mesh, optax.adamw(1e-3, weight_decay=0.01))
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((8, 2048), jnp.int32)
    text = step.lower(state, toks, toks).compile().as_text()
    comps = computations(text)
    bodies = set(re.findall(r"\bwhile\(.*?\bbody=%?([\w.\-]+)", text))
    inside = bodies | {calls for comp in bodies
                       for _, _, _, op, calls in comps[comp] if calls}
    turned = [[name, shape] for comp in sorted(inside)
              for _, name, shape, op, _ in comps.get(comp, ())
              if op in ("copy", "transpose")
              and any(dims in shape for dims in FLASH_LAYOUT_SHAPES)]
    kernels = re.findall(
        r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"", text, re.M)
    return {"scan_bodies": len(bodies), "kernels": kernels, "turned": turned}


def probe_lower_for_tpu(meshes_json):
    """Mosaic custom calls in a small flash LM step lowered, from this CPU
    process, for the compile-only ``v5e:2x2`` topology, and the names of
    those instructions (what the profiler's ``XLA Ops`` events, and the
    benchmark's per-kernel metrics, tell the kernels apart by); the
    attention kernel's gradient alone at the training cell's blocks
    (:func:`flash_grad_calls`: what tells a run without a chip that Mosaic
    takes the kernels' blocks at the real size); and what
    the two programs that write the serving slots' state produce there
    (:func:`serve_cache_programs`), for the dense decoder's cache and for
    models/jamba.py's two kinds of state, for models/latent_moe.py's
    latent lanes, for models/retention.py's state matrices and for
    models/ssd_moe.py's states, lanes and padded expert stacks, and for
    models/conv_moe.py's merged lanes and windows.  One
    process for everything compiled
    for the chip (libtpu's lockfile); the compiles run in threads, XLA
    works outside the interpreter lock."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models import (conv_moe, jamba, latent_moe, retention,
                                    ssd_moe)
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import train as train_mod

    assert jax.default_backend() == "cpu"
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def mosaic_calls(axes):
        mesh = mesh_mod.make_mesh(axes, devices=topo.devices)
        cfg = tfm.TransformerConfig(
            vocab_size=512, d_model=256, n_layers=2, n_heads=4, d_ff=512,
            max_seq_len=256, attn_impl="flash",
            n_experts=4 if "ep" in axes else 0)
        step, init = train_mod.make_transformer_train_step(cfg, mesh)
        state = jax.eval_shape(init, jax.random.PRNGKey(0))
        toks = jax.ShapeDtypeStruct((8, 256), jnp.int32)
        text = step.lower(state, toks, toks).compile().as_text()
        names = re.findall(
            r"^\s*%?(\S+) = .*custom_call_target=\"tpu_custom_call\"",
            text, re.M)
        return text.count("tpu_custom_call"), names

    meshes = json.loads(meshes_json)
    sizes = dict(SERVE_CACHE)
    slots = sizes.pop("slots")
    cfg = tfm.TransformerConfig(**sizes)
    state_sizes = dict(SERVE_STATE)
    state_slots = state_sizes.pop("slots")
    jcfg = jamba.JambaConfig(**state_sizes)
    latent_sizes = dict(SERVE_LATENT)
    latent_slots = latent_sizes.pop("slots")
    lcfg = latent_moe.LatentMoEConfig(**latent_sizes)
    sparse_sizes = dict(SERVE_SPARSE)
    sparse_slots = sparse_sizes.pop("slots")
    scfg = latent_moe.LatentMoEConfig(**sparse_sizes)
    retention_sizes = dict(SERVE_RETENTION)
    retention_slots = retention_sizes.pop("slots")
    rcfg = retention.RetentionConfig(**retention_sizes)
    ssd_sizes = dict(SERVE_SSD)
    ssd_slots = ssd_sizes.pop("slots")
    mcfg = ssd_moe.SsdMoEConfig(**ssd_sizes)
    conv_sizes = dict(SERVE_CONV)
    conv_slots = conv_sizes.pop("slots")
    ccfg = conv_moe.ConvMoEConfig(**conv_sizes)
    one_chip = SingleDeviceSharding(topo.devices[0])
    # Fewer threads than submissions: the later compiles take the threads
    # that fall free, so that the probe loads the machine no more than
    # before it had them.
    with ThreadPoolExecutor(len(meshes) + 2) as pool:
        serve_cache = pool.submit(
            serve_cache_programs, cfg, slots,
            slots * cfg.max_seq_len * cfg.d_model,      # one layer's lane
            one_chip,
            # one layer's wq, wk or wv
            cfg.d_model * cfg.n_heads * cfg.head_dim)
        serve_state = pool.submit(
            serve_cache_programs, jcfg, state_slots,
            # one layer's recurrent state: [slots, d_state, d_inner]
            state_slots * jcfg.mamba_d_state * jcfg.d_inner, one_chip)
        serve_latent = pool.submit(
            serve_cache_programs, lcfg, latent_slots,
            # one layer's lane of latents: [slots, cache_len, kv_lora_rank]
            latent_slots * lcfg.max_seq_len * lcfg.kv_lora_rank, one_chip)
        serve_sparse = pool.submit(
            serve_cache_programs, scfg, sparse_slots,
            sparse_slots * scfg.max_seq_len * scfg.kv_lora_rank, one_chip)
        serve_retention = pool.submit(
            serve_cache_programs, rcfg, retention_slots,
            # one layer's state matrices: [slots, KVH, head_dim, rows]
            retention_slots * rcfg.num_key_value_heads * rcfg.head_dim
            * rcfg.state_rows, one_chip)
        serve_ssd = pool.submit(
            serve_cache_programs, mcfg, ssd_slots,
            # one layer's recurrent state: [slots, heads, head_dim, state]
            ssd_slots * mcfg.d_inner * mcfg.ssm_state_size, one_chip)
        serve_conv = pool.submit(
            serve_cache_programs, ccfg, conv_slots,
            # one layer's lane: [slots, cache_len, KVH x head_dim]
            conv_slots * ccfg.max_seq_len * ccfg.num_key_value_heads
            * ccfg.head_dim, one_chip)
        flash_alone = [pool.submit(flash_grad_calls, seq_len, one_chip)
                       for seq_len in FLASH_ALONE]
        flash_layout = pool.submit(flash_step_layout_copies, topo.devices[0])
        found = list(pool.map(mosaic_calls, meshes))
    print("RESULT", json.dumps({
        "device_kind": topo.devices[0].device_kind,
        "serve_cache": serve_cache.result(),
        "serve_state": serve_state.result(),
        "serve_latent": serve_latent.result(),
        "serve_sparse": serve_sparse.result(),
        "serve_retention": serve_retention.result(),
        "serve_ssd": serve_ssd.result(),
        "serve_conv": serve_conv.result(),
        "flash_alone": [calls.result() for calls in flash_alone],
        "flash_layout": flash_layout.result(),
        "tpu_custom_call": [n for n, _ in found],
        "kernel_names": [names for _, names in found]}))


_INSTR = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\("
    r"(?:.*\bcalls=%?([\w.\-]+))?")
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")


def computations(hlo_text):
    """A compiled program's computations by name, each the list of its
    instructions as ``(root, name, type, opcode, calls)``."""
    comps, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(.*->.*\{\s*$", line)
        instr = _INSTR.match(line)
        if head:
            body = comps.setdefault(head.group(1), [])
        elif body is not None and instr:
            body.append(instr.groups())
    return comps


def big_ops(hlo_text, min_elems):
    """The instructions of a compiled program that produce an array of at
    least ``min_elems`` elements, as ``[name, opcode]``: those outside
    fused computations (a fusion's inner instructions never reach memory)
    and other than plumbing (parameters, tuples, bitcasts, the ``while``
    itself).  A fusion is named by its root's opcode (``fusion:scatter``)."""
    comps = computations(hlo_text)
    fused = {calls for instrs in comps.values()
             for _, _, _, op, calls in instrs if op == "fusion"}
    roots = {comp: op for comp, instrs in comps.items()
             for root, _, _, op, _ in instrs if root}
    found = []
    for comp, instrs in comps.items():
        if comp in fused:
            continue
        for _, name, shape, op, calls in instrs:
            elems = [math.prod(map(int, dims.split(",")))
                     for dims in re.findall(r"\w+\[([\d,]+)\]", shape)]
            if op in _PLUMBING or max(elems, default=0) < min_elems:
                continue
            found.append([name, "fusion:" + roots.get(calls, "?")
                          if op == "fusion" else op])
    return found


# The leaves each model's forward casts to the compute type at their use
# (models/transformer.py's COMPUTE_DTYPE_LEAVES, spelled out: the tests hold
# the model to it; models/jamba.py upcasts a_log, dt_bias, d and its
# convolution to float32 and is not in the list with them).
SHARED_CAST_LEAVES = frozenset(
    {"embed", "wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"})
# The dense decoder's engine holds wq, wk and wv as one leaf, wqkv.
DENSE_CAST_LEAVES = SHARED_CAST_LEAVES | {"wqkv"}
JAMBA_CAST_LEAVES = SHARED_CAST_LEAVES | {
    "in_proj", "x_proj", "dt_proj", "out_proj"}
RETENTION_CAST_LEAVES = SHARED_CAST_LEAVES | {"head", "wg"}
LATENT_MOE_CAST_LEAVES = frozenset(
    {"embed", "head", "wq_a", "wq_b", "wkv_a", "w_uk", "w_uv", "wo", "w_in",
     "w_gate", "w_out", "shared_in", "shared_gate", "shared_out"})

_HLO_TYPES = {"bfloat16": "bf16", "float32": "f32"}


def converts_to(hlo_text, dtype):
    """The dimensions of every array of ``dtype`` (a numpy name) that a
    ``convert`` of a compiled program produces, inside fusions and out."""
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(
                r"= %s\[([\d,]*)\]\S* convert\(" % _HLO_TYPES[dtype],
                hlo_text)]


def weight_dims(params, names):
    """What a convert of a weight can look like: for every leaf of the
    ``params`` pytree whose key is in ``names`` the sorted dimensions of
    the whole leaf and, where it is a stack of matrices, of one layer's
    slice of it, without the ones, as a set of tuples.  Compare with
    ``dims_key`` of a convert."""
    import jax

    found = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if path[-1].key in names:
            found.add(dims_key(leaf.shape))
            if leaf.ndim > 2:
                found.add(dims_key(leaf.shape[1:]))
    return found


def dims_key(dims):
    return tuple(sorted(d for d in dims if d != 1))


class Probes:
    """Every probe, started at once; ``result(name)`` waits for one."""

    def __init__(self):
        self.scratch = tempfile.mkdtemp(prefix="hvd-chip-probes-")
        self.set_dir = os.path.join(self.scratch, "set_cache_dir")
        cwds = [os.path.join(self.scratch, d) for d in ("cwd_a", "cwd_b")]
        for d in (self.set_dir, *cwds):
            os.mkdir(d)
        me = os.path.abspath(__file__)
        self._procs = {
            "smoke_on_cpu": self._spawn(
                [os.path.join(REPO, "chip_smoke.py")]),
            "cache_set": self._spawn(
                [me, "cache"], JAX_COMPILATION_CACHE_DIR=self.set_dir),
            "cache_unset_a": self._spawn(
                [me, "cache"], cwd=cwds[0], JAX_COMPILATION_CACHE_DIR=None),
            "cache_unset_b": self._spawn(
                [me, "cache"], cwd=cwds[1], JAX_COMPILATION_CACHE_DIR=None),
            "bare_init": self._spawn([me, "bare_init"], HVD_SIZE=None),
            "lower_for_tpu": self._spawn(
                [me, "lower_for_tpu", json.dumps(MESHES)]),
        }
        self._done = {}

    @staticmethod
    def _spawn(args, *, cwd=REPO, **env_changes):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        for k, v in env_changes.items():
            if v is None:
                env.pop(k, None)
            else:
                env[k] = v
        return subprocess.Popen(
            [sys.executable, *args], cwd=cwd, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)

    def result(self, name):
        """``(returncode, stdout, stderr)`` of probe ``name``."""
        if name not in self._done:
            proc = self._procs[name]
            out, err = proc.communicate(timeout=300)
            self._done[name] = (proc.returncode, out, err)
        return self._done[name]

    def close(self):
        for proc in self._procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.communicate()
        shutil.rmtree(self.scratch, ignore_errors=True)


if __name__ == "__main__":
    globals()["probe_" + sys.argv[1]](*sys.argv[2:])
