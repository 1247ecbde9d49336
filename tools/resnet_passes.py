"""Count the passes a compiled ResNet training step makes over its
activations, with no chip.

ResNet-50's step is memory-bound almost end to end (PERF.md section 5), so
what it costs is how often an activation is read and written.  This tool
compiles ``make_resnet_train_step_hvd`` at the benchmark cell's shape,
read from ``perfbench/configs/resnet50.json``
(128 images of 224 x 224 a chip, SGD with momentum through
``DistributedOptimizer``) ahead of time for ``v5e`` (the topology
``tools/measure_overlap.py`` builds: libtpu needs no such hardware), and
reads the optimized entry computation:

* the fusions, and the bytes of their operands and results summed (what
  the step moves if every fusion reads and writes its arrays once), and
  the part of them in main memory: the compiler keeps the smaller
  activations in its fast memory space (``S(1)`` in a layout), and a pass
  over one of those is not the 819 GB/s kind;
* the REDUCE-ONLY fusions over an activation: every result a vector (a
  ``[C]`` of statistics or of a gradient), an operand a whole
  ``[batch, H, W, C]`` activation.  Such a fusion is a pass that reads an
  activation and writes nothing of its size; forward ones (no
  ``transpose(`` in the op's name) are a norm layer's statistics that did
  not fold into the convolution before them, backward ones the sums of a
  norm layer's gradient;
* the largest fusion families by name, and XLA's own
  ``cost_analysis()["bytes accessed"]``.

Usage::

    python tools/resnet_passes.py [--chips 1|4] [--hlo-out FILE]

The compile takes about 45 s and libtpu's lock file (one such process at a
time).  Logical bytes, no tile padding; a count, not a time.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import Counter
from typing import Dict, List, NamedTuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from measure_overlap import (  # noqa: E402
    entry_bounds,
    opcode,
    shape_bytes,
    topology_devices,
)

CELL_CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "perfbench", "configs", "resnet50.json")

_INST_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_ARRAY_RE = re.compile(r"\w+\[([\d,]*)\](\{[^}]*\})?")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


class Fusion(NamedTuple):
    name: str
    results: List[List[int]]       # dims of each result array
    operands: List[List[int]]      # dims of each operand array
    read_bytes: int
    write_bytes: int
    hbm_bytes: int                 # of both, what lies in main memory
    backward: bool


def _dims(shape: str) -> List[List[int]]:
    return [[int(d) for d in m.group(1).split(",") if d]
            for m in _ARRAY_RE.finditer(shape)]


def _hbm_bytes(shape: str) -> int:
    """Bytes of the arrays of ``shape`` whose layout names no fast memory
    space."""
    return sum(shape_bytes(m.group(0)) for m in _ARRAY_RE.finditer(shape)
               if "S(1)" not in (m.group(2) or ""))


def _operand_text(rhs: str, op: str) -> str:
    """What stands between the opcode's parentheses."""
    start = rhs.index(op + "(") + len(op) + 1
    depth = 1
    for i in range(start, len(rhs)):
        depth += {"(": 1, ")": -1}.get(rhs[i], 0)
        if not depth:
            return rhs[start:i]
    return rhs[start:]


def entry_fusions(hlo: str) -> List[Fusion]:
    """The fusion instructions of the entry computation, each with the
    shapes of what it reads (looked up by operand name: the chip's text
    prints operands bare) and of what it writes."""
    all_lines = hlo.splitlines()
    start, end = entry_bounds(all_lines)
    shapes: Dict[str, str] = {}
    found = []
    for ln in all_lines[start:end]:
        m = _INST_RE.match(ln)
        if not m:
            continue
        name, rhs = m.groups()
        op = opcode(rhs)
        if op is None:
            continue
        shape = rhs[:rhs.index(op + "(")]
        shapes[name] = shape
        if op == "fusion":
            found.append((name, shape, rhs))
    fusions = []
    for name, shape, rhs in found:
        read = "".join(shapes.get(o, "") for o in re.findall(
            r"%([\w.\-]+)", _operand_text(rhs, "fusion")))
        meta = _OP_NAME_RE.search(rhs)
        fusions.append(Fusion(
            name, _dims(shape), _dims(read), shape_bytes(read),
            shape_bytes(shape), _hbm_bytes(read) + _hbm_bytes(shape),
            bool(meta) and "transpose(" in meta.group(1)))
    return fusions


def reduce_only(f: Fusion, batch: int) -> bool:
    """Every result a vector, an operand a whole activation of ``batch``
    images with the vector's length as its channels."""
    if not f.results or any(len(r) != 1 for r in f.results):
        return False
    channels = {r[0] for r in f.results}
    return any(len(o) == 4 and o[0] == batch and o[3] in channels
               for o in f.operands)


def passes(hlo: str, batch: int) -> dict:
    fusions = entry_fusions(hlo)
    out = {
        "fusions": len(fusions),
        "fusion_bytes": sum(f.read_bytes + f.write_bytes for f in fusions),
        "fusion_hbm_bytes": sum(f.hbm_bytes for f in fusions),
    }
    sides = out["reduce_only"] = {
        side: {"count": 0, "read_bytes": 0, "hbm_bytes": 0}
        for side in ("forward", "backward")}
    count: Counter = Counter()
    moved: Counter = Counter()
    hbm: Counter = Counter()
    for f in fusions:
        if reduce_only(f, batch):
            side = sides["backward" if f.backward else "forward"]
            side["count"] += 1
            side["read_bytes"] += f.read_bytes
            side["hbm_bytes"] += f.hbm_bytes
        family = re.sub(r"[.\d]+$", "", f.name)
        count[family] += 1
        moved[family] += f.read_bytes + f.write_bytes
        hbm[family] += f.hbm_bytes
    out["families"] = [
        {"name": k, "count": count[k], "bytes": b, "hbm_bytes": hbm[k]}
        for k, b in moved.most_common(8)]
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--hlo-out", default=None,
                    help="also write the compiled step's text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import resnet
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.parallel import optimizer as opt_mod
    from horovod_tpu.parallel import train as train_mod

    with open(CELL_CONFIG) as f:
        cell = json.load(f)
    per_chip, image, o = (cell["train"][k] for k in (
        "per_chip_batch", "image_size", "optimizer"))
    n = args.chips
    devices = topology_devices("v5e:2x2")[:n]
    mesh = mesh_mod.make_mesh({"dp": n}, devices=devices)
    dist = opt_mod.DistributedOptimizer(
        optax.sgd(o["learning_rate"], momentum=o["momentum"]),
        axis=("dp",))
    step, init = train_mod.make_resnet_train_step_hvd(
        resnet.ResNetConfig(blocks=tuple(cell["blocks"]),
                            width=cell["width"],
                            num_classes=cell["num_classes"]), mesh, dist)
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct(
        (per_chip * n, image, image, 3), jnp.float32)
    y = jax.ShapeDtypeStruct((per_chip * n,), jnp.int32)
    compiled = step.lower(state, x, y).compile()
    hlo = compiled.as_text()
    if args.hlo_out:
        with open(args.hlo_out, "w") as f:
            f.write(hlo)
    print(json.dumps({
        "device_kind": devices[0].device_kind, "chips": n,
        "per_chip_batch": per_chip,
        **passes(hlo, per_chip),
        "cost_analysis_bytes":
            compiled.cost_analysis()["bytes accessed"]}, indent=1))


if __name__ == "__main__":
    main()
