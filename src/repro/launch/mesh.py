"""Production meshes.

Target hardware: TPU v5e pods — 256 chips per pod (16x16 ICI torus),
2 pods over DCI for the multi-pod configuration.

* single-pod: (16, 16) over ('data', 'model') — 256 chips.
  M-AVG learners live on the 'data' axis (P = 16 learners, each a 16-way
  tensor-parallel group).
* multi-pod: (2, 16, 16) over ('pod', 'data', 'model') — 512 chips.
  Faithful mode: P = 32 learners over ('pod','data'). Hierarchical mode
  (beyond paper, DESIGN.md section 5): P = 2 learners — one per pod — each
  copy FSDP-sharded over 'data' x 'model'; the only inter-pod traffic is
  the meta-level average every K steps, amortising the slow DCI link
  exactly the way the paper amortises its Infiniband allreduce.

* host: (n, 1) over ('data', 'model') — the n chips of one host (a v5e
  2x2 host: n = 4), one M-AVG learner per chip; the meta average is the
  only cross-chip traffic.

Every mesh here has Auto axes: the learner stack is placed by the
state/batch shardings and GSPMD partitions the step. (``jax.make_mesh``
defaults to Explicit axes in JAX 0.9; under them the shardings become
part of each array's type and ``vmap`` over the learner stack refuses
operands whose specs differ.)

This module defines FUNCTIONS only — importing it never touches jax
device state, so tests see one CPU device while dryrun.py (which sets
XLA_FLAGS before any jax import) sees 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")
HOST_AXES = ("data", "model")


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """(n, 1) over ('data', 'model') on the n devices of ``jax.devices()``
    — the learners of a single host spread over its chips."""
    return _auto_mesh((len(jax.devices()), 1), HOST_AXES)


def learner_axes(mesh, *, hierarchical: bool = False):
    """Mesh axes the learner (paper's P) dimension is sharded over."""
    if "pod" in mesh.shape:
        return ("pod",) if hierarchical else ("pod", "data")
    return ("data",)


def num_learners(mesh, *, hierarchical: bool = False) -> int:
    out = 1
    for a in learner_axes(mesh, hierarchical=hierarchical):
        out *= mesh.shape[a]
    return out


def fsdp_axes(mesh, *, hierarchical: bool = False):
    """Axes used to shard each learner's copy beyond tensor parallelism."""
    if hierarchical and "pod" in mesh.shape:
        return "data"
    return None


def make_debug_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU integration tests (requires >=4 host devices)."""
    return _auto_mesh((data, model), ("data", "model"))
