"""Device meshes.

* host: (n, 1) over ('data', 'model') — the n chips of one host (a v5e
  2x2 host: n = 4), one M-AVG learner per chip; the meta average is the
  only cross-chip traffic.
* debug: a small ('data', 'model') mesh for CPU tests on virtual devices.

A mesh with a leading 'pod' axis puts the learners on ('pod', 'data'),
or under hierarchical mode one learner per pod, each copy FSDP-sharded
over 'data': ``learner_axes`` and ``fsdp_axes``.

Every mesh here has Auto axes: the learner stack is placed by the
state/batch shardings and GSPMD partitions the step. (``jax.make_mesh``
defaults to Explicit axes in JAX 0.9; under them the shardings become
part of each array's type and ``vmap`` over the learner stack refuses
operands whose specs differ.)

This module defines FUNCTIONS only — importing it never touches jax
device state; a mesh is built only when a caller asks for one.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

HOST_AXES = ("data", "model")


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh():
    """(n, 1) over ('data', 'model') on the n devices of ``jax.devices()``
    — the learners of a single host spread over its chips."""
    return _auto_mesh((len(jax.devices()), 1), HOST_AXES)


def learner_axes(mesh, *, hierarchical: bool = False):
    """Mesh axes the learner (paper's P) dimension is sharded over."""
    if "pod" in mesh.shape:
        return ("pod",) if hierarchical else ("pod", "data")
    return ("data",)


def fsdp_axes(mesh, *, hierarchical: bool = False):
    """Axes used to shard each learner's copy beyond tensor parallelism."""
    if hierarchical and "pod" in mesh.shape:
        return "data"
    return None


def make_debug_mesh(data: int = 2, model: int = 2):
    """Tiny mesh for CPU integration tests (requires >=4 host devices)."""
    return _auto_mesh((data, model), ("data", "model"))
