"""Canonical logical activation axis names.

One vocabulary shared by the model zoo (models/transformer.py), the MoE
package (moe/layer.py), and the engine's rule table — the names here map to
mesh axes via ``default_activation_rules``. Keeping them in one module means
a rename cannot silently desynchronize a with_logical_constraint from the
installed rules.
"""
from __future__ import annotations

import contextvars
from contextlib import contextmanager
from typing import Any

import flax.linen as nn
import jax

BATCH = "act_batch"
SEQ = "act_seq"
EMBED = "act_embed"
HEADS = "act_heads"
MLP = "act_mlp"
EXPERT = "act_expert"
#: batch WITHOUT the expert axis: inside the MoE dispatch/combine the
#: expert axis belongs to the EXPERT dim; a plain BATCH constraint there
#: would claim it for the token dim too, and the conflicting annotations
#: force GSPMD into replicate-then-repartition ("involuntary full
#: rematerialization" in the pipe x expert dryrun, VERDICT r04 weak #3)
BATCH_NOEXP = "act_batch_noexp"


def constrain(x: jax.Array, *names: str | None) -> jax.Array:
    return nn.with_logical_constraint(x, tuple(names))


# The rules name mesh AXES; nothing at trace time names the MESH. Code that
# needs one for its own ``shard_map`` (the attention dispatcher's per-shard
# kernel) finds it here, by the route ``parallel/tensor.py:tp_overlap_scope``
# takes: a context the engine opens around the loss, beside the rules.
_MESH: contextvars.ContextVar[Any] = \
    contextvars.ContextVar("model_mesh", default=None)


@contextmanager
def model_mesh_scope(mesh):
    """Model code traced inside the context resolves its logical axis names
    onto ``mesh`` (trace-time, like ``nn.logical_axis_rules``)."""
    tok = _MESH.set(mesh)
    try:
        yield
    finally:
        _MESH.reset(tok)


def mesh_specs(*logical_names: tuple) -> tuple | None:
    """``(mesh, spec, ...)``: the scoped mesh and each tuple of logical
    axis names as the ``PartitionSpec`` the active rules give it; None
    outside a :func:`model_mesh_scope`."""
    mesh = _MESH.get()
    if mesh is None:
        return None
    return (mesh, *(nn.logical_to_mesh_axes(names)
                    for names in logical_names))
