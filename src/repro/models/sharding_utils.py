"""Activation sharding constraints inside model code.

Without internal constraints GSPMD's propagation can legally pick
pathological layouts -- e.g. all-gathering the batch after the (vocab-
sharded) embedding gather and running pure tensor-parallel over all chips
(observed on qwen train_4k; see EXPERIMENTS.md §Dry-run).  ``constrain``
pins activations to batch-sharded layouts whenever a mesh context is
active, and is a no-op under single-device tests.

Spec tokens: 'batch' expands to the mesh's batch axes (('pod','data') on
the multi-pod mesh), 'model' passes through, None replicates.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _active_mesh():
    """The mesh set by an enclosing ``with jax.set_mesh(mesh):``, else None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x: jax.Array, *spec_tokens) -> jax.Array:
    """with_sharding_constraint(x, P(...)) resolved against the active mesh."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    resolved = []
    for tok in spec_tokens:
        if tok == "batch":
            axes = tuple(a for a in ("pod", "data") if a in names)
            resolved.append(axes if axes else None)
        elif tok == "batch_full":
            # FSDP: batch spans every mesh axis.
            resolved.append(tuple(mesh.axis_names))
        elif tok is None:
            resolved.append(None)
        elif isinstance(tok, str):
            resolved.append(tok if tok in names else None)
        else:
            resolved.append(tok)
    return jax.lax.with_sharding_constraint(x, P(*resolved))
