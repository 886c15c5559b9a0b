"""Model families. What the SPMD train step (train/spmd.py) takes of one is a
`Model`: each family module exports its own as `MODEL`."""

from __future__ import annotations

from typing import Callable, NamedTuple


class Model(NamedTuple):
    init: Callable           # (cfg, key) -> params
    logical_axes: Callable   # (cfg) -> the params' tree of logical-axis tuples
    # (params, tokens, targets, cfg, attn_fn, mesh=None) -> (loss, {name: scalar}):
    # the objective, and the model's own scalars for the step's metrics dict;
    # `mesh` is the mesh the step is sharded over, for a kernel-or-dense choice
    loss: Callable
