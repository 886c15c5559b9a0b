"""Model families. What the SPMD train step (train/spmd.py) and the serving
engine (serve/llm_paged.py) take of one is a `Model`: each family module
exports its own as `MODEL`, and `model_of(cfg)` finds it from a family's
configuration, so the engine names no family."""

from __future__ import annotations

import sys
from typing import Callable, NamedTuple, Optional


class Model(NamedTuple):
    init: Callable           # (cfg, key) -> params
    logical_axes: Callable   # (cfg) -> the params' tree of logical-axis tuples
    # (params, tokens, targets, cfg, attn_fn, mesh=None) -> (loss, {name: scalar}):
    # the objective, and the model's own scalars for the step's metrics dict;
    # `mesh` is the mesh the step is sharded over, for a kernel-or-dense choice
    loss: Callable
    # -- what serving takes; None where the family does not serve.
    # The engine's one cache contract: (params, tokens, cfg, pool, tables,
    # lengths, block_size, platform=, head_rows=, fresh=) -> (logits [B, S, V], or
    # [B, 1, V] of the positions `head_rows` [B] names, pool); `fresh`
    # (static) says that every sequence starts at position 0, so a family may
    # attend over the rows in hand and read nothing back, and write rows that
    # fill whole blocks as whole pages (`llama.write_pages`); and (cfg, num_blocks,
    # block_size) -> the pool it reads and writes: a dict of page-shaped
    # arrays, pages on the second axis, [L, num_blocks, ...] (a row a token,
    # [L, num_blocks, block_size, row], or a kind of state that keeps rows a
    # BLOCK: `lfm2`'s `conv` [Lc, num_blocks, 2, H]; L is a leaf's own), the
    # cache (a PD hand-off moves them, whatever their names and whatever
    # follows the second axis: everything a sequence has lives in its pages),
    # and, if the family counts anything, one entry `counters`: {name: int32
    # or float32 scalar} that the last step left for the engine's records.
    # `head_rows` also says where a call's LIVE tokens end: the tokens after
    # position `head_rows[b]` are a bucket's padding, and a family whose state
    # is no row a token writes nothing of them (`lfm2.forward_paged`)
    forward_paged: Optional[Callable] = None
    init_kv_pool: Optional[Callable] = None
    # The pool's second CLASS of page: the names of the leaves whose pages are
    # a SEQUENCE's, not a span of tokens' ([L, num_sequences, ...]: a state
    # that sums over the whole past, `nemotron_h`'s `ssm` and `conv`). A
    # family that names any takes `init_kv_pool(..., num_sequences=)` (page 0
    # of the class its garbage page, as block 0 is) and `forward_paged(...,
    # state_pages=)`, int32 [B]: the page each sequence's state lives in, 0
    # for a dead row. The engine hands a sequence one such page at admission,
    # carries its id as the LAST column of the sequence's table row, and
    # keeps no prefix cache over such a pool (a block's hash says nothing of
    # a running sum). Such a page may as well be a window layer's RING of rows
    # (`laguna`'s `k_win` and `v_win`, `window` rows a sequence whatever its
    # length): the allocator's stats and the engine's records count either as
    # `state_pages_used`
    sequence_leaves: tuple = ()


def model_of(cfg) -> Model:
    """The `MODEL` of the family whose module defines `cfg`'s class."""
    module = sys.modules.get(type(cfg).__module__)
    if not isinstance(getattr(module, "MODEL", None), Model):
        raise TypeError(
            f"{type(cfg).__module__}.{type(cfg).__qualname__} is no model family's "
            f"configuration: the module that defines it exports no `MODEL` "
            f"(ray_tpu.models.Model)")
    return module.MODEL
