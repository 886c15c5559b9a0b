"""SPMD training step: mesh-sharded forward/backward/update, XLA-compiled once.

This is the compute core the reference delegates to torch DDP/FSDP
(train/torch/train_loop_utils.py:177) — here it is native: one pjit'd step over a
Mesh whose axes express dp/fsdp/tp/sp, with donation for in-place HBM reuse and
jax.checkpoint (in the model) for rematerialization.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import Model, llama
from ray_tpu.ops.platform import target_platform
from ray_tpu.parallel import sharding as shd
from ray_tpu.util.compile_cache import ensure_compile_cache


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array


def make_optimizer(learning_rate: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100):
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=learning_rate, warmup_steps=warmup,
        decay_steps=10000, end_value=learning_rate * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def init_state(cfg, key, optimizer=None, model: Model = llama.MODEL) -> TrainState:
    """`model` is the family's record (models/__init__.py): `llama.MODEL` by
    default, `moe.MODEL` with an `MoEConfig`; the same one goes to
    `state_shardings` and `make_train_step`."""
    optimizer = optimizer or make_optimizer()
    params = model.init(cfg, key)
    opt_state = optimizer.init(params)
    return TrainState(params=params, opt_state=opt_state, step=jnp.zeros((), jnp.int32))


def mirror_opt_shardings(opt_state, params, param_sh, rep):
    """Sharding tree for an optax state: any subtree whose pytree STRUCTURE
    mirrors the params (adam mu/nu, etc.) gets the param sharding tree; other
    leaves (step counts) replicate. Structure matching is unambiguous where
    shape matching is not — e.g. wq and wo share [L, h, h] but carry
    transposed PartitionSpecs, so a shape-keyed map silently missharded one
    of them and paid resharding collectives every optimizer step."""
    pdef = jax.tree.structure(params)

    def rec(node):
        if jax.tree.structure(node) == pdef:
            return param_sh
        if isinstance(node, tuple) and hasattr(node, "_fields"):  # NamedTuple
            return type(node)(*(rec(c) for c in node))
        if isinstance(node, (list, tuple)):
            return type(node)(rec(c) for c in node)
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        return rep

    return rec(opt_state)


def state_shardings(cfg, mesh: Mesh, state: TrainState,
                    model: Model = llama.MODEL) -> TrainState:
    """Sharding tree for TrainState: params by logical axes; opt_state mirrors params."""
    ax = model.logical_axes(cfg)
    param_sh = shd.tree_shardings(mesh, ax)
    rep = shd.replicated(mesh)
    return TrainState(
        params=param_sh,
        opt_state=mirror_opt_shardings(state.opt_state, state.params, param_sh, rep),
        step=rep,
    )


def default_attn_fn(mesh: Mesh) -> Callable:
    """The attn_fn for a step sharded over `mesh`: llama.auto_attention told
    the mesh's platform, and on a TPU mesh of several devices run under
    `jax.shard_map` (batch over data x fsdp, heads over tensor — the layout
    the rule table already gives q/k/v). The SPMD partitioner cannot split a
    Mosaic kernel itself ("Mosaic kernels cannot be automatically
    partitioned"), and attention is independent per batch row and per KV-head
    group, so each device runs the kernel on its own shard with no
    communication. Axes the specs do not name (seq, expert, pipe) see the
    whole sequence; a `seq` axis > 1 wants ring attention passed explicitly."""
    platform = target_platform(mesh=mesh)
    attn = partial(llama.auto_attention, causal=True, platform=platform)
    if platform != "tpu" or mesh.size == 1:
        return attn
    q_spec = shd.spec_from_logical(("batch", None, "heads", None))
    kv_spec = shd.spec_from_logical(("batch", None, "kv_heads", None))
    return jax.shard_map(attn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)


def make_train_step(
    cfg,
    mesh: Mesh,
    optimizer=None,
    attn_fn: Callable | None = None,
    model: Model = llama.MODEL,
) -> Callable:
    """Build the jitted SPMD train step: (state, tokens, targets) -> (state, metrics).
    The metrics are `loss`, `grad_norm`, `step` and whatever scalars the
    model's loss returns beside its objective (an MoE's `nll`, `aux_loss`,
    `router_load_max`; Llama's none).

    Gradients are averaged over (data, fsdp) implicitly by XLA from the sharded loss;
    param/optimizer shards (fsdp axis) are all-gathered/reduce-scattered by XLA as
    needed — the ZeRO-3 pattern without manual collectives.
    """
    ensure_compile_cache(target_platform(mesh=mesh))
    optimizer = optimizer or make_optimizer()
    if attn_fn is None:
        attn_fn = default_attn_fn(mesh)
    batch_sh = NamedSharding(mesh, P(("data", "fsdp"), None))

    def step_fn(state: TrainState, tokens, targets):
        def loss(params):
            return model.loss(params, tokens, targets, cfg, attn_fn, mesh=mesh)

        (lossval, scalars), grads = jax.value_and_grad(loss, has_aux=True)(state.params)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(new_params, new_opt, state.step + 1)
        return new_state, {**scalars, "loss": lossval, "grad_norm": gnorm,
                           "step": new_state.step}

    def compile_step(state: TrainState):
        sh = state_shardings(cfg, mesh, state, model)
        state_sh = TrainState(sh.params, sh.opt_state, sh.step)
        return jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    return compile_step


def make_auto_train_step(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    optimizer=None,
    attn_fn: Callable | None = None,
    num_microbatches: int = 2,
) -> Callable:
    """Pick the right train step for the mesh's layout: the GPipe pipeline
    step when a `pipe` axis > 1 is present (parallel/pipeline.py — the
    reference delegates PP to its engines, vllm_models.py:251), the
    single-program SPMD step otherwise. Both return compile_step(state)."""
    if dict(mesh.shape).get("pipe", 1) > 1:
        from ray_tpu.parallel.pipeline import make_pp_train_step

        return make_pp_train_step(cfg, mesh, num_microbatches,
                                  optimizer=optimizer, attn_fn=attn_fn)
    return make_train_step(cfg, mesh, optimizer=optimizer, attn_fn=attn_fn)


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step"], meta_fields=[]
)
