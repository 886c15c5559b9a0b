"""Pipeline parallelism: GPipe microbatch schedule over a `pipe` mesh axis.

The reference delegates pipeline parallelism to its engines
(/root/reference/python/ray/llm/_internal/serve/engines/vllm/vllm_models.py:251
`pipeline_parallel_size` is handed to vLLM; Train hands torch FSDP/DeepSpeed the
module) — so this framework supplies it natively, the TPU way:

- The llama params are already scan-stacked `[L, ...]`; sharding that leading
  dim over the mesh's `pipe` axis IS the stage assignment — no module surgery,
  each stage holds `L/P` contiguous layers in its HBM.
- Inside one `jax.shard_map` over the full mesh, microbatches rotate between
  stage neighbors with `lax.ppermute` (the GPipe schedule: `M + P - 1` ticks,
  stage s processes microbatch `t - s` at tick t). Activations are the only
  cross-stage traffic — the lowest-bandwidth axis, so `pipe` sits on the
  slower links (mesh.py AXES order).
- Tensor parallelism composes inside each stage Megatron-style: wq/wk/wv and
  w_gate/w_up are output-sharded over `tensor`, wo/w_down input-sharded, with
  one `psum` after each (2 collectives/layer).
- Autodiff runs INSIDE the shard_map (`value_and_grad` of the local loss) so
  gradient reductions are explicit per-leaf `psum`s — no reliance on
  shard_map transpose rules for replicated operands: layer grads reduce over
  (data, fsdp) only (their shards are pipe-local), embed/head/final-norm
  grads also over `pipe` (non-owning stages contribute exact zeros through
  the `where` routing).

In PP layouts the `fsdp` axis acts as plain data parallelism for the step
(params are replicated across it, like ZeRO-0): PP already partitions the
model by depth, and composing it with ZeRO-3 gathers would double-pay
collectives on the fast axis. The batch is sharded over (data, fsdp).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.ops.platform import target_platform


def layer_specs() -> dict:
    """PartitionSpecs for the scan-stacked layer params in a PP layout:
    leading (scan) dim over `pipe`, Megatron in/out dims over `tensor`."""
    t = "tensor"
    return {
        "attn_norm": P("pipe", None),
        "wq": P("pipe", None, t),
        "wk": P("pipe", None, t),
        "wv": P("pipe", None, t),
        "wo": P("pipe", t, None),
        "mlp_norm": P("pipe", None),
        "w_gate": P("pipe", None, t),
        "w_up": P("pipe", None, t),
        "w_down": P("pipe", t, None),
    }


def param_specs(cfg: llama.LlamaConfig) -> dict:
    tree = {
        "embed": P(None, None),
        "layers": layer_specs(),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = P(None, None)
    return tree


BATCH_SPEC = P(("data", "fsdp"), None)


def _check(cfg: llama.LlamaConfig, mesh: Mesh) -> tuple[int, int]:
    for ax in ("pipe", "tensor", "data", "fsdp"):
        if ax not in mesh.shape:
            raise ValueError(f"PP mesh needs a {ax!r} axis, got {dict(mesh.shape)}")
    for ax in ("seq", "expert"):
        if mesh.shape.get(ax, 1) != 1:
            raise ValueError(f"PP step does not compose with {ax!r}>1 yet")
    Pst, T = mesh.shape["pipe"], mesh.shape["tensor"]
    if cfg.num_layers % Pst:
        raise ValueError(f"num_layers={cfg.num_layers} not divisible by pipe={Pst}")
    if cfg.num_heads % T or cfg.num_kv_heads % T:
        # kv_heads < tensor would need wk/wv replication across tensor ranks
        # (not implemented) — reject clearly rather than die in a reshape.
        raise ValueError(
            f"heads {cfg.num_heads}/kv {cfg.num_kv_heads} not divisible by tensor={T}")
    return Pst, T


def make_pp_loss_and_grad(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    attn_fn: Callable | None = None,
) -> Callable:
    """Build `(params, tokens, targets) -> (loss, grads)` — one shard_map over
    the full mesh, grads globally reduced and sharded like the params."""
    Pst, _ = _check(cfg, mesh)
    M = num_microbatches
    specs = param_specs(cfg)
    if attn_fn is None:
        # already per-device here (the whole loss runs inside the shard_map
        # below), so the kernel needs no wrapping of its own
        attn_fn = partial(llama.auto_attention, causal=True,
                          platform=target_platform(mesh=mesh))

    attend = llama.plain_attend(attn_fn)
    tensor_sum = partial(jax.lax.psum, axis_name="tensor")

    def local_loss(params, tokens, targets):
        """Per-device loss; nonzero only on last-stage devices. All arrays are
        LOCAL shards (manual mode): layers [L/P, ...], tokens [B_local, S]."""
        stage = jax.lax.axis_index("pipe")
        Bl, S = tokens.shape
        if Bl % M:
            raise ValueError(f"local batch {Bl} not divisible by microbatches {M}")
        Bm = Bl // M
        toks_mb = tokens.reshape(M, Bm, S)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (Bm, S))

        def stage_fn(x):
            # the stage's local heads and MLP columns through the one layer,
            # its two row-sharded products summed over `tensor`
            def body(x, layer):
                return llama.decoder_layer(cfg, x, layer, None, positions, attend,
                                           reduce=tensor_sum)[0], None

            return jax.lax.scan(llama.remat_body(body, cfg), x, params["layers"])[0]

        perm = [(i, i + 1) for i in range(Pst - 1)]

        def tick(carry, t):
            recv, outputs = carry
            # stage 0 feeds microbatch t (clipped past the drain ticks, where
            # its compute is discarded); later stages consume the rotation
            mb = jax.lax.dynamic_index_in_dim(
                toks_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            emb = params["embed"][mb].astype(cfg.dtype)
            x = stage_fn(jnp.where(stage == 0, emb, recv))
            # last stage completes microbatch t-(P-1) at tick t
            idx_out = t - (Pst - 1)
            safe = jnp.clip(idx_out, 0, M - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, safe, axis=0, keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(idx_out >= 0, x, cur), safe, axis=0)
            return (jax.lax.ppermute(x, "pipe", perm), outputs), None

        recv0 = jnp.zeros((Bm, S, cfg.hidden_size), cfg.dtype)
        out0 = jnp.zeros((M, Bm, S, cfg.hidden_size), cfg.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (recv0, out0), jnp.arange(M + Pst - 1))

        # head + loss: computed everywhere (identical FLOPs keep stages in
        # lockstep), meaningful only on the last stage — `is_last` masks the
        # rest, which also zeroes their embed/head grads exactly.
        logits = llama.lm_head(params, outputs.reshape(Bl, S, cfg.hidden_size), cfg)
        nll_sum, count = llama.token_nll(logits, targets)
        global_count = jax.lax.psum(count, ("data", "fsdp"))
        # Seed the loss on exactly ONE device per batch shard: last stage,
        # tensor rank 0. Tensor replicas compute identical losses, and SPMD
        # autodiff sums every device's seed — an unmasked loss would flow T
        # cotangents through each psum and double (T-fold) every upstream
        # gradient. With the single seed, tensor-sharded matmul grads come
        # back exact per shard, and tensor-replicated leaves recover their
        # full gradient from the psum over `tensor` in `body`.
        owner = jnp.logical_and(stage == Pst - 1,
                                jax.lax.axis_index("tensor") == 0)
        return jnp.where(owner, nll_sum, 0.0) / jnp.maximum(global_count, 1)

    def body(params, tokens, targets):
        loss_local, grads = jax.value_and_grad(
            lambda p: local_loss(p, tokens, targets))(params)
        loss = jax.lax.psum(loss_local, ("data", "fsdp", "pipe", "tensor"))
        # Explicit reductions (see module docstring + the seed note in
        # local_loss): tensor-SHARDED matmul grads are exact per shard and
        # pipe-local — reduce over batch axes only; tensor-replicated leaves
        # (norms/embed/head) hold partial contributions per tensor rank (the
        # loss is seeded on rank 0, but cotangents reach every rank's replica
        # through the psum transposes) — reduce over `tensor` too, and over
        # `pipe` for the stage-shared leaves (zeros off the owning stage).
        norm_leaves = ("attn_norm", "mlp_norm")
        reduced = dict(grads)
        reduced["layers"] = {
            k: jax.lax.psum(
                g, ("data", "fsdp", "tensor") if k in norm_leaves
                else ("data", "fsdp"))
            for k, g in grads["layers"].items()
        }
        for k in ("embed", "final_norm", "lm_head"):
            if k in grads:
                reduced[k] = jax.lax.psum(
                    grads[k], ("data", "fsdp", "pipe", "tensor"))
        return loss, reduced

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs, BATCH_SPEC, BATCH_SPEC),
        out_specs=(P(), specs),
        check_vma=False,
    )


def pp_state_shardings(cfg: llama.LlamaConfig, mesh: Mesh, state) -> "object":
    """TrainState sharding tree for PP layouts (params by param_specs;
    opt_state mirrors the param pytree structure; scalars replicated)."""
    from ray_tpu.train.spmd import TrainState, mirror_opt_shardings

    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), param_specs(cfg),
                            is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    return TrainState(
        params=param_sh,
        opt_state=mirror_opt_shardings(state.opt_state, state.params, param_sh, rep),
        step=rep,
    )


def make_pp_train_step(
    cfg: llama.LlamaConfig,
    mesh: Mesh,
    num_microbatches: int,
    optimizer=None,
    attn_fn: Callable | None = None,
) -> Callable:
    """PP analog of train.spmd.make_train_step: returns compile_step(state) ->
    jitted (state, tokens, targets) -> (state, metrics)."""
    from ray_tpu.train import spmd

    optimizer = optimizer or spmd.make_optimizer()
    loss_and_grad = make_pp_loss_and_grad(cfg, mesh, num_microbatches, attn_fn)
    batch_sh = NamedSharding(mesh, BATCH_SPEC)

    def step_fn(state, tokens, targets):
        loss, grads = loss_and_grad(state.params, tokens, targets)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = spmd.TrainState(new_params, new_opt, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": new_state.step}

    def compile_step(state):
        state_sh = pp_state_shardings(cfg, mesh, state)
        return jax.jit(
            step_fn,
            in_shardings=(state_sh, batch_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    return compile_step


# --------------------------------------------------------------------------
# Actor-stage pipelines on compiled graphs (the first real consumer of
# dag/compiled.py): each stage callable lives in a long-lived actor, the
# stages are chained into a compiled actor graph, and microbatches stream
# through pre-negotiated shm channels with depth-P pipelining — zero
# control-plane dispatch per microbatch (the Podracer shape, arXiv
# 2104.06272, vs. per-call .remote()+get of the original task model).


class _PipelineStage:
    """Hosts one stage callable; process-isolated by default so stages run
    truly in parallel (own GIL, own device context)."""

    def __init__(self, fn_blob: bytes):
        import cloudpickle

        self._fn = cloudpickle.loads(fn_blob)

    def run(self, x):
        return self._fn(x)


class CompiledStagePipeline:
    """Chain ``stage_fns`` into a compiled actor graph and stream inputs
    through it.

    ``run(inputs)`` submits every microbatch up front — the bounded channel
    rings cap in-flight work at depth x RAY_TPU_DAG_CHANNEL_SLOTS frames —
    and drains results in order: the GPipe fill/drain schedule, driven by
    data instead of RPCs. ``teardown()`` releases the graph and the stage
    actors.
    """

    def __init__(self, stage_fns, *, isolate_process: bool = True):
        import cloudpickle

        import ray_tpu
        from ray_tpu.dag import InputNode

        if not stage_fns:
            raise ValueError("pipeline needs at least one stage")
        stage_cls = ray_tpu.remote(_PipelineStage)
        self._actors = [
            stage_cls.options(isolate_process=isolate_process).remote(
                cloudpickle.dumps(fn))
            for fn in stage_fns
        ]
        with InputNode() as inp:
            node = inp
            for a in self._actors:
                node = a.run.bind(node)
        self._dag = node.experimental_compile()

    def run(self, inputs, timeout: float | None = None) -> list:
        refs = [self._dag.execute(x) for x in inputs]
        return [r.get(timeout=timeout) for r in refs]

    def teardown(self) -> None:
        import ray_tpu

        self._dag.teardown()
        for a in self._actors:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
