"""The toy family of the rehearsal (`test_new_architecture_is_additions.py`):
a two-layer residual MLP language model that only trains, so it has no
`serve_app`. Its step returns a scalar of its own beside the loss (`aux`, the
mean squared activation of the last layer, standing where a router's load or
an auxiliary loss would), and its layers run under a `jax.named_scope`."""

from __future__ import annotations

import typing

MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers", "vocab_size")


class State(typing.NamedTuple):
    params: dict
    step: int


def init(model: dict, key):
    import jax

    h, f, v, n = (model["hidden_size"], model["intermediate_size"],
                  model["vocab_size"], model["num_hidden_layers"])
    ks = jax.random.split(key, 2 + 2 * n)
    return {"embed": jax.random.normal(ks[0], (v, h)) * 0.5,
            "head": jax.random.normal(ks[1], (h, v)) * h ** -0.5,
            "up": [jax.random.normal(ks[2 + 2 * i], (h, f)) * h ** -0.5 for i in range(n)],
            "down": [jax.random.normal(ks[3 + 2 * i], (f, h)) * f ** -0.5 for i in range(n)]}


def forward(params: dict, tokens):
    import jax
    import jax.numpy as jnp

    x = params["embed"][tokens]
    for up, down in zip(params["up"], params["down"]):
        with jax.named_scope("mlp"):
            x = x + jnp.tanh(x @ up) @ down
    return x @ params["head"], jnp.mean(x * x)


def train_state_and_step(model: dict, trainer: dict, mesh, key):
    import jax
    import jax.numpy as jnp

    def loss_fn(params, tokens, targets):
        logits, aux = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll), aux

    @jax.jit
    def step(state, tokens, targets):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, tokens, targets)
        params = jax.tree.map(lambda p, g: p - trainer["learning_rate"] * g,
                              state.params, grads)
        return State(params, state.step + 1), {"loss": loss, "aux": aux}

    return State(jax.jit(lambda k: init(model, k))(key), 0), step


def train_flops_per_token(model: dict, seq_len: int) -> float:
    h, f, v, n = (model["hidden_size"], model["intermediate_size"],
                  model["vocab_size"], model["num_hidden_layers"])
    return 3.0 * 2.0 * (n * 2 * h * f + h * v)
