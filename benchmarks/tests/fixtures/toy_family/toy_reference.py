"""The plain reference of the toy family: the same two-layer residual MLP in
numpy float64, sharing only the names of the weight tensors."""

import numpy as np


def loss(params, tokens, targets, m) -> float:
    x = np.asarray(params["embed"], np.float64)[np.asarray(tokens)]
    for up, down in zip(params["up"], params["down"]):
        x = x + np.tanh(x @ np.asarray(up, np.float64)) @ np.asarray(down, np.float64)
    logits = x @ np.asarray(params["head"], np.float64)
    logits = logits - logits.max(axis=-1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(axis=-1, keepdims=True))
    return float(-logp[np.arange(len(targets)), np.asarray(targets)].mean())
