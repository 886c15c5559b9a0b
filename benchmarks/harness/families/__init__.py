"""One module per model family, found by the `family` key of a configuration
file (see spec.Cell), as a traffic `kind` and a metric's `reader` are. A
family module is everything the cell drivers take from the program for one
architecture, so a new architecture is a new file here, a reference under
`benchmarks/reference/` (the configuration's `reference` key) and a
configuration file, and edits none. A family may serve only or train only:
it then leaves `serve_app` or `train_state_and_step` out, and a cell that
asks for the missing one ends with a `SystemExit` that names the family and
the cell (`spec.Cell.family_entry`). It holds:

- `MODEL_KEYS`: the published config.json keys the configuration file keeps
  at its top level; `cell.config["model"]` is the view of them.
- `model_config(model, **extra)`: the program's model configuration.
- `seeded_params(cfg, seed)`: weights made on the device in one jitted call.
- `serve_app(cfg, model, engine, tokenizer)`: the serving app through the
  program's normal entry point, and the engine class it constructs (the
  harness taps that class's `__init__`, see engine_tap.py).
- `train_state_and_step(model, trainer, mesh, key)`: the sharded train state,
  made in one jitted call, and the compiled step `step(state, tokens, targets)
  -> (state, {"loss": ..., <any other scalar>: ...})`; every scalar of that
  dict reaches the readers as the series `step.<name>`.
- the yardstick's shapes functions that hold for this architecture, by the
  names the metric files give (`train_flops_per_token`, ...): a reader looks
  them up here, so a family whose arithmetic differs brings its own.
"""

from __future__ import annotations


def seeded_key(seed: int):
    """`--seed` is any whole number up to a little over 2**31; PRNGKey takes
    it as it is (jax folds the high bits in)."""
    import jax

    return jax.random.PRNGKey(int(seed) % (2 ** 63))
