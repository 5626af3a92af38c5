"""One file per architecture, found by a configuration file's ``model_type``
(``manifest.Manifest.cell``). Each holds what only that architecture knows:

* ``WIDTHS`` — the published keys that are never cut;
* ``program_config(keys)`` — the program's own configuration object from the
  published keys as run (``manifest.published_keys`` of the file);
* ``seeded_params(cfg, seed)`` — the served weights, on the device in one
  jitted call, in the type they are served in;
* ``describe(cfg)`` — one line for the log;
* ``matmul_params(keys)``, ``train_step_flops(keys, batch, seq)`` — the
  operations a train step requires, for ``train.mfu_causal``.
"""

REQUIRED = (
    "WIDTHS", "program_config", "seeded_params", "describe", "matmul_params",
    "train_step_flops",
)
