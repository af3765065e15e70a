"""A configuration's architecture module: every answer of the harness
that depends on the architecture comes from it.

A configuration file names its module under ``"reference"``, a path from
the checkout's root; ``bench/run.py`` loads that file by path and hands
the module to the warm-up, the check and the metric readers (``ctx.arch``).
A new architecture is a new module and a configuration that names it: no
file of the harness changes.  The module exports:

- ``model_config(cfg) -> repro.configs.ModelConfig``: the program's
  configuration for the configuration file ``cfg``; raises ``Refused``
  where the program has no path for it.
- ``leaf_std(path, shape, d_model) -> float``: the standard deviation each
  parameter leaf is drawn with (``bench.core.weights.draw_tree``).  The
  leaves architectures share (norms, token tables, attention, dense MLP)
  may take the common rule, ``bench.core.weights.leaf_std``.
- ``logits_at(cfg, seed, seqs, positions, quant=False)``: the plain float32
  reference's logits at the listed positions of each sequence, from the
  weights drawn again from the seed.  ``quant=True`` is the control, the
  reference one precision step below the configuration's; a module with
  no control raises ``Refused``.
- ``prefill_flops(cfg, prompt, cached)`` and ``decode_flops(cfg, context)``:
  the FLOPs the model requires for a prompt's uncached positions with its
  first token, and for one later token whose input sits at position
  ``context - 1`` (``step_mfu.*``).
- ``page_op_bytes(cfg, page_size, inits, copies)``: the HBM bytes the
  cache's page inits and copies must move (``pim_page_roofline.*``).
"""


class Refused(Exception):
    """The run cannot measure here; exit non-zero, print no result."""
