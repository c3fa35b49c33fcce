"""The system under test, as the benchmark builds it: found by the
configuration file's ``family``. A family is four files, each found by that
name, so that a model of another architecture joins the benchmark as new
files only:

- ``bench/families/<family>.py`` builds the program's side, each value made
  on the device from the seed in one jitted call:

  - ``model_config(c, **program)``: the program's
    ``repro.configs.base.ModelConfig`` of configuration ``c``;
    ``program`` carries a mix's own program settings (``loss_chunk``...);
    a configuration the family cannot build raises;
  - ``params(c, seed)``: the program's parameter tree, in the
    configuration's dtype;
  - ``user_adapters(c, seed, users, rank)``: one adapter pytree per user,
    as ``ServeEngine(user_adapters=)`` takes them.

  Weights come from ``bench/seeded.py``: a family's leaves of its own take
  ids from ``seeded.FAMILY_LEAVES`` up, declared with ``seeded.leaves``.
- ``bench/reference/<family>.py``, the plain reference, which imports
  nothing of the program and rebuilds every weight from the seed:

  - ``hidden(c, seed, seqs, users, rank, *, weights, rows, multiple)``:
    last hidden states of token sequences, user ``users[i]``'s adapters on
    sequence i, as one (n, S, d) array; ``weights="fp8"`` is the control;
  - ``head_stats(c, seed, xs, seqs, gather, *, weights)``: at each
    position the best logit, its token, and the logits of the tokens
    ``gather[i][p]``;
  - ``logits(c, seed, seqs, reads, users, rank, *, weights)``: full logits
    at some positions (small sizes, for tests);
  - ``lora_init(c, key, rank)``: the adapters a training session starts
    from, rebuilt from the session's key;
  - ``train_steps(c, seed, adapter_key, rank, batches, opt, *, weights)``:
    each step's loss, the first gradient and the adapters' change.
- ``bench/roofline/<family>.py``, the counts of the work the algorithm
  needs (a multiply-add is 2 operations):

  - ``layer_windows(c)``: for each layer, how many keys a query attends to
    (itself included), or None where it attends to its whole context;
    the kernels' roofline readers add up each layer's least time with it;
  - ``forward_flops(c, pos, n, rank, heads)``: n causal queries at
    positions pos .. pos + n - 1 through every layer, each layer's
    attention over the keys it reads, adapters of ``rank``, and ``heads``
    rows of the output head (``mfu.prefill``, ``mfu.decode``);
  - ``train_flops_per_token(c, seq, rank)``: ColA's required work per
    trained token on sequences of ``seq`` (``mfu.train``).
- ``bench/configs/<name>.json``: the configuration, naming its family.
"""
from __future__ import annotations

from bench import core


def family(c: dict, root: str = core.ROOT):
    """The family file (``bench/families/<family>.py``) of configuration
    ``c`` in the checkout ``root``."""
    return core.module("families", c["family"], root)
