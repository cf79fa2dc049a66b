"""The mechanism classes every program files its device work under.

A program names what its device ops do with ``jax.named_scope``s of one
vocabulary: ``<class>`` or ``<class>/<detail>`` (``attend/mla``,
``ffn/experts``). XLA keeps each op's name stack as its ``op_name``; a
profiler trace carries it beside every executed instruction, and
``benchmark/readers/device_scopes.py`` files each op under the LAST
component of its stack that is a class name — the innermost, so a
``cache_write`` inside ``attend`` counts as ``cache_write``. Scopes nest
that way on purpose: a mixer block is under ``project``, its reading of
the cache under ``attend`` and its writes under ``cache_write``, so
norms, RoPE and residual adds fall in the class of their block. No Flax
module and no other scope may take a class's name. A scope changes only
an op's location metadata: the programs XLA is handed are the same with
or without them (``tests/test_device_scopes.py`` pins their text).

  project      a mixer block's own work apart from the cache: its norm,
               q/k/v/o, RoPE, the residual add, latent and linear
               projections
  attend       reading what the context left behind: local, ring and
               latent attention and their kernels, the sparse index and
               its gathers, the linear recurrence, the SGU's mix over its
               gate history, every read of cached rows
  cache_write  writing a step's or a block's rows and states into the
               slot cache (every family's ``_write_rows``), the pool's
               scatter in ``_prefill_finish``
  ffn          a feed-forward block whole: norm, GLU, SwiGLU, gMLP's
               projections, routed and shared experts and their router,
               the residual add
  head         the embedding lookup, the final norm, the vocabulary
               product, and the loss or log-probabilities over it
  sample       what a decode step does after the logits: the draw, the
               stop and infill rules, the slots' bookkeeping
  optimizer    the train step's gradient accumulation, update and
               finite gate
"""

CLASSES = ("project", "attend", "cache_write", "ffn", "head", "sample",
           "optimizer")
