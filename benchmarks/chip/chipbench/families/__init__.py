"""One module per published ``model_type``: all that depends on a model's
architecture.

``spec.family(model_type)`` loads ``families/<model_type>.py`` by its file
name, so a configuration of a new architecture is added as new files
only.  A family module imports nothing of the program under test at
import time, and provides:

- ``model(name, hf)``: the frozen, hashable :class:`spec.Model` (or a
  subclass with fields of its own) parsed from the keys of a published
  ``config.json``, refusing any key that would change the arithmetic and
  that the family has no reference for;
- ``program_model(m)``: the program's ``ModelConfig`` for ``m``;
- ``leaves(m)``: ``(path, shape)`` of every weight, by its path in the
  served parameter tree (``weights.py`` draws them);
- ``logits(m, w, tokens, rows, cols, quant)``: the plain float32
  reference, jitted with ``m`` and ``quant`` static, written from the
  helpers in ``reference.py``;
- the counts that ``counts.py`` builds the rooflines from:
  ``token_flops(m)`` (matrix-product operations per token outside the
  attention scores), ``key_flops(m)`` (attention operations of one query
  per key it attends), ``kv_bytes_per_token(m)``,
  ``run_weight_bytes(m, tokens)`` (weight bytes a run over ``tokens`` real
  token rows reads) and ``least_weight_bytes(m)`` (the least weight bytes
  any run reads).

Modules whose names start with ``_`` are shared blocks, not families.
"""
