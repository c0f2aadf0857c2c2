"""The plain references.  A configuration names its own in ``"reference"``,
and the harness loads ``chipbench/reference/<reference>.py`` by file path
(``spec.load_reference``).  Such a module provides

- ``init(key, model)``: the parameters as the system under test draws them
  from ``key``: the same key tree, shapes and dtypes;
- ``loss(params, model, batch, precision)``: the token-mean cross-entropy
  of one pod's batch from float32 parameters, in the precision ``"fp32"``
  or the control's ``"fp8"`` (``common.py``);
- ``flops_per_token(model, seq)``: the model FLOPs of one token's forward
  pass through one layer at sequence length ``seq``, two per multiply-add,
  the quadratic terms of attention and the SSD scan over their causal
  half; ``costs.train_flops_per_token`` adds the head and the backward.

``model`` is the ``"model"`` group of the configuration file.  ``common.py``,
``codec.py`` and ``train.py`` are shared by every reference.
"""
