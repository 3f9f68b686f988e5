"""Plain references, one module per configuration, written from the papers.

A reference imports nothing of ``paddle1_tpu``. Its interface:

``init_params(cfg, key)``
    every weight of the model from one PRNG key, float32, as a flat dict;
``loss(params, batch, cfg, nm)``
    ``(scalar loss, state_updates)`` where ``nm`` is a
    :class:`~benchmarks.reference.numerics.Numerics` and ``state_updates``
    maps a parameter name to the value a non-trained state takes after the
    step (batch-norm running statistics), or is empty.
"""
