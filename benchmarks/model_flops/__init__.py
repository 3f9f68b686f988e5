"""Model FLOPs of one optimizer step, one module per configuration:
``train_step_flops(cfg, env) -> float``, from the shapes alone. A multiply
and an add count as two; recomputation is not counted; elementwise work,
normalisations, softmax and the optimizer are left out."""
