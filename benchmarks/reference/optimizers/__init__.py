"""Plain update rules of the references, one module per ``kind`` of a
configuration's ``optimizer``. State is a dict per parameter name.

``init(params, opt)`` -> state;
``update(params, grads, state, lr, opt)`` -> (new params, new state, the
gradient as the update rule gets it). A new optimizer is a new file here
and one beside ``benchmarks/programs/optimizers``."""
