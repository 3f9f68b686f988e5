"""The program's optimizer for a ``kind`` of a configuration's
``optimizer``, one module per kind: ``make(paddle, opt, lr, parameters)``,
``lr`` being the first step's, -> (the ``paddle.optimizer`` object, (slot
name, factor)): the optimizer slot that, after one step from zero state,
holds factor^-1 x the gradient the update rule was given."""
