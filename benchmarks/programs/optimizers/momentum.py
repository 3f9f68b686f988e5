def make(paddle, opt, lr, parameters):
    return paddle.optimizer.Momentum(
        learning_rate=lr, momentum=opt["momentum"],
        weight_decay=opt["weight_decay"], parameters=parameters), \
        ("velocity", 1.0)
