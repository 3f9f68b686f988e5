def make(paddle, opt, lr, parameters):
    return paddle.optimizer.AdamW(
        learning_rate=lr, beta1=opt["beta1"],
        beta2=opt["beta2"], epsilon=opt["epsilon"],
        weight_decay=opt["weight_decay"], parameters=parameters), \
        ("moment1", 1.0 / (1.0 - opt["beta1"]))
