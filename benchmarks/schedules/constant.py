def lr_at(schedule, step):
    return float(schedule["peak"])
