"""Devlin et al. 2018, A.2: linear from 0 to ``peak`` over
``warmup_steps``; the run stands ``first_step`` steps into the job."""


def lr_at(schedule, step):
    at = schedule.get("first_step", 0) + step + 1
    return float(schedule["peak"]) * min(1.0, at / schedule["warmup_steps"])
