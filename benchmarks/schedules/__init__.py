"""Learning-rate schedules, one module per ``kind`` of a configuration's
``optimizer.lr_schedule``: ``lr_at(schedule, step)`` -> the learning rate
of the job's ``step``-th step (0-based from the start of the run), as a
Python float. A new schedule is a new file here."""
