"""Mean share of the slots that are live in each decode tick, over the
traced part of the window (scheduler count read by bench/engine_driver.py),
in percent."""


def read(obs):
    return obs["sched"]["occupancy"]
