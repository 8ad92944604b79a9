"""Mean time from a request's due time until the scheduler takes it from
the queue into a slot (the start of the tick that admits it), over the
requests due in the traced part of the window, in ms."""


def read(obs):
    return obs["sched"]["queue_wait_ms"]
