"""The serving lane's host time per launch: the mean ``dispatch``
(which holds ``pack``), ``handoff`` (the two hops to and from the
watchdog worker) and ``resolve`` (tickets, callbacks, accounting)
slices over the window, summed, in us.  None where the program does not
time all three."""

import readers

STAGES = ("dispatch", "handoff", "resolve")


def read(ctx):
    means = [readers.stage_mean_us(ctx, s) for s in STAGES]
    return None if None in means else sum(means)
