"""Share, in percent, of a day window in which no operation ran on the device
(profiler trace, `bench.trace`)."""


def read(run):
    return 100.0 * run.trace["idle_share"]
