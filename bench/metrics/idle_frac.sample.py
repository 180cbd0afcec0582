"""Share of the traced window in which no operation ran on the device,
in a sample cell (moves ``rrr_sets_per_s``)."""


def read(run):
    return 100.0 * run.trace.idle_frac()
