"""Share of the traced window in which no operation ran on the device,
in a select cell (moves ``select_s``)."""


def read(run):
    return 100.0 * run.trace.idle_frac()
