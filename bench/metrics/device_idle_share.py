"""Share of the traced window in which the chip runs no operation: one
minus the union of the device operations' intervals over the window
(averaged over the cell's chips). Layer: device."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
