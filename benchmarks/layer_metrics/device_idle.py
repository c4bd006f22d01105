"""``device.idle.<cell tag>``, for every cell: 1 - the union of the device's
operation intervals over the traced window, in percent."""


def read(run, trace):
    return 100.0 * trace.idle_share()
