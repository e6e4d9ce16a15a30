"""Share of the traced window in which no operation ran on the chip, in %,
under a closed loop (1 - union of the device-op intervals / window), as
``device_idle_share`` reads it under an open one."""


def read(run):
    return 100.0 * run["trace"].idle_share
