"""1 - the union of the device's operation intervals over the traced window,
mean over the chips. In percent."""


def read(run):
    return run.idle_percent()
