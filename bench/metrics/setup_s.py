"""Set-up seconds: process start to the first due request (weights made on
the device, chains composed, the cell's shapes compiled or read back)."""


def compute(run):
    return run.setup_s
