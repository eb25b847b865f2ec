"""Seconds from process start to the window's opening (host clock):
weights, calibration, engine, compile-cache loads, warm-up, lead-in."""


def read(run):
    return run.setup_s
