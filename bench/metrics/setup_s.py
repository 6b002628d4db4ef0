"""Seconds from process start to the window's opening: weights, compilation
or cache reads, shape warm-up and the traffic's prebuilt state."""


def read(run):
    return run.setup_s
