"""Seconds from process start until the window opens: traffic, pool
initialisation, warm-up (compiles included), connecting every lane and the
pre-roll."""


def read(ctx):
    return ctx["setup_s"]
