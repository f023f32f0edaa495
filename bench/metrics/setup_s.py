"""Set-up seconds: from process start (imports, JAX start-up, the
compile cache) through calibration, trace build and warm-up, to the
window's first pass."""


def read(run):
    return run.setup_s
