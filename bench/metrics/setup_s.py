"""Set-up seconds: from process start to the window (data, build,
snapshot, warm-up and any compiling)."""


def read(r):
    return r["setup_s"]
