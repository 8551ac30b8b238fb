"""Set-up (host clock): process start to the window's open: JAX and device
start-up, instances, the service, and the warm-up of the cell's shapes with
its compiles or cache loads."""


def read(ctx):
    return ctx["setup_s"]
