"""Compile cache: ``backend_compile_duration`` events of JAX's monitoring
inside the window (persistent-cache hits raise the event too).  Should
read 0.  Source: program counter."""


def read(run):
    return float(run["compiles_in_window"])
