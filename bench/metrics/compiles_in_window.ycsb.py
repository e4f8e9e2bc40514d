"""compiles_in_window.ycsb: programs JAX compiled or loaded from its
persistent cache while the window ran (JAX's monitoring events).  Each is
a stall a user would feel in the tail."""


def read(run):
    return run.compiles_in_window
