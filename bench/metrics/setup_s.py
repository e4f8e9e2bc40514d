"""setup_s: seconds from the start of the run to the measured window.

Process start-up, JAX's start on the device, making the data from the
seed, loading it through the program, compiling, and warming up every
batch shape of the traffic (host clock)."""


def read(run):
    return run.setup_s
