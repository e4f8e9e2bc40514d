"""The chip benchmark: cells named in ``BENCHMARK.json``, found by name.

Run one cell from the repository root::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found through the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``   - a deployment: sizes, settings, source;
* ``bench/workloads/<traffic>.json`` - a traffic mix, read by ``traffic.py``;
* ``bench/metrics/<metric>.py``     - a reader with ``read(run)``;
* ``bench/systems/<system>.py``     - the driver a configuration names;
* ``bench/reference/<ref>.py``      - the plain reference it is checked by;
* ``bench/controls/<system>.<name>.py`` - a control: the system with one
  stated guarantee broken;
* ``bench/peaks.json``              - device peaks, keyed by ``device_kind``.

``python3 -m bench.control --workload <cell> --control <name> ...`` runs
a cell under a control of its system (``bench/control.py``); its
``correct`` must be false.

Importing any module of this package imports no JAX and touches no
device: the entry point does that under its ``__main__`` check.
"""
