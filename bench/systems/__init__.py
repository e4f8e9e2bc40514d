"""Drivers of the system under test, one module per kind of deployment.

A configuration names its driver (``"system"``); the module defines
``System(config, seed, tracer, log)`` with:

* ``setup()``         - make the data from the seed and load it;
* ``dataset()``       - the ``traffic.Dataset`` the generator draws from;
* ``warm_up(shapes)`` - run every batch shape the traffic will issue,
  without changing the loaded state;
* ``step(step)``      - issue one closed-loop step, keep its answers;
* ``counters()``      - the program's own counters, as a flat dict;
* ``check(limits)``   - compare every kept answer with the plain
  reference, returning ``(checks, observed)``;
* ``close()``         - release files and directories.
"""
