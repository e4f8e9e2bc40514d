"""Run a cell with one of its system's stated guarantees broken.

    python3 -m bench.control --workload <cell> --control <name> --seed <n> --seconds <s>

Runs the cell as ``bench.run`` does, with the control
``bench/controls/<system>.<name>.py`` applied (through the program's
public surface only), and prints the same result line; its ``correct``
has to come out false, on the compared number the control names
(``FAILS``).  A control belongs to a system (``kv_store``,
``range_filter``), so every cell on that system has it, and a new one is
a new file.  The benchmark's own runs never run a control;
``tests/bench`` runs every control at a size a test run holds.
"""
from __future__ import annotations

import argparse
import json
import sys

if __package__ in (None, ""):
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import registry  # noqa: E402
from bench import run as bench_run  # noqa: E402


def run_control(cell: str, control: str, seed: int, seconds: float, *,
                root: str = registry.ROOT, **kw) -> dict:
    bench = registry.load_benchmark(root)
    system = registry.load_config(registry.cell(bench, cell)["config"],
                                  root)["system"]
    ctl = registry.load_control(system, control, root)
    over = bench_run._merge(getattr(ctl, "CONFIG_OVERRIDES", None) or {},
                            kw.pop("config_overrides", None))
    return bench_run.run_cell(cell, seed, seconds, False, root=root,
                              config_overrides=over,
                              fault=getattr(ctl, "fault", None), **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        result = run_control(args.workload, args.control, args.seed,
                             args.seconds)
    except (bench_run.NoChip, registry.RegistryError) as e:
        bench_run.log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
