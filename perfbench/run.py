"""python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its files under perfbench/ by name, and
hands the run to the cell's adapter. The last line of standard output is the
result. No TPU, or fewer chips than the cell asks for: non-zero exit, no
result. This process never touches JAX where the adapter starts ranks of
its own.
"""

from __future__ import annotations

import argparse
import importlib

from perfbench import harness


def main(argv=None) -> None:
    harness.process_start()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import tpunet  # noqa: F401  the system under test; absent, the run ends here

    cell = harness.cell(args.workload)
    adapter = importlib.import_module(f"perfbench.adapters.{cell['adapter']}")
    res = adapter.run(cell, args.seed, args.seconds, bool(args.trace))
    harness.emit(res)


if __name__ == "__main__":
    main()
