"""python3 -m perfbench.control --workload <cell> --seeds 1,2,3 [--faults]

The readings that a cell's limits are set from, on the chip at the cell's
own size (builder's contract, "How correct is decided", steps 2 to 5).
The benchmark's own runs never call this.

The CONTROL is the plain reference computed one precision below what the
configuration states, put in the program's place and judged against the
float32 reference with the cell's own limits, exactly as a run judges the
program: it has to come out `correct: false`. With --faults the faults a
cell can have are planted in the reference and judged the same way. One
JSON line a seed: for the control and each fault its readings, beside the
limits, and `correct`.
"""

from __future__ import annotations

import argparse
import json

from perfbench import compare, harness

BELOW = {"bfloat16": "fp8", "float32": "bf16"}


def main(argv=None, platform: str = "tpu") -> None:
    from perfbench.adapters import _train

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--precision", help="another precision than the control's")
    a = ap.parse_args(argv)
    cell = harness.cell(a.workload)
    precision = a.precision or BELOW[cell["config"]["compute_dtype"]]
    harness.claim_device(1, platform)
    kinds = [("control", dict(precision=precision))]
    if a.faults:
        kinds.append(("half_batch", dict(precision="f32", fault="half_batch")))
        if cell.get("ranks", 1) > 1:
            kinds.append(("no_exchange", dict(precision="f32", fault="no_exchange")))
    for seed in (int(s) for s in a.seeds.split(",")):
        exact = _train.reference_steps(cell, seed, "f32")
        line = {"seed": seed, "control": precision}
        for name, kw in kinds:
            other = _train.reference_steps(cell, seed, **kw)
            ok, compared = compare.judge(compare.train(other, exact)[0], cell["limits"])
            line[name] = {"correct": ok, "compared": compared}
            del other
            harness.release()
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
