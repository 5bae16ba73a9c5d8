"""A transport-only rank, driven over its stdin: no JAX, so it never asks for
the chip.

chip_smoke.py holds the one chip of its machine, so every other rank of the
collectives it runs has to be a process like this one: it loads libtpunet.so,
joins a Communicator and contributes constant vectors to the all-reduces it
is told about. One JSON object per line in, one per line out:

  {"op": "init", "lib": path, "coordinator": "127.0.0.1:P", "rank": r, "world": w}
  {"op": "all_reduce", "dtype": "float32", "n": N | [N0, N1, ...],
   "fill": v, "reps": k, "expect": e | null}
                                       k times: a blocking all-reduce of
                                       N x v (of each N in turn: the chunks
                                       of the trainer's boundary exchange)
  {"op": "close"}

Every reply is {"ok": true, "op": ..., "seconds": [...]}; a failure replies
{"ok": false, "error": ...} and the process exits 1. Rank 0 hosts the
bootstrap, as in any tpunet job.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def _dtype(name: str):
    import numpy as np

    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def serve(lines, out) -> None:
    import numpy as np

    comm = None

    def reply(**kw):
        out.write(json.dumps(kw) + "\n")
        out.flush()

    for line in lines:
        msg = json.loads(line)
        op = msg["op"]
        if op == "init":
            from tpunet import _native
            from tpunet.collectives import Communicator

            _native.load(Path(msg["lib"]))
            comm = Communicator(msg["coordinator"], msg["rank"], msg["world"])
            reply(ok=True, op=op)
        elif op == "all_reduce":
            sizes = msg["n"] if isinstance(msg["n"], list) else [msg["n"]]
            sends = [np.full(n, msg["fill"], _dtype(msg["dtype"])) for n in sizes]
            seconds = []
            for _ in range(msg["reps"]):
                t0 = time.perf_counter()
                gots = [comm.all_reduce(send) for send in sends]
                seconds.append(time.perf_counter() - t0)
                want = msg.get("expect")
                for got in gots:
                    if want is not None and not (got == got.dtype.type(want)).all():
                        raise RuntimeError(
                            f"all_reduce gave {got[:4]}..., expected {want}")
            reply(ok=True, op=op, seconds=seconds)
        elif op == "close":
            break
        else:
            raise ValueError(f"unknown op {op!r}")
    if comm is not None:
        comm.close()


def main() -> None:
    try:
        serve(sys.stdin, sys.stdout)
    except Exception as e:  # the one boundary: report, then fail the process
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"}),
              flush=True)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
