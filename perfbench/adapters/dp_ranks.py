"""A data-parallel job, one process a rank, over the tpunet transport.

The cell's file says how many ranks there are; the first `chips` of them
hold one chip each and run the real model, the others are shape twins on
the CPU (perfbench/twin.py). Every rank runs the program's own
make_train_step(cross_host=True), so the job issues whatever collectives
the trainer issues. This process, the parent, never touches JAX: it starts
the ranks, relays rank 0's result and stops every rank before it returns.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from perfbench import harness

RANK_TIMEOUT_S = 1150


def chip_env(rank: int) -> dict:
    """What tells libtpu that this process is a host of its own with one
    chip, chip `rank` of the machine (proven by PR 21)."""
    return {"TPU_VISIBLE_CHIPS": str(rank),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{8476 + rank}",
            "TPU_PROCESS_PORT": str(8476 + rank),
            "CLOUD_TPU_TASK_ID": "0"}


def _free_port() -> int:
    """A loopback port for the job's coordinator, below the kernel's
    ephemeral range and searched from a number of this process's own: the
    kernel's next ephemeral port (bind to 0) can be handed to another
    parent, or to any outgoing connection, in the seconds before rank 0
    binds it."""
    base = 20000 + os.getpid() * 61 % 12000
    for port in range(base, base + 61):
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
            return port
    raise SystemExit(f"no free loopback port in {base}..{base + 60}")


def run(cell: dict, seed: int, seconds: float, trace: bool,
        platform: str = "tpu", fault: str | None = None) -> dict:
    world, chips = cell["ranks"], cell["chips"]
    port = _free_port()
    pipes = [os.pipe() for _ in range(world - 1)]
    out_fd, out_path = tempfile.mkstemp(prefix="perfbench-result-", suffix=".json")
    os.close(out_fd)
    cell_fd, cell_path = tempfile.mkstemp(prefix="perfbench-cell-", suffix=".json")
    with os.fdopen(cell_fd, "w") as f:
        json.dump(cell, f)
    procs = []
    try:
        for r in range(world):
            env = dict(os.environ)
            for k, v in cell.get("env", {}).items():
                if v is None:
                    env.pop(k, None)
                else:
                    env[k] = str(v)
            twin = r >= chips
            if twin or platform == "cpu":
                env["JAX_PLATFORMS"] = "cpu"
            elif chips > 1:
                env.update(chip_env(r))
            argv = [sys.executable, "-m", "perfbench.adapters.dp_ranks",
                    "--cell", cell_path, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(int(trace)),
                    "--rank", str(r), "--port", str(port), "--out", out_path,
                    "--platform", "cpu" if twin else platform]
            if fault:
                argv += ["--fault", fault]
            if r == 0:
                fds = [w for _, w in pipes]
                argv += ["--ctl-write", ",".join(map(str, fds))]
            else:
                fds = [pipes[r - 1][0]]
                argv += ["--ctl-read", str(fds[0])]
            procs.append(subprocess.Popen(argv, cwd=harness.ROOT, env=env,
                                          pass_fds=fds, stdout=sys.stderr))
        for rd, wr in pipes:
            os.close(rd)
            os.close(wr)
        # a rank that failed leaves the others waiting for it in the
        # bootstrap or the ring until their own timeouts: stop at the first
        deadline = time.monotonic() + RANK_TIMEOUT_S
        while time.monotonic() < deadline:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes) or any(codes):
                break
            time.sleep(0.2)
        if any(c != 0 for c in codes):
            raise SystemExit(f"rank exit codes {codes}")
        with open(out_path) as f:
            return json.load(f)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
        os.unlink(out_path)
        os.unlink(cell_path)


def _rank_main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--ctl-read", type=int)
    ap.add_argument("--ctl-write", default="")
    ap.add_argument("--fault")
    a = ap.parse_args(argv)
    from perfbench.adapters import _train

    with open(a.cell) as f:
        cell = json.load(f)
    cell["_coordinator"] = f"127.0.0.1:{a.port}"
    # the exchange left out: the chip rank's world is itself alone, and the
    # other ranks are not started
    solo = a.fault == "no_exchange"
    if solo and a.rank != 0:
        return
    res = _train.run_rank(
        cell, a.seed, a.seconds, bool(a.trace), platform=a.platform,
        rank=a.rank, world=1 if solo else cell["ranks"],
        is_twin=a.rank >= cell["chips"], ctl_read=a.ctl_read,
        ctl_write=[] if solo else [int(x) for x in a.ctl_write.split(",") if x],
        fault=a.fault)
    if a.rank == 0:
        with open(a.out, "w") as f:
            json.dump(res, f)


if __name__ == "__main__":
    _rank_main()
