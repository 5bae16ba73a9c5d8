"""Can four processes each hold one chip of a four-chip host?

    python -m benchmarks.chip_per_process [--ranks 4] [--steps 3]

ROADMAP's cross-host cell (Speed item 5) wants four tpunet ranks on one
host, one chip each, with gradient all-reduce over the SHM engine. Whether
the runtime allows that is a fact about the installed libtpu, and this tool
asks it. The parent never imports JAX, so it never holds a chip. It gives
each child its chip through the environment before the child starts
(TPU_VISIBLE_CHIPS and a 1x1x1 process grid of its own). Each child reports
the devices JAX shows it and the chip device files it has open, then waits.
Only if every child holds exactly one TPU chip while all the others hold
theirs (a chip is opened by one process at a time, and JAX numbers each
process's only device 0, so ids say nothing), and no two have the same
device file open, do the children go on: they join a tpunet world of `ranks`
and take `steps` cross_host=True train steps of chip_smoke.py's model with
TPUNET_SHM=1.

Prints one JSON line per rank and a last line
  {"one_chip_per_process": true|false, "ranks": N, "errors": [...], ...}
and exits non-zero when the answer is no.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import subprocess
import sys

from benchmarks import REPO_ROOT, free_port, pump_lines

CLAIM_TIMEOUT_S = 180
STEPS_TIMEOUT_S = 900


def chip_env(rank: int) -> dict:
    """What tells libtpu that this process is a host of its own with one
    chip: chip `rank` of the machine."""
    return {
        "TPU_VISIBLE_CHIPS": str(rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{8476 + rank}",
        "TPU_PROCESS_PORT": str(8476 + rank),
        "CLOUD_TPU_TASK_ID": "0",
    }


def _chip_files() -> list[str]:
    """The accelerator device files this process has open."""
    found = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if (target.startswith(("/dev/accel", "/dev/vfio/"))
                and target != "/dev/vfio/vfio"):  # the container, shared
            found.add(target)
    return sorted(found)


def child(rank: int, world: int, steps: int) -> None:
    from benchmarks import claim_device

    dev = claim_device()  # a TPU, or this rank exits non-zero
    print(json.dumps({"rank": rank, **dev, "chip_files": _chip_files()}),
          flush=True)
    coordinator = sys.stdin.readline().strip()  # the parent's go, or EOF
    if not coordinator:
        return

    import chip_smoke
    from tpunet import distributed

    distributed.initialize(coordinator, rank, world)
    state, step, tokens, labels, key = chip_smoke.train_setup(
        chip_smoke.FULL, cross_host=True)
    compiled, compile_s, kernels = chip_smoke.compile_counted(
        step, (state, tokens, labels, key), chip_smoke.FULL.train_kernels)
    distributed.global_communicator().barrier()  # start the steps together
    losses, step_s = chip_smoke.fit_timed(compiled, state, tokens, labels,
                                          key, steps)
    distributed.finalize()
    print(json.dumps({"rank": rank, "compile_s": round(compile_s, 2),
                      "kernels": kernels,
                      "losses": [round(x, 4) for x in losses],
                      "step_s": [round(s, 3) for s in step_s]}), flush=True)


def _next_json(lines: queue.Queue, timeout: float) -> dict | None:
    """The next JSON line of a child, or None if it ended or stayed silent."""
    try:
        while (line := lines.get(timeout=timeout)) is not None:
            if line.startswith("{"):
                return json.loads(line)
    except queue.Empty:
        pass
    return None


def run(ranks: int, steps: int, child_cmd: list[str], log_dir) -> int:
    log_dir.mkdir(parents=True, exist_ok=True)
    logs = [log_dir / f"chip_per_process.rank{r}.stderr" for r in range(ranks)]
    procs = [
        subprocess.Popen(
            child_cmd + ["--rank", str(r), "--ranks", str(ranks),
                         "--steps", str(steps)],
            cwd=REPO_ROOT, text=True, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=open(logs[r], "w"),
            env={**os.environ, **chip_env(r), "TPUNET_SHM": "1"})
        for r in range(ranks)]
    lines = [pump_lines(p) for p in procs]

    def failure(r: int) -> dict:
        procs[r].kill()
        return {"rank": r, "exit": procs[r].wait(),
                "stderr": logs[r].read_text()[-1500:]}

    try:
        # Stage 1: what does each process see?
        claims = [_next_json(lines[r], CLAIM_TIMEOUT_S) for r in range(ranks)]
        errors = [failure(r) for r, c in enumerate(claims) if c is None]
        for c in claims:
            if c is not None:
                print(json.dumps(c), flush=True)
        if not errors:  # all alive and waiting: each holds what it claimed
            files = [f for c in claims for f in c["chip_files"]]
            if (any(c["device_count"] != 1 for c in claims)
                    or len(set(files)) != len(files)):
                errors.append({"error": "the processes do not hold one "
                                        "distinct chip each", "claims": claims})
        if errors:
            print(json.dumps({"one_chip_per_process": False, "ranks": ranks,
                              "errors": errors}))
            return 1

        # Stage 2: the cross-host steps.
        coordinator = f"127.0.0.1:{free_port()}"
        for p in procs:
            p.stdin.write(coordinator + "\n")
            p.stdin.flush()
        results = [_next_json(lines[r], STEPS_TIMEOUT_S) for r in range(ranks)]
        errors = [failure(r) for r, row in enumerate(results) if row is None]
        for row in results:
            if row is not None:
                print(json.dumps(row), flush=True)
        print(json.dumps({
            "one_chip_per_process": True, "ranks": ranks,
            "device_kind": claims[0]["device_kind"], "shm": True,
            "cross_host_steps_ok": not errors, "errors": errors,
            "step_s_slowest_rank": None if errors else [
                max(s) for s in zip(*(row["step_s"] for row in results))],
        }))
        return 1 if errors else 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--ranks", type=int, default=4)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--rank", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.rank is not None:
        child(args.rank, args.ranks, args.steps)
        return 0

    from benchmarks import place_compile_cache
    from tpunet import _native

    _native.build_native()
    place_compile_cache()
    return run(args.ranks, args.steps,
               [sys.executable, "-m", "benchmarks.chip_per_process"],
               REPO_ROOT / "chiprun_out")


if __name__ == "__main__":
    sys.exit(main())
