"""User-space stacks of a wedged process, without a debugger.

    python -m tools.stackscan <pid> [library-name]

This sandbox has no gdb. For every thread parked in a syscall,
/proc/<pid>/task/<tid>/syscall gives the user stack pointer; this reads the
stack from /proc/<pid>/mem and names every word that points into the
library's text (default libtpunet.so) with `nm`. Not an unwinder: stale
return addresses show up too, but the chain of tpunet:: frames a thread is
parked under reads clearly (it named the FenceAsync -> WaitTicket wait and
the spinning async worker behind the peer-death wedge fixed in PR 21). A
thread that is running shows as <running>: scan twice to tell a spin from a
wake-up. Needs root (or the same uid and ptrace rights).
"""

import bisect
import os
import re
import struct
import subprocess
import sys


def symbols(lib: str) -> list[tuple[int, str]]:
    out = subprocess.run(["nm", "-C", "--defined-only", "-n", lib],
                         capture_output=True, text=True, check=True).stdout
    syms = []
    for line in out.splitlines():
        parts = line.split(None, 2)
        if len(parts) == 3 and parts[1] in "TtWw":
            syms.append((int(parts[0], 16), parts[2]))
    return syms


def scan(pid: int, libname: str = "libtpunet.so", depth: int = 16384) -> dict:
    """{tid: [innermost-first names of `libname` functions found on the
    thread's stack]}; ["<running>"] for a thread that is not in a syscall."""
    segs, base, lib = [], None, None
    for line in open(f"/proc/{pid}/maps"):
        f = line.split()
        if len(f) < 6 or libname not in f[5]:
            continue
        lo, hi = (int(x, 16) for x in f[0].split("-"))
        lib = f[5]
        if base is None:
            base = lo - int(f[2], 16)
        if "x" in f[1]:
            segs.append((lo, hi))
    if lib is None:
        raise SystemExit(f"pid {pid} has no {libname} mapped")
    syms = symbols(lib)
    addrs = [a for a, _ in syms]
    res = {}
    with open(f"/proc/{pid}/mem", "rb", 0) as mem:
        for task in sorted(os.listdir(f"/proc/{pid}/task"), key=int):
            sc = open(f"/proc/{pid}/task/{task}/syscall").read().split()
            if sc[0] == "running" or len(sc) < 9:
                res[task] = ["<running>"]
                continue
            try:
                mem.seek(int(sc[-2], 16))  # the user stack pointer
                data = mem.read(depth)
            except OSError as e:
                res[task] = [f"<unreadable: {e}>"]
                continue
            frames = []
            for (word,) in struct.iter_unpack("<Q", data[:len(data) & ~7]):
                if any(lo <= word < hi for lo, hi in segs):
                    j = bisect.bisect_right(addrs, word - base) - 1
                    name = re.sub(r"\(.*", "", syms[j][1]) if j >= 0 else "?"
                    if not frames or frames[-1] != name:
                        frames.append(name)
            res[task] = frames[:14]
    return res


if __name__ == "__main__":
    for tid, frames in scan(int(sys.argv[1]), *sys.argv[2:3]).items():
        print(tid, " <- ".join(frames))
