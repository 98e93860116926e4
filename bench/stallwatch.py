#!/usr/bin/env python3
"""Watches a process for stalls from outside it, where none of its own
threads can look while the stall lasts.

    python3 bench/stallwatch.py --pid <pid> --fd <read end of a pipe>

The watched process writes a byte to the pipe every 50 ms
(`drive.Watchdog` with `beat_fd`).  When none has come for AFTER seconds,
this process reads every thread of the watched one from /proc (name,
state, kernel wait channel, CPU ticks since the last beat, and the kernel
stack and system call where it may read them) and prints one JSON line;
again every AFTER seconds while the stall lasts.  It reads nothing else
and exits when the pipe closes.  It takes no part in a
measured run: `sweep.py` starts it for each window.
"""
import argparse
import json
import os
import select
import sys
import time

AFTER = 0.5


def read_threads(pid: int) -> list:
    """Every thread of `pid`: tid, name, state, CPU ticks, and the wait
    channel, system call and kernel stack where /proc lets us read them."""
    out = []
    base = f"/proc/{pid}/task"
    for tid in sorted(os.listdir(base), key=int):
        path = f"{base}/{tid}"
        try:
            with open(f"{path}/stat") as fh:
                stat = fh.read()
        except OSError:
            continue
        fields = stat[stat.rindex(")") + 2:].split()
        row = {"tid": int(tid),
               "comm": stat[stat.index("(") + 1:stat.rindex(")")],
               "state": fields[0], "ticks": int(fields[11]) + int(fields[12])}
        for name in ("wchan", "syscall", "stack"):
            try:
                with open(f"{path}/{name}") as fh:
                    row[name] = fh.read().strip()
            except OSError:
                pass
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pid", type=int, required=True)
    ap.add_argument("--fd", type=int, required=True)
    args = ap.parse_args(argv)
    last_beat = time.monotonic()
    base = {t["tid"]: t["ticks"] for t in read_threads(args.pid)}
    base_at = last_snap = last_beat
    while True:
        ready, _, _ = select.select([args.fd], [], [], 0.05)
        now = time.monotonic()
        if ready:
            if not os.read(args.fd, 4096):
                return 0
            last_beat = now
            if now - base_at >= 0.5:
                base = {t["tid"]: t["ticks"] for t in read_threads(args.pid)}
                base_at = now
            continue
        if now - last_beat < AFTER or now - last_snap < AFTER:
            continue
        try:
            snap = read_threads(args.pid)
        except OSError:
            return 0
        for t in snap:
            t["dticks"] = t["ticks"] - base.get(t["tid"], t["ticks"])
        print(json.dumps({"since_beat_s": round(now - last_beat, 3),
                          "threads": snap}), flush=True)
        last_snap = now


if __name__ == "__main__":
    sys.exit(main())
