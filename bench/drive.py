"""Load drivers: an open loop and the refresh thread.

Each submission is wrapped in a `jax.profiler.TraceAnnotation` named for the
benchmark's own call ("bench.submit", "bench.insert", "bench.probe") when the
run is traced, so that idle gaps on the device can be attributed to what the
host was doing.  Completion times are taken in the future's done-callback,
which runs on the thread that resolved it.
"""
from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class Gate:
    """Lets submitters through unless an insert holds it: `close()` waits
    until no submit is in progress and bars new ones until `open()`."""

    def __init__(self):
        self._cond = threading.Condition()
        self._active = 0
        self._closed = False

    def __enter__(self):
        with self._cond:
            while self._closed:
                self._cond.wait()
            self._active += 1

    def __exit__(self, *exc):
        with self._cond:
            self._active -= 1
            self._cond.notify_all()

    def close(self) -> None:
        with self._cond:
            self._closed = True
            while self._active:
                self._cond.wait()

    def open(self) -> None:
        with self._cond:
            self._closed = False
            self._cond.notify_all()


def annotation(traced: bool):
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


class Record:
    """Per-query timings and outcomes of one loop."""

    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.result: List[object] = [None] * n
        self.error: List[Optional[BaseException]] = [None] * n

    def callback(self, i: int) -> Callable:
        def cb(fut):
            self.done[i] = time.perf_counter()
            try:
                self.result[i] = fut.result()
            except BaseException as exc:      # recorded, counted as failed
                self.error[i] = exc
        return cb


def open_loop(session, queries: list, offsets: np.ndarray, t0: float,
              submitters: int, traced: bool, rec: Optional[Record] = None,
              gate: Optional[Gate] = None) -> Record:
    """Submit query i at t0 + offsets[i], from `submitters` threads taking
    every submitters-th query; returns once every query has been sent."""
    rec = rec if rec is not None else Record(len(queries))
    rec.due[:] = t0 + offsets
    span = annotation(traced)
    gate = gate if gate is not None else contextlib.nullcontext()

    def worker(k: int) -> None:
        for i in range(k, len(queries), submitters):
            wait = rec.due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            try:
                with gate, span("bench.submit"):
                    rec.sent[i] = time.perf_counter()
                    fut = session.submit(queries[i])
            except Exception as exc:          # refused at submit: failed
                rec.error[i] = exc
                rec.done[i] = time.perf_counter()
                continue
            fut.add_done_callback(rec.callback(i))

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(submitters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return rec


class Refresher:
    """Inserts `batches[k]` at t0 + (k + 0.5) * every, then submits probe k;
    records when each insert returned and when the probe was answered.

    With a `gate`, each insert lands at a quiet point: submits are barred,
    every query already sent is answered (`drained()` polled), the batch is
    inserted, and submits resume; queries due meanwhile wait, and their
    latency counts the wait."""

    def __init__(self, store, session, batches: list, probes: list,
                 every: float, traced: bool, gate: Optional[Gate] = None,
                 drained: Optional[Callable[[], bool]] = None):
        self.gate = gate
        self.drained = drained
        self.store = store
        self.session = session
        self.batches = batches
        self.probes = probes
        self.every = every
        self.span = annotation(traced)
        n = len(batches)
        self.inserted = np.full(n, np.nan)
        self.rec = Record(n)
        self.thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        def run() -> None:
            for k, batch in enumerate(self.batches):
                due = t0 + (k + 0.5) * self.every
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                with self.span("bench.insert"):
                    if self.gate is not None:
                        self.gate.close()
                        while not self.drained():
                            time.sleep(0.0005)
                    try:
                        self.store.add_batch(batch)
                    finally:
                        if self.gate is not None:
                            self.gate.open()
                self.inserted[k] = time.perf_counter()
                self.rec.due[k] = self.rec.sent[k] = self.inserted[k]
                try:
                    with self.span("bench.probe"):
                        fut = self.session.submit(self.probes[k])
                except Exception as exc:
                    self.rec.error[k] = exc
                    self.rec.done[k] = time.perf_counter()
                    continue
                fut.add_done_callback(self.rec.callback(k))

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def join(self) -> None:
        if self.thread is not None:
            self.thread.join()


def outstanding(records: List[Record]) -> int:
    """Queries of `records` sent and not yet resolved."""
    return sum(int(np.count_nonzero(~np.isnan(r.sent) & np.isnan(r.done)))
               for r in records)


class Watchdog:
    """A thread that wakes every 50 ms and records each time it woke late by
    100 ms or more: (offset from t0, seconds late, process CPU seconds and
    involuntary context switches in that time).  CPU time that advanced
    during a late wake-up means the process ran (another thread held the
    interpreter); none means the whole process was not running.  It wakes
    rarely so as not to take the interpreter from the system under test.

    With `beat_fd`, each wake-up also writes one byte there, so that a
    process outside this one (`stallwatch.py`) sees when it stops, and
    `names` maps each Python thread's native id to its name."""

    PERIOD = 0.05
    LATE = 0.1

    def __init__(self, t0: float, beat_fd: Optional[int] = None):
        import resource

        self._res = resource
        self.t0 = t0
        self.beat_fd = beat_fd
        self.late: List[tuple] = []
        self.names: Dict[int, str] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        usage = self._res.getrusage(self._res.RUSAGE_SELF)
        cpu, nivcsw = time.process_time(), usage.ru_nivcsw
        last = time.perf_counter()
        while not self._stop.wait(self.PERIOD):
            now = time.perf_counter()
            usage = self._res.getrusage(self._res.RUSAGE_SELF)
            cpu2, nivcsw2 = time.process_time(), usage.ru_nivcsw
            if now - last - self.PERIOD >= self.LATE:
                self.late.append((round(last - self.t0, 3),
                                  round(now - last - self.PERIOD, 3),
                                  round(cpu2 - cpu, 3), nivcsw2 - nivcsw))
            last, cpu, nivcsw = now, cpu2, nivcsw2
            if self.beat_fd is not None:
                self.names.update((t.native_id, t.name)
                                  for t in threading.enumerate())
                try:
                    os.write(self.beat_fd, b".")
                except BlockingIOError:
                    pass

    def stop(self) -> List[tuple]:
        self._stop.set()
        self._thread.join()
        return self.late


class StallWatch:
    """A `Watchdog` that beats to `stallwatch.py`, run as a child process for
    one window: `stop()` returns the watchdog's late wake-ups and the
    watcher's snapshots of every thread taken during the stalls."""

    def __init__(self, t0: float):
        read_fd, self._beat = os.pipe()
        os.set_blocking(self._beat, False)
        self._out = tempfile.TemporaryFile(mode="w+")
        self._proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "stallwatch.py"),
             "--pid", str(os.getpid()), "--fd", str(read_fd)],
            pass_fds=(read_fd,), stdout=self._out)
        os.close(read_fd)
        self.watchdog = Watchdog(t0, beat_fd=self._beat)

    def stop(self) -> Tuple[List[tuple], List[dict]]:
        late = self.watchdog.stop()
        os.close(self._beat)
        self._proc.wait(timeout=30)
        self._out.seek(0)
        snaps = [json.loads(line) for line in self._out if line.strip()]
        self._out.close()
        return late, snaps


_IDLE_WAITS = ("futex", "epoll", "poll", "select", "nanosleep", "pipe_read")


def stall_threads(snap: dict, names: Dict[int, str],
                  limit: int = 24) -> List[str]:
    """One line per thread of a `stallwatch.py` snapshot that is a Python
    thread, used CPU since the last beat, or waits on something other than
    a lock, a poll or a sleep: name (tid), state, wait channel, CPU ticks
    and the top of its kernel stack."""
    out = []
    for t in snap["threads"]:
        wchan = t.get("wchan", "?")
        idle = t["state"] == "S" and any(w in wchan for w in _IDLE_WAITS)
        if t["tid"] not in names and idle and t.get("dticks", 0) == 0:
            continue
        stack = " / ".join(line.split("] ")[-1].split("+")[0] for line in
                           t.get("stack", "").splitlines()[:4])
        out.append(f"{names.get(t['tid'], t['comm'])} ({t['tid']}): "
                   f"{t['state']} {wchan} ticks+{t.get('dticks', 0)}"
                   + (f" [{stack}]" if stack else ""))
    return out[:limit]


def cpu_steal_s() -> Optional[float]:
    """Seconds of CPU the hypervisor took from this machine's cores so far
    (/proc/stat 'steal'), or None where the kernel does not report it."""
    try:
        with open("/proc/stat") as fh:
            fields = fh.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def wait_all(records: List[Record], deadline: float) -> None:
    """Wait until every sent query of `records` is resolved, or `deadline`."""
    while time.perf_counter() < deadline:
        if outstanding(records) == 0:
            return
        time.sleep(0.01)
