#!/usr/bin/env python3
"""Make a hole in a serving run on purpose, to see how the step account reads it.

    python tools/induce_stall.py --mode stop|hog [--after 10] [--hold 2] -- \\
        python3 benchmarks/run.py --workload <cell> --seed <n> --seconds 45 --trace 0

Runs the command after ``--`` as a child, passes its output through, and
``--after`` seconds after the child prints ``--marker`` (the benchmark's
``[window] open``) holds the child's serve loop up for ``--hold`` seconds:

- ``stop``: ``SIGSTOP`` then ``SIGCONT`` to the whole process — off the CPU
  and off the run queue, as a frozen container or a debugger would leave it;
- ``hog``: pins the child's MAIN thread (the serve loop's) to the core it is
  on and runs a busy loop on that core at a priority the thread cannot
  compete with (``SCHED_FIFO`` where allowed, else nice -20 against +19) —
  off the CPU but ON the run queue, with involuntary switches, as a
  neighbour on a shared host would leave it.

What the account made of it is the child's ``serving: stall {...}`` line on
standard error.  This process never imports JAX: the chip is the child's.
Such a run is INDUCED: a way to read the instrument, never a result.
"""

import argparse
import os
import signal
import subprocess
import sys
import threading
import time


def say(msg):
    print(f"[induce] {msg}", file=sys.stderr, flush=True)


def stop(pid, hold):
    os.kill(pid, signal.SIGSTOP)
    time.sleep(hold)
    os.kill(pid, signal.SIGCONT)
    say(f"stop: SIGSTOP, {hold} s, SIGCONT to pid {pid}")


def hog(pid, hold):
    # field 39 of /proc/<pid>/stat: the core the main thread last ran on
    with open(f"/proc/{pid}/stat") as f:
        core = int(f.read().rsplit(")", 1)[1].split()[36])
    before = os.sched_getaffinity(pid)
    os.sched_setaffinity(pid, {core})       # tid == pid: the main thread only
    busy = subprocess.Popen([sys.executable, "-c", "while True: pass"])
    os.sched_setaffinity(busy.pid, {core})
    try:
        os.sched_setscheduler(busy.pid, os.SCHED_FIFO, os.sched_param(50))
        how = "SCHED_FIFO 50"
    except (PermissionError, OSError):
        how = "nice"
        for who, nice in ((busy.pid, -20), (pid, 19)):
            try:
                os.setpriority(os.PRIO_PROCESS, who, nice)
                how += f" {who}:{nice}"
            except (PermissionError, OSError) as e:
                how += f" {who}:{type(e).__name__}"
    time.sleep(hold)
    busy.kill()
    busy.wait()
    os.sched_setaffinity(pid, before)
    try:
        os.setpriority(os.PRIO_PROCESS, pid, 0)
    except (PermissionError, OSError):
        pass
    say(f"hog: main thread of pid {pid} pinned to core {core} beside a busy "
        f"loop ({how}) for {hold} s; affinity restored to {len(before)} "
        "cores")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("stop", "hog"), required=True)
    ap.add_argument("--after", type=float, default=10.0)
    ap.add_argument("--hold", type=float, default=2.0)
    ap.add_argument("--marker", default="[window] open")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    command = args.command[1:] if args.command[:1] == ["--"] \
        else args.command
    child = subprocess.Popen(command, stdout=subprocess.PIPE, text=True,
                             bufsize=1)
    act = {"stop": stop, "hog": hog}[args.mode]

    def later():
        time.sleep(args.after)
        if child.poll() is None:
            try:
                act(child.pid, args.hold)
            except Exception as e:      # the run goes on; the line says why
                say(f"{args.mode} failed: {e!r}")

    armed = False
    for line in child.stdout:
        sys.stdout.write(line)
        sys.stdout.flush()
        if not armed and args.marker in line:
            armed = True
            threading.Thread(target=later, daemon=True).start()
    sys.exit(child.wait())


if __name__ == "__main__":
    main()
