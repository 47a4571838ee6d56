"""A diagnostic beside the program (PR 35; PERF.md section 7, ROADMAP S12):

    python3 tools/stall_probe.py <out-prefix> python3 benchmarks/run.py --workload ... --trace 0

Run one benchmark command as a child, timestamp its `serve: step` WARNING lines, and sample the
child's MAIN THREAD from outside every 5 ms: scheduler state (R running or
runnable, S sleeping on a futex / poll, D disk), the thread's and the process's
CPU time, this sampler's own lateness (was the whole machine stalled?), the
cgroup's throttling counters and the machine's steal time. For each slow step,
print what the 400 ms before its line looked like. Imports no jax."""
import collections
import os
import subprocess
import sys
import threading
import time

TICK = os.sysconf("SC_CLK_TCK")


def read(path):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def cpu_of(stat: str):
    rest = stat.rsplit(")", 1)[1].split()
    return rest[0], (int(rest[11]) + int(rest[12])) / TICK  # state, utime+stime s


def throttled():
    for path in ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat"):
        text = read(path)
        if text:
            fields = dict(line.split()[:2] for line in text.splitlines() if line)
            return (int(fields.get("nr_throttled", 0)),
                    int(fields.get("throttled_usec",
                                   fields.get("throttled_time", 0))))
    return (-1, -1)


def steal():
    first = read("/proc/stat").splitlines()[0].split()
    return int(first[8]) / TICK if len(first) > 8 else -1.0


def main():
    out_path, command = sys.argv[1], sys.argv[2:]
    child = subprocess.Popen(command, stdout=open(out_path + ".out", "w"),
                             stderr=subprocess.PIPE, text=True)
    pid = child.pid
    ring = collections.deque(maxlen=400)
    stop = threading.Event()

    def sample():
        due = time.monotonic()
        while not stop.is_set():
            now = time.monotonic()
            late = now - due
            thread = read(f"/proc/{pid}/task/{pid}/stat")
            process = read(f"/proc/{pid}/stat")
            if thread and process:
                state, thread_cpu = cpu_of(thread)
                ring.append((time.time(), state, thread_cpu, cpu_of(process)[1],
                             late, throttled(), steal(),
                             read("/proc/loadavg").split()[0],
                             read(f"/proc/{pid}/task/{pid}/wchan") or "-"))
            due = max(due + 0.005, time.monotonic())
            time.sleep(max(0.0, due - time.monotonic()))

    threading.Thread(target=sample, daemon=True).start()
    print(f"probe: cgroup cpu.max = {read('/sys/fs/cgroup/cpu.max').strip()!r}; "
          f"cpus = {os.cpu_count()}, affinity {len(os.sched_getaffinity(0))}; "
          f"cpu.stat = {throttled()}", flush=True)
    with open(out_path + ".err", "w") as err:
        for line in child.stderr:
            at = time.time()
            err.write(line)
            if "serve: step" not in line:
                continue
            window = [s for s in list(ring) if at - 0.4 <= s[0] <= at]
            if len(window) < 2:
                continue
            states = collections.Counter(s[1] for s in window)
            first, last = window[0], window[-1]
            gaps = [b[0] - a[0] for a, b in zip(window, window[1:])]
            print(f"probe: {line.split(':', 2)[1].strip()[:60]} | 400 ms before: "
                  f"samples {len(window)} (largest gap between samples "
                  f"{max(gaps) * 1e3:.0f} ms, sampler's worst lateness "
                  f"{max(s[4] for s in window) * 1e3:.0f} ms), main thread states "
                  f"{dict(states)}, thread cpu +{(last[2] - first[2]) * 1e3:.0f} ms, "
                  f"process cpu +{(last[3] - first[3]) * 1e3:.0f} ms, throttled "
                  f"+{last[5][0] - first[5][0]} periods +{(last[5][1] - first[5][1]) / 1e3:.0f} ms, "
                  f"steal +{(last[6] - first[6]) * 1e3:.0f} ms, load {last[7]}, wchan "
                  f"{dict(collections.Counter(s[8] for s in window))}; states in order: "
                  f"{''.join(s[1] for s in window)}", flush=True)
    stop.set()
    code = child.wait()
    print(f"probe: child exit {code}; cpu.stat = {throttled()}", flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
