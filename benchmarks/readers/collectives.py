# Reader of the parallel layer's metric: the collective time a sharded
# train step does NOT hide. On a device's 'XLA Ops' line a collective
# is exposed for as long as no other op runs beside it: a synchronous
# all-gather for its whole length, an asynchronous one for the wait in
# its `-done` (the `-start` returns at once and compute overlaps the
# transfer), a fused one as its fusion. Collectives are found by the
# HLO opcode in the instruction's name or, for a fusion, in what it
# calls — not by shapes.
"""exposed_collective_ms: collective time not hidden behind compute."""
import bisect
import re

from ..harness.trace import _union
from . import program_spans

COLLECTIVE = re.compile(
    r"\b(all-gather|reduce-scatter|all-reduce|all-to-all|"
    r"collective-permute|collective-broadcast)")


def is_collective(hlo: str) -> bool:
    """By the instruction's own name ('%all-gather.3 = ...', the
    `-start` and `-done` halves of an asynchronous one, '%all-reduce-
    scatter-fusion.2 = ...') or by the computation a fusion calls
    ('calls=%all-gather...'). Time is read here, not bytes: the
    double counting FT005 warns of does not arise."""
    head, _, rest = hlo.partition(" = ")
    if COLLECTIVE.search(head):
        return True
    called = re.search(r"calls=%?([\w.\-]+)", rest)
    return bool(called and COLLECTIVE.search(called.group(1)))


def _minus(intervals, cover) -> float:
    """Total length of merged `intervals` outside merged `cover`."""
    starts = [c[0] for c in cover]
    total = 0.0
    for start, end in intervals:
        i = max(bisect.bisect_right(starts, start) - 1, 0)
        cursor = start
        while i < len(cover) and cover[i][0] < end:
            if cover[i][1] > cursor:
                total += max(cover[i][0] - cursor, 0.0)
                cursor = max(cursor, cover[i][1])
            i += 1
        total += max(end - cursor, 0.0)
    return total


def exposed_ms_per_run(trace: dict, module: str):
    """(exposed ms, all collective ms) per whole run of `module` in the
    window, mean over devices; None when it ran on no device or the
    program has no collective."""
    exposed, total, runs = 0.0, 0.0, 0
    for count, ops in program_spans.ops_of_runs(trace, module):
        runs += count
        collective, other = [], []
        for start, end, event in ops:
            (collective if is_collective(event.name) else other).append(
                (start, end))
        collective = _union(collective)
        total += sum(end - start for start, end in collective)
        exposed += _minus(collective, _union(other))
    if not runs or not total:
        return None
    return exposed * 1e-6 / runs, total * 1e-6 / runs


def exposed_collective_ms(run: dict, module: str = "train_step"):
    trace = program_spans.program_trace(run)
    if not trace:
        return None
    found = exposed_ms_per_run(trace, module)
    if not found:
        return None
    print(f"[bench] collectives per {module} run, mean over "
          f"{len(trace['devices'])} device(s): {found[1]:.3f} ms of "
          f"collective ops on the 'XLA Ops' line, {found[0]:.3f} ms of it "
          f"with no other op running beside it", flush=True)
    return found[0]
