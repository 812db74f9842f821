"""Share of the traced window in which the device idles while the host
is inside ``StencilProblem.run``, in %, averaged over devices.

Each idle interval of a device counts by its overlap with the program's
host spans ``repro.run``: the part of the device's idle time that the
program's own host path causes, which a host-side change can win back.

The trace's device clock is not the host's: on a TPU v5e it has read
0.2-1.6 ms early, by an amount that changes from run to run and, by
100-300 µs, within a run's first second.  So each call's ops are first
shifted by the causal bound of the calls around it: the least shift that
puts none of their first ops before the host's launch of that call (the
runtime's ``PJRT_LoadedExecutable_Execute`` event on the calling
thread).  The call launched with the least delay then starts at its
launch, so the reading errs low by that least delay.  Calls are paired
with launches by one shift for the whole window: in a loop that awaits
every call, the device idles at each launch.  Where no shift does that,
the reading is ``None``.
"""
import bisect
import math

from bench.trace import Op, Reduced

SPAN = "repro.run"
LAUNCH = "PJRT_LoadedExecutable_Execute"
REACH = 5e-3       # s: the most the two clocks are taken to differ by
NEAR = 20          # calls on either side whose bound a call's shift takes


def read(ctx):
    r = ctx.trace
    runs = _merge((o.start, o.end) for o in r.host if o.name == SPAN)
    launches = sorted(o.start for o in r.host if o.name.startswith(LAUNCH))
    if r.window_s <= 0 or not runs or not launches or not r.devices:
        return None
    idle = 0.0
    for ops in r.devices:
        moved = aligned(r.window, ops, launches)
        if moved is None:
            return None
        gaps = Reduced(window=r.window, devices=[moved], host=[]).gaps()[0]
        idle += _overlap(gaps, runs)
    return 100.0 * idle / len(r.devices) / r.window_s


def aligned(window, ops, launches):
    """``ops`` on the host's clock: each call's ops shifted by the largest
    lead of a launch over its call's first op among the ``NEAR`` calls on
    either side; ``None`` where calls and launches do not pair."""
    shift = causal_shift(window, ops, launches)
    if shift is None:
        return None
    gaps = Reduced(window=window, devices=[ops], host=[]).gaps()[0]
    starts = [a for a, _ in gaps]
    firsts, leads = [], []
    for t in launches:
        i = bisect.bisect_right(starts, t - shift) - 1
        if i >= 0 and t - shift <= gaps[i][1]:
            firsts.append(gaps[i][1])
            leads.append(t - gaps[i][1])
    if not leads:
        return None
    local = [max(leads[max(0, k - NEAR):k + NEAR + 1])
             for k in range(len(leads))]
    out = []
    for o in ops:
        s = local[max(0, bisect.bisect_right(firsts, o.start) - 1)]
        out.append(Op(o.name, o.start + s, o.end + s))
    return out


def causal_shift(window, ops, launches):
    """Seconds to add to the device clock of ``ops``: the least shift,
    within ``REACH`` either way, that puts every launch in an idle gap of
    the device, or ``None``.  Outside the window, where the device's ops
    are cut off, it is taken to idle.  Calls that last longer than twice
    ``REACH`` leave no other such shift."""
    a, b = window
    gaps = Reduced(window=window, devices=[ops], host=[]).gaps()[0]
    gaps = _merge([(-math.inf, a)] + gaps + [(b, math.inf)])
    ends = [g[1] for g in gaps]
    events = []             # (shift, 0) opens a feasible range, (shift, 1)
    for t in launches:      # closes it
        i = bisect.bisect_left(ends, t - REACH)
        while i < len(gaps) and gaps[i][0] <= t + REACH:
            lo, hi = max(t - gaps[i][1], -REACH), min(t - gaps[i][0], REACH)
            if lo <= hi:
                events += [(lo, 0), (hi, 1)]
            i += 1
    covered = 0
    for shift, close in sorted(events):
        covered += -1 if close else 1
        if covered == len(launches):
            return shift
    return None


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(xs, ys):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, j = 0.0, 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            total += min(b, ys[k][1]) - max(a, ys[k][0])
            k += 1
    return total
