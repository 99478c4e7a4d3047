"""From the profiler's ``.xplane.pb`` to numbers.

``load`` reads a trace with nothing but JAX (``jax.profiler.ProfileData``)
into plain lists of events; everything below it is arithmetic on those
lists, so the tests drive it with a small recorded trace (kept as the
profiler's own text form) and with events made by hand.

A device plane is one whose name starts with ``/device:``; its line "XLA
Ops" holds one event per executed HLO operation (fusions, custom calls,
collectives) and "XLA Modules" one per executed program. Host planes hold
the ``TraceAnnotation`` spans that the benchmark's drivers write, named
``bench/<what>``.
"""

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WRAPPERS = ("while", "conditional", "call")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = field(default_factory=dict)

    @property
    def end_ns(self):
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    # device plane name -> line name -> events by start
    devices: dict
    # the benchmark's own host spans, by start
    spans: list


def newest_xplane(trace_dir):
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def from_profile_data(pd):
    devices, spans = {}, []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:")
        if not is_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            keep = []
            for e in line.events:
                if is_device:
                    # an operation's name is its whole HLO text: keep the
                    # instruction's name, and the text beside the stats
                    stats = {k: v for k, v in e.stats}
                    name = e.name
                    if " = " in name:
                        stats["hlo_text"] = name
                        name = name.split(" = ", 1)[0].lstrip("%")
                    keep.append(Event(
                        name, float(e.start_ns), float(e.duration_ns), stats))
                elif e.name.startswith(SPAN_PREFIX):
                    spans.append(Event(
                        e.name[len(SPAN_PREFIX):], float(e.start_ns),
                        float(e.duration_ns)))
            if is_device and keep:
                keep.sort(key=lambda ev: ev.start_ns)
                devices.setdefault(plane.name, {})[line.name] = keep
    spans.sort(key=lambda ev: ev.start_ns)
    return Trace(devices, spans)


def load(path):
    from jax.profiler import ProfileData

    if path.endswith(".textproto"):
        with open(path) as f:
            return from_profile_data(ProfileData.from_text_proto(f.read()))
    return from_profile_data(ProfileData.from_file(path))


# ---------------------------------------------------------------------------
# arithmetic on events
# ---------------------------------------------------------------------------


def clip(events, lo_ns, hi_ns):
    """The parts of ``events`` inside [lo, hi)."""
    out = []
    for e in events:
        s, t = max(e.start_ns, lo_ns), min(e.end_ns, hi_ns)
        if t > s:
            out.append(Event(e.name, s, t - s, e.stats))
    return out


def union_ns(events):
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda ev: ev.start_ns):
        if cur_e is None or e.start_ns > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start_ns, e.end_ns
        else:
            cur_e = max(cur_e, e.end_ns)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events, lo_ns, hi_ns):
    """The idle intervals of [lo, hi): ``[(start, end), ...]``."""
    out, at = [], lo_ns
    for e in sorted(events, key=lambda ev: ev.start_ns):
        if e.start_ns > at:
            out.append((at, min(e.start_ns, hi_ns)))
        at = max(at, e.end_ns)
        if at >= hi_ns:
            break
    if at < hi_ns:
        out.append((at, hi_ns))
    return [(s, t) for s, t in out if t > s]


def device_ops(trace):
    """device plane name -> its "XLA Ops" events."""
    return {
        name: lines[OPS_LINE]
        for name, lines in sorted(trace.devices.items())
        if OPS_LINE in lines
    }


def window_ns(trace):
    """(lo, hi) over every device's operations and the host spans."""
    evs = [e for ops in device_ops(trace).values() for e in ops]
    if not evs:
        return None
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def busy_and_window_s(trace, lo_ns=None, hi_ns=None):
    """Seconds in which an operation ran, averaged over the devices, and
    the length of the window. The window defaults to the stretch from the
    first to the last device operation."""
    ops = device_ops(trace)
    if not ops:
        return 0.0, 0.0
    w = window_ns(trace)
    lo = w[0] if lo_ns is None else lo_ns
    hi = w[1] if hi_ns is None else hi_ns
    busy = [union_ns(clip(evs, lo, hi)) for evs in ops.values()]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def idle_share(trace):
    """1 - busy / window over the traced window, or None without device
    operations."""
    busy, window = busy_and_window_s(trace)
    if window <= 0 or busy <= 0:
        return None
    return 1.0 - busy / window


def module_durations_ns(trace, prefixes):
    """Durations of the executed programs ("XLA Modules") whose name
    starts with one of ``prefixes``, over all devices."""
    return [
        e.dur_ns for lines in trace.devices.values()
        for e in lines.get(MODULES_LINE, []) if e.name.startswith(prefixes)]


def time_by_name(events, key=lambda e: e.name):
    out = {}
    for e in events:
        k = key(e)
        out[k] = out.get(k, 0.0) + e.dur_ns
    return out


def top_device_ops(trace, n=10):
    """[[name, seconds], ...]: the operations that took most device time,
    summed over calls, averaged over devices."""
    ops = device_ops(trace)
    if not ops:
        return []
    total = {}
    for evs in ops.values():
        # a loop's event spans the events of its body, which are listed
        # too: leave the wrapper out
        evs = [e for e in evs if not e.name.startswith(WRAPPERS)]
        for k, v in time_by_name(evs).items():
            total[k] = total.get(k, 0.0) + v / len(ops)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps_by_span(trace, n=10):
    """[[what the host was doing, seconds], ...]: the first device's idle
    time inside the window, attributed to the benchmark's host span that
    covers most of each gap ("(no span)" where none does), largest first.
    """
    ops = device_ops(trace)
    if not ops:
        return []
    evs = next(iter(ops.values()))
    lo, hi = evs[0].start_ns, max(e.end_ns for e in evs)
    by = {}
    for s, t in gaps(evs, lo, hi):
        best, best_cover = "(no span)", 0.0
        for sp in trace.spans:
            if sp.start_ns >= t:
                break
            cover = min(sp.end_ns, t) - max(sp.start_ns, s)
            if cover > best_cover:
                best, best_cover = sp.name, cover
        by[best] = by.get(best, 0.0) + (t - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]
