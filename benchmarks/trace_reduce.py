"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read: the device's busy time and idle gaps, the time of operations by name,
the executions of the step's program, and what the host was doing in the
longest gaps. Read with `jax.profiler.ProfileData` and nothing else.
"""

import glob
import os
import re
from types import SimpleNamespace

import harness

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# operations that only hold others, which the trace lists as well: a scanned
# window is one `while` around every step's operations
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


class TraceError(Exception):
    """The trace does not hold what a metric needs."""


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name):
    """An operation's own name. The trace names a device operation by its
    whole HLO instruction, `%fusion.5 = f32[...] fusion(... %operand ...)`;
    only what stands before ` = ` is the operation, the rest names its
    operands, and a pattern must not match those."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merged (start, end) pairs of `intervals`, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Trace:
    """One traced window, times in seconds.

    `chips` device planes are read (the lowest-numbered). `window_s` runs from
    the first to the last event of any plane, host threads included, so an
    idle device at either end counts as idle."""

    def __init__(self, path, chips=1):
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        devices, self.host = {}, []
        lo, hi = None, None
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            lines = {}
            for line in plane.lines:
                events = [(e.start_ns, e.duration_ns, short_name(e.name))
                          for e in line.events]
                if not events:
                    continue
                lines[line.name] = events
                lo = min(lo, events[0][0]) if lo is not None else events[0][0]
                end = max(s + d for s, d, _ in events)
                hi = max(hi, end) if hi is not None else end
            if m:
                devices[int(m.group(1))] = lines
            elif plane.name.startswith("/host:"):
                self.host += [ev for evs in lines.values() for ev in evs]
        if not devices:
            raise TraceError("the trace has no /device:TPU:<n> plane")
        self.devices = [devices[i] for i in sorted(devices)[:chips]]
        if any(OP_LINE not in d for d in self.devices):
            raise TraceError(f"a device plane has no {OP_LINE!r} line")
        self.t0, self.window_s = lo, (hi - lo) / 1e9
        self._busy = [_union((s, s + d) for s, d, _ in dev[OP_LINE])
                      for dev in self.devices]
        self.busy_s = sum(sum(e - s for s, e in b) for b in self._busy) \
            / 1e9 / len(self.devices)

    def op_seconds(self):
        """name -> seconds on the device, averaged over the chips; containers
        left out, since their time is their operations'."""
        out = {}
        for dev in self.devices:
            for _, d, name in dev[OP_LINE]:
                if not CONTAINER.match(name):
                    out[name] = out.get(name, 0.0) + d / 1e9 / len(self.devices)
        return out

    def seconds_matching(self, patterns):
        """Device seconds of the operations whose name matches any of the
        regular expressions, and how many events that was. No match is an
        error: a kernel that is not in the trace has no share of anything."""
        rx = [re.compile(p) for p in patterns]
        total, count = 0.0, 0
        for dev in self.devices:
            for _, d, name in dev[OP_LINE]:
                if any(r.search(name) for r in rx):
                    total += d / 1e9 / len(self.devices)
                    count += 1
        if not count:
            raise TraceError(f"no device operation matches {patterns}")
        return total, count

    def program_rate(self, patterns):
        """Executions per second of the program whose module name matches,
        over the whole cycles in the trace: (n - 1) starts apart."""
        rx = [re.compile(p) for p in patterns]
        starts = sorted(s for s, _, name in self.devices[0].get(MODULE_LINE, [])
                        if any(r.search(name) for r in rx))
        if len(starts) < 2:
            raise TraceError(f"fewer than two executions of {patterns} in the "
                             f"trace's {MODULE_LINE!r} line")
        return (len(starts) - 1) / ((starts[-1] - starts[0]) / 1e9)

    def top_ops(self, n=10):
        """The `n` operations that took most time. The instances of one named
        operation (`bigdl_flash_fwd.3`, `.4`, ...: one a layer) count together;
        XLA's own `fusion.N` are different programs and stay apart."""
        families = {}
        for name, s in self.op_seconds().items():
            family = re.sub(r"\.\d+$", "", name)
            key = name if family == "fusion" else family
            families[key] = families.get(key, 0.0) + s
        ops = sorted(families.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ops[:n]]

    def idle_gaps(self, n=10):
        """The longest gaps of the first chip, each named by the host events
        that cover its middle, innermost last."""
        busy = self._busy[0]
        edges = [(self.t0, self.t0)] + [tuple(b) for b in busy] + \
                [(self.t0 + int(self.window_s * 1e9),) * 2]
        gaps = sorted(((b[0] - a[1], a[1]) for a, b in zip(edges, edges[1:])
                       if b[0] > a[1]), reverse=True)[:n]
        out = []
        for dur, start in gaps:
            mid = start + dur // 2
            cover = sorted(((d, name) for s, d, name in self.host
                            if s <= mid <= s + d), reverse=True)
            label = " > ".join(name for _, name in cover[-3:]) or "host: no event"
            out.append([label[:120], dur / 1e9])
        return out


# the train runner's programs: `jit_step` runs one optimizer step, `jit_window`
# as many as the traffic fuses
STEP_PROGRAM = [r"^jit_step"]
WINDOW_PROGRAM = [r"^jit_window"]


def steps_per_second(run):
    """Optimizer steps a second in the traced window, from the device's own
    record of the step programs."""
    fuse = int(run.traffic["fuse_steps"])
    if fuse > 1:
        return run.trace.program_rate(WINDOW_PROGRAM) * fuse
    return run.trace.program_rate(STEP_PROGRAM)


def roofline_share(run, parts, patterns):
    """Least time the chip could take for `parts` [(flops, bytes)] of one
    step, each part at the larger of its two bounds, over the device time of
    the operations matching `patterns`, in percent."""
    least = sum(max(f / run.peak["flops_per_s"], b / run.peak["hbm_bytes_per_s"])
                for f, b in parts)
    seconds, _ = run.trace.seconds_matching(patterns)
    return 100.0 * least * steps_per_second(run) * run.trace.window_s / seconds


def per_layer(cell, win, device, peak):
    """Every per-layer metric of `cell` that finds something to read, with the
    device's busy time and the breakdown for the result line."""
    trace = Trace(find_xplane(win["trace_dir"]), cell.chips)
    noted = win["traced"]
    s0 = noted["spans0"]
    run = SimpleNamespace(
        trace=trace, config=cell.config, traffic=cell.traffic,
        work=cell.work, peak=peak, chips=cell.chips,
        memory_peak_bytes=device["memory_peak_bytes"],
        dispatched_steps=noted["step1"] - noted["step0"],
        # the model's observable state leaves after the window's last step, by path
        state=win.get("state", {}),
        # name -> (count, seconds) of the program's spans in the traced seconds
        spans={k: (v["count"] - s0.get(k, {}).get("count", 0),
                   (v["total_ms"] - s0.get(k, {}).get("total_ms", 0.0)) / 1e3)
               for k, v in noted["spans1"].items()})
    values = {}
    for metric in cell.per_layer():
        reader = harness.load_module(cell.path("metrics", metric["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            values[metric["name"]] = float(value)
    return {"values": values, "busy_s": trace.busy_s, "window_s": trace.window_s,
            "breakdown": {"device_ops": trace.top_ops(), "idle_gaps": trace.idle_gaps()}}
