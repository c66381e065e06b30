"""The device's record and the program's spans on one clock.

`trace_reduce.py` reads a profile through `jax.profiler.ProfileData`, which
shows an event's name and time and nothing of its metadata. The phase of the
step an operation belongs to is in its event metadata: the stat `tf_op` holds
the `jax.named_scope` path (`jit(step)/transpose(jvp(bigdl_loss))/...`),
`hlo_category` what kind of operation it is. This module reads the XSpace
protobuf itself (the few fields it needs, by their wire format: the chip
machine has no light `xplane_pb2`), and joins the program's own spans to it:
the plane `Task Environment` states `profile_start_time` in Unix nanoseconds,
every event's offset is relative to it, and `bigdl_tpu.obs.trace` records a
span's Unix start. No host tracer runs.

One `Scoped` a profile, memoised by path (`load`), so that the six readers
under `metrics/` parse once. Times are picoseconds from `profile_start_time`,
whole numbers as the profile holds them.
"""

import bisect
import functools
import json
import re
import sys
from collections import namedtuple

import trace_reduce
from trace_reduce import TraceError

# the phases `optim/optimizer.py` names (bigdl_tpu.obs.trace.SCOPE_*), and
# the rest: every device operation is billed to exactly one of these seven
SCOPES = ("bigdl_update", "bigdl_loss", "bigdl_cast", "bigdl_grad_scale")
FORWARD, BACKWARD, NO_SCOPE = "model forward", "model backward", "no scope"
BILLS = SCOPES + (FORWARD, BACKWARD, NO_SCOPE)
_SCOPE = re.compile(r"(?:^|[/(;])(%s)(?=[/):;]|$)" % "|".join(SCOPES))

DISPATCH = ("train/step", "train/window")
FEED_WAIT, EPOCH = "train/feed_wait", "train/epoch"

Op = namedtuple("Op", "start end name tf_op category program")
Span = namedtuple("Span", "start end name tid args")


@functools.lru_cache(maxsize=None)
def bill(tf_op):
    """The one of `BILLS` that an operation with this `tf_op` belongs to. A
    phase's scope counts wherever it stands in the path, so the wrappers that
    differentiation puts around it (`jvp(..)`, `transpose(jvp(..))`) are
    looked through; of the model's own operations those under `transpose(`
    are the backward pass. A fusion carries its root's `tf_op`."""
    if not tf_op:
        return NO_SCOPE
    m = _SCOPE.search(tf_op)
    if m:
        return m.group(1)
    return BACKWARD if "transpose(" in tf_op else FORWARD


_WRAPPED = re.compile(r"^\w+\((.*)\)$")
_STRUCTURAL = re.compile(r"^(jit\(.*\)|pjit|while|body|cond|closed_call|checkpoint|"
                         r"remat|rematted_computation|branch_\d+_fun|core_call|"
                         r"custom_jvp_call|custom_vjp_call\w*|bigdl_\w+)$")


@functools.lru_cache(maxsize=None)
def module_of(tf_op):
    """Which of the model's modules an operation belongs to, for a breakdown
    a reader can act on: the outermost and the innermost module of the path,
    numbers dropped (`block>MultiHeadAttention`, `TimeDistributed`,
    `decoder`). Every component of a path but its last (the primitive) is a
    scope; JAX's own (`jit(..)`, `while/body`, ...) and the phases are not
    modules."""
    parts = []
    for part in tf_op.split(";")[-1].rstrip(":").split("/")[:-1]:
        while _WRAPPED.match(part) and not part.startswith("jit("):
            part = _WRAPPED.match(part).group(1)
        parts.append(part)
    named = [re.sub(r"\d+$", "", p) for p in parts
             if p and not _STRUCTURAL.match(p)]
    if not named:
        return ""
    return named[0] if named[0] == named[-1] else f"{named[0]}>{named[-1]}"


# ------------------------------------------------- the protobuf, by the wire
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes
    (a view) for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise TraceError(f"wire type {wire} in the profile")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stats(buf_list, stat_names):
    """XStat messages -> {stat name: value}; a `ref_value` names its string
    in the plane's stat metadata."""
    out = {}
    for buf in buf_list:
        name = value = None
        for no, v in _fields(buf):
            if no == 1:
                name = stat_names.get(v)
            elif no in (3, 4):              # uint64, int64
                value = v
            elif no == 5:
                value = _text(v)
            elif no == 7:
                value = stat_names.get(v, "")
        if name is not None:
            out[name] = value
    return out


def _map_entry(buf):
    key = value = None
    for no, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def _plane(buf):
    """One XPlane, one level deep: name, the raw lines, metadata and stats."""
    out = {"name": "", "lines": [], "event_metadata": [], "stat_metadata": [],
           "stats": []}
    keys = {3: "lines", 4: "event_metadata", 5: "stat_metadata", 6: "stats"}
    for no, v in _fields(buf):
        if no == 2:
            out["name"] = _text(v)
        elif no in keys:
            out[keys[no]].append(v)
    return out


def _stat_names(plane):
    names = {}
    for entry in plane["stat_metadata"]:
        key, value = _map_entry(entry)
        for no, v in _fields(value):
            if no == 2:
                names[key] = _text(v)
    return names


def _event_metadata(plane, stat_names):
    """metadata id -> (short name, tf_op, hlo_category, program id)."""
    out = {}
    for entry in plane["event_metadata"]:
        key, value = _map_entry(entry)
        name, display, stats = "", "", []
        for no, v in _fields(value):
            if no == 2:
                name = _text(v)
            elif no == 4:
                display = _text(v)
            elif no == 5:
                stats.append(v)
        got = _stats(stats, stat_names)
        out[key] = (display or trace_reduce.short_name(name),
                    got.get("tf_op") or "", got.get("hlo_category") or "",
                    str(got.get("program_id") or ""))
    return out


def _fusion_bills(plane):
    """(program id, fusion's name) -> the bills of the operations fused into
    it, from the HLO modules the profile carries (plane `/host:metadata`, one
    `Hlo Proto` a program). A fusion is one device operation with one
    `tf_op`, its root's; XLA fuses across the step's phases (the optimizer's
    update of a weight rides in the epilogue of the matrix product that makes
    its gradient), and only the module says what a fusion holds."""
    stat_names = _stat_names(plane)
    out = {}
    for entry in plane["event_metadata"]:
        program, value = _map_entry(entry)
        stats = [v for no, v in _fields(value) if no == 5]
        for buf in stats:
            proto = None
            for no, v in _fields(buf):
                if no == 1 and stat_names.get(v) != "Hlo Proto":
                    break
                if no == 6:
                    proto = v
            if proto is not None:
                _module_fusions(proto, str(program & (2 ** 64 - 1)), out)
    return out


def _module_fusions(hlo_proto, program, out):
    computations, fusions = {}, []     # id -> bills held; (name, called ids)
    for no, module in _fields(hlo_proto):
        if no != 1:                                     # HloProto.hlo_module
            continue
        for no, comp in _fields(module):
            if no != 3:                                 # .computations
                continue
            comp_id, held = None, set()
            for no, v in _fields(comp):
                if no == 5:
                    comp_id = v
                elif no == 2:                           # .instructions
                    name = opcode = op_name = ""
                    called = []
                    for no, w in _fields(v):
                        if no == 1:
                            name = _text(w)
                        elif no == 2:
                            opcode = _text(w)
                        elif no == 7:                   # OpMetadata.op_name
                            op_name = next((_text(x) for n, x in _fields(w)
                                            if n == 2), "")
                        elif no == 38:                  # called computations
                            if isinstance(w, int):
                                called.append(w)
                            else:
                                i = 0
                                while i < len(w):
                                    c, i = _varint(w, i)
                                    called.append(c)
                    if opcode == "fusion":
                        fusions.append((name, called))
                    elif opcode not in ("parameter", "constant", "tuple",
                                        "get-tuple-element", "bitcast"):
                        held.add(bill(op_name))
            computations[comp_id] = held
    for name, called in fusions:
        out[(program, name)] = set().union(
            *(computations.get(c, set()) for c in called))


def _line(buf, wanted):
    """(name, [(start_ps, end_ps, metadata id)]) of an XLine whose name is in
    `wanted`, else (name, None): the events of other lines are not read."""
    name, t0_ns, events = "", 0, []
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            t0_ns = v
        elif no == 4:
            events.append(v)
    if name not in wanted:
        return name, None
    out = []
    for ev in events:
        meta = offset_ps = duration_ps = 0
        for no, v in _fields(ev):
            if no == 1:
                meta = v
            elif no == 2:
                offset_ps = v
            elif no == 3:
                duration_ps = v
        start = t0_ns * 1000 + offset_ps
        out.append((start, start + duration_ps, meta))
    # a holder (`while`) before what it holds, where they start together
    out.sort(key=lambda e: (e[0], -e[1]))
    return name, out


# ------------------------------------------------------------------ the join
class Scoped:
    """One profile: the first `chips` devices' operations with their scope
    and category, and the program's spans on the profile's clock.

    `spans_between(t0_unix_ns, t1_unix_ns)` gives the program's span records
    (`bigdl_tpu.obs.trace.spans_between`, or a test's list through a
    lambda); it is asked for the profile's extent."""

    def __init__(self, path, spans_between, chips=1):
        with open(path, "rb") as f:
            space = memoryview(f.read())
        devices, self.start_unix_ns, self.fusion_bills = {}, None, {}
        for no, buf in _fields(space):
            if no != 1:
                continue
            plane = _plane(buf)
            m = trace_reduce.DEVICE_PLANE.match(plane["name"])
            if m:
                devices[int(m.group(1))] = plane
            elif plane["name"] == "/host:metadata":
                self.fusion_bills = _fusion_bills(plane)
            elif plane["name"] == "Task Environment":
                got = _stats(plane["stats"], _stat_names(plane))
                self.start_unix_ns = got.get("profile_start_time")
        if not devices:
            raise TraceError("the trace has no /device:TPU:<n> plane")
        if self.start_unix_ns is None:
            raise TraceError("the trace states no profile_start_time")
        self.ops, self.modules = [], []     # a list a chip
        for n in sorted(devices)[:chips]:
            plane = devices[n]
            meta = _event_metadata(plane, _stat_names(plane))
            lines = dict(_line(buf, (trace_reduce.OP_LINE, trace_reduce.MODULE_LINE))
                         for buf in plane["lines"])
            if not lines.get(trace_reduce.OP_LINE):
                raise TraceError(f"a device plane has no {trace_reduce.OP_LINE!r} events")
            self.ops.append([Op(s, e, *meta.get(m, ("?", "", "", "")))
                             for s, e, m in lines[trace_reduce.OP_LINE]])
            self.modules.append([(s, e, meta.get(m, ("?",))[0]) for s, e, m in
                                 lines.get(trace_reduce.MODULE_LINE) or []])
        self.t0 = min(ops[0].start for ops in self.ops)
        self.t1 = max(max(op.end for op in ops) for ops in self.ops)
        self.spans = sorted(
            (Span((r.start_unix_ns - self.start_unix_ns) * 1000,
                  (r.start_unix_ns + r.dur_ns - self.start_unix_ns) * 1000,
                  r.name, r.tid, r.args or {})
             for r in spans_between(self.start_unix_ns + self.t0 // 1000,
                                    self.start_unix_ns + self.t1 // 1000 + 1)),
            key=lambda s: (s.start, -s.end))
        self._billed = [self._sweep(ops) for ops in self.ops]

    def _sweep(self, ops):
        """Every busy picosecond of one chip billed to the operation that
        runs innermost at it (a `while` holds its body's operations, and is
        billed only what they leave): {key: ps} by bill, by category, by
        (bill, short name) and by (bill, module); the time of fusions that hold a
        phase's operations and are billed elsewhere, by phase; and the idle
        gaps [(start, end)] between the first and the last operation. The
        bills sum to the union of the intervals."""
        tables = {}, {}, {}, {}, {}
        gaps, stack, now = [], [], ops[0].start

        def charge(op, ps):
            if ps <= 0:
                return
            billed = bill(op.tf_op)
            held = self.fusion_bills.get((op.program, op.name), ())
            keys = [billed, op.category, (billed, op.name),
                    (billed, module_of(op.tf_op))]
            for table, key in zip(tables, keys):
                table[key] = table.get(key, 0) + ps
            for scope in held:
                if scope in SCOPES and scope != billed:
                    tables[4][scope] = tables[4].get(scope, 0) + ps

        def close_until(t):
            nonlocal now
            while stack and stack[-1].end <= t:
                top = stack.pop()
                charge(top, top.end - now)
                now = max(now, top.end)

        for op in ops:
            close_until(op.start)
            if stack:
                charge(stack[-1], op.start - now)
            elif op.start > now:
                gaps.append((now, op.start))
            now = max(now, op.start)
            stack.append(op)
        close_until(float("inf"))
        return (*tables, gaps)

    def _mean(self, table):
        out = {}
        for billed in self._billed:
            for key, ps in billed[table].items():
                out[key] = out.get(key, 0.0) + ps / 1e12 / len(self._billed)
        return out

    @property
    def busy_s(self):
        return sum(self._mean(0).values())

    def by_scope(self):
        """Device seconds by bill, all seven; they sum to the busy time. A
        profile in which nothing carries a phase's scope is an error."""
        got = self._mean(0)
        if not any(got.get(s) for s in SCOPES):
            raise TraceError(
                "no device operation carries a bigdl_* scope: the executable "
                "was served by a compile cache filled before the scopes "
                "existed (the cache key has to cover the metadata: "
                "jax_compilation_cache_include_metadata_in_key), or the "
                "program's step opens no scope")
        return {b: got.get(b, 0.0) for b in BILLS}

    def by_category(self):
        return self._mean(1)

    def by_module(self):
        """{(bill, module): seconds}."""
        return self._mean(3)

    def held_elsewhere(self):
        """{phase: seconds} of fusions that hold a phase's operations and are
        billed to another bill, their root's: with the phase's own bill an
        upper bound of its time. Empty where the profile carries no module."""
        return self._mean(4)

    def top_ops(self, which, n=10):
        """The `n` operation families of one bill that took most time, as
        `trace_reduce.Trace.top_ops` groups them."""
        families = {}
        for (billed, name), seconds in self._mean(2).items():
            if billed == which:
                family = re.sub(r"\.\d+$", "", name)
                key = name if family == "fusion" else family
                families[key] = families.get(key, 0.0) + seconds
        return sorted(families.items(), key=lambda kv: -kv[1])[:n]

    # ------------------------------------------------------ the step loop
    def loop_spans(self):
        """The spans of the thread that dispatches the steps."""
        tids = [s.tid for s in self.spans if s.name in DISPATCH]
        if not tids:
            return []
        tid = max(set(tids), key=tids.count)
        return [s for s in self.spans if s.tid == tid]

    def dispatches(self):
        return [s for s in self.loop_spans() if s.name in DISPATCH]

    def gaps(self):
        """Idle gaps of the first chip, [(start, end)]."""
        return self._billed[0][5]

    def name_gap(self, start, end):
        """What the step loop was in at the gap's middle: the innermost span
        over it; where that span holds others (an epoch), or none is over it,
        also the spans before and after."""
        mid = (start + end) // 2
        spans = self.loop_spans()
        over = [s for s in spans if s.start <= mid <= s.end]
        inner = max(over, key=lambda s: s.start) if over else None
        inside = [s for s in spans if inner is None or
                  (s is not inner and inner.start <= s.start and s.end <= inner.end)]
        if inner is not None and not inside:
            return inner.name
        before = max((s for s in inside if s.end < mid), key=lambda s: s.end,
                     default=None)
        after = min((s for s in inside if s.start > mid), key=lambda s: s.start,
                    default=None)
        label = "between %s and %s" % (
            before.name if before else "the start of the trace",
            after.name if after else "the end of the trace")
        return f"{inner.name}: {label}" if inner is not None else label

    def longest_gaps(self, n=10):
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.name_gap(s, e), (e - s) / 1e12] for s, e in gaps]

    def epoch_boundaries(self):
        """[(start, end)]: from the end of an epoch's last dispatch to the end
        of the next epoch's first."""
        spans = self.loop_spans()
        epochs = sorted(s for s in spans if s.name == EPOCH)
        starts = [e.start for e in epochs]
        last, first = {}, {}
        for d in (s for s in spans if s.name in DISPATCH):
            i = bisect.bisect_right(starts, d.start) - 1
            if i >= 0 and d.start <= epochs[i].end:
                first.setdefault(i, d)
                last[i] = d
        return [(last[i].end, first[i + 1].end) for i in sorted(last)
                if i + 1 in first]

    def idle_split(self):
        """Idle seconds of the first chip at the epoch boundaries (whole gaps
        that reach into one), and of the rest the part under a
        `train/feed_wait` span: (epoch_end_s, in_feed_wait_s)."""
        bounds = self.epoch_boundaries()
        waits = [s for s in self.loop_spans() if s.name == FEED_WAIT]
        at_end = in_wait = 0
        for g0, g1 in self.gaps():
            if any(g0 < b1 and g1 > b0 for b0, b1 in bounds):
                at_end += g1 - g0
            else:
                in_wait += sum(max(0, min(g1, w.end) - max(g0, w.start))
                               for w in waits)
        return at_end / 1e12, in_wait / 1e12


    def report(self):
        """What a builder reads after a traced run: device seconds by bill
        with each bill's largest operations, by category, the ten longest
        idle gaps by name, and the idle split."""
        bills = self.by_scope()
        at_end, in_wait = self.idle_split()
        return {"busy_s": self.busy_s, "extent_s": (self.t1 - self.t0) / 1e12,
                "by_scope": bills,
                "held_elsewhere": self.held_elsewhere(),
                "top_ops": {b: self.top_ops(b, 6) for b in BILLS if bills[b]},
                "by_module": [[f"{b}: {m}", t] for (b, m), t in sorted(
                    self.by_module().items(), key=lambda kv: -kv[1])[:16]],
                "by_category": dict(sorted(self.by_category().items(),
                                           key=lambda kv: -kv[1])[:10]),
                "longest_gaps": self.longest_gaps(10),
                "idle_epoch_end_s": at_end, "idle_in_feed_wait_s": in_wait,
                "dispatches": len(self.dispatches()),
                "spans": self.span_totals()}

    def span_totals(self):
        """{name: [count, seconds, longest]} of the program's spans that lie
        in or reach into the profile's extent, every thread's."""
        out = {}
        for s in self.spans:
            got = out.setdefault(s.name, [0, 0.0, 0.0])
            took = (s.end - s.start) / 1e12
            out[s.name] = [got[0] + 1, got[1] + took, max(got[2], took)]
        return out


_LOADED = {}


def load(run):
    """The `Scoped` of the run's profile, or None where the program is from
    before its spans had a clock (a reader then has nothing to read). The
    first reader's call parses, and leaves the report on standard error."""
    from bigdl_tpu.obs import trace as program
    if not hasattr(program, "spans_between"):
        return None
    path = trace_reduce.find_xplane(program.trace_dir())
    if path not in _LOADED:
        _LOADED[path] = Scoped(path, program.spans_between, run.chips)
        print("scoped_trace: " + json.dumps(_LOADED[path].report()),
              file=sys.stderr)
    return _LOADED[path]


def steps(run):
    """Optimizer steps the device ran in the traced window."""
    return trace_reduce.steps_per_second(run) * run.trace.window_s
