"""The program's own scopes and host spans in a traced window.

The program names its work: ``jax.named_scope`` scopes in the jitted steps
(``pier.blocks``, ``pier.head``, ``pier.optimizer``, ``pier.outer.delta``,
``pier.outer.exchange``, ``pier.outer.update``), which every device op
carries in its name-stack path (the ``tf_op`` stat of its trace event,
e.g. ``jit(stepfn)/.../transpose(jvp(pier.blocks))/dot_general``), and
``jax.profiler.TraceAnnotation`` host spans (``trainer.*``, ``pipeline.*``,
``python.gc``). ``load(trace_dir)`` reads the newest ``*.xplane.pb`` under
``trace_dir`` (the one ``bench/devtrace.py`` reads) and keeps the device
ops with their paths, the XLA modules, the program's host spans and the
benchmark's ``bench.*`` spans, as JSON-able lists. ``cut`` bounds that to
the window that ``devtrace.reduce`` set, from the ``bench.*`` spans only.

``window(ctx)`` gives a reader the :class:`Window` of its run, read once
and kept in ``ctx``. A program without the scopes or spans gives a window
in which the readers find nothing, and they return ``None``.

``python bench/progtrace.py <trace_dir>`` prints a few ops with their paths
and the window's device time by scope, its spans and its idle split;
``--dump <out.json.gz> [--steps FIRST:COUNT]`` writes the kept form, cut to
COUNT steps from step FIRST (``_trim``).
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

import devtrace

TRACE_DIR = Path(__file__).resolve().parent.parent / ".bench_trace"
SCOPE_STAT = "tf_op"
SCOPE = re.compile(r"pier\.[a-z_.]*[a-z]")
PROGRAM_SPANS = ("trainer.", "pipeline.", "python.gc")
GC_SPAN = "python.gc"
OUTSIDE = "outside any program span"


def scope_of(path: str) -> tuple[str | None, bool]:
    """(the innermost ``pier.*`` scope of an op's name-stack path, whether
    the op is in a backward pass: under a ``transpose(...)``)."""
    scope, backward = None, False
    for part in path.split("/"):
        backward = backward or part.startswith("transpose(")
        found = SCOPE.findall(part)
        if found:
            scope = found[-1]
    return scope, backward


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of each field of the protobuf message in
    ``buf[start:end]``; a length-delimited value is its (start, end)."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def scope_paths(xplane: bytes) -> dict:
    """``{device plane name: {op event name: name-stack path}}``.

    The path is the ``tf_op`` stat of the op's event metadata, which
    ``ProfileData`` does not show, read from the ``XSpace`` protobuf
    (``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1; XPlane.name
    2, event_metadata 4, stat_metadata 5; map entries key 1, value 2;
    XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7)."""
    buf = memoryview(xplane)
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pn, value in _fields(buf, *plane):
            if pn == 2:
                name = _text(buf, value)
            elif pn == 4:
                metas.append(value)
            elif pn == 5:
                entry = dict(_fields(buf, *value))
                sm = dict(_fields(buf, *entry[2]))
                stat_names[sm.get(1, 0)] = _text(buf, sm[2]) if 2 in sm \
                    else ""
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        paths = out.setdefault(name, {})
        for entry in metas:
            meta = dict(_fields(buf, *entry)).get(2)
            if meta is None:
                continue
            ev_name = path = ""
            for mn, value in _fields(buf, *meta):
                if mn == 2:
                    ev_name = _text(buf, value)
                elif mn == 5:
                    st = dict(_fields(buf, *value))
                    if stat_names.get(st.get(1)) == SCOPE_STAT:
                        path = (_text(buf, st[5]) if 5 in st
                                else stat_names.get(st.get(7), ""))
            if path and not paths.get(ev_name):
                paths[ev_name] = path
    return out


def load(trace_dir) -> dict:
    """The kept form: per TPU device its ops (name, start, duration, path)
    and modules; the host spans, program and ``bench.*``, each with the
    index of its thread line."""
    from jax.profiler import ProfileData

    xplane = devtrace._newest_xplane(Path(trace_dir)).read_bytes()
    paths = scope_paths(xplane)
    pd = ProfileData.from_serialized_xspace(xplane)
    devices, host, lines = [], [], 0
    for plane in pd.planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "modules": []}
            known = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    dev["ops"] = [[devtrace._short(e.name), e.start_ns,
                                   e.duration_ns, known.get(e.name, "")]
                                  for e in line.events]
                elif line.name == devtrace.MODULES_LINE:
                    dev["modules"] = [[devtrace._short(e.name), e.start_ns,
                                       e.duration_ns] for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [[e.name, e.start_ns, e.duration_ns, lines]
                         for e in line.events
                         if e.name.startswith(PROGRAM_SPANS
                                              + (devtrace.HOST_SPAN,))]
                host += spans
                lines += bool(spans)
    devices.sort(key=lambda d: d["id"])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _intersect(xs, ys) -> list:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _overlap(intervals, a, b) -> float:
    """Length of [a, b) covered by sorted, disjoint ``intervals``."""
    i = max(bisect.bisect_right(intervals, [a]) - 1, 0)
    tot = 0
    while i < len(intervals) and intervals[i][0] < b:
        tot += max(0, min(b, intervals[i][1]) - max(a, intervals[i][0]))
        i += 1
    return tot


@dataclass
class Window:
    """The traced window's ops with their scopes, and the program's spans.

    ``devices``: per device ``{"ops": [[name, start, end, scope,
    backward]], "modules": [[name, start, end]]}``; ``spans``: program host
    spans ``[name, start, end, line]``; ``line`` is the span's thread line,
    and ``main`` the line of the benchmark's own spans ``bench`` (``[name,
    start, end]``: the training loop).
    """

    start_ns: float
    end_ns: float
    devices: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    bench: list = field(default_factory=list)
    main: int | None = None

    def module_runs(self, prefix: str) -> float:
        """Runs of XLA modules named ``prefix``..., averaged over devices."""
        n = len(self.devices) or 1
        return sum(name.startswith(prefix) for d in self.devices
                   for name, _, _ in d["modules"]) / n

    def scoped_s(self, module: str, pick) -> float:
        """Device seconds of the ops inside runs of module ``module``... for
        which ``pick(scope, backward)`` holds, averaged over devices."""
        n = len(self.devices) or 1
        tot = 0
        for dev in self.devices:
            runs = _union((s, e) for name, s, e in dev["modules"]
                          if name.startswith(module))
            starts = [a for a, _ in runs]
            for _, s, e, scope, bwd in dev["ops"]:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][1] and pick(scope, bwd):
                    tot += e - s
        return tot * 1e-9 / n

    def per_run_ms(self, module: str, pick) -> float | None:
        """``scoped_s`` per run of the module (ms); None where no op of the
        module matched (no run, or a program without the scope)."""
        runs = self.module_runs(module)
        secs = self.scoped_s(module, pick)
        return 1e3 * secs / runs if runs and secs > 0 else None

    def idle(self) -> list:
        """The first device's idle intervals inside the window."""
        if not self.devices:
            return []
        busy = _union((s, e) for _, s, e, _, _ in self.devices[0]["ops"])
        edges = ([self.start_ns] + [x for ab in busy for x in ab]
                 + [self.end_ns])
        return [[a, b] for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def named(self, name: str) -> list:
        return [sp for sp in self.spans if sp[0] == name]

    def idle_in(self, name: str) -> tuple[float, int]:
        """(seconds the first device idled inside spans ``name``, the number
        of those spans)."""
        if not self.devices:
            return 0.0, 0
        idle = self.idle()
        spans = self.named(name)
        return sum(_overlap(idle, s, e) for _, s, e, _ in spans) * 1e-9, \
            len(spans)

    def idle_by_span(self, within: str | None = None) -> dict:
        """The first device's idle seconds (inside the benchmark's spans
        ``within`` where given), by the innermost program span the host was
        in: ``python.gc`` on any thread (a collection holds every thread),
        else the latest-started span open on the training loop's thread,
        else ``OUTSIDE``."""
        main = [sp for sp in self.spans
                if sp[3] == self.main and sp[0] != GC_SPAN]
        gc = _union((s, e) for n, s, e, _ in self.spans if n == GC_SPAN)
        cuts = sorted({self.start_ns, self.end_ns}
                      | {x for _, s, e, _ in main for x in (s, e)}
                      | {x for ab in gc for x in ab})
        cuts = [c for c in cuts if self.start_ns <= c <= self.end_ns]
        out = {}
        idle = self.idle()
        if within is not None:
            idle = _intersect(idle, _union(
                (s, e) for n, s, e in self.bench if n == within))
        for a, b in zip(cuts, cuts[1:]):
            secs = _overlap(idle, a, b)
            if secs <= 0:
                continue
            if _overlap(gc, a, b) > 0:
                label = GC_SPAN
            else:
                open_ = [sp for sp in main if sp[1] <= a and b <= sp[2]]
                label = max(open_, key=lambda sp: sp[1])[0] if open_ \
                    else OUTSIDE
            out[label] = out.get(label, 0) + secs * 1e-9
        return out


def cut(kept: dict, start_ns: float, end_ns: float) -> Window:
    """The kept form inside [start_ns, end_ns): ops and modules that start
    there, spans that overlap it (clipped)."""
    devices = []
    for dev in kept["devices"]:
        ops = []
        for name, s, d, path in dev["ops"]:
            if start_ns <= s < end_ns:
                scope, bwd = scope_of(path)
                ops.append([name, s, s + d, scope, bwd])
        devices.append({"ops": ops, "modules": [
            [n, s, s + d] for n, s, d in dev["modules"]
            if start_ns <= s < end_ns]})
    spans, bench, main = [], [], None
    for name, s, d, line in kept["host"]:
        if not (s < end_ns and s + d > start_ns):
            continue
        span = [name, max(s, start_ns), min(s + d, end_ns)]
        if name.startswith(devtrace.HOST_SPAN):
            main = line
            bench.append(span)
        else:
            spans.append(span + [line])
    return Window(start_ns=start_ns, end_ns=end_ns, devices=devices,
                  spans=spans, bench=bench, main=main)


def _log_window(win: Window, log) -> None:
    secs = win.scoped_s("jit_stepfn", lambda scope, _b: True)
    none = win.scoped_s("jit_stepfn", lambda scope, _b: scope is None)
    if secs > 0:
        log(f"progtrace: {100 * none / secs:.2f} % of jit_stepfn op time "
            f"({none:.6f} of {secs:.6f} s) is in no pier.* scope")
    for within in (None, "bench.train_step"):
        split = win.idle_by_span(within)
        tot = sum(split.values())
        named = tot - split.get(OUTSIDE, 0)
        log(f"progtrace: device idle{' in ' + within if within else ''} "
            f"{tot:.6f} s, {100 * named / tot if tot else 0:.1f} % in "
            f"program spans; by innermost span: " + ", ".join(
                f"{name} {secs:.6f} s" for name, secs in
                sorted(split.items(), key=lambda kv: -kv[1])))


def window(ctx) -> Window:
    """The run's window, read from the trace once and kept in ``ctx``.

    The trace under ``TRACE_DIR`` is the run's own only if its ``bench.*``
    spans bound the window of ``ctx["trace"]``; any other (none there, or
    one left by an earlier run) gives an empty window, in which the readers
    find nothing."""
    if "program_trace" not in ctx:
        red = ctx["trace"]
        kept = {"devices": [], "host": []}
        try:
            found = load(TRACE_DIR)
            if _bench_window(found) == (red.start_ns, red.end_ns):
                kept = found
            else:
                ctx["log"](f"progtrace: the trace under {TRACE_DIR} is not "
                           f"this run's")
        except (FileNotFoundError, ValueError) as e:
            ctx["log"](f"progtrace: {e}")
        ctx["program_trace"] = cut(kept, red.start_ns, red.end_ns)
        _log_window(ctx["program_trace"], ctx["log"])
    return ctx["program_trace"]


def _trim(kept: dict, first: int, steps: int) -> dict:
    """The kept form cut to ``steps`` steps of the window from step
    ``first`` (``devtrace.trim``'s cut), and to the window's end where they
    are its last steps: the last step's outer sync runs on the device
    while the host is in ``bench.block_until_ready``."""
    nb = [h for h in kept["host"] if h[0] == "bench.next_batch"]
    ts = [h for h in kept["host"] if h[0] == "bench.train_step"]
    t0 = nb[first][1]
    t1 = ts[first + steps - 1][1] + ts[first + steps - 1][2]
    if first + steps == len(ts):
        t1 = max(s + d for n, s, d, _ in kept["host"]
                 if n.startswith(devtrace.HOST_SPAN))
    inside = lambda e: t0 <= e[1] and e[1] + e[2] <= t1  # noqa: E731
    return {"devices": [{"id": d["id"],
                         "ops": [e for e in d["ops"] if inside(e)],
                         "modules": [e for e in d["modules"] if inside(e)]}
                        for d in kept["devices"]],
            "host": [h for h in kept["host"] if inside(h)]}


def _bench_window(kept: dict) -> tuple[float, float]:
    """``devtrace.reduce``'s window: the first ``bench.next_batch`` to the
    end of the last ``bench.*`` span."""
    bench = [h for h in kept["host"] if h[0].startswith(devtrace.HOST_SPAN)]
    starts = [s for n, s, _, _ in bench if n == "bench.next_batch"]
    if not starts:
        raise ValueError("the trace holds no bench.next_batch span")
    return min(starts), max(s + d for _, s, d, _ in bench)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    kept = load(args[0])
    if "--steps" in args:  # --steps FIRST:COUNT
        first, count = map(int, args[args.index("--steps") + 1].split(":"))
        kept = _trim(kept, first, count)
    if "--dump" in args:
        with gzip.open(args[args.index("--dump") + 1], "wt") as f:
            json.dump(kept, f)
    for name, _, _, path in kept["devices"][0]["ops"][:8] if kept["devices"] \
            else ():
        print(f"{name}: {path!r}")
    win = cut(kept, *_bench_window(kept))
    _log_window(win, print)
    scopes = {}
    for _, s, e, scope, bwd in (win.devices[0]["ops"] if win.devices
                                else []):
        key = f"{scope}{' (backward)' if bwd else ''}"
        scopes[key] = scopes.get(key, 0) + (e - s) * 1e-9
    for key, secs in sorted(scopes.items(), key=lambda kv: -kv[1]):
        print(f"  {secs:10.6f} s  {key}")
    names = {}
    for name, _, _, _ in win.spans:
        names[name] = names.get(name, 0) + 1
    print(f"program spans: {names}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
