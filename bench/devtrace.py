"""Reduce a profiler trace of the window to what the per-layer metrics read.

``load(trace_dir)`` reads the newest ``*.xplane.pb`` that
``jax.profiler.stop_trace`` wrote and keeps, as plain lists, what the
reduction needs: the ops and the XLA modules on every TPU device, and the
benchmark's own host spans (``bench.*``, ``jax.profiler.TraceAnnotation``).
``reduce`` turns that into a :class:`Reduced`. The kept form is JSON, so a
trimmed copy of a real chip trace serves as the test fixture
(``bench/fixtures/``), and the reduction the tests check is the one runs use.

``python bench/devtrace.py <trace_dir>`` prints the planes, lines and the
busiest event names of a trace, to look at one by hand;
``--dump <out.json.gz>`` writes the kept form, and ``--steps FIRST:COUNT``
cuts it to COUNT steps of the window first.
"""

from __future__ import annotations

import gzip
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN = "bench."
LONG_GAP_NS = 100_000
COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
               "reduce-scatter", "all-to-all")


def _newest_xplane(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.838 = (...)`` ->
    ``fusion.838``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir) -> dict:
    """The kept form of a trace: device ops and modules, host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(_newest_xplane(Path(trace_dir))))
    devices, host = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"id": int(m.group(1)), "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[_short(e.name), e.start_ns, e.duration_ns]
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events
                         if e.name.startswith(HOST_SPAN)]
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


@dataclass
class Reduced:
    """A traced window: per device, its ops and modules inside the window."""

    start_ns: float
    end_ns: float
    devices: list = field(default_factory=list)   # [{"ops", "modules"}]
    host: list = field(default_factory=list)      # [name, start, end]

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def busy_intervals(self, dev) -> list:
        return _union((s, s + d) for _, s, d in dev["ops"])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(sum(b - a for a, b in self.busy_intervals(d))
                  for d in self.devices)
        return tot * 1e-9 / len(self.devices)

    def modules(self, prefix: str) -> tuple[float, float]:
        """(seconds, count) of XLA module runs named ``prefix``..., averaged
        over the devices."""
        n = len(self.devices) or 1
        evs = [(s, d) for dev in self.devices
               for name, s, d in dev["modules"] if name.startswith(prefix)]
        return sum(d for _, d in evs) * 1e-9 / n, len(evs) / n

    def ops(self, match, module_prefix: str | None = None
            ) -> tuple[float, float]:
        """(seconds, count) of ops for which ``match(name)`` holds,
        inside modules named ``module_prefix``... where given, averaged over
        the devices."""
        n = len(self.devices) or 1
        secs = count = 0
        for dev in self.devices:
            spans = None
            if module_prefix is not None:
                spans = [(s, s + d) for name, s, d in dev["modules"]
                         if name.startswith(module_prefix)]
            for name, s, d in dev["ops"]:
                if not match(name):
                    continue
                if spans is not None and not any(a <= s < b
                                                  for a, b in spans):
                    continue
                secs += d
                count += 1
        return secs * 1e-9 / n, count / n

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (seconds a chip), and the idle
        gaps of the first device grouped by the host span that overlaps
        each most: total seconds, with the count of gaps over 0.1 ms and
        the longest."""
        n = len(self.devices) or 1
        per_op = {}
        for dev in self.devices:
            for name, _, d in dev["ops"]:
                per_op[name] = per_op.get(name, 0) + d
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        by_label = {}
        if self.devices:
            busy = self.busy_intervals(self.devices[0])
            edges = ([self.start_ns] + [x for ab in busy for x in ab]
                     + [self.end_ns])
            host = sorted(self.host, key=lambda h: h[1])
            j = 0
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                while j < len(host) and host[j][2] <= a:
                    j += 1
                best, label = 0, "outside the benchmark's spans"
                k = j
                while k < len(host) and host[k][1] < b:
                    ov = min(b, host[k][2]) - max(a, host[k][1])
                    if ov > best:
                        best, label = ov, host[k][0]
                    k += 1
                tot, cnt, top_gap = by_label.get(label, (0, 0, 0))
                by_label[label] = (tot + b - a, cnt + (b - a >= LONG_GAP_NS),
                                   max(top_gap, b - a))
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1][0])[:top]
        return {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                "idle_gaps": [[f"host in {k}: {c} gaps over 0.1 ms, longest "
                               f"{m * 1e-6:.3f} ms", t * 1e-9]
                              for k, (t, c, m) in gaps]}


def reduce(kept: dict) -> Reduced:
    """The window runs from the start of the first ``bench.next_batch`` span
    to the end of the last ``bench.*`` span (``bench.block_until_ready`` in
    a run); everything outside it is dropped."""
    host = [[n, s, s + d] for n, s, d in kept["host"]]
    starts = [s for n, s, _ in host if n == "bench.next_batch"]
    if not starts:
        raise ValueError("the trace holds no bench.next_batch span")
    t0, t1 = min(starts), max(e for _, _, e in host)
    devices = []
    for dev in kept["devices"]:
        devices.append({
            "ops": [e for e in dev["ops"] if t0 <= e[1] < t1],
            "modules": [e for e in dev["modules"] if t0 <= e[1] < t1]})
    return Reduced(start_ns=t0, end_ns=t1, devices=devices,
                   host=[h for h in host if h[2] > t0 and h[1] < t1])


def trim(kept: dict, first: int, steps: int) -> dict:
    """The kept form cut to ``steps`` steps of the window from step
    ``first``: from that step's ``bench.next_batch`` to the end of the last
    step's ``bench.train_step``."""
    nb = [h for h in kept["host"] if h[0] == "bench.next_batch"]
    ts = [h for h in kept["host"] if h[0] == "bench.train_step"]
    t0 = nb[first][1]
    t1 = ts[first + steps - 1][1] + ts[first + steps - 1][2]
    inside = lambda e: t0 <= e[1] and e[1] + e[2] <= t1  # noqa: E731
    return {"devices": [{"id": d["id"],
                         "ops": [e for e in d["ops"] if inside(e)],
                         "modules": [e for e in d["modules"] if inside(e)]}
                        for d in kept["devices"]],
            "host": [h for h in kept["host"] if inside(h)]}


def is_collective(name: str) -> bool:
    return name.split(".")[0] in COLLECTIVES or any(
        name.startswith(c + "-") for c in COLLECTIVES)


def summary(kept: dict) -> str:
    lines = [f"{len(kept['devices'])} device plane(s), "
             f"{len(kept['host'])} host spans"]
    for dev in kept["devices"]:
        for key in ("modules", "ops"):
            evs = dev[key]
            tot = {}
            for name, _, d in evs:
                tot[name] = tot.get(name, 0) + d
            lines.append(f"TPU:{dev['id']} {key}: {len(evs)} events")
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                lines.append(f"  {d * 1e-6:10.3f} ms  {name}")
    names = sorted({n for n, _, _ in kept["host"]})
    lines.append(f"host spans: {names}")
    return "\n".join(lines)


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    kept = load(args[0])
    if "--steps" in args:  # --steps FIRST:COUNT
        first, count = map(int, args[args.index("--steps") + 1].split(":"))
        kept = trim(kept, first, count)
    if "--dump" in args:
        out = args[args.index("--dump") + 1]
        with gzip.open(out, "wt") as f:
            json.dump(kept, f)
    print(summary(kept))
    return 0


if __name__ == "__main__":
    sys.exit(main())
