"""From a `jax.profiler` trace of part of the window to device numbers.

The trace is read with `jax.profiler.ProfileData` (XPlane). On each device
plane, busy time is the union of the intervals in which an operation ran
(the "XLA Ops" line); a program's device time is the sum of its events on
the "XLA Modules" line; a kernel's time is the sum of the durations of its
operations. The harness wraps each call into the cluster in a
`TraceAnnotation` named `bench.step`, so the device events of one cluster
step are those that start within that annotation, and each idle gap on the
device is named by what the host was doing over it.

Names found in traces of this program on a TPU v5e: the programs are
`jit__pf(<hash>)` (whole-prompt prefill), `jit__decode(<hash>)` (one decode
step) and `jit__ins(<hash>)` (the KV splice into the decode pool); an
operation is named by its HLO text, `%<name>.<n> = <shape> <op>(...)`, and
the decode attention kernel's is `%paged_decode_op.<n> = ...
custom_call_target="tpu_custom_call"`.
"""
from __future__ import annotations

import dataclasses
import re
import shutil
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

STEP = "bench.step"
PROGRAMS = {                # program kind -> module-name pattern
    "prefill": re.compile(r"jit__pf\b|jit__pf\("),
    "decode": re.compile(r"jit__decode\b|jit__decode\("),
    "insert": re.compile(r"jit__ins\b|jit__ins\("),
}
KERNELS = {"paged_decode": re.compile(r"paged_decode")}


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds, trace clock
    end: float
    meta: str = ""          # program name and source path, where given


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    modules: List[Event]
    ops: List[Event]
    steps: List[Event]      # host `bench.step` annotations
    host: List[Event]       # every host annotation and runtime event
    breakdown: dict
    host_span: Tuple[float, float] = (float("-inf"), float("inf"))

    def in_step(self, evs: List[Event], step: Event) -> List[Event]:
        return [e for e in evs if step.start <= e.start < step.end]

    def program_time(self, kind: str, step: Event) -> float:
        """Device time of one program kind within a host step: its module
        events, or, where the trace has no module line (the CPU), the
        union of its operations."""
        pat = PROGRAMS[kind]
        if self.modules:
            return sum(e.end - e.start
                       for e in self.in_step(self.modules, step)
                       if pat.search(e.name))
        return _union([(e.start, e.end) for e in self.in_step(self.ops, step)
                       if pat.search(e.meta)])

    def pairs(self, records) -> list:
        """(record, annotation) for the harness's step records that the
        trace holds. Records are on the host's `perf_counter`, annotations
        on the trace clock: the records made while the profiler ran and
        the annotations are matched in order, at the
        shift whose start times line up best at one constant offset, each
        record left unmatched costing a millisecond."""
        lo, hi = self.host_span
        records = [r for r in records if lo <= r.t0 and r.t1 <= hi]
        best = None
        for shift in range(-3, 4):
            pr = [(r, self.steps[i + shift]) for i, r in enumerate(records)
                  if 0 <= i + shift < len(self.steps)]
            if not pr:
                continue
            offs = sorted(a.start - r.t0 for r, a in pr)
            off = offs[len(offs) // 2]
            err = sum(abs(a.start - r.t0 - off) for r, a in pr) / len(pr)
            score = err + 1e-3 * (len(records) - len(pr))
            if best is None or score < best[0]:
                best = (score, pr)
        return best[1] if best else []

    def kernel_time(self, kernel: str, step: Event) -> float:
        pat = KERNELS[kernel]
        return sum(e.end - e.start for e in self.in_step(self.ops, step)
                   if pat.search(_short(e.name)) or pat.search(e.meta))


def _stats(ev) -> Dict[str, str]:
    try:
        return {k: str(v) for k, v in ev.stats}
    except Exception:       # a stat the binding cannot convert
        return {}


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def read(path: Path) -> Summary:
    """Reduce one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    modules, ops, host, cpu_ops = [], [], [], []
    devices = 0
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in \
            plane.name
        for line in plane.lines:
            for ev in line.events:
                # device events are named by their HLO text; host events
                # of XLA:CPU carry the program in their stats
                st = {} if is_dev else _stats(ev)
                e = Event(ev.name, ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9,
                          st.get("hlo_module", ""))
                if is_dev and line.name == "XLA Modules":
                    modules.append(e)
                elif is_dev and line.name == "XLA Ops":
                    ops.append(e)
                elif not is_dev and plane.name.startswith("/host"):
                    # XLA:CPU runs operations on host threads
                    (cpu_ops if "hlo_op" in st else host).append(e)
        devices += is_dev
    if not devices:
        ops = [e for e in cpu_ops if not e.name.startswith("end: ")]
    steps = sorted((e for e in host if e.name == STEP),
                   key=lambda e: e.start)
    # the traced window runs from the first event recorded to the last
    every = ops + host + modules
    lo = min((e.start for e in every), default=0.0)
    hi = max((e.end for e in every), default=0.0)
    busy = _union([(max(e.start, lo), min(e.end, hi)) for e in ops])
    return Summary(hi - lo, busy, modules, ops, steps, host,
                   _breakdown(ops, host, lo, hi))


_CONTAINERS = {"while", "conditional", "call"}


def _short(name: str) -> str:
    """`%paged_decode_op.5 = bf16[...] custom-call(...)` -> `paged_decode_op`."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _breakdown(ops: List[Event], host: List[Event], lo, hi) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by the innermost host event over each."""
    per_op: Dict[str, float] = {}
    for e in ops:
        key = _short(e.name)
        if key in _CONTAINERS:
            continue                    # their bodies' operations count
        per_op[key] = per_op.get(key, 0.0) + (e.end - e.start)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(_gaps([(e.start, e.end) for e in ops], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        over = [e for e in host if e.start <= mid < e.end]
        label = min(over, key=lambda e: e.end - e.start).name if over \
            else "host: nothing traced"
        named.append([label, b - a])
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": named}


class Tracer:
    """Traces part of the window: from `at_s` into it until it has seen
    `min_prefills` prefill steps and `min_decode_steps` decode steps
    complete, or for `max_span_s`. A trace of this program holds hundreds
    of thousands of device operations per long prefill, and stopping the
    profiler writes them all out, so the traced span is kept to what the
    readers need."""

    def __init__(self, mix: dict):
        t = mix["trace"]
        self.at = float(t["at_s"])
        self.min_prefills = int(t["min_prefills"])
        self.min_decode = int(t["min_decode_steps"])
        self.max_span = float(t["max_span_s"])
        self.dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-"))
        self.running = False
        self.t0 = self.t1 = 0.0
        self.prefills = self.decodes = 0

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.perf_counter()
        self.running = True

    def observe(self, rec):
        """Count a finished step; stop once the span holds enough."""
        if not self.running or rec.t0 < self.t0:
            return
        self.prefills += rec.prefill_tokens > 0
        self.decodes += bool(rec.decode_ctx)
        if (self.prefills >= self.min_prefills
                and self.decodes >= self.min_decode) \
                or rec.t1 - self.t0 >= self.max_span:
            self.stop()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False

    def summary(self) -> Optional[Summary]:
        try:
            files = sorted(self.dir.rglob("*.xplane.pb"))
            if not files:
                return None
            s = read(files[-1])
            s.host_span = (self.t0, self.t1)
            return s
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
