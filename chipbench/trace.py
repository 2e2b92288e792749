"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the Chrome-format trace that ``jax.profiler`` writes into
a small neutral form, :class:`Trace`: per device, the ops that ran on it as
(start ns, end ns, op name, scope path); the host's spans as (start ns,
end ns, name); and the window, the host span that brackets the profiled
segment. Everything after that is plain interval arithmetic on the
neutral form, which the tests check on a small trace of known answers.

- busy: the union of a device's op intervals inside the window;
- idle share: 1 - busy / window, mean over the devices;
- scope time: the summed duration of the ops whose scope path (JAX's name
  stack, e.g. ``jit(step)/.../mlp_core/dot_general``) holds a scope;
- collective time: the summed duration of all-gather, all-reduce,
  reduce-scatter, collective-permute and all-to-all ops (their async
  start/done halves included);
- idle gaps: the holes in a device's busy union, each named by the
  innermost host span around its middle.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

KNOWN_SCOPES = ("paged_attn_core_fused", "attn_core", "mlp_core")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"all_gather|all_reduce|reduce_scatter|collective_permute|all_to_all")
_SUFFIX = re.compile(r"[.:_-]?\d+$")

Op = Tuple[int, int, str, str]        # start ns, end ns, name, scope path
Span = Tuple[int, int, str]           # start ns, end ns, name


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Op]]
    spans: List[Span]
    window: Tuple[int, int]

    @property
    def n_devices(self) -> int:
        return max(len(self.ops), 1)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, ops: Sequence[Op]):
        lo, hi = self.window
        for s, e, name, scope in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield s, e, name, scope

    def busy_intervals(self, device: int) -> List[Tuple[int, int]]:
        merged: List[List[int]] = []
        for s, e, _, _ in sorted(self._clipped(self.ops[device])):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self) -> float:
        """Union of op intervals inside the window, mean over devices."""
        tot = sum(e - s for d in self.ops for s, e in self.busy_intervals(d))
        return tot * 1e-9 / self.n_devices

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def _sum(self, pred) -> float:
        return sum(e - s for d in self.ops for s, e, n, sc in
                   self._clipped(self.ops[d]) if pred(n, sc)) * 1e-9

    def scope_s(self, scope: str) -> float:
        """Summed over devices: ops whose scope path holds ``scope`` as a
        whole path element."""
        pat = re.compile(rf"(^|/|\(){re.escape(scope)}($|/|\))")
        return self._sum(lambda n, sc: bool(pat.search(sc)))

    def collective_s(self) -> float:
        """Summed over devices: collective ops."""
        return self._sum(lambda n, sc: bool(COLLECTIVE.search(n)))

    def top_ops(self, n: int) -> List[List]:
        """The n labels that took most device time, seconds per device."""
        tot: Dict[str, int] = {}
        for d in self.ops:
            for s, e, name, scope in self._clipped(self.ops[d]):
                key = label(name, scope)
                tot[key] = tot.get(key, 0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / self.n_devices] for k, v in top]

    def idle_gaps(self, n: int) -> List[List]:
        """The n longest holes in any device's busy union inside the
        window, each named by the innermost host span around its middle."""
        gaps = []
        lo, hi = self.window
        for d in self.ops:
            edges = [lo]
            for s, e in self.busy_intervals(d):
                edges += [s, e]
            edges.append(hi)
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, (a + b) // 2))
        gaps.sort(reverse=True)
        return [[self.host_at(mid), g * 1e-9] for g, mid in gaps[:n]]

    def host_at(self, t: int) -> str:
        best = None
        for s, e, name in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "(no host span)"


def label(name: str, scope: str) -> str:
    """An op's innermost known scope, else its name without the number."""
    parts = re.split(r"[/()]", scope)
    for part in reversed(parts):
        if part in KNOWN_SCOPES:
            return part
    return _SUFFIX.sub("", name) or name


CONTAINERS = ("while", "conditional", "call")


def load(path: str, device_ids: Sequence[int], window_span: str) -> Trace:
    """Read the ``*.trace.json.gz`` that ``jax.profiler`` writes beside its
    ``.xplane.pb`` (Chrome trace format, one clock, microseconds): the ops
    of the ``XLA Ops`` thread of the ``/device:TPU:<id>`` processes of
    ``device_ids``, each with JAX's name stack from its ``tf_op`` argument;
    every host span of ``/host:CPU``; and the window, the host span named
    ``window_span``. A ``while``, ``conditional`` or ``call`` op is left
    out: it holds the ops of its body, which are read one by one."""
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    device = {}
    for pid, name in procs.items():
        m = re.match(r"/device:TPU:(\d+)$", name)
        if m and int(m.group(1)) in device_ids:
            device[pid] = int(m.group(1))
    ops: Dict[int, List[Op]] = {d: [] for d in device.values()}
    spans: List[Span] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        s = int(round(e["ts"] * 1e3))
        end = s + int(round(e.get("dur", 0) * 1e3))
        pid = e["pid"]
        if pid in device:
            if threads.get((pid, e["tid"])) != "XLA Ops":
                continue
            args = e.get("args", {})
            if args.get("hlo_category") not in CONTAINERS:
                ops[device[pid]].append((s, end, e["name"],
                                         args.get("tf_op", "")))
        elif procs.get(pid, "").startswith("/host:CPU"):
            spans.append((s, end, e["name"]))
    wins = [(s, e) for s, e, name in spans if name == window_span]
    if not wins:
        raise RuntimeError(f"no host span {window_span!r} in the trace")
    if not any(ops.values()):
        raise RuntimeError("the trace has no XLA Ops for the devices "
                           f"{list(device_ids)}")
    window = max(wins, key=lambda w: w[1] - w[0])
    if not any(s < window[1] and e > window[0]
               for d in ops.values() for s, e, _, _ in d):
        raise RuntimeError("no device op falls inside the host span "
                           f"{window_span!r}: the clocks do not line up")
    return Trace(ops=ops, spans=spans, window=window)
