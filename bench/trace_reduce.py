"""From a profiler trace to the numbers the per-layer metrics read.

In: the ``.xplane.pb`` that ``jax.profiler`` writes. Its device planes
(``/device:TPU:<id>``) hold one event per operation that ran on the chip,
on the line ``XLA Ops``; its host plane holds the benchmark's own spans,
written by ``jax.profiler.TraceAnnotation`` under names that start with
``bench.``. Both are on the trace's one clock.

Out: device busy time (the union of the operations' intervals) in the
window and inside each kind of host span, averaged over the chips used;
device time by operation and by category, collectives apart and the part of
them that no other operation overlaps; and the longest idle gaps, each
labelled with the innermost host span it fell in.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all", "async-collective")
# operations that only hold other operations: counted neither as compute
# nor in the table of operations, or they would cover all their children
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"[.:]\d+$")


def op_base(name: str) -> str:
    """``all-gather-start.3`` -> ``all-gather-start``; ``%fusion.12`` ->
    ``fusion``."""
    base = name.lstrip("%").split(" ")[0].split("=")[0]
    while _SUFFIX.search(base):
        base = _SUFFIX.sub("", base)
    return base


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, dtype=bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def _covered(us: np.ndarray, ue: np.ndarray, a: float, b: float) -> float:
    """Length of [a, b] covered by disjoint sorted intervals."""
    if us.size == 0 or b <= a:
        return 0.0
    return float(np.clip(np.minimum(ue, b) - np.maximum(us, a), 0.0, None).sum())


class DeviceOps:
    """The operations of one chip: start and end in seconds, and the kind
    of each (``op_base`` of its name), as an index into ``kinds``."""

    def __init__(self, starts, ends, kind_ids, kinds: list[str]):
        self.starts = np.asarray(starts, dtype=np.float64)
        self.ends = np.asarray(ends, dtype=np.float64)
        self.kinds = list(kinds)
        self.kind_ids = np.asarray(kind_ids, dtype=np.int64)
        coll = np.array([k.startswith(COLLECTIVES) for k in self.kinds], bool)
        cont = np.array([k in CONTAINERS for k in self.kinds], bool)
        self.coll = coll[self.kind_ids] if self.kinds else np.zeros(0, bool)
        self.cont = cont[self.kind_ids] if self.kinds else np.zeros(0, bool)
        self.busy = _union(self.starts, self.ends)
        leaf = ~self.cont
        self.compute = _union(self.starts[leaf & ~self.coll],
                              self.ends[leaf & ~self.coll])

    @classmethod
    def from_names(cls, start_ns, dur_ns, name_ids, name_id: dict) -> "DeviceOps":
        """From nanosecond starts and durations and each event's index into
        the names of ``name_id``; names of one kind share a kind index."""
        kinds: dict[str, int] = {}
        to_kind = np.zeros(len(name_id), np.int64)
        for name, i in name_id.items():
            to_kind[i] = kinds.setdefault(op_base(name), len(kinds))
        st = np.asarray(start_ns, np.float64) * 1e-9
        en = st + np.asarray(dur_ns, np.float64) * 1e-9
        ids = to_kind[np.asarray(name_ids, np.int64)] if len(name_ids) else []
        return cls(st, en, ids, list(kinds))


class Reduction:
    """The trace of one window, reduced. ``ops`` maps a device id to its
    ``DeviceOps``; ``spans`` are ``(name, start_s, end_s)`` host spans with
    the ``bench.`` prefix taken off."""

    def __init__(self, ops: dict[int, DeviceOps],
                 spans: list[tuple[str, float, float]]):
        self.ops = ops
        self.spans = sorted(spans, key=lambda s: (s[1], -s[2]))

    # -- reading ---------------------------------------------------------
    @classmethod
    def from_dir(cls, trace_dir: str, device_ids: Iterable[int]) -> "Reduction":
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        return cls.from_xplane(files[-1], device_ids)

    @classmethod
    def from_xplane(cls, path: str, device_ids: Iterable[int]) -> "Reduction":
        import jax

        wanted = set(device_ids)
        data = jax.profiler.ProfileData.from_file(path)
        ops: dict[int, DeviceOps] = {}
        spans: list[tuple[str, float, float]] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) in wanted:
                st, du, ids = [], [], []
                name_id: dict[str, int] = {}
                for line in plane.lines:
                    if line.name != OP_LINE:
                        continue
                    for ev in line.events:
                        st.append(ev.start_ns)
                        du.append(ev.duration_ns)
                        ids.append(name_id.setdefault(ev.name, len(name_id)))
                ops[int(m.group(1))] = DeviceOps.from_names(st, du, ids, name_id)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name[len(SPAN_PREFIX):],
                                          ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns) * 1e-9))
        return cls(ops, spans)

    # -- spans -------------------------------------------------------------
    def spans_named(self, name: str) -> list[tuple[float, float]]:
        """The host spans called ``name`` that end inside the window."""
        a, b = self.window()
        return [(s, e) for n, s, e in self.spans if n == name and a <= s and e <= b]

    def window(self) -> tuple[float, float]:
        """The ``bench.window`` span."""
        w = [(s, e) for n, s, e in self.spans if n == "window"]
        if not w:
            raise ValueError("the trace holds no bench.window span")
        return w[0]

    def window_s(self) -> float:
        a, b = self.window()
        return b - a

    def label_at(self, t: float) -> str:
        """The innermost host span that holds time ``t``."""
        best: Optional[tuple[str, float, float]] = None
        for n, a, b in self.spans:
            if a <= t <= b and (best is None or b - a < best[2] - best[1]):
                best = (n, a, b)
        return best[0] if best else "outside spans"

    # -- device time -------------------------------------------------------
    def _mean(self, fn) -> float:
        if not self.ops:
            return 0.0
        return float(np.mean([fn(d) for d in self.ops.values()]))

    def busy_between(self, a: float, b: float) -> float:
        """Device busy seconds in [a, b], averaged over the chips."""
        return self._mean(lambda d: _covered(*d.busy, a, b))

    def busy_s(self) -> float:
        return self.busy_between(*self.window())

    def busy_in(self, name: str) -> float:
        """Device busy seconds inside all host spans called ``name``."""
        return sum(self.busy_between(a, b) for a, b in self.spans_named(name))

    def collective_between(self, a: float, b: float) -> float:
        """Device seconds in collective operations in [a, b], averaged over
        the chips."""
        return self._mean(lambda d: _covered(
            *_union(d.starts[d.coll], d.ends[d.coll]), a, b))

    def collective_s(self) -> float:
        return self.collective_between(*self.window())

    def collective_in(self, name: str) -> float:
        """Collective seconds inside all host spans called ``name``."""
        return sum(self.collective_between(a, b)
                   for a, b in self.spans_named(name))

    def collective_exposed_s(self) -> float:
        """Collective seconds during which no other operation runs."""
        a, b = self.window()

        def one(d: DeviceOps) -> float:
            us, ue = _union(d.starts[d.coll], d.ends[d.coll])
            total = 0.0
            for s, e in zip(us, ue):
                s, e = max(s, a), min(e, b)
                if e > s:
                    total += (e - s) - _covered(*d.compute, s, e)
            return total
        return self._mean(one)

    def top_ops(self, k: int = 10) -> list[list]:
        """Device seconds per kind of operation in the window, averaged
        over the chips, most first."""
        a, b = self.window()
        totals: dict[str, float] = {}
        for d in self.ops.values():
            keep = ~d.cont & (d.ends > a) & (d.starts < b)
            dur = np.minimum(d.ends, b) - np.maximum(d.starts, a)
            per = np.bincount(d.kind_ids[keep], weights=dur[keep],
                              minlength=len(d.kinds))
            for kind, t in zip(d.kinds, per):
                if t > 0:
                    totals[kind] = totals.get(kind, 0.0) + float(t)
        n = max(len(self.ops), 1)
        ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t / n] for name, t in ranked]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The longest stretches of the window in which a chip ran nothing,
        each named by the host span it fell in."""
        a, b = self.window()
        starts, ends = [], []
        for d in self.ops.values():
            us, ue = d.busy
            starts.append(np.concatenate([[a], np.clip(ue, a, b)]))
            ends.append(np.concatenate([np.clip(us, a, b), [b]]))
        if not starts:
            return []
        gs, ge = np.concatenate(starts), np.concatenate(ends)
        top = np.argsort(gs - ge, kind="stable")[:k]
        return [[self.label_at(0.5 * (gs[i] + ge[i])), float(ge[i] - gs[i])]
                for i in top if ge[i] > gs[i]]

    def per_device(self) -> dict:
        """Per chip: operations read, busy seconds in the window and the
        first and last operation's time from the window's start."""
        a, b = self.window()
        return {str(k): {"ops": int(d.starts.size),
                         "busy_s": _covered(*d.busy, a, b),
                         "first_s": float(d.starts.min() - a) if d.starts.size else None,
                         "last_s": float(d.ends.max() - a) if d.ends.size else None}
                for k, d in self.ops.items()}

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
