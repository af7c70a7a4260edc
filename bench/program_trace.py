#!/usr/bin/env python3
"""The program's own spans and name scopes, read from a profiler trace.

The program writes host spans named ``repro.<layer>[.<part>]``
(``repro.utils.spans``) with their arguments as stats, and names the
operations of the D-PSGD step with ``jax.named_scope`` (``dpsgd.grad``,
``dpsgd.quantize``, ``dpsgd.mix``, ``dpsgd.update``). ``ProgramReduction``
is ``trace_reduce.Reduction`` plus both: the ``repro.`` spans with their
arguments, and each device operation's scope, the innermost ``dpsgd.*``
component of its name-scope path (an async start, which the TPU leaves
without one, takes its done's). Every method of ``Reduction`` gives what
it gives there, but for ``idle_gaps``: a gap that a program span covers is
labelled ``<bench span>/<program span>``, for example
``train/repro.train.prep``. On a trace without program spans the labels are
``Reduction``'s.

``readings`` turns them into per-layer numbers: the planner's pre-screen and
exact certifications per plan, the channel scan's host records per trace,
the training call's host preparation and post-processing per call, and the
device time of the step's gradient, mix and update per round.

    python3 bench/program_trace.py <trace dir> [--devices 0,1,2,3]

prints those readings, the program spans per unit of work and the labelled
breakdown of the trace that ``jax.profiler.start_trace(<trace dir>)`` wrote.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Iterable, Optional

import numpy as np

import trace_reduce as tr

PROGRAM_PREFIX = "repro."
SCOPE = re.compile(r"dpsgd\.[A-Za-z_]+")


def scope_of(path: str) -> Optional[str]:
    """The innermost ``dpsgd.*`` component of a name-scope path, or None:
    ``jit(f)/while/body/dpsgd.mix/dot_general`` -> ``dpsgd.mix``."""
    found = SCOPE.findall(path)
    return found[-1] if found else None


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf: memoryview):
    """(field number, value) of each field of one protobuf message: a
    varint as an int, anything else as the bytes it spans."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            n = {1: 8, 5: 4}.get(wire)
            if n is None:
                n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        yield key >> 3, value


def op_scopes(path: str, planes: Iterable[str]) -> dict[str, dict[str, str]]:
    """For each plane named in ``planes``: the scope of each operation
    name, from the ``tf_op`` stat (the op's name-scope path) of the plane's
    event metadata; on the TPU the ops' own events carry no such stat.
    ``ProfileData`` does not expose event metadata, so this walks the
    ``.xplane.pb`` itself (XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4, stat_metadata = 5; map entries key = 1, value = 2;
    XEventMetadata.name = 2, display_name = 4, stats = 5; XStatMetadata.id
    = 1, name = 2; XStat.metadata_id = 1, str_value = 5, ref_value = 7, the
    id of the stat metadata whose name is the text)."""
    wanted = set(planes)
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    out: dict[str, dict[str, str]] = {}
    for field, plane in _fields(raw):
        if field != 1:
            continue
        top = list(_fields(plane))
        name = next((bytes(v).decode() for g, v in top if g == 2), "")
        if name not in wanted:
            continue
        texts = {}
        for g, v in top:
            if g == 5:
                stat = dict(_fields(dict(_fields(v))[2]))
                texts[stat.get(1, 0)] = bytes(stat.get(2, b"")).decode()
        scopes = out.setdefault(name, {})
        for g, v in top:
            if g != 4:
                continue
            names, scope = [], None
            for h, w in _fields(dict(_fields(v))[2]):
                if h in (2, 4):
                    names.append(bytes(w).decode())
                elif h == 5:
                    stat = dict(_fields(w))
                    if texts.get(stat.get(1)) != "tf_op":
                        continue
                    text = (bytes(stat[5]).decode("utf-8", "replace")
                            if 5 in stat
                            else texts.get(stat.get(7), ""))
                    scope = scope_of(text)
            if scope is not None:
                scopes.update(dict.fromkeys(filter(None, names), scope))
    return out


def pair_async(ops: tr.DeviceOps, scope_ids: np.ndarray) -> np.ndarray:
    """``scope_ids`` with each unscoped async start given the scope of the
    next done of its kind on the chip. On the TPU an async collective's
    start is a fusion without op metadata (its collective sits in the
    fused computation); its done carries the collective's name scope."""
    out = np.array(scope_ids, np.int64)
    for k, kind in enumerate(ops.kinds):
        done = kind[:-len("start")] + "done"
        if not kind.endswith("-start") or done not in ops.kinds:
            continue
        dones = np.flatnonzero(ops.kind_ids == ops.kinds.index(done))
        dones = dones[np.argsort(ops.starts[dones], kind="stable")]
        starts = np.flatnonzero((ops.kind_ids == k) & (out < 0))
        nxt = np.searchsorted(ops.starts[dones], ops.starts[starts])
        hit = nxt < dones.size
        out[starts[hit]] = out[dones[nxt[hit]]]
    return out


class ProgramReduction(tr.Reduction):
    """A ``Reduction`` that also holds the program's spans and scopes.

    ``program_spans`` are ``(name, start_s, end_s, args)`` with the full
    ``repro.`` name; ``scope_ids`` maps a device id to one index into
    ``scope_names`` per operation, in the order of its ``DeviceOps``, -1
    where the operation has no ``dpsgd.*`` scope."""

    def __init__(self, ops, spans, program_spans=(),
                 scope_ids: Optional[dict] = None,
                 scope_names: Iterable[str] = ()):
        super().__init__(ops, spans)
        self.program_spans = sorted(program_spans,
                                    key=lambda s: (s[1], -s[2]))
        self.scope_names = list(scope_names)
        self.scope_ids = {k: pair_async(d, (scope_ids or {}).get(
            k, np.full(d.starts.size, -1))) for k, d in ops.items()}

    # -- reading ---------------------------------------------------------
    @classmethod
    def from_xplane(cls, path: str, device_ids) -> "ProgramReduction":
        import jax

        wanted = set(device_ids)
        data = jax.profiler.ProfileData.from_file(path)
        devices = [p.name for p in data.planes
                   if (m := tr.DEVICE_PLANE.match(p.name))
                   and int(m.group(1)) in wanted]
        by_plane = op_scopes(path, devices) if devices else {}
        ops, scope_ids, names = {}, {}, {}
        spans, program_spans = [], []
        for plane in data.planes:
            if plane.name in devices:
                st, du, ids = [], [], []
                name_id: dict[str, int] = {}
                for line in plane.lines:
                    if line.name != tr.OP_LINE:
                        continue
                    for ev in line.events:
                        st.append(ev.start_ns)
                        du.append(ev.duration_ns)
                        ids.append(name_id.setdefault(ev.name, len(name_id)))
                dev = int(tr.DEVICE_PLANE.match(plane.name).group(1))
                ops[dev] = tr.DeviceOps.from_names(st, du, ids, name_id)
                scopes = by_plane.get(plane.name, {})
                of_name = np.array(
                    [names.setdefault(scopes[n], len(names)) if n in scopes
                     else -1 for n in name_id], np.int64)
                scope_ids[dev] = of_name[np.asarray(ids, np.int64)]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        a = ev.start_ns * 1e-9
                        b = (ev.start_ns + ev.duration_ns) * 1e-9
                        if ev.name.startswith(tr.SPAN_PREFIX):
                            spans.append((ev.name[len(tr.SPAN_PREFIX):], a, b))
                        elif ev.name.startswith(PROGRAM_PREFIX):
                            program_spans.append((ev.name, a, b,
                                                  dict(ev.stats)))
        return cls(ops, spans, program_spans, scope_ids, names)

    # -- program spans -----------------------------------------------------
    def program_spans_named(self, name: str) -> list[tuple]:
        """The program spans called ``name`` (``repro.`` included) that lie
        inside the window, with their arguments."""
        a, b = self.window()
        return [(s, e, args) for n, s, e, args in self.program_spans
                if n == name and a <= s and e <= b]

    def program_s(self, name: str) -> tuple[float, int]:
        """(seconds, count) of the program spans called ``name``."""
        spans = self.program_spans_named(name)
        return sum(e - s for s, e, _ in spans), len(spans)

    def busy_in_program(self, name: str) -> float:
        """Device busy seconds inside the program spans called ``name``."""
        return sum(self.busy_between(s, e)
                   for s, e, _ in self.program_spans_named(name))

    def label_at(self, t: float) -> str:
        """``Reduction``'s label, followed by ``/<program span>`` where a
        program span holds time ``t``: the innermost one."""
        label = super().label_at(t)
        inner = None
        for n, a, b, _ in self.program_spans:
            if a <= t <= b and (inner is None or b - a < inner[2] - inner[1]):
                inner = (n, a, b)
        return label if inner is None else f"{label}/{inner[0]}"

    # -- scopes ------------------------------------------------------------
    def _scoped(self, scope: str, span: str, collectives: bool) -> float:
        if scope not in self.scope_names or not self.ops:
            return 0.0
        want = self.scope_names.index(scope)
        spans = self.spans_named(span)

        def one(dev: int, d: tr.DeviceOps) -> float:
            keep = (self.scope_ids[dev] == want) & ~d.cont
            if collectives:
                keep &= d.coll
            us, ue = tr._union(d.starts[keep], d.ends[keep])
            return sum(tr._covered(us, ue, a, b) for a, b in spans)
        return float(np.mean([one(k, d) for k, d in self.ops.items()]))

    def busy_in_scope(self, scope: str, span: str) -> float:
        """Device seconds of operations under ``scope`` inside the host
        spans ``span``, averaged over the chips; operations that only hold
        others are left out, as in ``top_ops``."""
        return self._scoped(scope, span, collectives=False)

    def collective_in_scope(self, scope: str, span: str) -> float:
        """The collective part of ``busy_in_scope``."""
        return self._scoped(scope, span, collectives=True)

    # -- readings ----------------------------------------------------------
    def spans_per_unit(self) -> dict:
        """Program spans of each name per ``bench.unit`` span."""
        units = len(self.spans_named("unit"))
        counts: dict[str, int] = {}
        for n, *_ in self.program_spans:
            counts[n] = counts.get(n, 0) + 1
        if not units:
            return {}
        return {n: c / units for n, c in sorted(counts.items())}

    def readings(self) -> dict:
        """The per-layer numbers the program's spans and scopes give, each
        None where the trace holds nothing to read: milliseconds, except
        ``planner_certs`` (certifications per plan) and
        ``collective_in_mix_pct`` (the share of the collective time inside
        ``train`` spans that lies under ``dpsgd.mix``)."""
        out: dict = {}
        _, plans = self.program_s("repro.plan")
        _, scans = self.program_s("repro.scan")
        trains = self.program_spans_named("repro.train")
        for key, name in (("planner_screen_ms", "repro.plan.screen"),
                          ("planner_certify_ms", "repro.plan.certify")):
            out[key] = 1e3 * self.program_s(name)[0] / plans if plans else None
        out["planner_certs"] = (self.program_s("repro.plan.certify")[1] / plans
                                if plans else None)
        out["scan_records_ms"] = (1e3 * self.program_s("repro.scan.records")[0]
                                  / scans if scans else None)
        for key, name in (("train_prep_ms", "repro.train.prep"),
                          ("train_post_ms", "repro.train.post")):
            out[key] = (1e3 * (self.program_s(name)[0]
                               - self.busy_in_program(name)) / len(trains)
                        if trains and self.ops else None)
        rounds = sum(int(args.get("rounds", 0)) for *_, args in trains)
        for key, scope in (("step_grad_ms", "dpsgd.grad"),
                           ("step_mix_ms", "dpsgd.mix"),
                           ("step_update_ms", "dpsgd.update")):
            t = self.busy_in_scope(scope, "train") if rounds else 0.0
            out[key] = 1e3 * t / rounds if t > 0 else None
        coll = self.collective_in("train")
        mixed = self.collective_in_scope("dpsgd.mix", "train")
        out["collective_in_mix_pct"] = (
            100 * mixed / coll
            if coll > 0 and "dpsgd.mix" in self.scope_names else None)
        return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--devices", default="0",
                    help="comma-separated device ids to read (default 0)")
    args = ap.parse_args(argv)
    red = ProgramReduction.from_dir(
        args.trace_dir, [int(d) for d in args.devices.split(",")])
    print(json.dumps({"readings": red.readings(),
                      "spans_per_unit": red.spans_per_unit(),
                      "breakdown": red.breakdown()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
