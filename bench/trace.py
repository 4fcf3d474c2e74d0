"""Reduce a JAX profiler trace (``.xplane.pb``) to device time.

Per device plane (``/device:...``), from its op line (``XLA Ops`` where the
plane has one):

* ``busy_ns``     the union of the intervals in which an op ran;
* ``class_ns``    self time (an event's duration less its nested events')
                  summed per class, by a caller's classifier;
* ``exposed_ns``  the time collective ops (all-to-all, all-gather, ...)
                  ran with no other op beside them on that device, and
                  ``exposed_by``, the same for each kind of collective.
                  Which ops are collectives, and of which kind, is read from
                  the compiled step's HLO by opcode (``collective_kinds``);
* ``ops``         self time per op name, and ``gaps``, the idle intervals
                  between busy ones.

Host planes give the spans (``TraceAnnotation`` and the runtime's own) that
``label_gaps`` ties each idle gap to.

    python bench/trace.py <file.xplane.pb>     # print the planes and lines
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+"
                 r"([\w\-]+)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
KINDS = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
         "collective-permute")
_PRODUCTS = ("convolution", "dot")


def collective_kinds(hlo_text: str) -> dict:
    """``{instruction: kind}`` for the collectives of a compiled HLO module,
    by opcode: an ``all-to-all``, ``all-gather``, ``all-reduce``,
    ``reduce-scatter`` or ``collective-permute`` instruction (or its
    ``-start``/``-done`` half), and a fusion whose computation holds one and
    computes no product (the TPU compiler's asynchronous collective start
    and done fusions).  A fusion that computes a product beside its
    collective (the compiler's collective matmul) is compute: the collective
    runs hidden inside it."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _HEAD.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        m = _OP.match(line)
        if m and cur is not None:
            calls = _CALLS.search(line)
            cur.append((m.group(1), m.group(2),
                        calls.group(1) if calls else None))
    seen = {}

    def inside(comp):
        """(collective kinds, whether a product is computed) in ``comp``
        and the computations its fusions call."""
        if comp not in seen:
            seen[comp] = (set(), False)
            kinds, product = set(), False
            for _, op, calls in comps.get(comp, ()):
                kind = _opcode_kind(op)
                if kind:
                    kinds.add(kind)
                product |= op in _PRODUCTS
                if op == "fusion" and calls:
                    k, p = inside(calls)
                    kinds |= k
                    product |= p
            seen[comp] = (kinds, product)
        return seen[comp]

    out = {}
    for ops in comps.values():
        for name, op, calls in ops:
            kind = _opcode_kind(op)
            if kind is None and op == "fusion" and calls:
                kinds, product = inside(calls)
                if kinds and not product:
                    kind = sorted(kinds)[0]
            if kind:
                out[name] = kind
    return out


def _opcode_kind(op: str) -> str | None:
    for suffix in ("-start", "-done"):
        if op.endswith(suffix):
            op = op[:-len(suffix)]
    return op if op in KINDS else None


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name metadata}`` of a compiled HLO module's
    text: the JAX name path of the operation each instruction came from."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


_NAME = re.compile(r"^%?([\w.\-]+)")


def _events(line, short=False):
    """``(name, start, end, stats)`` per event; ``short`` cuts a TPU op
    event's name (its whole HLO instruction) to the instruction's name."""
    out = []
    for ev in line.events:
        name = ev.name
        if short:
            m = _NAME.match(name)
            name = m.group(1) if m else name
        out.append((name, float(ev.start_ns),
                    float(ev.start_ns + ev.duration_ns), dict(ev.stats)))
    return out


def load(path: str):
    """``(devices, hosts)``: ``{plane name: [event]}`` for each device's
    ``XLA Ops`` line and for every host line, each event ``(name, start,
    end, stats)`` in nanoseconds on the trace's clock.  Asynchronous copies
    (``Async XLA Ops``) span the time they are in flight, not busy time,
    and are left out."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, hosts = {}, {}
    for plane in pd.planes:
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"]
        if ops:
            devices[plane.name] = [e for ln in ops
                                   for e in _events(ln, short=True)]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                hosts[f"{plane.name}/{ln.name}"] = _events(ln)
    return devices, hosts


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _nesting(events):
    """``(order, child)``: the events sorted by start (longest first), and
    the time of the events nested directly inside each on the same line."""
    order = sorted(events, key=lambda ev: (ev[1], -(ev[2] - ev[1])))
    child = [0.0] * len(order)
    stack = []
    for i, (_, s, e, _) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and order[stack[-1]][2] >= e:
            child[stack[-1]] += e - s
        stack.append(i)
    return order, child


def self_times(events):
    """``[(event, self ns)]``: each event's duration less the time of the
    events nested inside it on the same line.  Events that overlap without
    one containing the other (asynchronous ops) are siblings."""
    order, child = _nesting(events)
    return [(ev, (ev[2] - ev[1]) - c) for ev, c in zip(order, child)]


def reduce_device(events, classify, kinds):
    """One device's op events reduced as the module docstring says.
    ``classify(name, stats) -> class`` or None to leave an event out of
    the classes (it still counts as busy).  ``exposed_ns`` is the time
    collective ops ran with no other op beside them: ``kinds`` (the step's
    ``collective_kinds``) names them and their kind.  An op that holds
    others (a loop) hides no collective: only ops with no nested op count
    as compute."""
    busy = union([(s, e) for _, s, e, _ in events])
    class_ns = defaultdict(float)
    ops = defaultdict(float)
    comm, compute = defaultdict(list), []
    order, child = _nesting(events)
    for (name, s, e, stats), nested in zip(order, child):
        own = (e - s) - nested
        ops[name] += own
        cls = classify(name, stats)
        if cls is not None:
            class_ns[cls] += own
        kind = kinds.get(name)
        if kind is not None:
            comm[kind].append((s, e))
        elif not nested:
            compute.append((s, e))
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    compute = union(compute)
    return {"busy_ns": _length(busy), "class_ns": dict(class_ns),
            "exposed_ns": _minus(union([iv for v in comm.values()
                                        for iv in v]), compute),
            "exposed_by": {k: _minus(union(v), compute)
                           for k, v in comm.items()},
            "ops": dict(ops), "gaps": gaps}


def label_gaps(gaps, hosts, window, top=10):
    """The ``top`` longest gaps, each named by the host span that overlaps
    it most (the shortest on a tie); spans that cover half of ``window``
    or more (the whole trace, the loop) name nothing."""
    (w0, w1) = window
    spans = [(n, s, e) for evs in hosts.values() for n, s, e, _ in evs
             if e > s and (e - s) < 0.5 * (w1 - w0)]
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, key = "no host span", (0.0, 0.0)
        for n, s, e in spans:
            ov = min(e, g1) - max(s, g0)
            if ov > 0 and (ov, -(e - s)) > key:
                best, key = n, (ov, -(e - s))
        out.append((best, (g1 - g0) * 1e-9))
    return out


def describe(path: str) -> str:
    """Planes, lines, event counts and a few events with their stats."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    rows = []
    for plane in pd.planes:
        rows.append(f"plane {plane.name}")
        for ln in plane.lines:
            evs = list(ln.events)
            rows.append(f"  line {ln.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                rows.append(f"    {ev.name!r} start {ev.start_ns} dur "
                            f"{ev.duration_ns} {dict(ev.stats)}")
    return "\n".join(rows)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
