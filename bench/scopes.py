"""Device time per model layer, and host time per server step, from a
profiler trace (``.xplane.pb``).

Every XLA op on a TPU's plane carries a ``tf_op`` stat in its event
metadata: the JAX name stack it was traced under, e.g.
``jit(decode_step)/while/body/closed_call/checkpoint/ffn/``
``jit(_gemm_op_impl)/redmule_gemm/pallas_call:``. The program names its layers with ``jax.named_scope`` (``SCOPES``), so the
first layer name in that stack says which layer an op's device time goes
to, whatever shape its products have. ``jax.profiler.ProfileData`` does not
expose event-metadata stats, so this module reads the few fields it needs
from the protobuf wire format (XPlane's field numbers: XSpace planes 1;
XPlane name 2, lines 3, event_metadata 4, stat_metadata 5; XLine name 2,
timestamp_ns 3, events 4; XEvent metadata_id 1, offset_ps 2, duration_ps 3;
XEventMetadata name 2, stats 5; XStat metadata_id 1, str_value 5;
XStatMetadata name 2; a map entry's key 1 and value 2).

The host side comes from ``reduce.host_spans``: the program's
``server.step`` spans and the ``harvest.wait`` spans inside them.
"""
from __future__ import annotations

import bisect
import collections

import readers
import reduce

SCOPES = ("embed", "attn", "recurrent", "ffn", "moe", "lm_head", "sample")
UNSCOPED = "unscoped"
STEP, WAIT = "server.step", "harvest.wait"


def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_entry(b):
    f = dict(_fields(b))
    return f.get(1, 0), f.get(2, b"")


def _name(b) -> str:
    return next((bytes(v).decode() for k, v in _fields(b) if k == 2), "")


class DevicePlane:
    """One device plane's lines and its ops' names and ``tf_op`` stacks."""

    def __init__(self, data: bytes, plane: str = "/device:TPU:0"):
        buf = memoryview(data)
        for k, p in _fields(buf):
            if k == 1 and _name(p) == plane:
                break
        else:
            raise ValueError(f"the trace holds no plane {plane!r}")
        lines, emeta, smeta = [], {}, {}
        for k, v in _fields(p):
            if k == 3:
                lines.append(v)
            elif k == 4:
                key, val = _map_entry(v)
                emeta[key] = val
            elif k == 5:
                key, val = _map_entry(v)
                smeta[key] = _name(val)
        tf_op = next((key for key, n in smeta.items() if n == "tf_op"), None)
        self.names: dict[int, str] = {}
        self.stacks: dict[int, str] = {}
        for key, val in emeta.items():
            for f, v in _fields(val):
                if f == 2:
                    self.names[key] = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op and 5 in stat:
                        self.stacks[key] = bytes(stat[5]).decode()
        self.lines: dict[str, list[tuple[int, float, float]]] = {}
        for line in lines:
            name, t0, events = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    name = bytes(v).decode()
                elif f == 3:
                    t0 = v
                elif f == 4:
                    ev = dict(_fields(v))
                    start = t0 + ev.get(2, 0) / 1e3
                    events.append((ev.get(1, 0), start, start + ev.get(3, 0) / 1e3))
            self.lines[name] = events

    def events(self, line: str) -> list[tuple[int, float, float]]:
        """(metadata id, start_ns, end_ns) of a line's events."""
        return self.lines.get(line, [])


def scope_of(stack: str) -> str:
    """The first layer scope in a ``tf_op`` name stack, or ``UNSCOPED``."""
    for part in stack.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def program_scopes(dev: DevicePlane, program: str, lo: float = float("-inf"),
                   hi: float = float("inf")) -> list[dict[str, float]]:
    """Device seconds per scope of each execution of ``program`` wholly
    inside [lo, hi] ns: the sum over its non-container XLA ops."""
    ops = sorted((s, e, m) for m, s, e in dev.events("XLA Ops")
                 if reduce.op_base(dev.names.get(m, "")) not in reduce._CONTAINERS)
    starts = [s for s, _, _ in ops]
    out = []
    for m, s, e in dev.events("XLA Modules"):
        if dev.names.get(m, "").split("(")[0] != program or s < lo or e > hi:
            continue
        split = collections.defaultdict(float)
        for a, b, op in ops[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)]:
            split[scope_of(dev.stacks.get(op, ""))] += (min(b, e) - a) * 1e-9
        out.append(dict(split))
    return out


def host_steps(profile, lo: float = float("-inf"),
               hi: float = float("inf")) -> list[tuple[float, float]]:
    """(seconds, seconds of ``harvest.wait`` inside) of each ``server.step``
    span wholly inside [lo, hi] ns."""
    spans = reduce.host_spans(profile, {STEP, WAIT})
    waits = [(s, e) for n, s, e in spans if n == WAIT]
    out = []
    for n, s, e in spans:
        if n == STEP and lo <= s and e <= hi:
            wait = sum(b - a for a, b in waits if s <= a and b <= e)
            out.append(((e - s) * 1e-9, wait * 1e-9))
    return out


def reduce_trace(data: bytes, profile, n_chips: int = 1,
                 window: str = reduce.WINDOW) -> dict:
    """What the per-layer readers need, to add to a run's ``rec["trace"]``:
    ``scopes``, each step program's mean device ms per scope and call
    count, over the first ``n_chips`` chips; ``host_steps``, the
    ``server.step`` spans and their ``harvest.wait`` time (``host_steps``);
    both inside the traced window."""
    win = reduce.host_spans(profile, {window})
    if not win:
        raise ValueError(f"the trace holds no host span {window!r}")
    lo, hi = min(w[1] for w in win), max(w[2] for w in win)
    scopes = {}
    for chip in range(n_chips):
        dev = DevicePlane(data, f"/device:TPU:{chip}")
        for program in readers.PROGRAMS.values():
            scopes.setdefault(program, []).extend(program_scopes(dev, program, lo, hi))
    return {
        "scopes": {p: {"calls": len(calls), "ms": {
            s: 1e3 * sum(c.get(s, 0.0) for c in calls) / len(calls)
            for s in sorted({s for c in calls for s in c})}}
            for p, calls in scopes.items() if calls},
        "host_steps": host_steps(profile, lo, hi),
    }


def scope_ms(rec: dict, program: str, scope: str) -> float | None:
    """Mean device ms per call of ``program`` under ``scope``, from a run's
    record; None where the run holds no such reading."""
    split = rec.get("trace", {}).get("scopes", {}).get(program)
    return split["ms"].get(scope) if split else None
