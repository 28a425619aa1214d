"""The program's own names in a device trace: each device op's layer, from
the `jax.named_scope` path XLA keeps in the op's metadata, and the
program's host spans (`serve.<phase>` from the engine's tick, `train.feed`
from the data iterator) beside the benchmark's `bench.*` ones.

The TPU's op events carry no path. The profile carries each program it
saw compiled (an HloProto on its metadata plane), and an op event's name
starts with its instruction's name, so an op's path is the `op_name` of
that instruction in the program whose event holds the op.

It extends bench_trace's reduction and changes none of its numbers:

  read_xplane   bench_trace.read_xplane's events, with each op's path in a
                list beside the device's ops, and the program's spans kept
                with the benchmark's
  reduce        bench_trace.reduce over the same events (so idle gaps are
                named after the innermost span of either kind), plus
                scopes  program -> {scope -> device seconds}
                spans   program span name -> [count, seconds]

An op counts toward every scope that is a component of its path (`.../
mixer/fold/...`, or `transpose(jvp(mixer))` under a transformation), so
`fold` and `sketch` are parts of `mixer`; an op with none of SCOPES goes to
OUTSIDE. Control flow whose event spans its body's ops is left out, as in
bench_trace's op list.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

import bench_trace

# the program's named scopes (models/transformer.py, core/sketches.py,
# core/decode.py, serve/engine.py, train/step.py)
SCOPES = ("embed", "mixer", "sketch", "fold", "ffn", "logits", "sampler",
          "loss", "optimizer")
OUTSIDE = "outside"
# the program's host spans (serve/telemetry.Tracer, data/loader.py)
PROGRAM_SPAN_PREFIXES = ("serve.", "train.")
# the profile's plane of program metadata, and the stat on each program's
# entry there that holds its compiled HLO (an HloProto)
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def scopes_of(path: str) -> tuple[str, ...]:
    """The SCOPES that are components of an op's path."""
    parts = path.split("/")
    return tuple(s for s in SCOPES
                 if any(p == s or f"({s})" in p for p in parts))


# ---------------------------------------------------------------------------
# the compiled programs a profile carries: protobuf wire format, read by
# field number (XSpace, XPlane, XEventMetadata, XStat, XStatMetadata in
# tsl/profiler/protobuf/xplane.proto; HloProto, HloModuleProto,
# HloComputationProto, HloInstructionProto, OpMetadata in xla/service/
# hlo.proto and xla/xla_data.proto)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a message: an int for a
    varint, the bytes for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _first(buf, number: int, default=b""):
    return next((v for k, v in _fields(buf) if k == number), default)


def _op_names(hlo_proto) -> dict[str, str]:
    """{instruction name: op_name} of every instruction of a program."""
    out = {}
    module = _first(hlo_proto, 1)                        # HloProto.hlo_module
    for k, comp in _fields(module):
        if k != 3:                                       # .computations
            continue
        for k2, inst in _fields(comp):
            if k2 != 2:                                  # .instructions
                continue
            name = meta = b""
            for k3, v in _fields(inst):
                if k3 == 1:                              # .name
                    name = v
                elif k3 == 7:                            # .metadata
                    meta = v
            out[bytes(name).decode()] = bytes(_first(meta, 2)).decode()
    return out


def program_op_names(xspace) -> dict[str, dict[str, str]]:
    """{program event name, e.g. 'jit_decode_all(506...)': {instruction
    name: op_name}} from the compiled programs in the profile."""
    buf = memoryview(xspace)
    for k, plane in _fields(buf):
        if k != 1 or bytes(_first(plane, 2)).decode() != METADATA_PLANE:
            continue
        stat_names, programs = {}, []
        for k2, entry in _fields(plane):
            if k2 == 5:                                  # stat_metadata
                sm = _first(entry, 2)
                stat_names[_first(sm, 1, 0)] = bytes(_first(sm, 2)).decode()
            elif k2 == 4:                                # event_metadata
                programs.append(_first(entry, 2))
        out = {}
        for em in programs:
            name = bytes(_first(em, 2)).decode()
            for k3, stat in _fields(em):
                if k3 == 5 and stat_names.get(_first(stat, 1, 0)) == \
                        HLO_PROTO_STAT:
                    out[name] = _op_names(_first(stat, 6))
        return out
    return {}


def _holder(programs, starts, t: int):
    """The name of the program event (of `programs`, sorted, with their
    `starts`) that holds time `t`, or None."""
    i = bisect.bisect_right(starts, t) - 1
    return programs[i][2] if i >= 0 and t < programs[i][1] else None


def _paths(ops, programs, op_names) -> list[str]:
    """Each op's op_name, from the program whose event holds its start."""
    progs = sorted(programs)
    starts = [s for s, _, _ in progs]
    return [op_names.get(_holder(progs, starts, s), {}).get(
        name.split(" = ", 1)[0].strip().lstrip("%"), "")
        for s, _, name in ops]


def read_xplane(path: str, n_chips: int):
    """(devices, host_spans) as bench_trace.read_xplane reads them, with
    each device's op paths under "paths" (aligned with "ops"; "" where the
    profile holds none) and the program's spans among the host spans."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    op_names = program_op_names(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    prefixes = (bench_trace.HOST_SPAN_PREFIX,) + PROGRAM_SPAN_PREFIXES
    devices, host = [], []
    for plane in pd.planes:
        if bench_trace.DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "programs": []}
            for line in plane.lines:
                key = {bench_trace.OPS_LINE: "ops",
                       bench_trace.MODULES_LINE: "programs"}.get(line.name)
                if key:
                    dev[key] = [(int(e.start_ns), int(e.end_ns), e.name)
                                for e in line.events]
            dev["paths"] = _paths(dev["ops"], dev["programs"], op_names)
            devices.append((plane.name, dev))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events
                            if e.name.startswith(prefixes))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return [d for _, d in devices[:n_chips]], host


def scope_seconds(devices: list[dict], window_ns) -> dict:
    """{program: {scope: device seconds}} over the window: each op, clipped
    to the window, counts in the program whose event holds its start."""
    w0, w1 = window_ns
    out: dict[str, dict[str, float]] = {}
    for dev in devices:
        progs = sorted((max(s, w0), min(e, w1), bench_trace.program_name(n))
                       for s, e, n in dev["programs"] if e > w0 and s < w1)
        starts = [s for s, _, _ in progs]
        for (s, e, name), path in zip(dev["ops"], dev["paths"]):
            s, e = max(s, w0), min(e, w1)
            program = _holder(progs, starts, s)
            if e <= s or program is None or bench_trace.CONTAINERS.match(
                    name):
                continue
            acc = out.setdefault(program, {})
            for scope in scopes_of(path) or (OUTSIDE,):
                acc[scope] = acc.get(scope, 0.0) + (e - s) * 1e-9
    return out


def span_totals(host_spans, window_ns) -> dict:
    """{program span name: [count, seconds]} of the spans that start in
    the window (whole: the benchmark's loop closes the window between
    steps, so a span that starts in it ends in it)."""
    w0, w1 = window_ns
    out: dict[str, list] = {}
    for s, e, name in host_spans:
        if name.startswith(PROGRAM_SPAN_PREFIXES) and w0 <= s < w1:
            c = out.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    return out


@dataclass
class Scoped:
    reduced: bench_trace.Reduced
    scopes: dict = field(default_factory=dict)
    spans: dict = field(default_factory=dict)

    def per_call_ms(self, program: str, scope: str) -> float | None:
        """Device ms in `scope` per call of `program` (a program name as
        bench_trace keeps it, e.g. 'jit_decode_all')."""
        calls = self.reduced.programs.get(program, [0])[0]
        secs = self.scopes.get(program, {}).get(scope)
        return secs / calls * 1e3 if calls and secs is not None else None


def reduce(devices: list[dict], host_spans, window_ns,
           n_top: int = 10) -> Scoped:
    return Scoped(bench_trace.reduce(devices, host_spans, window_ns, n_top),
                  scope_seconds(devices, window_ns),
                  span_totals(host_spans, window_ns))
