"""Device time of the program's named scopes in a traced serve run's decode
program, and the roofline share of the kernel a scope holds.

The program wraps its kernels in `jax.named_scope`s (`mla.attend`,
`moe.experts`, ...), and XLA keeps a scope in each op's framework name (the
HLO metadata's op_name, e.g. `jit(f)/while/body/moe.experts/gather`).  The
profiler's device op events carry only the op's HLO instruction; the
framework names are in the optimized HLO module that the trace's
`/host:metadata` plane stores for each program ("Hlo Proto").  So the
reduction reads the decode program's module from there, maps each
instruction to its scope, and sums the device time of the op events by
scope.  The grouped products of `jax.lax.ragged_dot` are the exception:
XLA's TPU lowering gives the kernel (`ragged-dot-*`) an op_name of its own,
so those ops count towards `moe.experts`, the one scope that calls them.
So do the unscoped ops whose results are such a kernel's operands: the
kernel is a custom call, so XLA copies out of the stacked parameters the
layer's expert weights that it reads (`dynamic-slice_bitcast_fusion.*`,
named after the scan's slice), and those copies exist only to feed it.

The trace and its HLO are protobuf; the reduction reads the few fields it
needs by number (`_fields`).  The generated modules that would parse them
ship only inside the `tensorflow` package, whose import loads the
TensorFlow runtime (seconds, and its own claim on the device).

Decode program: the XLA module with the most device time among those that
ran once per decode call of the window (as `serve_step_mfu` picks it).
Only its executions inside the `bench.window` span count.  The traced run's
`.xplane.pb` is found under the cell's trace directory; its reduction is
kept in `ctx` so that each reader of a run loads it once.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Optional, Tuple

import bench_harness as H
import bench_peaks
import bench_trace

SCOPES = ("mla.attend", "moe.route", "moe.experts", "moe.shared")
KERNEL_SCOPE = {"ragged-dot": "moe.experts"}   # kernels that lose their scope
_SCOPE = re.compile(r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?:/|$)")
_INSTR = re.compile(r"^%?([\w.\-]+)")


def scope_of(name: str, op_name: Optional[str]) -> Optional[str]:
    """The scope of an op: from its framework name, else from the kernel's
    own name."""
    m = _SCOPE.search(op_name or "")
    if m:
        return m.group(1)
    for kernel, s in KERNEL_SCOPE.items():
        if kernel in name:
            return s
    return None


# ---------------------------------------------------------------------------
# the optimized HLO of one program, from the trace's metadata plane
# (protobuf wire format, read by field number: XSpace.planes 1; XPlane.name
# 2, .event_metadata 4 (map entry: key 1, value 2), .stat_metadata 5;
# XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .bytes_value 6;
# XStatMetadata.name 2; HloProto.hlo_module 1; HloModuleProto.computations
# 3; HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7, .id 35, .operand_ids 36; OpMetadata.op_name 2)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, span: Tuple[int, int]
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of the message at `span`; a
    length-delimited value is the (start, end) span of its bytes."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _sub(buf, span, num) -> List:
    return [v for n, v in _fields(buf, span) if n == num]


def _text(buf, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _ints(buf, span, num) -> List[int]:
    """A repeated integer field, packed or not."""
    out = []
    for n, v in _fields(buf, span):
        if n != num:
            continue
        if isinstance(v, int):
            out.append(v)
            continue
        i, end = v
        while i < end:
            x, i = _varint(buf, i)
            out.append(x)
    return out


Instr = Tuple[str, Optional[str], List[str]]   # name, op_name, operands


def program_instructions(xplane, module: str) -> List[Instr]:
    """(name, framework name or None, operand names) of each instruction of
    the optimized HLO module named `module` (as the trace's module events
    name it, e.g. `jit__lambda(12)`); empty where the trace does not hold
    it."""
    buf = open(xplane, "rb").read()
    for plane in _sub(buf, (0, len(buf)), 1):
        names = _sub(buf, plane, 2)
        if not names or _text(buf, names[0]) != "/host:metadata":
            continue
        stat_names = {}
        for entry in _sub(buf, plane, 5):
            key, val = _sub(buf, entry, 1), _sub(buf, entry, 2)
            if key and val:
                stat_names[key[0]] = "".join(_text(buf, s)
                                             for s in _sub(buf, val[0], 2))
        for entry in _sub(buf, plane, 4):
            for md in _sub(buf, entry, 2):
                name = _sub(buf, md, 2)
                if not name or _text(buf, name[0]) != module:
                    continue
                for stat in _sub(buf, md, 5):
                    sid, raw = _sub(buf, stat, 1), _sub(buf, stat, 6)
                    if sid and raw and stat_names.get(sid[0]) == "Hlo Proto":
                        return _instructions(buf, raw[0])
    return []


def _instructions(buf, hlo) -> List[Instr]:
    out = []
    for mod in _sub(buf, hlo, 1):
        for comp in _sub(buf, mod, 3):
            rows = []
            for ins in _sub(buf, comp, 2):
                name = _sub(buf, ins, 1)
                op = [o for md in _sub(buf, ins, 7) for o in _sub(buf, md, 2)]
                ids = _ints(buf, ins, 35)
                rows.append((_text(buf, name[0]) if name else "",
                             _text(buf, op[0]) if op else None,
                             ids[0] if ids else None, _ints(buf, ins, 36)))
            by_id = {i: n for n, _, i, _ in rows if i is not None}
            out += [(n, op, [by_id[o] for o in operands if o in by_id])
                    for n, op, _, operands in rows]
    return out


def instruction_scopes(instrs: List[Instr]) -> Dict[str, str]:
    """Instruction name -> scope, for the instructions that have one: by
    `scope_of`, and each unscoped operand of a kernel that `KERNEL_SCOPE`
    places takes that kernel's scope."""
    out = {}
    for name, op, _ in instrs:
        s = scope_of(name, op)
        if s is not None:
            out[name] = s
    for name, op, operands in instrs:
        if _SCOPE.search(op or "") or scope_of(name, None) is None:
            continue
        for o in operands:
            out.setdefault(o, out[name])
    return out


def _cell_name(ctx) -> Optional[str]:
    """The cell whose configuration and traffic the run's ctx holds."""
    spec = H.benchmark_spec()
    for cell in spec["workloads"]:
        if cell["config"] == ctx["config"]["name"]:
            try:
                if H.load_traffic(cell["traffic"]) == ctx["traffic"]:
                    return cell["name"]
            except H.BenchError:
                continue
    return None


def decode_program(ctx) -> Optional[Tuple[str, Optional[int]]]:
    t = ctx.get("trace")
    if not t or "decode_steps" not in ctx:
        return None
    progs = [p for p in t["programs"] if p["count"] == ctx["decode_steps"]]
    if not progs:
        return None
    p = max(progs, key=lambda p: p["device_s"])
    return p["name"], p["program_id"]


def reduce_scopes(xplane, program: Tuple[str, Optional[int]]
                  ) -> Dict[str, float]:
    """Seconds of device op time per scope inside the window's executions of
    `program`, summed over the device planes (one plane per chip)."""
    from jax.profiler import ProfileData
    module = f"{program[0]}({program[1]})"
    scopes = instruction_scopes(program_instructions(xplane, module))
    pd = ProfileData.from_file(str(xplane))
    window = None
    for plane in pd.planes:
        if bench_trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == bench_trace.WINDOW_SPAN and (
                        window is None or ev.duration_ns > window[1] - window[0]):
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
    if window is None:
        return {}
    out = {s: 0.0 for s in SCOPES}
    scope_by_name: Dict[str, Optional[str]] = {}
    for plane in pd.planes:
        if not bench_trace.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        runs: List[Tuple[float, float]] = []
        for ev in lines[bench_trace.MODULE_LINE].events \
                if bench_trace.MODULE_LINE in lines else ():
            base, pid = bench_trace.module_base(ev.name)
            stat = bench_trace._stat(ev, "program_id")
            pid = int(stat) if stat is not None else pid
            s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
            if (base, pid) == program and window[0] <= s and e <= window[1]:
                runs.append((s, e))
        runs.sort()
        starts = [s for s, _ in runs]
        for ev in lines[bench_trace.OP_LINE].events \
                if bench_trace.OP_LINE in lines else ():
            s = float(ev.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue
            if ev.name not in scope_by_name:
                m = _INSTR.match(ev.name)
                instr = m.group(1) if m else ev.name
                scope_by_name[ev.name] = scopes.get(instr,
                                                    scope_of(instr, None))
            scope = scope_by_name[ev.name]
            if scope is not None:
                out[scope] += ev.duration_ns / 1e9
    return out


def scope_seconds(ctx) -> Optional[Dict[str, float]]:
    """Device seconds per scope in the decode program, or None where the run
    has no trace, no decode program or no trace directory to read."""
    if "scope_s" in ctx:
        return ctx["scope_s"]
    program = decode_program(ctx)
    cell = _cell_name(ctx) if program is not None else None
    if cell is None:
        return None
    try:
        xplane = bench_trace.newest_xplane(H.TRACE_DIR / cell)
    except FileNotFoundError:
        return None
    ctx["scope_s"] = reduce_scopes(xplane, program)
    return ctx["scope_s"]


def roofline_share(ctx, device, scope: str, counts_of) -> Optional[float]:
    """% of the least time the chip needs for a scope's kernel over the
    window's decode steps (per step the larger of FLOPs over peak and bytes
    over bandwidth, from `counts_of(counts_module, config, positions)`),
    over that scope's device time.  None where nothing was read."""
    secs = scope_seconds(ctx)
    if not secs or secs.get(scope, 0.0) <= 0:
        return None
    pk = bench_peaks.peaks_for(device["kind"])
    counts = H.load_flops(H.benchmark_spec(), ctx["config"]["name"])
    need = 0.0
    for prompt, n in ctx["decode_positions"]:
        for i in range(n):
            c = counts_of(counts, ctx["config"], [prompt + i] * ctx["batch"])
            need += max(c["flops"] / pk["bf16_flops_per_s"],
                        c["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * need / secs[scope]
