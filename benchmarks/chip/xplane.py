"""From the profiler's trace of a window to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU the plane
``/device:TPU:<n>`` has a line ``XLA Modules``, one event per run of a
jitted program, and a line ``XLA Ops``, one event per operation, named by
its HLO instruction (``%name = <shape> <opcode>(...)``).  Device times are
on the device's clock, which the trace does not align with the host's to
better than about a millisecond, so the window is taken on the device: from
the start of the second traced run of the cell's pass (the first is held
up by the profiler's start) to the end of the last.  A loop's ``while``
op spans the ops of its body, which are events of their own, so it is
left out.

Which of the harness's named scopes made an operation (``gemm.<name>``,
``attn``, ``decode``, ``ssd``, ``kv_write``, ``state``, ``norm``,
``xla.<i>``) comes from the op's ``op_name`` in the compiled module's HLO
text; operations that the compiler added (layout copies, async slices)
have none and are labelled ``-``.  The time of a Covenant call (``covenant_matmul``,
``covenant_attention``, ``covenant_decode_attention``, ``covenant_ssd``) is
that of every op in its scope: the Pallas kernel and what the wrapper and
the compiler put around it (pads, repeats, transposes, operand copies).
A Pallas kernel is an instruction with ``custom_call_target=
"tpu_custom_call"``.  An op of the pass that is no Pallas kernel is the
program's glue where it lies in a Covenant call's scope (the ``ops.py``
wrappers) or has no name stack (what the compiler added around the calls);
any other is the harness's own (its KV and state writes, the SSM state
step, norms, splits, casts, gates, residual adds), which no change to the
program can move.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil

import counts

FAMILIES = ("gemm", "attn", "decode", "ssd")
SCOPES = FAMILIES + ("kv_write", "state", "norm", "xla")
CONTAINERS = ("while", "conditional", "call")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
INSTR = re.compile(r"^%([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?op_name="([^"]*)"',
                      re.M)
HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?\b([a-z][\w\-]*)\((.*)$")
OPERAND = re.compile(r"%([\w.\-]+)")
PALLAS = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Op:
    instr: str            # HLO instruction name
    opcode: str
    kernel: bool          # a Pallas kernel
    start: float          # seconds, device clock
    end: float
    device: int

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reading:
    """What a traced window holds, for the metric readers."""
    window_s: float               # first pass start to last pass end
    busy_s: float                 # union of device ops in the window
    passes: int
    call_s: dict                  # family -> device seconds of its calls
    glue_s: float                 # device seconds of the program's glue
    harness_s: float              # device seconds of the harness's own ops
    work: dict                    # family -> flops, bytes, roofline_s,
                                  # peak_s (flops over each call's peak)
    peak: dict
    xla_s: list                   # device seconds per distinct GEMM, all reps
    xla_reps: int                 # runs of the XLA GEMMs in the trace
    xla_mult: list                # how often the pass makes each
    ops_s: dict                   # "<scope>:<kernel or opcode>" -> seconds
    gaps: list                    # (where, seconds), longest first

    def breakdown(self) -> dict:
        top = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> op_name (the JAX name stack) of a compiled
    module's text.  A copy that the compiler made of an argument (named
    after the argument, outside the program's name stack) for one Covenant
    call alone, every user of it in that call's scope, takes the call's
    name: it lays out the call's operand, and is the program's glue."""
    names = dict(HLO_LINE.findall(hlo_text))
    users: dict[str, list[str]] = {}
    copies = []
    for line in hlo_text.splitlines():
        m = HLO_INSTR.match(line)
        if not m:
            continue
        instr, opcode, rest = m.groups()
        for operand in OPERAND.findall(rest.split("metadata=")[0]):
            users.setdefault(operand, []).append(instr)
        if opcode == "copy" and not names.get(instr, "jit(").startswith(
                "jit("):
            copies.append(instr)
    for c in copies:
        scopes = {scope(names.get(u, "")) for u in users.get(c, [])}
        if len(scopes) == 1 and family(next(iter(scopes))):
            names[c] = names[users[c][0]]
    return names


def newest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def events(path: str):
    """Device ops and module runs of one trace file: ([Op], [(module name,
    start, end, device)])."""
    from jax.profiler import ProfileData

    ops, modules = [], []
    parsed = {}                 # event name -> (instr, opcode, kernel)
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    name = ev.name
                    p = parsed.get(name)
                    if p is None:
                        im = INSTR.match(name)
                        instr, opcode = im.groups() if im else (name, "?")
                        p = parsed[name] = (instr, opcode, PALLAS in name)
                    if p[1] in CONTAINERS:
                        continue
                    ops.append(Op(*p, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                  dev))
            elif line.name == "XLA Modules":
                modules.extend((ev.name, ev.start_ns * 1e-9,
                                ev.end_ns * 1e-9, dev) for ev in line.events)
    return ops, modules


def union_s(intervals) -> float:
    """Length of the union of (start, end) intervals."""
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


def within(ops, spans):
    """The ops that start inside one of the (start, end) spans."""
    spans = sorted(spans)
    out, j = [], 0
    for o in sorted(ops, key=lambda o: o.start):
        while j < len(spans) and spans[j][1] < o.start:
            j += 1
        if j < len(spans) and spans[j][0] <= o.start <= spans[j][1]:
            out.append(o)
    return out


def scope(op_name: str) -> str:
    """The harness's named scope in a name stack, or ``-``."""
    for part in op_name.split("/"):
        if part.split(".")[0] in SCOPES:
            return part
    return "-"


def family(scope_name: str) -> str | None:
    """The Covenant call family of a harness scope, or None."""
    fam = scope_name.split(".")[0]
    return fam if fam in FAMILIES else None


def idle_gaps(ops, runs, names: dict) -> list:
    """Each gap in the device's activity inside the window: between two
    runs of the pass (the host syncing, looping and dispatching), or inside
    one, named by the operation that the device waited to start."""
    spans = sorted((s, e) for _, s, e, _ in runs)
    out = [("between passes", s1 - e0)
           for (_, e0), (s1, _) in zip(spans, spans[1:]) if s1 > e0]
    ops = sorted(ops, key=lambda o: o.start)
    j = 0
    for s, e in spans:
        t = s
        while j < len(ops) and ops[j].start <= e:
            o = ops[j]
            if o.start > t:
                out.append((f"in pass, before {label(o, names)}",
                            o.start - t))
            t = max(t, o.end)
            j += 1
        if e > t:
            out.append(("in pass, at its end", e - t))
    return sorted(out, key=lambda g: -g[1])


def label(o: Op, names: dict) -> str:
    """``<scope>:<kernel>`` for a Pallas kernel, ``<scope>:<opcode>`` for
    any other op; the scope is ``harness`` for an op of the harness outside
    its named scopes, and ``-`` for one with no name stack."""
    what = o.instr.rsplit(".", 1)[0] if o.kernel else o.opcode
    op_name = names.get(o.instr, "")
    where = scope(op_name)
    if where == "-" and op_name:
        where = "harness"
    return f"{where}:{what}"


def read(trace_dir: str, *, work: list, passes: int, peak: dict,
         names: dict, xla_mult: list,
         pass_module: str = "jit_cell_pass",
         xla_module: str = "jit_xla_gemms", keep: bool = False) -> Reading:
    """Reduce the newest trace under ``trace_dir``, which holds 1 +
    ``passes`` runs of the pass; ``work`` is every ``common.Call`` of the
    last ``passes`` of them; ``names`` maps each module
    (``pass_module``, ``xla_module``) to ``op_names`` of its HLO text.  The
    trace is deleted after, unless ``keep``."""
    path = newest_xplane(trace_dir)
    try:
        ops, modules = events(path)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    runs = [m for m in modules if m[0].startswith(pass_module + "(")]
    xruns = [m for m in modules if m[0].startswith(xla_module + "(")]
    devices = sorted({m[3] for m in runs})
    if not devices or len(runs) != (passes + 1) * len(devices):
        raise RuntimeError(f"{len(runs)} runs of {pass_module} in the trace "
                           f"for 1 + {passes} passes on {len(devices)} "
                           f"devices")
    first = {d: min(m[1] for m in runs if m[3] == d) for d in devices}
    runs = [m for m in runs if m[1] > first[m[3]]]
    dev0 = [m for m in runs if m[3] == devices[0]]
    w0, w1 = min(m[1] for m in dev0), max(m[2] for m in dev0)
    win = within(ops, [(s, e) for _, s, e, _ in runs])
    busy = sum(union_s([(o.start, o.end) for o in win if o.device == d])
               for d in devices) / len(devices)

    call_s: dict[str, float] = {}
    glue_s = harness_s = 0.0
    ops_s: dict[str, float] = {}
    pnames = names.get(pass_module, {})
    seen = {}                   # instr -> (family, glue, harness, label)
    for o in win:
        a = seen.get(o.instr)
        if a is None:
            op_name = pnames.get(o.instr, "")
            fam = family(scope(op_name))
            a = seen[o.instr] = (
                fam, not o.kernel and bool(fam or not op_name),
                not o.kernel and bool(op_name) and not fam, label(o, pnames))
        fam, glue, harness, lab = a
        if fam:
            call_s[fam] = call_s.get(fam, 0.0) + o.dur
        if glue:
            glue_s += o.dur
        elif harness:
            harness_s += o.dur
        ops_s[lab] = ops_s.get(lab, 0.0) + o.dur

    xla_s = [0.0] * len(xla_mult)
    xnames = names.get(xla_module, {})
    for o in within(ops, [(s, e) for _, s, e, _ in xruns]):
        m = re.match(r"xla\.(\d+)$", scope(xnames.get(o.instr, "")))
        if m:
            xla_s[int(m.group(1))] += o.dur

    agg: dict[str, dict] = {}
    for c in work:
        a = agg.setdefault(c.family, {"flops": 0.0, "bytes": 0.0,
                                      "roofline_s": 0.0, "peak_s": 0.0})
        a["flops"] += c.flops
        a["bytes"] += c.bytes
        a["roofline_s"] += counts.roofline_s(c, peak)
        a["peak_s"] += c.flops / peak[c.peak]

    return Reading(window_s=w1 - w0, busy_s=busy, passes=passes,
                   call_s=call_s, glue_s=glue_s, harness_s=harness_s,
                   work=agg, peak=peak,
                   xla_s=xla_s, xla_reps=len(xruns) // len(devices),
                   xla_mult=list(xla_mult), ops_s=ops_s,
                   gaps=idle_gaps([o for o in win if o.device == devices[0]],
                                  dev0, pnames))
