#!/usr/bin/env python3
"""Time one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<mix>.json``); their ``family`` and ``phase`` name the pass kind
(``passes/<family>_<phase>.py``), which chains the program's Covenant
kernels for one step of the cell's layers.  The run draws weights, state
and inputs from the seed on the device, compiles and warms the pass (the
set-up), then runs it back to back for ``--seconds``: a few seconds of
passes are dispatched ahead of the one waited for, so that the chip stays
fed while the host stands still, and the window ends when every pass sent
has finished.  With ``--trace 0`` it reports the cell's end-to-end
metrics; with ``--trace 1`` it traces the window with the profiler and
reports the per-layer metrics, each read by ``metrics/<name>.py``.  Either
way it then checks the checked passes against the plain f32 reference and
prints each compared number beside its limit (``limits/<cell>.json``).

The last line of standard output is one JSON object.  With no TPU, or
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu logs to a fixed /tmp path unless told otherwise; a run writes only
# inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import common  # noqa: E402
import counts  # noqa: E402
import passes  # noqa: E402
import xplane  # noqa: E402
import traffic as T  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the traced window: at most this long, so that the trace stays small
TRACE_SECONDS = 3.0
# passes after the first (which compiles) before the window opens
WARM_PASSES = 2
# seconds of passes in flight ahead of the one waited for, at most half
# the window
AHEAD_SECONDS = 4.0
# repetitions of XLA's own GEMMs in a traced run
XLA_REPS = 10
# the listener event that marks a program compiled or loaded from the cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell's entry, its configuration and its traffic mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, load_json(ROOT, cfg_entry["file"]), T.load(cell["traffic"])


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end ones, or with a trace
    its per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def require_devices(chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"run.py: JAX finds no TPU (platform "
                         f"{d.platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every program however quick to compile."""
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == COMPILE_EVENT:
            self.n += 1


def xla_operand(key, shape, dtype: str):
    if dtype == "int8":
        return common.int8(key, shape)
    return common.normal(key, shape)


def make_xla_gemms(shapes: list, key) -> tuple:
    """XLA's own lowering of the pass's GEMMs: one ``jnp.dot`` per distinct
    shape, each in a named scope ``xla.<i>``, and how often the pass makes
    each.  A shape is (m, n, k, dtype, batch), as ``counts.gemm`` takes
    it: bf16 in and f32 out, or int8 in and int32 out, and ``batch`` GEMMs
    with operands of their own, as one batched dot."""
    distinct = sorted(set(shapes), key=shapes.index)
    mult = [shapes.count(s) for s in distinct]
    operands = []
    for i, (m, n, k, dtype, batch) in enumerate(distinct):
        lead = (batch,) if batch > 1 else ()
        operands.append(
            (xla_operand(common.subkey(key, "xa", i), (*lead, m, k), dtype),
             xla_operand(common.subkey(key, "xb", i), (*lead, k, n), dtype)))

    @jax.jit
    def xla_gemms(operands):
        outs = []
        for i, (a, b) in enumerate(operands):
            acc = jnp.int32 if a.dtype == jnp.int8 else jnp.float32
            with jax.named_scope(f"xla.{i}"):
                outs.append(jnp.matmul(a, b, preferred_element_type=acc)
                            if a.ndim == 3 else
                            jnp.dot(a, b, preferred_element_type=acc))
        return outs

    return xla_gemms, operands, mult


def in_flight(pass_s: float, window_s: float) -> int:
    """How many passes to keep dispatched ahead of the one waited for:
    ``AHEAD_SECONDS`` of them, at most half the window, at least one."""
    ahead = min(AHEAD_SECONDS, window_s / 2)
    return max(1, round(ahead / max(pass_s, 1e-6)))


def drive(step, p, state, first: int, until: float, pass_s: float,
          depth: int, at_least: int = 1):
    """Run passes first, first+1, ... with up to ``depth`` of them in
    flight ahead of the one waited for.  Dispatch stops once the passes
    still running (``pass_s`` each) would end near ``until`` (and
    ``at_least`` were sent); then every pass sent is waited for.  Return
    the state, the last pass's taps, and the passes run."""
    i = first
    pool = p.inputs
    pending = collections.deque()
    while True:
        state, taps, done = step(p.params, state, pool[i % len(pool)])
        pending.append(done)
        i += 1
        if len(pending) > depth:
            pending.popleft().block_until_ready()
        # the runtime may hold fewer passes in flight than were sent ahead
        while pending and pending[0].is_ready():
            pending.popleft()
        now = time.perf_counter()
        if now + len(pending) * pass_s >= until and i - first >= at_least:
            break
    jax.block_until_ready((state, taps))
    return state, taps, i - first


def log(t_start: float, what: str) -> None:
    print(f"[{time.perf_counter() - t_start:8.3f} s] {what}", file=sys.stderr,
          flush=True)


def delete(tree) -> None:
    for a in jax.tree.leaves(tree):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()


def check(p, got: dict, passes_checked: list, limits: dict | None,
          low=None) -> dict:
    """Each tap's widest gap against the reference (or, with ``low``, the
    control's), the largest over the checked passes, beside its limit."""
    with jax.default_matmul_precision("highest"):
        want = p.reference(passes_checked, None)
        if low is not None:
            got = p.reference(passes_checked, low)
    worst: dict[str, float] = {}
    for name, e in common.errors(got, want).items():
        base = name.split("@")[0]
        prev = worst.get(base, -1.0)
        worst[base] = e if (e != e or prev != prev) else max(prev, e)
    if limits is not None and set(limits) != set(worst):
        raise RuntimeError(f"limits for {sorted(limits)}, readings for "
                           f"{sorted(worst)}")
    return {t: {"value": worst[t],
                "limit": None if limits is None else limits[t]}
            for t in sorted(worst)}


def run(cfg: dict, traffic: dict, *, seed: int, seconds: float, trace: bool,
        limits: dict | None, metrics: list[dict], device: dict,
        interpret: bool = False, t_start: float = T_START) -> dict:
    """One run of a cell, from the drawing of its weights to the check.
    With ``limits`` None the readings are reported and ``correct`` is None
    (for setting the limits)."""
    kind = f"{cfg['family']}_{traffic['phase']}"
    key = common.root_key(seed)
    log(t_start, f"devices ready; building {kind}")
    p = passes.load(kind).build(cfg, traffic, seed, key, interpret=interpret)
    jax.block_until_ready((p.params, p.state, p.inputs))
    log(t_start, "weights, state and inputs drawn")

    def cell_pass(params, state, x):
        state, taps = p.body(params, state, x)
        # one element of the output, to wait on without holding the pass's
        # outputs (its state is donated to the next)
        return state, taps, taps["out"].reshape(-1)[:1]

    step = jax.jit(cell_pass, donate_argnums=(1,))
    compiles = CompileCounter()

    # set-up: the leading checked passes (a pass with state is checked from
    # its start), then warm passes; the first pass compiles
    got, state = {}, p.state
    n_lead = max(p.check_first, 1)
    warm = []
    for i in range(n_lead + WARM_PASSES):
        t = time.perf_counter()
        state, taps, _ = step(p.params, state, p.inputs[i % len(p.inputs)])
        jax.block_until_ready((state, taps))
        if i >= n_lead:
            warm.append(time.perf_counter() - t)
        if i == 0:
            log(t_start, "first pass done (compiled or loaded)")
        if i < p.check_first:
            got.update({f"{t}@{i}": v for t, v in taps.items()})
            if i == p.check_first - 1 and p.inspect is not None:
                got.update(jax.block_until_ready(p.inspect(state, i)))
    done = n_lead + WARM_PASSES
    pass_s = min(warm)
    window_s = min(seconds, TRACE_SECONDS) if trace else seconds
    depth = in_flight(pass_s, window_s)
    xla = names = None
    if trace:
        xla = make_xla_gemms(p.xla_gemms, key)
        jax.block_until_ready(xla[0](xla[1]))
        x0 = p.inputs[0]
        names = {
            "jit_cell_pass": xplane.op_names(
                step.lower(p.params, state, x0).compile().as_text()),
            "jit_xla_gemms": xplane.op_names(
                xla[0].lower(xla[1]).compile().as_text())}
    setup_s = time.perf_counter() - t_start
    log(t_start, f"set-up done; window opens, {depth} passes of "
        f"{1e3 * pass_s:.1f} ms in flight")

    compiles.n = 0
    if trace:
        # device ops only: no Python or host-library tracing, which would
        # slow the host between passes
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        # the first traced pass is held up by the profiler's start and is
        # left out of the reading
        t0 = time.perf_counter()
        state, taps, n = drive(step, p, state, done, t0 + window_s, pass_s,
                               depth, 3)
        for _ in range(XLA_REPS):
            jax.block_until_ready(xla[0](xla[1]))
        jax.profiler.stop_trace()
    else:
        t0 = time.perf_counter()
        state, taps, n = drive(step, p, state, done, t0 + window_s, pass_s,
                               depth)
        t1 = time.perf_counter()
    in_window = compiles.n
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the window")
    last = done + n - 1
    if not p.check_first:
        got.update({f"{t}@{last}": v for t, v in taps.items()})
        if p.inspect is not None:
            got.update(jax.block_until_ready(p.inspect(state, last)))

    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(stats.get(
        "peak_bytes_in_use", 0)))
    # free the program's state before the reference runs
    delete((state, p.params, p.inputs, xla and xla[1]))
    log(t_start, f"window closed after {n} passes")
    result = {"correct": None, "attempted": n, "failed": 0}
    if trace:
        work = [c for i in range(done + 1, done + n) for c in p.calls(i)]
        reading = xplane.read(TRACE_DIR, work=work, passes=n - 1,
                              peak=counts.peaks(device["kind"]), names=names,
                              xla_mult=xla[2])
        values = {}
        for m in metrics:
            v = importlib.import_module(f"metrics.{m['name']}").read(reading)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reading.busy_s, window_s=reading.window_s)
        result["breakdown"] = reading.breakdown()
    else:
        measured = {"step_ms": 1e3 * (t1 - t0) / n, "setup_s": setup_s}
        unknown = [m["name"] for m in metrics if m["name"] not in measured]
        if unknown:
            raise RuntimeError(f"no end-to-end metric {unknown} in run.py")
        values = {m["name"]: {"value": measured[m["name"]], "unit": m["unit"]}
                  for m in metrics}
    result.update(metrics=values, device=device)

    passes_checked = list(range(p.check_first)) or [last]
    checks = check(p, got, passes_checked, limits)
    log(t_start, "checked against the reference")
    delete(got)
    if limits is not None:
        failed = [t for t, c in checks.items()
                  if not c["value"] <= c["limit"]]         # NaN fails
        result.update(correct=not failed, failed=len(failed))
    result["checks"] = checks
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg, traffic = cell_spec(bench, args.workload)
    device = require_devices(cell["chips"])
    enable_compile_cache()
    limits = load_json(HERE, "limits", f"{args.workload}.json")["limits"]
    result = run(cfg, traffic, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), limits=limits,
                 metrics=cell_metrics(bench, args.workload, bool(args.trace)),
                 device=device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
