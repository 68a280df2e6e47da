"""One run of one benchmark cell: set-up, measured window, check, report.

Everything a cell is made of is found by name from ``BENCHMARK.json``:

* its configuration: ``bench/configs/<config>.json`` (sizes, query, service
  settings, guarantees) and ``bench/configs/<config>.py`` (``generate(cfg,
  seed)`` -> plain tables);
* its reference: ``reference(tables, cfg)`` in that ``.py`` where it gives
  one, else message passing over the query's join tree
  (``bench/reference.py``);
* its traffic mix: ``bench/traffic/<traffic>.json``, a closed loop whose
  request is a list of steps, each an operation ``bench/ops/<op>.py``
  (``run(ctx, step, rec)``, and how its output is checked: see
  ``bench/check.py``) or a ``choose`` among alternatives, taken in equal
  shares in an order drawn from the seed;
* its metrics: ``bench/metrics/<metric>.py`` (``read(run)`` -> a number or
  None), the end-to-end ones with ``--trace 0`` and the per-layer ones with
  ``--trace 1``.

So a later cell, configuration, mix or metric is a new file and a new
entry; nothing here changes.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench.check import compare, limits
from bench.reference import JoinTree

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_of(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                   f"{[c['name'] for c in bench['workloads']]})")


def metrics_of(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The cell's end-to-end (untraced) or per-layer (traced) metrics."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell in m["workloads"]]


def load_config(name: str, overrides: Optional[dict] = None):
    with open(HERE / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(overrides or {})
    return cfg, load_module(HERE / "configs" / f"{name}.py")


def load_op(name: str):
    """The operation ``bench/ops/<name>.py``."""
    return load_module(HERE / "ops" / f"{name}.py")


def reference_for(cfg: dict, gen, tables):
    """The configuration's own reference where its module gives one, else
    message passing over its query's join tree."""
    if hasattr(gen, "reference"):
        return gen.reference(tables, cfg)
    return JoinTree(tables, cfg["query"])


@dataclass
class Context:
    """What the operations of a request see and leave behind."""

    cfg: dict
    tables: dict
    catalog: Any
    query: Any
    service: Any
    server: Any
    rng: np.random.Generator
    reply: Any = None
    kept: Dict[str, Any] = field(default_factory=dict)   # op -> to check

    def role(self, name: str) -> str:
        return self.cfg["roles"][name]

    def answer(self, rec: dict, op: str, params: dict,
               fn: Callable[[Any], Any]) -> None:
        """Time one SummaryFrame call on the last frame and keep its answer."""
        t0 = time.perf_counter()
        out = fn(self.reply.frame)
        rec["algebra_s"] = rec.get("algebra_s", 0.0) + time.perf_counter() - t0
        rec.setdefault("answers", []).append((op, params, out))


class Traffic:
    """A closed loop over a mix's request template."""

    def __init__(self, mix: dict, rng: np.random.Generator) -> None:
        if mix.get("loop") != "closed" or mix.get("clients") != 1:
            raise ValueError("only a closed loop with one client is defined")
        self.mix = mix
        self.rng = rng
        self.ops: Dict[str, Any] = {}        # the operations used, by name
        self._bags: Dict[int, List[int]] = {}

    def op(self, name: str):
        if name not in self.ops:
            self.ops[name] = load_op(name)
        return self.ops[name]

    def _pick(self, i: int, k: int) -> int:
        """Alternatives in equal shares: each block of k is a shuffle."""
        bag = self._bags.setdefault(i, [])
        if not bag:
            bag.extend(self.rng.permutation(k).tolist())
        return bag.pop()

    def steps(self, pick: Optional[int] = None) -> List[dict]:
        out: List[dict] = []
        for i, step in enumerate(self.mix["request"]):
            if "choose" in step:
                alts = step["choose"]
                out.extend(alts[self._pick(i, len(alts)) if pick is None
                                else pick % len(alts)])
            else:
                out.append(step)
        return out

    def warm_requests(self) -> List[List[dict]]:
        """One request of each kind the mix sends."""
        kinds = max([len(s["choose"]) for s in self.mix["request"]
                     if "choose" in s] or [1])
        return [self.steps(pick=k) for k in range(kinds)]

    def run(self, ctx: Context, steps: List[dict]) -> dict:
        from repro.obs.trace import span
        rec: dict = {"steps": steps, "t0": time.perf_counter()}
        try:
            for step in steps:
                with span(f"bench:{step['op']}", cat="bench"):
                    self.op(step["op"]).run(ctx, step, rec)
        except Exception as e:       # a failed request is counted, not fatal
            rec["error"] = f"{type(e).__name__}: {e}"
            traceback.print_exc(file=sys.stderr)
        rec["t1"] = time.perf_counter()
        return rec


@dataclass
class Run:
    """What a metric reader reads."""

    cell: dict
    records: List[dict]
    setup_s: float
    summary_levels: List[dict] = field(default_factory=list)
    trace: Any = None
    peaks: Optional[dict] = None


class CompileCounter:
    """Programs lowered (traced for a new shape) while ``on`` is set."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, jax) -> None:
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event == self.EVENT:
            self.count += 1


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def require_chips(jax, chips: int) -> None:
    from repro import device
    if device.platform() != "tpu":
        raise NoAccelerator(f"JAX runs on {device.platform()!r}, not a TPU")
    if len(jax.devices()) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX has "
                            f"{len(jax.devices())}")


def summary_levels(gfjs) -> List[dict]:
    return [{"vars": list(lvl.vars), "runs": int(len(lvl.freq))}
            for lvl in gfjs.levels] if gfjs is not None else []


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             started: float, require_tpu: bool = True,
             overrides: Optional[dict] = None,
             trace_dir: Optional[str] = None,
             control: bool = False) -> dict:
    """One run of cell ``name``; returns the result line's object.

    ``started`` is the process's start on the perf_counter clock (set-up
    is counted from it).  ``overrides`` replaces configuration sizes (tests
    run tiny ones); ``control`` answers the checks with the reference's own
    lower-precision answers in place of the program's (the control run).
    """
    import jax
    bench = manifest()
    cell = cell_of(bench, name)
    if require_tpu:
        require_chips(jax, cell["chips"])
    compiles = CompileCounter(jax)

    from repro import device
    from repro.obs.trace import Tracer
    from repro.relational.query import JoinQuery
    from repro.relational.table import Catalog, Table
    from repro.serve.server import JoinServer
    from repro.summary.service import JoinService

    cfg, gen = load_config(cell["config"], overrides)
    tables = gen.generate(cfg, seed)
    catalog = Catalog.of(*(Table(t, cols) for t, cols in tables.items()))
    query = JoinQuery.of(cfg["query"]["name"],
                         [(t, m) for t, m in cfg["query"]["tables"]])
    service = JoinService(catalog, **cfg["service"])
    rng = np.random.default_rng([seed, 0x7A1])
    ctx = Context(cfg, tables, catalog, query, service, JoinServer(service),
                  rng)
    with open(HERE / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = Traffic(json.load(f), rng)

    # -- set-up: the mix's own set-up steps, then each request kind warmed
    for steps in [traffic.mix.get("setup", [])] + traffic.warm_requests():
        warm = traffic.run(ctx, list(steps))
        if warm.get("error"):
            raise RuntimeError(f"set-up request failed: {warm['error']}")
    ctx.kept.clear()
    fallbacks0 = sum(device.host_fallbacks().values())

    # -- the measured window ------------------------------------------------
    tracer = Tracer() if trace else None
    tmp = None
    if trace:
        tmp = tempfile.TemporaryDirectory() if trace_dir is None else None
        trace_dir = tmp.name if tmp is not None else trace_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call: the
        opts.host_tracer_level = 2        # annotations stay, the trace small
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    root = (tracer.span("bench:window", cat="bench", device=True)
            if tracer else contextlib.nullcontext())
    records: List[dict] = []
    compiles.on = True
    t_start = time.perf_counter()
    setup_s = t_start - started
    with root as root_span:
        deadline = t_start + seconds
        while time.perf_counter() < deadline:
            records.append(traffic.run(ctx, traffic.steps()))
    t_end = time.perf_counter()
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    dev = device_info(jax)
    fallbacks = sum(device.host_fallbacks().values()) - fallbacks0
    print(f"requests {len(records)} in "
          f"{records[-1]['t1'] - records[0]['t0']!r} s" if records
          else "requests 0")
    print(f"host fallbacks in window: {fallbacks} "
          f"{device.host_fallbacks()}")
    print(f"compilations in window: {compiles.count}")
    print(f"setup_s {setup_s!r}")

    run = Run(cell, records, setup_s, summary_levels(
        ctx.reply.frame.gfjs if ctx.reply is not None else None))
    if trace:
        from bench.trace_reduce import find_xplane, reduce_trace
        spans = [(s.name, s.t0, s.t1) for s in tracer.spans]
        run.trace = reduce_trace(find_xplane(trace_dir), anchor="bench:window",
                                 anchor_t0=root_span.t0, spans=spans)
        if tmp is not None:
            tmp.cleanup()
        with open(HERE / "peaks.json") as f:
            kinds = json.load(f)["kinds"]
        if require_tpu and dev["kind"] not in kinds:
            raise KeyError(f"no peaks for device kind {dev['kind']!r} in "
                           f"bench/peaks.json")
        run.peaks = kinds.get(dev["kind"])

    metrics = {}
    for m in metrics_of(bench, name, trace):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # -- the check, once the program's state is released --------------------
    kept, ops = ctx.kept, traffic.ops
    del ctx, service, traffic, catalog
    gc.collect()
    t_check = time.perf_counter()
    salt = (seed * 0x9E3779B1 + 1) & 0xFFFFFFFFFFFFFFFF
    gaps = compare(records, ops, reference_for(cfg, gen, tables), salt, kept,
                   control=control)
    print(f"check_s {time.perf_counter() - t_check!r} (began "
          f"{t_check - t_end!r} s after the window)", flush=True)
    limit = limits(ops)
    checks = {k: {"value": v, "limit": limit[k]} for k, v in gaps.items()}
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    if trace:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(records),
        "failed": gaps["failed_requests"],
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        out["breakdown"] = {"device_ops": run.trace.top_programs(10),
                            "idle_gaps": run.trace.gaps[:10]}
    out["checks"] = checks
    return out


def main(args, started: float) -> int:
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), started=started,
                       trace_dir=args.trace_dir)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
