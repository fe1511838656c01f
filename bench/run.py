"""Benchmark harness: one cell of ``BENCHMARK.json``, run once.

    python3 bench/run.py --workload hpl16k.decay --seed 7 \
        --seconds 30 --trace 0

A cell names a configuration (``configs/<name>.json``) and a traffic
mix (``traffic/<name>.json``); its metrics are readers in
``metrics/<name>.py`` and its correctness limits are in
``checks/<cell>.json``.  Everything is found by name, so a cell, a
mix, a configuration or a metric is added as files plus entries.

A run:

1. refuses to start unless JAX sees the accelerator and as many chips
   as the cell asks for;
2. set-up: builds the fabric and runs the first passes of the cell's
   own traffic, one per cycle position of the mix (every message size
   or loss level), which compiles or loads every solver shape and
   fills the staging cache the traffic needs;
3. the window: closed-loop sweep passes, each a fresh
   ``make_engine("flow", topo).run_workloads(...)`` over the run's one
   fabric, until the passes' seconds reach ``--seconds``; the window
   closes at the end of the pass that crosses it.  The generator
   builds each pass's ops before its clock starts;
4. with ``--trace 1``, about ``TRACE_SECONDS`` of further passes
   under the profiler for the device numbers; the end-to-end metrics
   come from runs with ``--trace 0``;
5. compares a sample of the window's passes, drawn from the seed as
   they come (only the sampled passes' records are kept), every
   message size or loss level among them, against the plain reference
   (``reference.py``) and prints each compared number beside its
   limit, last on standard error and last in the result line.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` [, ``breakdown``],
``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the script's own directory would shadow the standard library's
# ``trace``; the harness's modules import as the ``bench`` package
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path[0] = ROOT
elif ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, gen, reference, roofline, sut, trace  # noqa: E402

#: seconds of passes under the profiler in a ``--trace 1`` run
TRACE_SECONDS = 2.0
#: passes of the window compared against the reference
CHECK_PASSES = 8


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r}")


def require_chip(chips: int) -> dict:
    """The device JAX runs on; exit non-zero on the CPU or with fewer
    chips than the cell asks for."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] == "cpu":
        raise SystemExit("bench: JAX found no accelerator (platform cpu)")
    if info["count"] < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"found {info['count']}")
    return info


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside
    the checkout, so only the first run of a cell there compiles."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # no eviction: an entry written without its access-time file made
    # every later write fail on the chip's machine
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts executables compiled, or loaded from the persistent
    cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        compile_event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **_):
            if event == compile_event:
                self.n += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def metric_names(bench: dict, cell: str, trace_on: bool) -> list:
    """The cell's end-to-end metrics (``--trace 0``) or per-layer
    metrics (``--trace 1``)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace_on:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def read_metrics(metrics: list, ctx: dict) -> dict:
    out = {}
    for m in metrics:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _traffic(config, mix, seed, index):
    tr = gen.pass_traffic(config, mix, seed, index)
    return tr, sut.workloads(tr)


def run_cell(config: dict, mix: dict, limits: dict, seed: int,
             seconds: float, trace_on: bool, metrics: list,
             device: dict, t_start: float = T_START) -> dict:
    """One run of a cell after the chip check; the result object."""
    import jax
    use_compile_cache()
    compiles = CompileCounter()
    topo = sut.build_fabric(config["fabric"])

    # ---- set-up: one pass per cycle position of the mix
    n_warm = gen.cycle_len(mix)
    for i in range(n_warm):
        tr, wls = _traffic(config, mix, seed, i)
        sut.run_pass(topo, wls, tr["loss_rate"])
    setup_s = time.perf_counter() - t_start

    # ---- the window
    sut.reset_solve_stats()
    cache0 = sut.staging_counts(topo)
    c0 = compiles.n
    picked = check.Sample(seed, n_warm, CHECK_PASSES)
    pass_s, pass_ops = [], []
    failed = 0
    index = n_warm
    while sum(pass_s) < seconds:
        tr, wls = _traffic(config, mix, seed, index)
        t0 = time.perf_counter()
        recs, _ = sut.run_pass(topo, wls, tr["loss_rate"])
        pass_s.append(time.perf_counter() - t0)
        pass_ops.append(sum(len(r) for r in recs))
        failed += sum(1 for s in recs for r in s
                      if r.error or r.t_sender_cqe < 0.0)
        picked.offer(index % n_warm, (tr, recs))
        index += 1
    n_compiles = compiles.n - c0
    solve_s = sut.solve_stats()["solve_s"]
    cache1 = sut.staging_counts(topo)
    mem = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        mem.get("peak_bytes_in_use", 0)))
    ctx = {"setup_s": setup_s, "pass_s": pass_s, "pass_ops": pass_ops,
           "window_s": sum(pass_s), "solve_s": solve_s,
           "staging_hits": cache1[0] - cache0[0],
           "staging_misses": cache1[1] - cache0[1],
           "compiles": n_compiles}

    result_extra = {}
    ref = reference.Reference(config)
    if trace_on:
        # the traced passes' ops are built before the profiler starts,
        # so the traced window, like the timed one, is passes only
        n_trace = max(1, math.ceil(
            TRACE_SECONDS / sorted(pass_s)[len(pass_s) // 2]))
        traced = [_traffic(config, mix, seed, index + k)
                  for k in range(n_trace)]
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            t0 = time.perf_counter()
            for tr, wls in traced:
                with jax.profiler.TraceAnnotation("bench.pass"):
                    sut.run_pass(topo, wls, tr["loss_rate"])
            trace_window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            planes = trace.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        traced = [tr for tr, _ in traced]
        work = [ref.run_pass(tr)[1] for tr in traced]
        ctx.update(planes=planes, trace_window_s=trace_window_s,
                   trace_passes=len(traced), trace_work=work,
                   n_links=ref.n_links, peaks=roofline.peaks(device["kind"]))
        devs = trace.device_planes(planes)
        busy = (sum(trace.busy_ns(p) for p in devs) / len(devs) * 1e-9
                if devs else 0.0)
        device.update(busy_s=busy, window_s=trace_window_s)
        result_extra["breakdown"] = {"device_ops": trace.top_ops(planes),
                                     "idle_gaps": trace.idle_gaps(planes)}

    # ---- correctness: the seeded sample of the window's passes
    gap = 0.0
    n_checked = 0
    for tr, recs in picked.items():
        want, _ = ref.run_pass(tr)
        gap = max(gap, check.max_rel_gap(sut.answers(recs), want))
        n_checked += sum(len(s) for s in want)
    lim = limits["max_rel_gap"]["limit"]
    correct = failed == 0 and gap <= lim
    checks = {"max_rel_gap": {"value": gap if math.isfinite(gap)
                              else "inf", "limit": lim,
                              "passes": len(picked.items()),
                              "ops": n_checked}}
    return dict({"correct": correct, "attempted": sum(pass_ops),
                 "failed": failed,
                 "metrics": read_metrics(metrics, ctx),
                 "device": device}, **result_extra, checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cell = find(bench["workloads"], args.workload, "workload")
    cfg = find(bench["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    mix = gen.load_json("traffic", cell["traffic"])
    limits = check.limits(cell["name"])
    sut.import_program()
    device = require_chip(cell["chips"])
    result = run_cell(config, mix, limits, args.seed, args.seconds,
                      bool(args.trace),
                      metric_names(bench, cell["name"], bool(args.trace)),
                      device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({c['passes']} passes, {c['ops']} ops compared)",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
