#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell's configuration (``bench/configs/<config>.json``, the simulated
system) and traffic mix (``bench/traffic/<mix>.json``, the job mix); the
mix names its driver (``bench/drivers/<driver>.py``, the calls of the
window); each per-layer metric is read by ``bench/metrics/<metric>.py``.

A run: check the device (a TPU of a kind in ``bench/peaks.json``, as many
chips as the cell asks for; anything else exits non-zero before any
result), turn on JAX's persistent compilation cache where the program
keeps it, let the driver build its inputs and load every program
(``setup_s``, from process start to the first timed call), run calls
for ``--seconds`` (the one running then finishes and counts), then check
what every call produced against the reference in ``bench/reference``.
With ``--trace 1`` the window runs under the JAX profiler, inside one
annotation, with the program's host spans on; the per-layer metrics are
read from that trace and those spans, and the reduction is written to
``bench_out/``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace
1``, ``breakdown``), and last ``checks``, each compared number with its
limit, the count of failed members among them. The same numbers end
standard error.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
ANNOTATION = "bench.window"


def fail(msg: str) -> None:
    sys.exit(f"bench: {msg}")


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        fail(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    if not os.path.isfile(path):
        fail(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str, root: str = ROOT):
    """The cell's entry, configuration, traffic mix, and the end-to-end
    and per-layer metrics it reports, all found by name under ``root``."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, cfg["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    traffic.setdefault("name", cell["traffic"])

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if mine(m) and m["moves"] in reported]
    return cell, config, traffic, e2e, layer


def check_device(chips: int):
    """The device as JAX reports it, and its peaks; exits without a TPU
    of a known kind, or with fewer chips than the cell needs."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        fail(f"no TPU (platform {d0.platform!r}); the benchmark has no "
             "other path")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    if d0.device_kind not in peaks:
        fail(f"device kind {d0.device_kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips], peaks[d0.device_kind]


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class CompileCount:
    """Programs compiled or loaded from the cache while it is on."""

    def __init__(self):
        import jax.monitoring as mon

        self.n, self.on = 0, False

        def listen(event, duration, **kw):
            if self.on and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        mon.register_event_duration_secs_listener(listen)


def traced_window(driver, seconds, rates):
    """The window under the profiler and the program's host spans:
    ``(calls, reduction, host spans, offset of the host clock)``."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    import reduce as RD
    from repro.obs import spans

    tracer = spans.get_tracer()
    spans.enable()
    ev0 = tracer.n_events
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with jax.profiler.trace(tmp):
            with TraceAnnotation(ANNOTATION):
                t_host = time.perf_counter_ns()
                calls = rates.run_window(driver.call, seconds)
        spans.disable()
        path = sorted(glob.glob(os.path.join(
            tmp, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        pd = ProfileData.from_file(path)
        window = RD.annotated_window(pd, ANNOTATION)
        red = RD.reduce_events(RD.line_events(pd), window)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    host = [(e["name"], tracer.origin_ns + e["ts_us"] * 1e3,
             tracer.origin_ns + (e["ts_us"] + e["dur_us"]) * 1e3)
            for e in tracer.events[ev0:] if e.get("ph") != "C"]
    return calls, red, host, window[0] - t_host


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, traffic, e2e, layer = cell_spec(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the program (src/repro) is not in this checkout")
    devs, peaks = check_device(cell["chips"])
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import jax

    from repro.compile_cache import use_persistent_cache

    cache = use_persistent_cache()
    # every program, the small ones too, so a later process loads them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import rates

    driver_mod = load_module(
        os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
        "bench_driver")
    driver = driver_mod.Driver(config, traffic, args.seed)
    compiles = CompileCount()
    driver.setup()
    setup_s = time.perf_counter() - T0

    compiles.on = True
    if args.trace:
        calls, red, host, offset = traced_window(driver, args.seconds, rates)
    else:
        calls = rates.run_window(driver.call, args.seconds)
    compiles.on = False
    window_s = calls[-1][1] - calls[0][0]
    mem = peak_bytes(devs)

    # a member the driver counts as failed (a message dropped, a
    # horizon not reached, a NaN) breaks a guarantee of the configuration
    checks = dict(driver.check(), failed=driver.failed)
    limits = dict(traffic["limits"], failed=0)
    correct = all(checks[k] <= limits[k] for k in limits)

    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=mem)
    out = dict(correct=correct, attempted=driver.attempted,
               failed=driver.failed)
    if args.trace:
        import reduce as RD

        ctx = dict(reduction=red, spans=host, shapes=driver.shapes,
                   peaks=peaks)
        metrics = {}
        for m in layer:
            reader = load_module(
                os.path.join(BENCH, "metrics", m["name"] + ".py"),
                "bench_metric")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
        device.update(busy_s=red["busy_ns"] * 1e-9,
                      window_s=red["window_ns"] * 1e-9)
        out.update(metrics=metrics, device=device, breakdown=dict(
            device_ops=RD.top_ops(red),
            idle_gaps=RD.label_gaps(red["gaps"], host, offset)))
        os.makedirs(os.path.join(ROOT, "bench_out"), exist_ok=True)
        dump = os.path.join(ROOT, "bench_out",
                            f"{args.workload}.{args.seed}.trace.json")
        with open(dump, "w") as f:
            json.dump(dict(reduction={k: v for k, v in red.items()
                                      if k != "gaps"},
                           gaps=red["gaps"][:100], spans=host,
                           offset_ns=offset, calls=calls), f)
    else:
        values = {driver_mod.RATE_METRIC: rates.window_rate(calls),
                  "setup_s": setup_s}
        out.update(metrics={m["name"]: dict(value=values[m["name"]],
                                            unit=m["unit"]) for m in e2e},
                   device=device)
    out["checks"] = {k: dict(value=checks[k], limit=limits[k])
                     for k in limits}

    print(f"bench: {args.workload} seed {args.seed}: setup {setup_s:.3f} s, "
          f"{len(calls)} calls in {window_s:.3f} s "
          f"({', '.join(f'{e - s:.3f}' for s, e, _ in calls)}), "
          f"{compiles.n} programs compiled or loaded in the window, "
          f"check {driver.check_s:.3f} s, cache {cache}", file=sys.stderr)
    for k in limits:
        print(f"bench: {k} {checks[k]!r} limit {limits[k]!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
