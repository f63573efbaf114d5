"""Run one cell of the benchmark once, on the accelerator it finds.

    python -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (data from the seed, the system built, every shape the cell's
traffic uses warmed) is timed as ``setup_s``; then the cell's units run
back to back for ``--seconds``.  After the window the answers are
compared with the plain reference (``bench.reference``) and the last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown``, and last ``compared``: each number compared with its
limit, also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import time

START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class NoAccelerator(RuntimeError):
    pass


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed directory ``<checkout>/.jax_cache``.  Every program is
    kept, however short its compile, so that a run whose cache is warm
    compiles nothing: not the small eager programs of set-up, and not the
    programs that the read path builds anew on every call."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def check_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, devs: list, *, make_system=None,
             overrides: dict | None = None, start: float = START) -> dict:
    """Set up, measure and check one cell; returns the result object.

    ``overrides`` ({"config": {...}, "mix": {...}}) replaces keys of the
    configuration or the mix: the tests' tiny sizes."""
    import jax
    from bench import spec as bspec
    from bench import trace as btrace
    from bench.timing import Window, compile_clock

    spec, _, config, mix = bspec.resolve(root, workload)
    overrides = overrides or {}
    config = {**config, **overrides.get("config", {})}
    mix = {**mix, **overrides.get("mix", {})}
    system = importlib.import_module(f"bench.systems.{config['system']}")
    kw = {"make_system": make_system} if make_system else {}
    cell = system.Cell(config, mix, seed, **kw)
    clock = compile_clock()
    cell.setup(log)
    setup_s = time.perf_counter() - start
    c_setup = clock()
    log(f"setup_s={setup_s!r} compile_s={c_setup[0]!r} "
        f"compiles={c_setup[1]} cache_loads={c_setup[2]} "
        f"peak_bytes_before_window={_peak_bytes(devs)}")

    tdir = root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1      # the spans, not the runtime's
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    window = Window(seconds)
    window.run(cell.unit)
    if trace:
        jax.profiler.stop_trace()
    c_win = clock()
    peak = _peak_bytes(devs)
    live = cell.live()
    log(f"window_s={window.window_s!r} units={len(window.latencies)} "
        f"ops={window.ops} compiles_in_window={c_win[1] - c_setup[1]} "
        f"cache_loads_in_window={c_win[2] - c_setup[2]} "
        f"compile_s_in_window={c_win[0] - c_setup[0]!r} peak_bytes={peak} "
        f"live={live}")

    cell.fetch()
    gc.collect()
    t0 = time.perf_counter()
    compared = cell.check(log)
    log(f"check_s={time.perf_counter() - t0!r}")

    summary = None
    if trace:
        summary = btrace.reduce(btrace.load(btrace.find(tdir)))
    run = {"setup_s": setup_s, "window_s": window.window_s,
           "ops": window.ops, "latencies_s": window.latencies,
           "peak_bytes": peak, "live": live, "trace": summary}
    metrics = {}
    for m in bspec.cell_metrics(spec, workload,
                                "per_layer" if trace else "end_to_end"):
        value = bspec.reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for v, lim in compared.values()),
              "attempted": window.ops, "failed": cell.failed,
              "metrics": metrics, "device": device}
    if summary:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = btrace.breakdown(summary)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, make_system=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        log("--seed must be a whole number >= 0")
        return 2
    from bench import spec as bspec
    try:
        _, cell, config, _ = bspec.resolve(ROOT, args.workload)
    except (KeyError, OSError, ValueError) as e:
        log(f"cannot resolve workload {args.workload!r}: {e}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    log(f"compile cache: {enable_compile_cache(ROOT)}")
    try:
        devs = check_devices(cell["chips"])
        bspec.peaks(ROOT, devs[0].device_kind)
    except (NoAccelerator, KeyError) as e:
        log(f"refused: {e}")
        return 3
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), devs, make_system=make_system)
    for k, c in result["compared"].items():
        log(f"compared {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
