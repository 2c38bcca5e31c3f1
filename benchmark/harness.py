"""One run of one cell: find the cell's files, refuse anything but the
chips it asks for, run it through the program's entry points, reduce
what was seen to the declared metrics, print one line."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import time

from benchmark import spec

ARGS = argparse.ArgumentParser(description=__doc__)
ARGS.add_argument("--workload", required=True)
ARGS.add_argument("--seed", type=int, default=0)
ARGS.add_argument("--seconds", type=float, required=True)
ARGS.add_argument("--trace", type=int, choices=(0, 1), default=0)
ARGS.add_argument("--rehearse", action="store_true",
                  help="the only way a CPU is accepted: the files' "
                       "rehearsal sizes, interpreted kernels; what it "
                       "prints says platform cpu and is no measurement")
ARGS.add_argument("--benchmark-json", default=None,
                  help="another BENCHMARK.json; its files are found "
                       "beside it (default: the checkout's)")
ARGS.add_argument("--trace-dir", default=None,
                  help="where the profiler writes (default: "
                       "<checkout>/.bench_trace/<workload>)")
ARGS.add_argument("--keep-trace", action="store_true",
                  help="leave the profiler's files in --trace-dir")

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_IMPORTED = time.perf_counter()  # the earlier lines say when, from here


def say(phase: str, **fields) -> None:
    """An earlier line of the output: information, not the result."""
    print(f"bench[{phase}] " + json.dumps(
        {"at_s": round(time.perf_counter() - _IMPORTED, 2), **fields}, default=str),
        flush=True)


def start_trace(trace_dir: str) -> None:
    """The profiler without its Python tracer, which slows the host
    loops that are being measured (seen on the v5e, PR 22: it doubled
    the engine's time between decode steps)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


class CompileCounter:
    """Programs built or fetched from the persistent cache so far (one
    event each, as ``chip_smoke.watch_compiles`` found)."""

    def __init__(self):
        import jax

        self.count, self.seconds, self.by_name = 0, 0.0, {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name=None, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration
            self.by_name[fun_name] = self.by_name.get(fun_name, 0.0) \
                + duration

    def __call__(self) -> int:
        return self.count


def fullest_chip_memory() -> dict:
    """``memory_stats()`` of the chip with the highest peak."""
    import jax

    fullest = max((d.memory_stats() or {} for d in jax.local_devices()),
                  key=lambda s: s.get("peak_bytes_in_use", 0))
    return {k: fullest.get(k) for k in ("peak_bytes_in_use", "bytes_limit")}


def device_or_refuse(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; anything but the cell's chips ends
    the run before any phase, with no result."""
    import jax

    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want or len(devices) < chips:
        raise SystemExit(
            f"this cell needs {chips} {want.upper()} device(s); JAX found "
            f"{len(devices)} of platform {devices[0].platform!r} "
            f"({devices[0].device_kind}). A CPU is accepted only with "
            "--rehearse.")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def read_per_layer(cell, run: dict, say_) -> dict:
    """Each per-layer metric through the reader its file names; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    reported = {m["name"] for m in cell.end_to_end}
    out = {}
    for metric in cell.per_layer:
        if metric["moves"] not in reported:
            continue
        reader = spec.load_module(cell.roots, "readers", metric["reader"])
        value = reader.read(metric, run)
        if value is None:
            say_("metric", name=metric["name"], left_out="nothing to read")
        else:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv, started: float) -> int:
    args = ARGS.parse_args(argv)
    cell = spec.load_cell(args.workload, args.benchmark_json)
    if args.rehearse:
        # Before jax is imported: the rehearsal owns its platform, with
        # as many virtual devices as the cell has chips.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        os.environ["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={cell.chips}"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    device = device_or_refuse(cell.chips, args.rehearse)
    say("setup", step="JAX started, device found",
        since_process_start_s=time.perf_counter() - started)

    from ray_tpu._private import compile_cache

    from benchmark import peaks, trace_reduce

    if not args.rehearse:
        peaks.peaks(device["kind"])  # an unknown device ends the run here
    # A rehearsal's tiny CPU programs are not worth keeping.
    cache = "off (rehearsal)" if args.rehearse else compile_cache.enable()
    compiles = CompileCounter()
    args.trace_dir = args.trace_dir or os.path.join(
        spec.ROOT, ".bench_trace", cell.name)
    if args.trace:
        shutil.rmtree(args.trace_dir, ignore_errors=True)
    say("setup", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache=cache,
        config=cell.config["name"], kind=cell.config["kind"])

    import importlib

    cell_runner = importlib.import_module(
        f"benchmark.{cell.config['kind']}_cell")
    run = cell_runner.run(cell, args, started, say, compiles)
    run.update(device_kind=device["kind"], chips=cell.chips,
               rehearse=args.rehearse, trace=None)
    say("compile", programs_built_or_fetched=compiles.count,
        seconds=compiles.seconds, slowest=dict(sorted(
            compiles.by_name.items(), key=lambda kv: -kv[1])[:4]),
        setup_s=run["setup_s"],
        wall_s=time.perf_counter() - started)

    device["memory_peak_bytes"] = run["memory"]["peak_bytes_in_use"]
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}
    if args.trace:
        path = trace_reduce.find_xplane(args.trace_dir)
        if path is not None:
            run["trace"] = trace_reduce.load(path)
            seen = trace_reduce.busy_and_window(run["trace"])
            if seen is not None:
                device["busy_s"], device["window_s"] = seen
            shown = trace_reduce.breakdown(run["trace"])
            if shown is not None:
                result["breakdown"] = shown
            say("trace", file=path, bytes=os.path.getsize(path),
                devices=sorted(run["trace"].devices))
        if "busy_s" not in device and not args.rehearse:
            raise SystemExit("the traced run shows no operation on the "
                             "device")
        result["metrics"] = read_per_layer(cell, run, say)
        if not args.keep_trace:
            shutil.rmtree(args.trace_dir, ignore_errors=True)
    else:
        run["values"]["setup_s"] = run["setup_s"]
        for metric in cell.end_to_end:
            if metric["name"] not in run["values"]:
                raise SystemExit(f"the cell did not measure "
                                 f"{metric['name']}")
            result["metrics"][metric["name"]] = {
                "value": run["values"][metric["name"]],
                "unit": metric["unit"]}
    print(json.dumps(result), flush=True)
    return 0
