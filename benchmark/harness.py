"""One run of one cell: find the cell's files, refuse anything but the
chips it asks for, run it through the program's entry points, reduce
what was seen to the declared metrics, print one line."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
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
# What ``benchmark/<kind>_cell.py:run(cell, args, started, say, compiles)``
# returns (``started``: the instant the device was found, where set-up's
# clock starts), for any kind a later PR adds as a file of its own: the result
# line's ``correct``, ``attempted`` and ``failed``; ``compared``, each
# number that decided ``correct`` beside its limit; ``setup_s``;
# ``values``, the end-to-end metrics by name; ``memory``, as
# ``fullest_chip_memory`` gives it. The readers of its per-layer metrics
# take what else they need from the same dict (``counters``, ``harness``,
# ``clients``, ``config``, ``traffic``).
RUNNER_KEYS = ("correct", "compared", "attempted", "failed", "setup_s",
               "values", "memory")
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


def held_to_contract(run: dict, kind: str) -> dict:
    """A runner's dict, or the end of the run with the keys it lacks."""
    missing = [key for key in RUNNER_KEYS if key not in run]
    if missing:
        raise SystemExit(f"benchmark/{kind}_cell.py:run returned no "
                         f"{missing}; it owes {list(RUNNER_KEYS)}")
    return run


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
    # Set-up's clock starts HERE (PR 54): what passed before (the
    # interpreter, ``import jax``, the TPU client taking its lease) was
    # 9.4 to 14.0 s of a 16 to 23 s ``setup_s`` and moved by 2 s from
    # lease to lease for one code (PERF.md section 2), so it stays on
    # this line as information and is in no metric.
    found = time.perf_counter()
    say("setup", step="JAX started, device found; set-up's clock starts",
        since_process_start_s=found - started)
    # Outside the ``try``: a directory that holds the benchmark without
    # the program ends here with no result and another code than 0.
    import ray_tpu  # noqa: F401

    lacking = lacks_the_model(cell.config)
    if lacking:
        raise SystemExit(lacking)
    try:
        return measured(cell, args, device, found, started)
    except (Exception, SystemExit) as exc:  # noqa: BLE001 — reported
        return raised(exc, device)


def lacks_the_model(config: dict) -> "str | None":
    """What to end with, where this tree's program has no module for the
    configuration's model (PR 59): the module of ``builder.path`` is
    imported before anything is built, outside ``main``'s ``try``, and
    a ``ModuleNotFoundError`` that names that module itself (or a
    package above it) ends the run as a missing ``ray_tpu`` does, with no
    result and another code than 0. The driver tries a new cell on the
    parent commit with the new benchmark files laid over it and takes
    that for "the parent cannot run it"; PR 55 needed a file of its own
    for it (``solar_builder.py``, which went). A module that is there
    and raises, or that lacks the attribute, is a run that broke: the
    build inside the ``try`` meets it again and it gets its result's
    line."""
    path = config.get("builder", {}).get("path")
    if path is None:
        return None  # a kind of cell that builds no model configuration
    module = path.rpartition(".")[0]
    try:
        importlib.import_module(module)
    except ModuleNotFoundError as exc:
        if exc.name and (module + ".").startswith(exc.name + "."):
            return (f"no module {exc.name}: this program cannot build "
                    f"{config.get('name')} ({path})")
    except Exception:  # noqa: BLE001 — raised again where it is reported
        pass
    return None


def raised(exc: BaseException, device: dict) -> int:
    """A run that raised once the device was found is a run that is not
    correct, and its result's line says where. The traceback goes to
    standard error as ever; but of a run that only exits with a code
    the next session learns that code and nothing else (the check of
    PR 54: "exited with code 1" in ``serve-longgen-closed``, which
    seventeen runs of the same tree on the chip did not meet again),
    where the numbers a run compared, under their names, reach the
    ledger. So the name of the exception and the last lines it passed
    through are a NAME under ``compared``, with the number 1 against
    the limit 0. No metric is printed: the run measured nothing that
    may be kept."""
    import re
    import traceback

    traceback.print_exception(exc, file=sys.stderr)
    frames = traceback.extract_tb(exc.__traceback__)[-4:]
    where = ".".join(f"{os.path.basename(f.filename)}.{f.lineno}"
                     for f in reversed(frames))
    name = re.sub(r"[^A-Za-z0-9_.-]", "_",
                  f"raised.{type(exc).__name__}.{where}")[:160]
    compared = {"raised": 1, "limit": 0, name: 1,
                "message": str(exc)[:300]}
    try:
        device["memory_peak_bytes"] = fullest_chip_memory()[
            "peak_bytes_in_use"]
    except Exception:  # noqa: BLE001 — the device may be what raised
        device["memory_peak_bytes"] = None
    print("bench[correct] " + json.dumps(compared), file=sys.stderr,
          flush=True)
    print(json.dumps({"correct": False, "attempted": 0, "failed": 0,
                      "metrics": {}, "device": device,
                      "compared": compared}, default=str), flush=True)
    return 0


def measured(cell, args, device: dict, found: float, started: float) -> int:
    """The run from the found device to the result's line."""
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

    cell_runner = importlib.import_module(
        f"benchmark.{cell.config['kind']}_cell")
    run = held_to_contract(
        cell_runner.run(cell, args, found, say, compiles),
        cell.config["kind"])
    run.update(device_kind=device["kind"], chips=cell.chips,
               rehearse=args.rehearse, trace=None)
    say("compile", programs_built_or_fetched=compiles.count,
        seconds=compiles.seconds, slowest=dict(sorted(
            compiles.by_name.items(), key=lambda kv: -kv[1])[:4]),
        setup_s=run["setup_s"], before_device_found_s=found - started,
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
    # Each number compared beside its limit ends standard error, and
    # comes last in the result's line: where a run is not correct the
    # driver's record keeps the end of each.
    result["compared"] = run["compared"]
    print("bench[correct] " + json.dumps(run["compared"], default=str),
          file=sys.stderr, flush=True)
    print(json.dumps(result, default=str), flush=True)
    return 0
