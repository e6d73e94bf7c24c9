"""One run of one cell of BENCHMARK.json:

    python yardstick/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Loads the cell's configuration, traffic mix and generator by
name, builds operands on the device from --seed, warms the cell's own
shapes (all of that is `setup_s`, less the seconds JAX's TPU client took
to come up, which the result line carries as `device.backend_start_s`),
measures for --seconds, prints rows a person can read and then, as the last
line, the one JSON object the driver reads. Without an accelerator (or with
fewer chips than the cell asks for) it exits non-zero and prints no result.

`--rehearse-cpu` is for the yardstick's own tests and for a builder without
a chip: the tiny sizes of the files' `rehearse` blocks on virtual CPU
devices. It prints `platform: cpu` and reports every timing as "not
measured"; only exact counts carry a value."""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up starts with the process

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def refuse(why: str, code: int = 3) -> "NoReturn":
    print(f"yardstick: {why}", file=sys.stderr)
    sys.exit(code)


def main(argv: list) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from yardstick import harness
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.Cell(manifest, args.workload, rehearse=args.rehearse_cpu)
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell.chips}").strip()
    # libtpu would log under /tmp/tpu_logs, a fixed path outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from tpu_mpi._runtime import enable_compile_cache
    except ImportError as e:
        refuse(f"the system under test is not in this checkout ({e})")
    enable_compile_cache()          # before the backend comes up

    import jax
    # every program of the run, however small, is found again by the next
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    run = harness.Run(cell, args.seed, seconds, bool(args.trace),
                      args.rehearse_cpu, T_PROCESS)
    t_backend = time.perf_counter()
    try:
        platform = jax.default_backend()
    except RuntimeError as e:
        refuse(f"JAX found no backend: {e}")
    # libtpu's own start-up took 8.6 to 14.2 s in twelve runs of the same
    # code in one call (PR 22): with it inside, two sets of the flagship
    # cell had medians 11.7% apart, more than the 10% two sets of one code
    # may differ by. `setup_s` leaves it out; the result line keeps it as
    # `device.backend_start_s`, the traced run as the metric backend_start_s
    run.backend_s = time.perf_counter() - t_backend
    run.t_process += run.backend_s
    if args.rehearse_cpu:
        if platform != "cpu":
            refuse("a rehearsal runs on the CPU backend only")
    elif platform != "tpu":
        refuse(f"JAX's backend is {platform!r}, not a TPU: a cell is "
               f"measured on the chip or not at all (--rehearse-cpu runs "
               f"the tiny rehearsal and measures nothing)")
    if len(jax.devices()) < cell.chips:
        refuse(f"{cell.name} needs {cell.chips} chips, JAX found "
               f"{len(jax.devices())}")
    run.devices = list(jax.devices()[:cell.chips])
    if not args.rehearse_cpu:
        peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
        kind = run.devices[0].device_kind
        if kind not in peaks:
            refuse(f"device_kind {kind!r} is not in yardstick/peaks.json: a "
                   f"share of some other chip's peak is not a measurement")
        run.peaks = peaks[kind]
    harness.count_compiles(run)
    run.phase("imports and backend")

    readers = cell.readers()
    if run.trace_on:
        for _spec, mod in readers:  # measurements a reader makes itself,
            if hasattr(mod, "prepare"):     # before the operands exist
                mod.prepare(run)
    cell.generator().run(run)       # set-up, window, checks -> run.results
    run.reduce_trace()

    e2e = dict(run.results.get("metrics", {}))
    e2e["setup_s"] = run.setup_s
    if run.trace_on:
        specs = cell.per_layer
        values = {spec["name"]: mod.read(run) for spec, mod in readers}
    else:
        specs = cell.end_to_end
        missing = [m["name"] for m in specs if e2e.get(m["name"]) is None]
        if missing:
            refuse(f"{cell.name}: the generator gave no {missing}", 4)
        values = e2e
    if args.rehearse_cpu:           # a CPU timing is no device metric
        exact = {spec["name"] for spec, mod in readers
                 if getattr(mod, "EXACT_COUNT", False)}
        values = {k: (v if k in exact else None) for k, v in values.items()}
        for m in specs:
            shown = values.get(m["name"])
            print(f"{m['name']}: "
                  f"{harness.NOT_MEASURED if shown is None else shown}")

    run.memory_row("at the end")
    run.row(f"backend start-up {run.backend_s:.2f} s (not in setup_s); set-up "
            "phases, seconds of setup_s so far: " + "  ".join(
                f"{name} {age:.2f}" for name, age in run.phases))
    print(f"setup_s {run.setup_s:.3f}  window_s {run.window_s:.3f}  "
          f"compiles_in_window {run.compiles_in_window}  "
          f"platform: {run.devices[0].platform}")
    if run.trace is not None:
        if run.trace.dropped_s:
            print(f"device trace buffers dropped: the last "
                  f"{run.trace.dropped_s:.3f} s of the profiled interval "
                  f"are cut, {run.trace.window_s:.3f} s are kept")
        seen = {c.ordinal for c in run.trace.chips}
        for d in run.devices:
            if d.id not in seen:
                print(f"chip {d.id}: no op in the trace (idle 100%, or its "
                      f"work leaves no op event, as a copy between chips)")
        for c in run.trace.chips:
            print(f"chip {c.ordinal}: busy {c.busy_s:.4f} s of "
                  f"{run.trace.window_s:.4f} s, idle {100 * c.idle_share:.2f}%")
    sys.stdout.flush()
    print(harness.result_line(run, values, specs))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
