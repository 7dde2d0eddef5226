#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the LAST line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in
a traced run), then ``compared``: each number ``correct`` rests on
beside its limit, which are also the last lines of stderr.  ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics.  Earlier lines are facts for a
reader (device, versions, sample counts, MFU, memory); nothing parses
them.  No TPU, or fewer chips than the cell asks for: exit non-zero, no
result line — there is no CPU fallback.
"""

import sys
import time

T_PROC0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.metadata  # noqa: E402
import os  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import result, spec

    cell = spec.resolve_cell(ROOT, args.workload)
    seconds = args.seconds if args.seconds is not None else cell.run_seconds

    import jax

    t_jax = time.perf_counter()
    devices = jax.devices()
    t_chip = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmarks/run.py: cell {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); jax reports {len(devices)} x "
              f"{devices[0].platform!r} — nothing was run", file=sys.stderr)
        return 1
    # the program's own rule for the compile cache: a fixed directory in
    # the checkout unless JAX_COMPILATION_CACHE_DIR says otherwise.  Set
    # before the first compile; every program is cached, also the small
    # ones (the reference's blocks, the idle frame), so that a second run
    # compiles nothing
    from flexflow_tpu.runtime.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    import jaxlib

    print(f"[start] {cell.name} seed {args.seed} seconds {seconds} trace "
          f"{args.trace}; {len(devices)} x {devices[0].device_kind}; jax "
          f"{jax.__version__} jaxlib {jaxlib.__version__} libtpu "
          f"{importlib.metadata.version('libtpu')}; compile cache {cache_dir}; "
          f"since process start: import jax {t_jax - T_PROC0:.2f}s, "
          f"jax.devices() (reaching the chip) {t_chip - t_jax:.2f}s",
          flush=True)

    driver = spec.resolve_dotted(spec.DRIVERS[cell.traffic["kind"]])
    out = driver(cell, args.seed, seconds, bool(args.trace), T_PROC0)

    if args.trace:
        from benchmarks.harness import readers, trace_reduce

        trace = out["trace"]
        if trace is None or not trace["devices"]:
            print("benchmarks/run.py: the traced tail holds no device "
                  "operation — no result", file=sys.stderr)
            return 1
        ctx = {"cell": cell, "facts": out["facts"], "trace": trace,
               "device_kind": devices[0].device_kind}
        values, units = {}, {}
        for metric in cell.per_layer:
            value = spec.resolve_dotted(metric["reader"])(
                ctx, **metric.get("args", {}))
            print(f"[layer] {metric['name']} = {value} {metric['unit']} "
                  f"({metric['layer']}; moves {metric['moves']})")
            if value is not None:
                values[metric["name"]] = value
                units[metric["name"]] = metric["unit"]
        unplaced = readers.scope_unplaced_share(ctx)
        if unplaced is not None:
            print(f"[layer] name scopes: {unplaced:.3f} % of device busy "
                  f"time is of instructions the compiled program's text "
                  f"does not place (a scope's share is left out past "
                  f"{readers.UNPLACED_LIMIT:.0%})")
        device = result.device_facts(readers.busy(ctx))
        breakdown = trace_reduce.breakdown(trace)
    else:
        values = {m["name"]: out["end_to_end"][m["name"]]
                  for m in cell.end_to_end}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        device, breakdown = result.device_facts(), None
    line = result.result_line(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], values=values, units=units, device=device,
        breakdown=breakdown, compared=out["compared"])
    print(line, flush=True)
    print(result.compared_lines(out["compared"]), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
