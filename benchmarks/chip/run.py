"""The chip benchmark of the serving stack: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's configuration through the program's serving entry,
warms every shape the traffic uses, drives the serving loop open-loop on
the wall clock for ``--seconds``, then checks a sample of what was served
against the plain reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``compared`` (each number that decides ``correct``
beside its limit, also printed as the last lines of standard error).

Runs only on a TPU with at least the chips the cell asks for: elsewhere
it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2

    import jax

    from chipbench import cache, harness, spec

    cache.configure(ROOT)
    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX runs on {devices[0].platform!r}, not a TPU", file=sys.stderr)
        return 1
    if len(devices) < int(cell["chips"]):
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX has "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"device        : {len(devices)} x {devices[0].device_kind}, "
        f"jax {jax.__version__}")
    result = harness.run(bench, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START, log)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
