"""The control of a cell's comparison, on the chip (not part of a run).

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n,n,...>

For each seed: one run of the cell as the benchmark makes it (a window of
``--seconds`` at the cell's own load), whose comparison puts the control
in the program's place: at each position of the sampled requests, the
token that the reference one precision step below the configuration's
(float8 for bfloat16, bfloat16 for float32) ranks first, read against the
float32 reference under the cell's limit.  The harness's own verdict has
to come out ``correct: false`` on every seed; the command exits non-zero
where it does not.  Prints one line per seed and a JSON summary last.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import cache, harness, spec

    cache.configure(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("control.py: not a TPU", file=sys.stderr)
        return 1
    bench = spec.benchmark()
    readings = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run(bench, args.workload, seed, args.seconds, False,
                          time.perf_counter(), lambda s: print(s, flush=True), control=True)
        c = out["compared"]["logit_gap_max"]
        readings.append({"seed": seed, "correct": out["correct"], "control": c["value"],
                         "limit": c["limit"]})
        print(f"seed {seed}: control logit_gap_max {c['value']!r} limit {c['limit']!r} "
              f"correct {str(out['correct']).lower()}", flush=True)
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0 if not any(r["correct"] for r in readings) else 1


if __name__ == "__main__":
    sys.exit(main())
