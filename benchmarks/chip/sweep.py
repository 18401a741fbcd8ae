"""Find a cell's knee on the chip (not part of a run).

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds 51 --rates 1.0,1.5,2.0

Builds the cell once, then for each offered rate drives one window of
``--seconds`` with the cell's traffic at that rate and an SLA too long to
matter (so the remote tier answers everything and its completion
latency shows).  For each rate it prints the requests due and answered
by the close, how many were still open at the close, the remote
completion latency (median, p90) over the first and the last third of the
window, and the tokens per second delivered.  The knee is the highest
rate at which completions keep pace: few open at the close and no
upward drift from the first third to the last.
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

NO_SLA_MS = 600_000.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import cache, harness, spec, traffic, window

    cache.configure(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: not a TPU", file=sys.stderr)
        return 1
    from repro.serving.admission import AdmissionConfig
    from repro.serving.lifecycle import QueuedRequest
    from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

    window.DRAIN_S = 20.0
    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    cfg = spec.config(w["config"], spec.config_file(bench, w["config"]))
    mix = spec.load_json(spec.traffic_file(w["traffic"]))
    mix["sla_ms"] = NO_SLA_MS
    served = harness.build(cfg, mix, args.seed, T_START)
    print(f"set-up {served.setup_s:.3f} s", flush=True)
    engine = served.engine
    registry = served.loop.scheduler.base_registry
    ondevice = served.loop.scheduler.ondevice
    vocab = min(cfg.remote.vocab_size, cfg.hedge.vocab_size)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["arrivals"]["rate_rps"] = rate
        reqs = traffic.generate(mix, args.seconds, args.seed, vocab)
        sched = MDInferenceScheduler(
            registry, ondevice, SchedulerConfig(t_sla_ms=NO_SLA_MS, seed=args.seed))
        loop = engine.make_loop(sched, admission=AdmissionConfig(
            max_chunk=served.max_chunk))

        def make_request(i, arrival_ms, reqs=reqs):
            return QueuedRequest(
                rid=i, tokens=reqs.prompts[i], n_steps=reqs.output_tokens,
                t_nw_est_ms=float(reqs.network_ms[i]),
                t_nw_actual_ms=float(reqs.network_ms[i]), arrival_ms=arrival_ms)

        win = window.drive(loop, reqs, make_request, seconds=args.seconds,
                           tick_ms=float(cfg.serving["tick_ms"]))
        res = harness._requests(win)
        end = win.t0 + args.seconds
        lat = np.asarray([1e3 * (r.resolved_s - r.due_s) + r.network_ms
                          for r in res if r.answered])
        due = np.asarray([r.due_s for r in res if r.answered])
        third = args.seconds / 3
        first = lat[due < win.t0 + third]
        last = lat[due >= win.t0 + 2 * third]
        row = {
            "rate_rps": rate,
            "due": len(res),
            "answered_by_close": sum(r.answered and r.resolved_s < end for r in res),
            "open_at_close": sum(not (r.answered and r.resolved_s < end) for r in res),
            "remote_ms_p50": float(np.percentile(lat, 50)),
            "remote_ms_p90": float(np.percentile(lat, 90)),
            "first_third_p50": float(np.percentile(first, 50)),
            "last_third_p50": float(np.percentile(last, 50)),
            "tokens_per_s": harness.end_to_end(
                cfg, mix, res, win.t0, args.seconds, win.closed_s)["tokens_per_s"][0],
            "compile_events": win.compile_events,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
