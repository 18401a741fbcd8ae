"""The trace reduction: busy time, program runs and idle gaps."""
import json
import pathlib

import numpy as np
import pytest

from chipbench import devtrace

HERE = pathlib.Path(__file__).resolve().parent


def _row(start_us, dur_us, name, module="", program=-1, run=-1):
    return [start_us * 1e3, dur_us * 1e3, name, module, program, run]


def test_reduce_on_a_table_made_by_hand():
    table = {
        "devices": {
            "0": {
                "ops": [_row(0, 10, "fusion.1", "jit_decode_fn", 7, 1),
                        _row(5, 10, "fusion.2", "jit_decode_fn", 7, 1),
                        _row(40, 20, "dot.3", "jit_prefill_fn", 8, 2)],
                "modules": [_row(0, 15, "jit_decode_fn(7)", "", 7, 1),
                            _row(40, 20, "jit_prefill_fn(8)", "", 8, 2)],
            },
            "1": {"ops": [_row(10, 30, "fusion.1", "jit_decode_fn", 9, 3)], "modules": []},
        },
        "host": [_row(15, 25, "bench.tick"), _row(60, 40, "bench.wait")],
    }
    tr = devtrace.reduce(table, window_s=100e-6)
    assert tr.devices["0"].busy_s == pytest.approx(35e-6)
    assert tr.devices["1"].busy_s == pytest.approx(30e-6)
    assert tr.busy_s == pytest.approx(32.5e-6)
    assert tr.runs("decode_fn") == {"decode_fn#7": [pytest.approx(15e-6)],
                                    "decode_fn#9": [pytest.approx(30e-6)]}
    assert tr.runs("prefill_fn") == {"prefill_fn#8": [pytest.approx(20e-6)]}
    idle = dict(tr.idle_by_host)
    assert idle["bench.tick"] == pytest.approx(25e-6)
    assert idle["bench.wait"] == pytest.approx(40e-6)
    ops = dict(tr.top_ops)
    assert ops["decode_fn/fusion"] == pytest.approx(50e-6)  # over both devices


def test_union_merges_overlaps():
    iv = np.asarray([[5.0, 9.0], [0.0, 2.0], [1.0, 3.0], [8.0, 12.0], [20.0, 21.0]])
    np.testing.assert_array_equal(devtrace._union(iv), [[0, 3], [5, 12], [20, 21]])


def test_reduce_on_a_trimmed_chip_trace():
    """A 300 ms slice of a traced phi3-mini.chat-university run on one v5e:
    the numbers the reduction gave when the slice was cut, and what must
    hold of any trace (busy time inside the window, idle time accounted
    for by host activity, the served decode program found)."""
    blob = json.loads((HERE / "data" / "trace_v5e_trimmed.json").read_text())
    tr = devtrace.reduce(blob["table"], blob["window_s"])
    want = blob["expected"]
    assert tr.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert dict(tr.idle_by_host) == pytest.approx(dict(want["idle_by_host"]), rel=1e-12)
    assert dict(tr.top_ops) == pytest.approx(dict(want["top_ops"]), rel=1e-12)
    assert 0 < tr.busy_s < tr.window_s
    assert sum(dict(tr.idle_by_host).values()) == pytest.approx(tr.window_s - tr.busy_s)
    decode = [d for durs in tr.runs("decode_fn").values() for d in durs]
    assert max(decode) > 7.45e9 / 819e9  # at least one run of the remote model


def _edge_view(offset_ns):
    """Two remote prefill runs and two remote decode runs in a trace whose
    clock runs 1 s ahead of ``perf_counter``, a hedge run beside them, and
    requests whose first tokens were stamped before the trace (their run
    lay before the profiler started), from its runs, and after it."""
    from types import SimpleNamespace

    from chipbench import counts, harness, spec

    cfg = spec.config("phi3-mini", spec.BENCH_DIR / "configs" / "phi3-mini.json")
    table = {
        "devices": {"0": {"ops": [], "modules": [
            _row(1_100_000, 20_000, "jit_prefill_fn(3)", "", 3, 1),
            _row(1_121_000, 50, "jit_prefill_fn(9)", "", 9, 2),  # the hedge's
            _row(1_500_000, 20_000, "jit_prefill_fn(2)", "", 2, 3),
            _row(2_000_000, 44_000, "jit_decode_fn(4)", "", 4, 4),
            _row(2_046_000, 44_000, "jit_decode_fn(4)", "", 4, 5),
        ]}},
        "host": [_row(1_000_000, 10, "bench.tick"), _row(2_100_000, 10, "bench.poll")],
        "perf_offset_ns": offset_ns,
    }
    trace = devtrace.reduce(table, window_s=1.2)

    def req(*chunks):
        return harness.Request(due_s=0.0, network_ms=0.0, answered=True,
                               chunks_s=list(chunks))

    requests = [
        req(0.05, 0.09),                 # prefilled before the trace
        req(0.1203, 1.0441, 1.0902),     # run 1 (with the next), then both decodes
        req(0.1204, 1.0443),             # run 1, then the first decode
        req(0.5202),                     # run 2
        req(0.5170),                     # 3 ms before run 2 ends: no run of its own
        req(1.3),                        # prefilled after the trace
    ]
    return SimpleNamespace(trace=trace, cfg=cfg, peak=counts.peaks("TPU v5 lite"),
                           requests=requests, mix={"prompt_tokens": 512, "output_tokens": 64},
                           traced=(0.0, 1.2), chips=1)


def test_work_and_time_come_from_the_same_runs_at_the_trace_edges():
    from chipbench import readings

    view = _edge_view(1e9)
    assert readings.prefill_runs(view) == [(pytest.approx(0.02), 2),
                                           (pytest.approx(0.02), 1)]
    assert readings.decode_runs(view) == [(pytest.approx(0.044), [512, 512]),
                                          (pytest.approx(0.044), [513])]
    assert readings.served_runs(view, "prefill_fn") == [pytest.approx(0.02)] * 2


def test_rooflines_of_the_edge_trace_stay_under_the_peak():
    import importlib.util

    from chipbench import counts, spec

    view = _edge_view(1e9)
    got = {}
    for name in ("prefill_roofline", "decode_roofline", "prefill_device_ms_per_row", "mfu"):
        modspec = importlib.util.spec_from_file_location(name, spec.metric_file(name))
        mod = importlib.util.module_from_spec(modspec)
        modspec.loader.exec_module(mod)
        got[name] = mod.read(view)
    m = view.cfg.remote
    least = (counts.least_seconds(2 * counts.prefill_flops(m, 512),
                                  counts.prefill_bytes(m, 2, 512), view.peak)
             + counts.least_seconds(counts.prefill_flops(m, 512),
                                    counts.prefill_bytes(m, 1, 512), view.peak))
    assert got["prefill_roofline"] == pytest.approx(100 * least / 0.04)
    assert got["prefill_device_ms_per_row"] == pytest.approx(40 / 3)
    assert 0 < got["decode_roofline"] < 100 and 0 < got["mfu"] < 100


def test_readers_find_nothing_without_the_clock_tie():
    from chipbench import readings

    view = _edge_view(None)
    assert readings.prefill_runs(view) == [] and readings.decode_runs(view) == []
