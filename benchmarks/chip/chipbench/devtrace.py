"""From a profiler trace to device busy time, program times and idle gaps.

Two steps.  :func:`extract` reads the ``.xplane.pb`` that
``jax.profiler`` writes into a small table of events: for each device the
intervals of its operations and of its programs (``XLA Ops`` and ``XLA
Modules``), the host spans that the benchmark wrapped around its own
calls (``bench.*``), and the offset of the trace's clock from
``perf_counter``, read from the ``perf_us`` those spans carry.
:func:`reduce` turns that table into the numbers the metrics read.  The reduction is tested on a trimmed table from a chip
run, committed beside the tests.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."


def _event_row(e, with_program: bool):
    stats = dict(e.stats) if with_program else {}
    return [
        float(e.start_ns),
        float(e.duration_ns),
        # A TPU operation is named by its whole HLO instruction; keep the
        # instruction's name (``%fusion.12 = bf16[...] ...`` -> ``fusion.12``).
        e.name.split(" = ", 1)[0].lstrip("%"),
        str(stats.get("hlo_module", "")),
        int(stats.get("program_id", -1)),
        int(stats.get("run_id", -1)),
    ]


def extract(log_dir: str) -> dict:
    """The event table of the one trace under ``log_dir``.

    ``{"devices": {id: {"ops": rows, "modules": rows}}, "host": rows,
    "perf_offset_ns": offset}``; a row is ``[start_ns, duration_ns, name,
    hlo_module, program_id, run_id]``, and a trace time less the offset is
    ``perf_counter`` time (``None`` where no span carries ``perf_us``).
    On a host without a TPU the operations that XLA ran on the CPU stand
    in for device 0 (the CPU rehearsal of the benchmark).
    """
    from jax.profiler import ProfileData

    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found {files}")
    pd = ProfileData.from_file(files[0])
    devices: Dict[str, dict] = {}
    host: list = []
    cpu_ops: list = []
    offsets: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] = [_event_row(e, True) for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] = [_event_row(e, True) for e in line.events]
            devices[m.group(1)] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(_event_row(e, False))
                        perf_us = dict(e.stats).get("perf_us")
                        if perf_us is not None:
                            offsets.append(float(e.start_ns) - 1e3 * float(perf_us))
                    elif line.name.startswith("tf_XLA") and e.duration_ns > 0:
                        row = _event_row(e, True)
                        if row[3]:
                            cpu_ops.append(row)
    if not devices and cpu_ops:
        devices["0"] = {"ops": cpu_ops, "modules": []}
    return {"devices": devices, "host": host,
            "perf_offset_ns": float(np.median(offsets)) if offsets else None}


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > reach[:-1]])
    return np.stack([iv[first, 0], np.maximum.reduceat(iv[:, 1], first)], 1)


def _intervals(rows) -> np.ndarray:
    if not rows:
        return np.zeros((0, 2))
    a = np.asarray([[r[0], r[0] + r[1]] for r in rows], dtype=float)
    return a


def program_key(row) -> str:
    """A program's name without JAX's ``jit_`` prefix, with its id (the
    profiler names a program run ``jit_<function>(<id>)``)."""
    name, pid = row[3] or row[2], row[4]
    m = re.match(r"^(.*)\((\d+)\)$", name)
    if m:
        name, pid = m.group(1), int(m.group(2))
    name = name[4:] if name.startswith("jit_") else name
    return f"{name}#{pid}"


@dataclasses.dataclass
class Device:
    busy_s: float
    # program key -> (start_ns, end_ns) of each run, on the trace's clock
    spans: Dict[str, List[Tuple[float, float]]]

    @property
    def programs(self) -> Dict[str, List[float]]:
        """Program key -> run durations (s)."""
        return {k: [(b - a) / 1e9 for a, b in v] for k, v in self.spans.items()}


@dataclasses.dataclass
class Trace:
    window_s: float
    devices: Dict[str, Device]
    idle_by_host: List[Tuple[str, float]]  # host activity -> idle seconds
    top_ops: List[Tuple[str, float]]  # device operation -> seconds
    perf_offset_ns: Optional[float] = None

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return float(np.mean([d.busy_s for d in self.devices.values()]))

    def runs(self, function: str) -> Dict[str, List[float]]:
        """Program key -> run durations, over every device, for the
        programs of the jitted function ``function``."""
        out: Dict[str, List[float]] = {}
        for d in self.devices.values():
            for key, durs in d.programs.items():
                if key.split("#")[0] == function:
                    out.setdefault(key, []).extend(durs)
        return out

    def run_spans(self, function: str) -> List[Tuple[float, float]]:
        """``(start, end)`` in ``perf_counter`` seconds of every run, on
        any device, of the programs of ``function``, in order of start;
        empty where the trace's clock is not tied to ``perf_counter``."""
        if self.perf_offset_ns is None:
            return []
        off = self.perf_offset_ns
        return sorted(
            ((a - off) / 1e9, (b - off) / 1e9)
            for d in self.devices.values()
            for key, spans in d.spans.items() if key.split("#")[0] == function
            for a, b in spans
        )


def _program_runs(dev: dict) -> Dict[str, List[Tuple[float, float]]]:
    """``(start_ns, end_ns)`` of each program's runs: from the ``XLA
    Modules`` line, or, where a device has none, from the span of each
    run's operations."""
    runs: Dict[str, List[Tuple[float, float]]] = {}
    if dev["modules"]:
        for r in dev["modules"]:
            runs.setdefault(program_key(r), []).append((r[0], r[0] + r[1]))
        return runs
    spans: Dict[tuple, list] = {}
    for r in dev["ops"]:
        s = spans.setdefault((program_key(r), r[5]), [r[0], r[0] + r[1]])
        s[0], s[1] = min(s[0], r[0]), max(s[1], r[0] + r[1])
    for (key, _), (s0, s1) in spans.items():
        runs.setdefault(key, []).append((s0, s1))
    return runs


# Control-flow operations contain the operations of their bodies.
_CONTAINERS = ("while", "conditional", "call")


def _ops_by_program(dev: dict):
    """``(program, operation kind, seconds)`` of each operation, without
    control-flow containers.  An operation belongs to the program run whose
    interval holds its start (TPU operations carry no program name)."""
    mods = sorted(dev["modules"], key=lambda r: r[0])
    starts = np.asarray([r[0] for r in mods])
    for r in dev["ops"]:
        kind = re.sub(r"[.\d]+$", "", r[2])
        if kind in _CONTAINERS:
            continue
        if r[3] or not mods:
            program = program_key(r).split("#")[0]
        else:
            i = int(np.searchsorted(starts, r[0], side="right")) - 1
            inside = i >= 0 and r[0] <= mods[i][0] + mods[i][1]
            program = program_key(mods[i]).split("#")[0] if inside else "?"
        yield program, kind, r[1] / 1e9


def reduce(table: dict, window_s: float, top: int = 10) -> Trace:
    """Busy time, program runs and idle gaps of a trace table."""
    host = _intervals(table["host"])
    host_names = [r[2] for r in table["host"]]
    devices: Dict[str, Device] = {}
    starts = []
    for dev in table["devices"].values():
        iv = _intervals(dev["ops"] or dev["modules"])
        if len(iv):
            starts.append(iv[:, 0].min())
    if not starts:
        raise RuntimeError("the trace holds no device operation")
    t0 = min(starts + ([host[:, 0].min()] if len(host) else []))
    t1 = t0 + window_s * 1e9
    op_time: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for key in sorted(table["devices"], key=int):
        dev = table["devices"][key]
        busy = _union(np.clip(_intervals(dev["ops"] or dev["modules"]), t0, t1))
        edges = np.concatenate([[t0], busy.reshape(-1), [t1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        devices[key] = Device(
            busy_s=float((busy[:, 1] - busy[:, 0]).sum() / 1e9),
            spans=_program_runs(dev),
        )
        for program, name, dur in _ops_by_program(dev):
            op_time[f"{program}/{name}"] = op_time.get(f"{program}/{name}", 0.0) + dur
        if key == min(table["devices"], key=int):
            for g0, g1 in gaps:
                name = _host_activity(host, host_names, g0, g1)
                idle[name] = idle.get(name, 0.0) + (g1 - g0) / 1e9
    return Trace(
        window_s=window_s,
        devices=devices,
        idle_by_host=sorted(idle.items(), key=lambda kv: -kv[1])[:top],
        top_ops=sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        perf_offset_ns=table.get("perf_offset_ns"),
    )


def _host_activity(host: np.ndarray, names, g0: float, g1: float) -> str:
    """The host span that covers most of the gap ``(g0, g1)``."""
    if len(host) == 0:
        return "host:unannotated"
    over = np.minimum(host[:, 1], g1) - np.maximum(host[:, 0], g0)
    i = int(np.argmax(over))
    return names[i] if over[i] > 0 else "host:unannotated"
