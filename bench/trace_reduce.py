"""From a profiler trace to the device metrics, the same way in every PR.

``extract`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact document (device ops by plane and line, host events by thread, the
window's bounds); ``reduce`` computes from that document:

- ``busy_s``: the union of the intervals in which an op ran, per device
  plane, averaged over the planes; ``window_s``: the traced window;
- ``kernel_s``: the summed device time of a named program's kernel alone,
  without the pads, relayouts and copies the program runs around it: the
  ops of the "XLA Ops" line that are a ``custom-call`` named after the
  program (a Pallas kernel lowers to one, ``%_decode_fused.1 = f32[...]
  custom-call(...)``);
- ``top_ops``: device time by op name, longest first;
- ``gaps``: the device's idle gaps, longest first, each labelled by the
  host event that overlaps it most.

``roofline_pct`` is the share of the least time a bytes-bound kernel could
take: bytes over the peak HBM bandwidth of the device kind
(``peaks.json``), divided by the time it took.
"""
from __future__ import annotations

import glob
import json
import os
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
#: the host annotation that run.py holds open over the measured window
WINDOW_EVENT = "bench.window"
#: host events shorter than this do not label a gap
MIN_HOST_NS = 20_000


def peak(kind: str, key: str) -> float:
    """A peak of ``device_kind`` from ``peaks.json``; an unknown kind is an
    error, never a default."""
    kinds = json.loads((HERE / "peaks.json").read_text())["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(kinds)})")
    return float(kinds[kind][key])


def roofline_pct(nbytes: float, seconds: float, kind: str) -> float:
    """100 x (nbytes / peak HBM bytes/s) / seconds."""
    return 100.0 * nbytes / peak(kind, "hbm_bytes_per_s") / seconds


def extract(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a compact document:
    ``{"window": [start_ns, end_ns] | None, "device": {plane: {line:
    [[name, start_ns, dur_ns], ...]}}, "host": [[thread, name, start_ns,
    dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    doc: dict = {"window": None, "device": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = doc["device"].setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [[e.name, int(e.start_ns),
                                     int(e.duration_ns)]
                                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_EVENT:
                        doc["window"] = [int(e.start_ns), int(e.end_ns)]
                    elif e.duration_ns >= MIN_HOST_NS:
                        doc["host"].append([line.name, e.name,
                                            int(e.start_ns),
                                            int(e.duration_ns)])
    return doc


def _union(intervals, lo: int, hi: int) -> list[list[int]]:
    """Merged [start, end] intervals clipped to [lo, hi]."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(raw: str) -> str:
    """An op's HLO text shortened to its name and result shape:
    ``%copy.1 = f32[32,8192,8,8]{1,3,2,0:T(8,128)} copy(...)`` ->
    ``copy.1 f32[32,8192,8,8]``."""
    name, sep, rhs = raw.partition(" = ")
    if not sep:
        return raw
    return f"{name.lstrip('%')} {rhs.split('{')[0].split(' ')[0]}"


def is_kernel(raw: str, program: str) -> bool:
    """Whether an op's HLO text is the program's kernel: a ``custom-call``
    whose name starts with the program's."""
    name, sep, rhs = raw.partition(" = ")
    return bool(sep) and name.lstrip("%").startswith(program) \
        and " custom-call(" in rhs


def _op_lines(lines: dict) -> list:
    if "XLA Ops" in lines:
        return lines["XLA Ops"]
    return [e for name, evs in lines.items()
            if name not in ("XLA Modules", "Steps") for e in evs]


def reduce(doc: dict, program: str, top: int = 10) -> dict | None:
    """Device metrics of a compact trace; None where it holds no device
    op (a host with no accelerator)."""
    planes = {p: _op_lines(lines) for p, lines in doc["device"].items()
              if "CUSTOM" not in p}
    planes = {p: ops for p, ops in planes.items() if ops}
    if not planes:
        return None
    if doc["window"] is not None:
        lo, hi = doc["window"]
    else:
        lo = min(s for ops in planes.values() for _, s, _ in ops)
        hi = max(s + d for ops in planes.values() for _, s, d in ops)
    busy, kernel_ns, by_op, gaps = 0, 0, {}, []
    for ops in planes.values():
        merged = _union(((s, s + d) for _, s, d in ops), lo, hi)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, d in ops:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped > 0:
                key = op_name(name)
                by_op[key] = by_op.get(key, 0) + clipped
                if is_kernel(name, program):
                    kernel_ns += clipped
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": busy / len(planes) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "kernel_s": kernel_ns / 1e9,
            "top_ops": [[n, t / 1e9] for n, t in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:top]],
            "gaps": [[_label(doc["host"], s, e), (e - s) / 1e9]
                     for s, e in gaps[:top]]}


def _label(host: list, s: int, e: int) -> str:
    """What the host did in [s, e]: the host event that overlaps it most."""
    best, label = 0, "no host event"
    for thread, name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best:
            best, label = ov, f"{thread}: {name}" if thread else name
    return label
