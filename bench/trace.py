"""Reduce a JAX profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: the device's ops, the benchmark's host spans, and the
window they lie in.

Device ops are the events of the ``XLA Ops`` line of each
``/device:<platform>:<n>`` plane, named by their HLO instruction.  An op
is a Pallas kernel when that instruction is a ``tpu_custom_call``; the
kernel's name is the instruction's, less its ``.<n>`` suffix
(``systolic_matmul``, ``fused_affine_act``).  Host spans are the
``TraceAnnotation``s the client writes (``pick``, ``invoke``, ``wait``,
``fetch``) inside one ``window`` span.

The device's clock in the trace can lag the host's by a millisecond or
more.  Each device program (``XLA Modules``) carries the ``run_id`` of
the host's ``DoEnqueueProgram`` that launched it; the device events are
shifted by the least amount that starts no program before its launch
ended.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

import numpy as np

SPANS = ("pick", "invoke", "wait", "fetch")
WINDOW = "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "DoEnqueueProgram"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
TOP = 10


@dataclass
class Trace:
    names: list            # device op names
    kernels: list          # Pallas kernel name of each op, or None
    device: np.ndarray     # device index of each op
    start: np.ndarray      # ns
    end: np.ndarray        # ns
    spans: list            # (name, start_ns, end_ns) host spans
    window: tuple          # (start_ns, end_ns)
    n_devices: int
    shift_ns: float = 0.0  # added to the device's clock

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, d: int) -> np.ndarray:
        """Disjoint busy intervals of device ``d`` inside the window."""
        lo, hi = self.window
        sel = self.device == d
        return union(np.clip(self.start[sel], lo, hi),
                     np.clip(self.end[sel], lo, hi))

    def busy_s(self) -> float:
        """Union of the intervals in which an op ran, mean over devices."""
        return float(np.mean([np.sum(b[:, 1] - b[:, 0]) if len(b) else 0.0
                              for b in map(self._busy, range(self.n_devices))])
                     ) / 1e9

    def kernel_s(self, fragment: str) -> float:
        """Summed durations of the Pallas kernels whose name holds
        ``fragment``, mean over devices."""
        sel = np.array([k is not None and fragment in k for k in self.kernels],
                       dtype=bool)
        return float(np.sum(self.end[sel] - self.start[sel])) / 1e9 / self.n_devices

    def xla_s(self) -> float:
        """Summed durations of the ops that are not Pallas kernels."""
        sel = np.array([k is None for k in self.kernels], dtype=bool)
        return float(np.sum(self.end[sel] - self.start[sel])) / 1e9 / self.n_devices

    def idle_gaps(self) -> list:
        """``(label, start_ns, end_ns)`` of each idle gap of device 0 in the
        window, labelled by the host span that covers most of it."""
        lo, hi = self.window
        edges = np.concatenate([[lo], self._busy(0).ravel(), [hi]])
        edges = edges.reshape(-1, 2)
        edges = edges[edges[:, 1] > edges[:, 0]]
        return [(n, a, b) for n, (a, b) in zip(labels(self.spans, edges), edges)]

    def breakdown(self) -> dict:
        """The ops that took most device time (by name), and the idle time
        by what the host was doing, each at most ``TOP`` entries."""
        ops = {}
        for n, k, s, e in zip(self.names, self.kernels, self.start, self.end):
            key = k or n
            ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
        idle = {}
        for lab, a, b in self.idle_gaps():
            idle[lab] = idle.get(lab, 0.0) + (b - a) / 1e9
        top = lambda d: sorted(([k, v] for k, v in d.items()),
                               key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": top(ops), "idle_gaps": top(idle)}


def union(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Disjoint, sorted ``(n, 2)`` intervals covering the given ones."""
    keep = end > start
    start, end = start[keep], end[keep]
    if not len(start):
        return np.zeros((0, 2))
    order = np.argsort(start, kind="stable")
    start, end = start[order], np.maximum.accumulate(end[order])
    new = np.concatenate([[True], start[1:] > end[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(start) - 1]])
    return np.stack([start[first], end[last]], axis=1)


def labels(spans: list, gaps: np.ndarray) -> list:
    """For each ``(start, end)`` gap, the name of the span that overlaps it
    most, or ``other``."""
    spans = sorted(spans, key=lambda sp: sp[1])
    start = np.array([sp[1] for sp in spans], dtype=float)
    reach = np.maximum.accumulate(np.array([sp[2] for sp in spans], dtype=float))
    first = np.searchsorted(reach, gaps[:, 0], side="right")
    stop = np.searchsorted(start, gaps[:, 1], side="left")
    out = []
    for (a, b), i, j in zip(gaps, first, stop):
        best, name = 0.0, "other"
        for n, s, e in spans[i:j]:
            o = min(b, e) - max(a, s)
            if o > best:
                best, name = o, n
        out.append(name)
    return out


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def load(path, n_devices: int = 1) -> Trace:
    """Read the trace under ``path`` (a directory the profiler wrote, or an
    ``.xplane.pb`` file)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        path = files[-1]
    pd = ProfileData.from_file(str(path))
    names, kernels, device, start, end, spans = [], [], [], [], [], []
    window, module_start, launch_end = None, {}, {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < n_devices:
            d = int(m.group(1))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        rid = _stats(ev).get("run_id")
                        if rid is not None:
                            module_start[(d, rid)] = ev.start_ns
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    names.append(op_name(ev.name))
                    kernels.append(kernel_name(ev.name))
                    device.append(d)
                    start.append(ev.start_ns)
                    end.append(ev.start_ns + ev.duration_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name == LAUNCH:
                        st = _stats(ev)
                        rid = st.get("run_id")
                        if rid is not None:
                            key = (int(st.get("device_ordinal", 0)), rid)
                            launch_end[key] = ev.start_ns + ev.duration_ns
    if window is None:
        raise ValueError(f"no {WINDOW!r} span in {path}")
    lags = [launch_end[k] - t for k, t in module_start.items()
            if k in launch_end]
    shift = max(0.0, max(lags)) if lags else 0.0
    return Trace(names, kernels, np.array(device, dtype=int),
                 np.array(start, dtype=float) + shift,
                 np.array(end, dtype=float) + shift,
                 spans, window, n_devices, shift)


_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(text: str) -> str:
    """``%fusion.95 = f32[9,9]{0,1:T(8,128)} fusion(), ...`` ->
    ``fusion.95 = f32[9,9] fusion``."""
    text = _LAYOUT.sub("", text)
    head = text.split("(", 1)[0] if " = " in text else text
    return head.lstrip("%").strip()


def kernel_name(text: str):
    """The Pallas kernel an op runs (its instruction's name less the
    ``.<n>`` suffix), or None for an XLA op."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    inst = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", inst)
