"""Map a trace's device ops to the served program's named scopes, and read
the program's own host spans.

The f1+f2 program names its work with ``jax.named_scope``: ``f1`` (the
vector-engine pre-processing), ``f2`` (the model), and inside ``f2`` one
scope ``conv<i>`` per convolution in program order, each split into
``im2col``, ``weights`` and ``gemm``.  The compiled program's text keeps
each instruction's scope in its metadata
(``op_name="jit(infer)/f2/conv3/im2col/..."``) and the trace names each
device op by the same instruction, so the map is read from the text the
program compiles to.  A fusion carries the scope of its fused root: work
that XLA fuses across two scopes (a convolution's output slice with the
activation after it) is put down to the root's.

``DSCSExecutor.__call__`` writes the host spans ``f1f2``, ``f3`` and
``account`` inside the client's ``invoke``, each with the call's number
as its ``call`` stat.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np

from bench import trace as tr

PROGRAM_SPANS = ("f1f2", "f3", "account")
CONV = re.compile(r"^f2/conv\d+(/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_INSTRUCTION = re.compile(r"^%([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")


def scope_of(op_name: str) -> str:
    """``jit(infer)/f2/conv3/gemm/jit(_pad)/pad`` -> ``f2/conv3/gemm``: the
    named scopes between the program's ``jit(...)`` and the first nested
    ``jit(...)`` or the primitive; ``""`` where there are none.  Of an
    instruction that XLA merged from several (``a;b``), the first scope."""
    for name in op_name.split(";"):
        parts = name.split("/")
        if not parts[0].startswith("jit("):
            continue              # a parameter's name, not a traced op
        scope = []
        for p in parts[1:-1]:
            if "(" in p:
                break
            scope.append(p)
        if scope:
            return "/".join(scope)
    return ""


def scope_map(hlo_text: str, neighbours: bool = True) -> dict:
    """Device op (as :func:`bench.trace.op_name` names it) -> scope path,
    for every instruction of the compiled program's entry computation.

    A fusion takes the scope of its fused root.  With ``neighbours``, an
    instruction that XLA added with no scope of its own (a layout copy of
    a parameter or of the im2col patches) borrows one (:func:`_borrow`)."""
    roots = {}          # computation -> its root's scope
    entry = []          # (name, op key, scope, called computation, operands)
    comp = in_entry = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            in_entry = comp is not None and line.startswith("ENTRY")
            continue
        text = line.strip()
        is_root = text.startswith("ROOT ")
        text = text.removeprefix("ROOT ")
        m = _INSTRUCTION.match(text)
        if comp is None or m is None:
            continue
        on = _OP_NAME.search(text)
        scope = scope_of(on.group(1)) if on else ""
        if is_root:
            roots[comp] = scope
        if in_entry:
            body = text.split(" = ", 1)[1]
            calls = _CALLS.search(body)
            entry.append((m.group(1), tr.op_name(text), scope,
                          calls.group(1) if calls else None,
                          _OPERAND.findall(body.split(", metadata=")[0])))
    scope = {name: roots.get(called) or own
             for name, _, own, called, _ in entry}
    if neighbours:
        _borrow(scope, {name: operands for name, *_, operands in entry})
    return {key: scope[name] for name, key, *_ in entry}


def _borrow(scope: dict, operands: dict) -> None:
    """Give each instruction with no scope a neighbour's, in place.  Work
    is done for its consumer, so it takes the scope of its first user that
    has one (following users as far as need be), unless it sits between
    two steps of one scope (the patches' layout copy between a
    convolution's ``im2col`` and its ``gemm``): then it finishes the
    earlier step, its first scoped operand's.  ``scope`` and ``operands``
    are in program order."""
    users = defaultdict(list)
    for name, ops in operands.items():
        for o in ops:
            users[o].append(name)
    after = dict(scope)
    changed = True
    while changed:
        changed = False
        for name in after:
            if not after[name]:
                after[name] = next((after[u] for u in users[name]
                                    if after[u]), "")
                changed |= bool(after[name])
    parent = lambda sc: sc.rpartition("/")[0]
    for name in [n for n, sc in scope.items() if not sc]:
        before = next((scope[o] for o in operands[name] if scope.get(o)), "")
        same = not after[name] or parent(before) == parent(after[name])
        scope[name] = before if before and same else after[name]


def scope_seconds(trace, smap: dict) -> tuple:
    """``({scope: seconds}, unscoped_s, outside_s)``, mean over devices:
    device time by scope of the ops of the mapped program, the time of its
    ops that carry no scope, and the time of ops of other programs (f3's
    top-1)."""
    by = defaultdict(float)
    unscoped = outside = 0.0
    for n, s, e in zip(trace.names, trace.start, trace.end):
        d = (e - s) / 1e9 / trace.n_devices
        scope = smap.get(n)
        if scope is None:
            outside += d
        elif scope:
            by[scope] += d
        else:
            unscoped += d
    return dict(by), unscoped, outside


def program_text(cfg: dict, executor=None) -> str:
    """The compiled f1+f2 program's text for a configuration: ``executor``'s
    own, or that of one built as the benchmark's runner builds it (the same
    program compiles to the same instruction names)."""
    import jax.numpy as jnp
    if executor is None:
        from bench.run import load_module
        root = Path(__file__).resolve().parents[1]
        executor = load_module(root, "runners", cfg["runner"]).Server(
            cfg, 0).executor
    s = int(cfg["image_size"])
    # uncommitted, as the request pool's frames are: a committed frame
    # lowers to another program, which would compile anew
    frame = jnp.zeros((cfg["batch"], s, s, cfg["in_channels"]), jnp.uint8)
    return executor.lower(frame).compile().as_text()


def for_run(run):
    """``(scope seconds, unscoped_s, outside_s)`` of a traced run, or None
    where the trace holds no op of the program or the program names no
    scope.  Read once per run (kept on ``run``); prints on standard error
    the share of the program's device time that found no scope, before
    and after XLA's layout copies take their neighbours' scope."""
    if not hasattr(run, "scopes"):
        run.scopes = _read(run)
    return run.scopes


def _read(run):
    if run.trace is None or not run.trace.names:
        return None
    text = program_text(run.cfg)
    smap = scope_map(text)
    by, unscoped, outside = scope_seconds(run.trace, smap)
    total = sum(by.values()) + unscoped
    if not any(smap.values()) or total <= 0:
        return None
    own = scope_seconds(run.trace, scope_map(text, False))[1]
    print(f"scopes: {100 * unscoped / total:.4f}% of the f1+f2 program's "
          f"device time ({unscoped * 1e3:.4f} of {total * 1e3:.4f} ms) "
          f"found no scope, {100 * own / total:.4f}% had none of its own; "
          f"{outside * 1e3:.4f} ms ran in other programs",
          file=sys.stderr, flush=True)
    return by, unscoped, outside


def conv_seconds(by: dict) -> float:
    return sum(v for k, v in by.items() if CONV.match(k))


def host_spans(path, names=PROGRAM_SPANS) -> list:
    """``(name, start_ns, end_ns, call)`` of the host spans ``names`` in the
    trace under ``path`` (a profiler directory or an ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(_xplane(path)))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats).get("call")))
    return sorted(out, key=lambda sp: sp[1])


def host_ms(spans: list, window: tuple, requests: int) -> dict:
    """Host time per request by span name, of the spans inside ``window``:
    ``dispatch_host_ms`` (``f1f2`` plus ``f3``) and ``account_host_ms``
    besides each name's own."""
    lo, hi = window
    ms = defaultdict(float)
    for sp in spans:
        if lo <= sp[1] and sp[2] <= hi:
            ms[sp[0]] += (sp[2] - sp[1]) / 1e6 / requests
    ms["dispatch_host_ms"] = ms["f1f2"] + ms["f3"]
    ms["account_host_ms"] = ms["account"]
    return dict(ms)


def _xplane(path) -> Path:
    path = Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no *.xplane.pb under {path}")
        return files[-1]
    return path


def innermost_labels(spans: list, gaps: np.ndarray) -> list:
    """For each ``(start, end)`` gap, the span that covers most of it, then
    within that span the nested span that covers more of the gap than the
    rest of its parent does, and so on down; ``other`` where no span
    overlaps the gap.  Where no span nests in another this is
    :func:`bench.trace.labels`."""
    spans = sorted(spans, key=lambda sp: sp[1])
    start = np.array([sp[1] for sp in spans], dtype=float)
    reach = np.maximum.accumulate(np.array([sp[2] for sp in spans],
                                           dtype=float))
    first = np.searchsorted(reach, gaps[:, 0], side="right")
    stop = np.searchsorted(start, gaps[:, 1], side="left")
    out = []
    for (a, b), i, j in zip(gaps, first, stop):
        cand = [(sp[0], sp[1], sp[2], min(b, sp[2]) - max(a, sp[1]))
                for sp in spans[i:j]]
        cand = [c for c in cand if c[3] > 0]
        out.append(_descend(cand))
    return out


def _descend(cand: list) -> str:
    """The innermost label for one gap; ``cand`` holds ``(name, start,
    end, overlap)`` of the spans that overlap it, by start."""
    order = {id(c): k for k, c in enumerate(cand)}

    def inside(c, p):       # of two spans alike, the later is inside
        return (c is not p and p[1] <= c[1] and c[2] <= p[2]
                and (p[1:3] != c[1:3] or order[id(p)] < order[id(c)]))

    def children(parent):
        pool = [c for c in cand if parent is None or inside(c, parent)]
        return [c for c in pool
                if not any(inside(c, q) for q in pool if q is not c)]
    node, covered = None, 0.0
    while True:
        kids = children(node)
        if not kids:
            break
        best = max(kids, key=lambda c: c[3])
        rest = covered - sum(c[3] for c in kids)
        if node is not None and best[3] <= rest:
            break
        node, covered = best, best[3]
    return node[0] if node is not None else "other"
