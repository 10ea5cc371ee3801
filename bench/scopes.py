"""Device time per program name scope, read from a profiler file.

The program runs the parts of a layer under ``jax.named_scope`` names
(``moe.route``, ``moe.experts``, ``mla.attend``, ...). XLA carries the
name stack of each operation into the device trace: on the TPU every
event of a device plane's ``XLA Ops`` line names its operation, and that
operation's ``tf_op`` stat holds the stack
(``jit(decode_sample_step)/.../moe.experts/ragged_dot``). This reads the
``.xplane.pb`` itself (the reduced :class:`bench.trace.Trace` keeps no
stats) and sums, for each kind of executable call, the device time of
the operations that ran inside those calls by the first program scope
in their stack. A fusion counts for the scope of the operation XLA named
it after. Containers (``while``, ``conditional``, ``call``) are left out
and their contents counted, as ``bench.trace`` does.

XLA's TPU lowering of ``jax.lax.ragged_dot`` gives its two operations,
the grouped-matmul kernel (``ragged-dot-none``) and the call that lays
out its groups (``ragged-dot-metadata``), no name stack; the program
calls ``ragged_dot`` only for the held experts, so those count for
``moe.experts`` (:data:`KERNELS`).
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace as T

SCOPE = re.compile(r"(?:^|/)((?:moe|mla)\.[a-z_]+)(?=/)")
# operations named by their kernel, not by the program's scopes
KERNELS = {"ragged-dot": "moe.experts"}


def _xspace(path: str):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        from tsl.profiler.protobuf import xplane_pb2
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs


def _strings(stats, stat_names) -> Iterable[Tuple[str, str]]:
    for s in stats:
        name = stat_names.get(s.metadata_id, "")
        if s.HasField("str_value"):
            yield name, s.str_value
        elif s.HasField("ref_value"):
            yield name, stat_names.get(s.ref_value, "")


def scope_of(texts: Iterable[Tuple[str, str]],
             op: str = "") -> Optional[str]:
    """The first program scope in the ``tf_op`` stat, else in any
    string stat of the operation ``op``, else the scope of its kernel
    (:data:`KERNELS`)."""
    texts = list(texts)
    for name, value in sorted(texts, key=lambda t: t[0] != "tf_op"):
        m = SCOPE.search(value)
        if m:
            return m.group(1)
    for prefix, scope in KERNELS.items():
        if op.startswith(prefix):
            return scope
    return None


def _plane_ops(plane):
    """(modules, ops) of one device plane: (start_ns, end_ns, name) and
    (start_ns, end_ns, scope or None)."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    modules: List[tuple] = []
    ops: List[tuple] = []
    for line in plane.lines:
        if line.name not in (T.OPS_LINE, T.MODULES_LINE):
            continue
        base = line.timestamp_ns
        for e in line.events:
            md = plane.event_metadata[e.metadata_id]
            s = base + e.offset_ps / 1e3
            end = s + e.duration_ps / 1e3
            if line.name == T.MODULES_LINE:
                modules.append((s, end, md.name))
                continue
            op = T.op_name(md.name)
            if T.is_container(op):
                continue
            texts = list(_strings(e.stats, stat_names)) \
                + list(_strings(md.stats, stat_names))
            ops.append((s, end, scope_of(texts, op)))
    return modules, ops


def scope_ns(path: str, calls: Dict[str, str]) -> dict:
    """For each kind of call (``{"decode": "decode_sample_step", ...}``,
    a mark its module name contains): {"calls": number of calls on the
    device, "scopes": {scope: device ns of its operations inside those
    calls}}, averaged over the device planes."""
    xs = _xspace(path)
    planes = [p for p in xs.planes if p.name.startswith(T.DEVICE_PREFIX)]
    out = {kind: {"calls": 0, "scopes": {}} for kind in calls}
    n = 0
    for plane in planes:
        modules, ops = _plane_ops(plane)
        if not ops:
            continue
        n += 1
        ops.sort()
        starts = [o[0] for o in ops]
        for kind, mark in calls.items():
            mine = [m for m in modules if mark in m[2]]
            out[kind]["calls"] += len(mine)
            acc = out[kind]["scopes"]
            for s, e, _ in mine:
                for os_, oe, scope in ops[bisect.bisect_left(starts, s):
                                          bisect.bisect_right(starts, e)]:
                    if scope is not None:
                        acc[scope] = acc.get(scope, 0.0) + (min(oe, e) - os_)
    if n > 1:
        for kind in out:
            out[kind]["calls"] /= n
            out[kind]["scopes"] = {k: v / n for k, v in
                                   out[kind]["scopes"].items()}
    return out


def per_call_ms(record: dict, kind: str, prefix: str) -> Optional[float]:
    """Device ms per call of ``kind`` under the scopes starting with
    ``prefix`` (``"moe."``), from a run's ``scope_ns``; None where the
    run has none."""
    sn = (record.get("scope_ns") or {}).get(kind)
    if not sn or not sn["calls"]:
        return None
    ns = sum(v for k, v in sn["scopes"].items() if k.startswith(prefix))
    if ns == 0:
        return None
    return ns / sn["calls"] / 1e6
