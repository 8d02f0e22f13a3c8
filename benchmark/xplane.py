"""Every device operation of a profiler trace with the program's own names:
what the readers of single kernels and single scopes need, and
``benchmark/trace.py``'s reduction (busy time, programs, the ten longest
operations) does not keep.

An ``XLA Ops`` event of a ``/device:TPU:<n>`` plane is named by its whole HLO
instruction (``%flash_fwd.3 = (bf16[...]) custom-call(...),
custom_call_target="tpu_custom_call"``).  Two names of the program reach it
(PERF.md, PR 24, looked up on a chip trace):

- a ``pallas_call``'s ``name=`` is the component of its ``op_name`` before
  ``pallas_call``, and XLA names the INSTRUCTION after that component
  (``%flash_fwd.3``, ``%fused_ce_bwd.1``);
- ``jax.named_scope`` ends up in the instruction's ``metadata op_name``
  (``jit(train_step)/transpose(jvp(loss_head))/fused_ce_bwd/pallas_call``),
  which the profiler keeps as the stat ``tf_op`` of the event's METADATA,
  not of the event.  ``jax.profiler.ProfileData`` shows an event's own stats
  only, so this file reads the ``.xplane.pb`` itself: the protobuf wire
  format of ``XSpace`` (tsl/profiler/protobuf/xplane.proto), the device
  planes only (a host plane can hold millions of events and is skipped by
  its length).  A fusion carries the ``op_name`` of one of the instructions
  fused into it, so a scope's sum is exact inside the scope and approximate
  where XLA fused across its edge.

``load(path)`` reads a path once a process.  A recorded cut
(``*.ops.json.gz``, written by this file's ``__main__``) loads the same way:
the readers' tests run over one.
"""
from __future__ import annotations

import gzip
import json
import re
from typing import Callable, Dict, Iterator, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'


class Op(NamedTuple):
    name: str       # the instruction's name, ``%flash_fwd.3``
    scope: str      # its ``op_name``, '' where the compiler kept none
    mosaic: bool    # a Pallas kernel
    start_s: float
    dur_s: float


# ------------------------------------------------------------ wire format

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for field, item in _fields(view):
        if field == 1:
            key = item
        elif field == 2:
            value = item
    return key, value


def _plane_ops(plane) -> List[Op]:
    """The ``XLA Ops`` events of one ``XPlane`` message."""
    stat_names: Dict[int, str] = {}
    metadata: Dict[int, memoryview] = {}
    lines = []
    for field, item in _fields(plane):
        if field == 3:
            lines.append(item)
        elif field == 4:
            key, value = _map_entry(item)
            metadata[key] = value
        elif field == 5:
            key, value = _map_entry(item)
            for f, v in _fields(value):
                if f == 2:
                    stat_names[key] = _text(v)
    op_name_stat = {k for k, v in stat_names.items() if v == "tf_op"}

    def describe(view):
        name = scope = ""
        for field, item in _fields(view):
            if field == 2:
                name = _text(item)
            elif field == 5:  # XStat of the metadata
                stat = dict(_fields(item))
                if stat.get(1) in op_name_stat:
                    scope = (_text(stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
        short = name.partition(" = ")[0]
        return short, scope.rstrip(":"), MOSAIC_TARGET in name

    described: Dict[int, tuple] = {}
    ops: List[Op] = []
    for line in lines:
        name, t0_ns, events = "", 0, []
        for field, item in _fields(line):
            if field == 2:
                name = _text(item)
            elif field == 3:
                t0_ns = item
            elif field == 4:
                events.append(item)
        if name != OPS_LINE:
            continue
        for event in events:
            meta = offset_ps = dur_ps = 0
            for field, item in _fields(event):
                if field == 1:
                    meta = item
                elif field == 2:
                    offset_ps = item
                elif field == 3:
                    dur_ps = item
            if meta not in described:
                described[meta] = describe(metadata[meta])
            ops.append(Op(*described[meta], t0_ns * 1e-9 + offset_ps * 1e-12,
                          dur_ps * 1e-12))
    return ops


def parse(raw: bytes) -> Dict[str, List[Op]]:
    """``{device plane: [Op, ...]}`` of a serialized ``XSpace``."""
    out: Dict[str, List[Op]] = {}
    for field, plane in _fields(memoryview(raw)):
        if field != 1:
            continue
        name = next((_text(v) for f, v in _fields(plane) if f == 2), "")
        if DEVICE_PLANE.match(name):
            out[name] = _plane_ops(plane)
    return out


# ------------------------------------------------------------------ loading

_LOADED: Dict[str, Dict[str, List[Op]]] = {}


def load(path: str) -> Dict[str, List[Op]]:
    """The device operations of ``path``, read once a process."""
    if path not in _LOADED:
        if path.endswith(".json.gz"):
            with gzip.open(path, "rt") as fp:
                _LOADED[path] = {
                    plane: [Op(*op) for op in ops]
                    for plane, ops in json.load(fp)["ops"].items()
                }
        else:
            with open(path, "rb") as fp:
                _LOADED[path] = parse(fp.read())
    return _LOADED[path]


def ops_of(run) -> Optional[Dict[str, List[Op]]]:
    """The device operations of a run's trace; None where there is no
    trace to read (an untraced run, a CPU)."""
    path = run.notes.get("xplane")
    return load(path) if path else None


# --------------------------------------------------------------- reductions

def in_scope(scope: str) -> Callable[[Op], bool]:
    """Operations whose ``op_name`` has ``scope`` among its components, bare
    or wrapped by a transformation: ``jvp(loss_head)/...``,
    ``transpose(jvp(loss_head))/...``, ``.../TransformerLM/loss_head/ln``."""
    pattern = re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)")
    return lambda op: bool(pattern.search(op.scope))


def kernel(prefix: str) -> Callable[[Op], bool]:
    """Pallas kernels whose ``name=`` starts with ``prefix``.  The name is
    a component of the call's ``op_name`` (``.../attn/flash_fwd/pallas_call``)
    and XLA names the instruction after it: ``%flash_fwd.3`` under a scope,
    ``%transpose_jvp_flash_bwd__.1`` where the kernel's name is itself the
    outermost component.  Either is enough."""
    in_op_name = re.compile(rf"(?:^|[/(]){re.escape(prefix)}")
    in_instruction = re.compile(rf"(?:^%?|_){re.escape(prefix)}")
    return lambda op: op.mosaic and bool(
        in_op_name.search(op.scope) or in_instruction.search(op.name)
    )


def seconds(ops: Dict[str, List[Op]], select: Callable[[Op], bool]) -> float:
    """Device time of the selected operations, averaged over the chips."""
    if not ops:
        return 0.0
    total = sum(
        op.dur_s for plane_ops in ops.values() for op in plane_ops if select(op)
    )
    return total / len(ops)


def ms_per_step(run, select: Callable[[Op], bool]) -> Optional[float]:
    """``seconds(...)`` of a run's trace per execution of its step program
    (``benchmark/trace.py`` counts those); None where nothing matches, as
    under a program that has no such name."""
    from benchmark import trace

    ops = ops_of(run)
    if not ops or not run.trace or not run.trace.get("devices"):
        return None
    program = trace.main_program(run.trace)
    total = seconds(ops, select)
    if not program or not total:
        return None
    return total / run.trace["programs"][program]["count"] * 1e3


# ---------------------------------------------------------------------- cut

def cut(ops: Dict[str, List[Op]], start_s: float, end_s: float):
    """The operations that start inside ``[start_s, end_s)``, whole."""
    return {
        plane: [op for op in plane_ops if start_s <= op.start_s < end_s]
        for plane, plane_ops in ops.items()
    }


if __name__ == "__main__":
    # python benchmark/xplane.py <file.xplane.pb> <out.ops.json.gz> [<nth>]
    # keeps the nth (default second) execution of the longest program: its
    # operations here, and the same interval of benchmark/trace.py's planes
    # (device lines and the host's annotations) beside them, so that one
    # file checks this reduction against that one.
    import sys

    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    from benchmark import trace

    source, target = sys.argv[1:3]
    nth = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    planes = trace.load(source)
    device = next(p for p in planes if DEVICE_PLANE.match(p))
    modules = sorted(planes[device][trace.MODULES_LINE], key=lambda e: e[1])
    longest = trace.program_name(max(modules, key=lambda e: e[2])[0])
    picked = [e for e in modules if trace.program_name(e[0]) == longest][nth]
    lo, hi = picked[1] - 2e-6, picked[1] + picked[2] + 2e-6
    kept = trace.cut(planes, lo, hi)
    kept = {
        plane: {
            line: [e for e in events if e[2] >= 1e-5 or plane != trace.HOST_PLANE]
            for line, events in lines.items()
        }
        for plane, lines in kept.items()
        if DEVICE_PLANE.match(plane) or plane == trace.HOST_PLANE
    }
    ops = cut(load(source), lo, hi)
    with gzip.open(target, "wt") as fp:
        json.dump({"planes": kept, "ops": ops}, fp)
    print(json.dumps({
        "program": longest, "start_s": lo, "end_s": hi,
        "ops": {plane: len(found) for plane, found in ops.items()},
    }))
