"""Device time by model part, from the trace's own ``tf_op``.

    python3 -m chipbench.opscopes <trace dir or .xplane.pb> [--program jit_decode_chunk] [--depth 3]

prints the device time of a trace by scope path: seconds, share, XLA's own
``bytes_accessed`` and ``flops`` of the operations under each path and the
rate they make together. It is what a ``perf_opt`` builder reads in place of
joining ``scripts/compiled_chunk.py`` to a trace by hand.

What the file holds that ``jax.profiler.ProfileData`` does not show (looked at
by hand, PR 38, on ``tests/data/tiny_annotated_v5e.xplane.pb``): an event of
the line ``XLA Ops`` carries only its own stats (``device_offset_ps``,
``device_duration_ps``), but it points by ``metadata_id`` at an
``XEventMetadata`` of its plane, one per HLO instruction, whose stats are
``tf_op`` (the instruction's ``op_name`` followed by ``:``, as in
``jit(decode_chunk)/while/body/closed_call/Llama/block_3/attn/q/dot_general:``),
``hlo_category`` (``loop fusion``, ``data formatting``, ``copy-done`` ...),
``source`` (file:line), ``program_id``, ``flops``, ``bytes_accessed``. A stat's
string is either in the stat or a reference to a ``XStatMetadata`` name.
Flax opens a ``jax.named_scope`` round every module call and the program
opens one round the work no module owns (docs/observability.md "Device time
by model part"), so an ``op_name`` is a path of model parts. Instructions the
compiler made up (the weights' prefetch ``copy-start`` / ``copy-done`` /
``slice-done``, layout copies) have no ``tf_op``: they are told by category,
and stay a part of their own (``data_movement``). The plane
``/host:metadata`` holds every program's ``HloProto`` under its runs' name;
through it such an instruction also gets its first named user's ``op_name``
(``ScopedOp.consumer``, ``consumers``), so the table says whose weights a
prefetch waited for.

The ``XSpace`` is decoded here, by a reader of the protobuf wire format that
knows the few fields it needs (``_read_wire``): the only generated
``xplane_pb2`` installed comes with the whole of TensorFlow, and
``tests/test_opscopes.py`` holds this reader to its answer on every recorded
trace. Imports nothing of the program.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import os
import re
import struct
import sys
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from chipbench import xplane

PARTS = ("mixer", "ffn", "head", "glue", "optimizer", "data_movement", "unscoped")


class ScopedOp(NamedTuple):
    """One executed instruction of the line ``XLA Ops``."""

    program: str        # its module run's name, ``jit_prefill(<fingerprint>)`` ("" outside any run)
    tf_op: str          # the instruction's op_name ("" where the compiler made it up)
    hlo_category: str
    start_s: float
    end_s: float
    name: str           # the HLO text, as ``xplane.Trace`` names the operation
    flops: int          # XLA's own count for one execution
    bytes_accessed: int
    run: int            # index of its module run on the device, -1 outside any
    consumer: str = ""  # an instruction without a name stack: its first named user's op_name


# ---- the trace file

class _EventMeta(NamedTuple):
    name: str
    stats: Dict[str, object]


class _Line(NamedTuple):
    name: str
    timestamp_ns: int
    events: List[Tuple[int, int, int]]  # (metadata_id, offset_ps, duration_ps)


class _Plane(NamedTuple):
    name: str
    lines: List[_Line]
    event_metadata: Dict[int, _EventMeta]  # of a device plane; of ``/host:metadata``, each program's ``Hlo Proto``


HLO_PLANE = "/host:metadata"  # one event metadata a program, named as its runs are, holding its HloProto


def _device(name: str) -> bool:
    return name.startswith("/device:TPU:")


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, pos
        shift += 7


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _fields(buf: bytes, pos: int, end: int):
    """(field number, value) of a message's fields: an int for a varint, a
    (start, end) pair into ``buf`` for a length-delimited or fixed field."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 1:
            value, pos = (pos, pos + 8), pos + 8
        elif wire == 5:
            value, pos = (pos, pos + 4), pos + 4
        else:
            raise ValueError(f"opscopes: wire type {wire} at byte {pos} is no XSpace field")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _wire_stat(buf: bytes, span: Tuple[int, int]):
    """(stat metadata id, value, whether the value is a reference) of an ``XStat``."""
    key, value, ref = 0, None, False
    for field, v in _fields(buf, *span):
        if field == 1:
            key = v
        elif field == 2:
            value = struct.unpack_from("<d", buf, v[0])[0]
        elif field == 3:
            value = v
        elif field == 4:
            value = _signed(v)
        elif field == 5:
            value = _text(buf, v)
        elif field == 6:
            value = buf[v[0]:v[1]]
        elif field == 7:
            value, ref = v, True
    return key, value, ref


def _wire_map_entry(buf: bytes, span: Tuple[int, int]) -> Optional[Tuple[int, int]]:
    """The value's span of a ``map<int64, message>`` entry."""
    for field, v in _fields(buf, *span):
        if field == 2:
            return v
    return None


def _wire_line(buf: bytes, span: Tuple[int, int], wanted=("XLA Modules", "XLA Ops")) -> Optional[_Line]:
    """A line of ``wanted``, else ``None`` (its events are not decoded)."""
    name, timestamp_ns, event_spans = "", 0, []
    for field, v in _fields(buf, *span):
        if field == 2:
            name = _text(buf, v)
        elif field == 3:
            timestamp_ns = _signed(v)
        elif field == 4:
            event_spans.append(v)
    if name not in wanted:
        return None
    return _Line(name, timestamp_ns, [_wire_event(buf, a, b) for a, b in event_spans])


def _wire_event(buf: bytes, pos: int, end: int) -> Tuple[int, int, int]:
    """(metadata_id, offset_ps, duration_ps) of an ``XEvent``; its own stats
    are skipped. A trace holds millions: the three varints are read in
    place, not through ``_fields``."""
    out = [0, 0, 0, 0]
    while pos < end:
        key = buf[pos]
        pos += 1
        if key & 7 == 0 and key < 0x20:
            result = shift = 0
            while True:
                byte = buf[pos]
                pos += 1
                result |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            out[key >> 3] = result
        elif key == 0x22 and buf[pos] < 0x80:
            pos += 1 + buf[pos]
        else:  # a field or a length this fast path does not know
            for f, x in _fields(buf, pos - 1, end):
                if f in (1, 2, 3) and isinstance(x, int):
                    out[f] = x
            break
    return out[1], out[2], out[3]


def _wire_plane(buf: bytes, span: Tuple[int, int]) -> _Plane:
    name, line_spans, meta_spans, stat_names = "", [], [], {}
    for field, v in _fields(buf, *span):
        if field == 2:
            name = _text(buf, v)
        elif field == 3:
            line_spans.append(v)
        elif field == 4:
            meta_spans.append(v)
        elif field == 5:
            entry = _wire_map_entry(buf, v)
            if entry is not None:
                sid, sname = 0, ""
                for f, x in _fields(buf, *entry):
                    if f == 1:
                        sid = x
                    elif f == 2:
                        sname = _text(buf, x)
                stat_names[sid] = sname
    if not _device(name) and name != HLO_PLANE:
        return _Plane(name, [], {})
    metadata = {}
    for entry_span in meta_spans:
        entry = _wire_map_entry(buf, entry_span)
        if entry is None:
            continue
        mid, mname, stats = 0, "", {}
        for f, x in _fields(buf, *entry):
            if f == 1:
                mid = x
            elif f == 2:
                mname = _text(buf, x)
            elif f == 5:
                key, value, ref = _wire_stat(buf, x)
                stats[stat_names.get(key, str(key))] = stat_names.get(value, "") if ref else value
        metadata[mid] = _EventMeta(mname, stats)
    lines = [_wire_line(buf, s) for s in line_spans]
    return _Plane(name, [line for line in lines if line is not None], metadata)


def _read_wire(path: str) -> List[_Plane]:
    with open(path, "rb") as f:
        buf = f.read()
    return [_wire_plane(buf, v) for field, v in _fields(buf, 0, len(buf)) if field == 1]


_cache: Dict[tuple, Optional[Dict[str, List[ScopedOp]]]] = {}
_summaries: Dict[tuple, Tuple[int, Dict[str, float]]] = {}  # of the trace in ``_cache``


def scoped_ops(xplane_path: str) -> Optional[Dict[str, List[ScopedOp]]]:
    """{device plane: its ``XLA Ops`` events in start order}, each joined to
    its instruction's metadata and put to the ``XLA Modules`` run that
    contains it. ``None`` where no instruction of the trace carries a
    ``tf_op`` or an ``hlo_category``: a file without metadata stats says
    nothing about parts, which is not the same as zero seconds."""
    stat = os.stat(xplane_path)
    key = (str(xplane_path), stat.st_size, stat.st_mtime_ns)
    if key not in _cache:
        _cache.clear()  # one trace at a time: a run reads one, twelve times
        _summaries.clear()
        _cache[key] = _scoped_ops(_read_wire(str(xplane_path)))
    return _cache[key]


_PASSED_ON = ("tuple", "get-tuple-element", "bitcast")


def named(tf_op: str) -> bool:
    """Whether ``tf_op`` is a name stack. The compiler gives a copy of a
    program's argument the argument's name (``state['pool'][0][0]``): that
    names no line of the model."""
    return tf_op.startswith(("jit(", "pjit("))


def consumers(hlo_proto: bytes) -> Dict[str, str]:
    """{instruction name: ``op_name`` of its first named user} for the
    instructions of a compiled program that carry no ``op_name`` of their
    own: a weight's prefetch (``slice-start`` -> ``slice-done`` ->
    ``ConcatBitcast`` -> the fusion that multiplies by it) is followed
    through unnamed users, breadth first, inside its computation. One whose
    users end in the computation's root (a prefetch for the loop's next
    turn) finds none. Decoded from ``HloProto`` by field number:
    ``hlo_module`` 1, ``computations`` 3, ``instructions`` 2; an
    instruction's ``name`` 1, ``opcode`` 2, ``metadata`` 7 (``op_name``
    2), ``id`` 35, ``operand_ids`` 36."""
    buf = hlo_proto
    names: Dict[int, Tuple[str, str, str]] = {}  # id -> (name, opcode, op_name)
    users: Dict[int, List[int]] = {}
    for f1, module in _fields(buf, 0, len(buf)):
        if f1 != 1:
            continue
        for f2, computation in _fields(buf, *module):
            if f2 != 3:
                continue
            for f3, instruction in _fields(buf, *computation):
                if f3 != 2:
                    continue
                name = opcode = op_name = ""
                iid, operands = 0, []
                for f, v in _fields(buf, *instruction):
                    if f == 1:
                        name = _text(buf, v)
                    elif f == 2:
                        opcode = _text(buf, v)
                    elif f == 7:
                        for fm, vm in _fields(buf, *v):
                            if fm == 2 and named(_text(buf, vm)):
                                op_name = _text(buf, vm)
                    elif f == 35:
                        iid = v
                    elif f == 36:
                        if isinstance(v, int):
                            operands.append(v)
                        else:  # packed
                            pos, end = v
                            while pos < end:
                                operand, pos = _varint(buf, pos)
                                operands.append(operand)
                names[iid] = (name, opcode, op_name)
                for operand in operands:
                    users.setdefault(operand, []).append(iid)
    out: Dict[str, str] = {}
    for iid, (name, opcode, op_name) in names.items():
        if op_name or opcode in _PASSED_ON or opcode == "parameter":
            continue
        seen, queue = {iid}, [iid]
        while queue and name not in out:
            for user in users.get(queue.pop(0), ()):
                if user in seen or user not in names:
                    continue
                seen.add(user)
                if names[user][2]:
                    out[name] = names[user][2]
                    break
                queue.append(user)
    return out


def _scoped_ops(planes: List[_Plane]) -> Optional[Dict[str, List[ScopedOp]]]:
    out: Dict[str, List[ScopedOp]] = {}
    described = False
    hlo = {m.name: m.stats.get("Hlo Proto") for p in planes if p.name == HLO_PLANE for m in p.event_metadata.values()}
    consumer_of: Dict[str, Dict[str, str]] = {}  # program -> consumers(), decoded when a run of it needs one
    for plane in planes:
        if not _device(plane.name):
            continue
        by_name = {line.name: line for line in plane.lines}
        runs = []
        if "XLA Modules" in by_name:
            line = by_name["XLA Modules"]
            for mid, offset_ps, duration_ps in line.events:
                start = (line.timestamp_ns + offset_ps * 1e-3) * 1e-9
                meta = plane.event_metadata.get(mid)
                runs.append((start, start + duration_ps * 1e-12, meta.name if meta else ""))
            runs.sort()
        run_starts = [r[0] for r in runs]
        ops: List[ScopedOp] = []
        if "XLA Ops" in by_name:
            line = by_name["XLA Ops"]
            for mid, offset_ps, duration_ps in line.events:
                start = (line.timestamp_ns + offset_ps * 1e-3) * 1e-9
                end = start + duration_ps * 1e-12
                meta = plane.event_metadata.get(mid) or _EventMeta("", {})
                stats = meta.stats
                tf_op = str(stats.get("tf_op") or "")
                category = str(stats.get("hlo_category") or "")
                described = described or bool(tf_op or category)
                i = bisect.bisect_right(run_starts, start) - 1
                if i >= 0 and end <= runs[i][1]:
                    program = runs[i][2]
                else:
                    i, program = -1, ""
                tf_op, consumer = tf_op.rsplit(":", 1)[0], ""
                if program and not named(tf_op):
                    if program not in consumer_of:
                        proto = hlo.get(program)
                        consumer_of[program] = consumers(proto) if isinstance(proto, bytes) else {}
                    consumer = consumer_of[program].get(_instruction_name(meta.name), "")
                ops.append(ScopedOp(
                    program, tf_op, category, start, end, meta.name,
                    int(stats.get("flops") or 0), int(stats.get("bytes_accessed") or 0), i, consumer,
                ))
            ops.sort(key=lambda op: op.start_s)
        out[plane.name] = ops
    return out if described else None


_INSTRUCTION_NAME = re.compile(r"^%?([\w.\-]+)")


def _instruction_name(hlo_text: str) -> str:
    """``%copy-done.12 = ...`` -> ``copy-done.12``, as the ``HloProto`` names it."""
    m = _INSTRUCTION_NAME.match(hlo_text)
    return m.group(1) if m else ""


# ---- from an op_name to a part

# what wraps a name stack without being a part of a model: control flow,
# jax.numpy's own nested jits, autodiff's marks round a component
_WRAPPER = re.compile(r"^(while|body|cond|closed_call|checkpoint|pjit|branch_\d+_fun|jit\(.*\))$")
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|custom_jvp|custom_vjp)\((.*)\)$")
_BLOCK = re.compile(r"^block_\d+$")


def scope_path(tf_op: str) -> List[str]:
    """The components of an ``op_name`` below its program's name, wrappers
    dropped, ``transpose(jvp(ViT))`` looked through to ``ViT`` and
    ``block_7`` folded to ``block_*``; the last one is the primitive. A
    fusion of instructions from several lines of the model lists their
    names with ``;`` between: the first one speaks for it."""
    out = []
    for part in tf_op.split(";", 1)[0].split("/")[1:]:
        inner = _TRANSFORM.match(part)
        while inner:
            part = inner.group(1)
            inner = _TRANSFORM.match(part)
        if not part or _WRAPPER.match(part):
            continue
        out.append("block_*" if _BLOCK.match(part) else part)
    return out


def _under(*names: str):
    return re.compile(r"/(?:%s)/" % "|".join(names))


# ordered: the first pattern found anywhere in "/<scope path>/" names the
# part, so ``gdn/o_norm`` is the mixer's and ``verify/Llama/lm_head`` the
# head's. The last component of a path is its primitive and is not searched
# (``attn/gather`` is a lax.gather in ``attn``; ``moe/gather/...`` the scope).
# Every name of ``DEVICE_SCOPE_NAMES`` stands in a row, beside the modules
# it is opened under: ``scripts/lint_basics.py`` reads this table and holds
# it to the tuple and to the parts docs/observability.md gives.
PART_TABLE = (
    ("mixer", _under("attn", "gdn", "conv", "gates", "state_update", "absorb", "expand")),
    ("ffn", _under("mlp", "moe", "shared_expert", "router", "group_rows", "gather", "experts", "combine")),
    ("head", _under("final_norm", "ln_final", "lm_head", "head", "sample", "loss", "accept")),
    ("optimizer", _under("optimizer", "grad_accumulate")),
    # block-level norms and residual adds, embeddings, whatever else lies
    # under a model's root (Flax names it by its class), and the program's
    # own bookkeeping scopes
    ("glue", _under(r"block_\*", "embed", "patch_embed", "commit", "step_io", "draft", "verify", r"[A-Z]\w*")),
)
# ``hlo_category`` of the instructions that move or relay data and compute
# nothing: the prefetch into fast memory and its waits, layout copies
DATA_MOVEMENT = re.compile(
    r"^(data formatting|copy|copy-start|copy-done|slice-start|slice-done|async-start|async-done|"
    r"send|send-done|recv|recv-done|host send|host recv|dynamic-update-slice|dynamic-slice)$", re.I,
)


@functools.lru_cache(maxsize=1 << 16)  # millions of events, a few thousand instructions
def part_of(tf_op: str, hlo_category: str) -> str:
    """The model part an instruction's time belongs to (``PARTS``). A copy
    of a program's argument carries the argument's name for a ``tf_op``
    (``state['pool'][0][0]``): no name stack, so it is told by category too."""
    if not named(tf_op):
        return "data_movement" if DATA_MOVEMENT.match(hlo_category or "") else "unscoped"
    path = scope_path(tf_op)
    owners = "/" + "/".join(path[:-1]) + "/"
    for part, pattern in PART_TABLE:
        if pattern.search(owners):
            return part
    return "unscoped"


def is_container(op: ScopedOp) -> bool:
    """A ``while`` holds its body's operations on the same line: its own
    event covers theirs, so it is skipped as ``xplane.CONTAINERS`` are."""
    return xplane.op_short_name(op.name) in xplane.CONTAINERS


# ---- the reductions the per-layer readers share

def whole_runs(ops: List[ScopedOp], pattern: str) -> List[List[ScopedOp]]:
    """The operations (containers skipped) of each whole traced run of the
    programs whose name matches ``pattern``. A run is whole if it holds as
    many operations as the fullest run of the same program traced (one the
    trace cut into holds fewer); programs are told apart by their run's
    name, fingerprint and all, so each prefill bucket is judged against
    its own."""
    rx = re.compile(pattern)
    by_run: Dict[int, List[ScopedOp]] = {}
    for op in ops:
        if op.run >= 0 and rx.search(op.program) and not is_container(op):
            by_run.setdefault(op.run, []).append(op)
    fullest: Dict[str, int] = {}
    for run in by_run.values():
        fullest[run[0].program] = max(fullest.get(run[0].program, 0), len(run))
    return [run for run in by_run.values() if len(run) == fullest[run[0].program]]


def part_seconds(runs: List[List[ScopedOp]]) -> Dict[str, float]:
    """Seconds of each part that has an instruction in ``runs``."""
    total: Dict[str, float] = {}
    for run in runs:
        for op in run:
            part = part_of(op.tf_op, op.hlo_category)
            total[part] = total.get(part, 0.0) + (op.end_s - op.start_s)
    return total


def first_device(by_device: Dict[str, List[ScopedOp]]) -> str:
    return min(by_device, key=lambda name: int(name.rsplit(":", 1)[1]))


def for_run(run) -> Optional[List[ScopedOp]]:
    """The first chip's operations of a traced ``RunView``; ``None`` for an
    untraced run or a trace without metadata stats. A file that does not
    decode raises, as ``xplane.load`` does: a broken reader is a failed run,
    not a line with metrics missing."""
    trace_dir = run.record.get("trace_dir")
    path = xplane.find_xplane(trace_dir) if trace_dir else None
    by_device = scoped_ops(path) if path else None
    return by_device[first_device(by_device)] if by_device else None


def _summary(run, pattern: Optional[str]) -> Tuple[int, Dict[str, float]]:
    """(whole runs, seconds by part) of the programs matching ``pattern``
    in ``run``'s trace; kept, because a dozen readers ask for the same."""
    ops = for_run(run) if pattern else None
    if not ops:
        return 0, {}
    key = (id(ops), pattern)  # ``ops`` lives as long as ``_cache`` holds it
    if key not in _summaries:
        runs = whole_runs(ops, pattern)
        _summaries[key] = (len(runs), part_seconds(runs))
    return _summaries[key]


def part_ms(run, pattern: Optional[str], part: str, per_run: float = 1.0) -> Optional[float]:
    """Milliseconds of ``part`` inside the whole traced runs of the programs
    matching ``pattern``, over runs x ``per_run``. ``None`` where nothing
    was traced or the part has no instruction there, never 0."""
    runs, seconds = _summary(run, pattern)
    return 1e3 * seconds[part] / (runs * per_run) if part in seconds else None


def unscoped_pct(run, pattern: Optional[str]) -> Optional[float]:
    """The share of those runs' operation time that no part owns."""
    _, seconds = _summary(run, pattern)
    total = sum(seconds.values())
    return 100.0 * seconds.get("unscoped", 0.0) / total if total else None


DECODE, PREFILL = r"^jit_decode_chunk\(", r"^jit_prefill\("


def decode_step_part_ms(run, part: str) -> Optional[float]:
    return part_ms(run, DECODE, part, run.record.get("chunk_steps") or 1)


def prefill_part_ms(run, part: str) -> Optional[float]:
    return part_ms(run, PREFILL, part)


def busiest_program(ops: List[ScopedOp]) -> Optional[str]:
    """A pattern for the program whose operations take most of the traced
    device time: the train step, as ``train_step_device_ms`` finds it."""
    total: Dict[str, float] = {}
    for op in ops:
        if op.run >= 0 and not is_container(op):
            total[op.program] = total.get(op.program, 0.0) + (op.end_s - op.start_s)
    return "^" + re.escape(max(total, key=total.get)) + "$" if total else None


def train_step_part_ms(run, part: str) -> Optional[float]:
    ops = for_run(run)
    return part_ms(run, busiest_program(ops), part) if ops else None


def train_step_unscoped_pct(run) -> Optional[float]:
    ops = for_run(run)
    return unscoped_pct(run, busiest_program(ops)) if ops else None


# ---- the table

def table(ops: List[ScopedOp], program: Optional[str] = None, depth: int = 3) -> List[dict]:
    """Rows by scope path cut to ``depth`` components (the primitive is no
    component), largest first: seconds, executions, XLA's bytes and flops."""
    rows: Dict[Tuple[str, str], dict] = {}
    rx = re.compile(program) if program else None
    for op in ops:
        if is_container(op) or (rx and not rx.search(op.program)):
            continue
        part = part_of(op.tf_op, op.hlo_category)
        kind = op.hlo_category or xplane.op_short_name(op.name)
        if named(op.tf_op):
            path = "/".join(scope_path(op.tf_op)[:-1][:depth]) or "(bare " + op.tf_op.split("/")[0] + ")"
        elif op.consumer:
            path = f"({kind} for) " + ("/".join(scope_path(op.consumer)[:-1][:depth]) or op.consumer)
        else:
            path = f"(no name stack: {kind})"
        row = rows.setdefault((part, path), dict(part=part, path=path, seconds=0.0, count=0, bytes=0, flops=0))
        row["seconds"] += op.end_s - op.start_s
        row["count"] += 1
        row["bytes"] += op.bytes_accessed
        row["flops"] += op.flops
    return sorted(rows.values(), key=lambda r: -r["seconds"])


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="device time of a trace by scope path")
    ap.add_argument("trace", help="a trace directory or an .xplane.pb file")
    ap.add_argument("--program", help="only the runs of programs matching this regex (jit_decode_chunk)")
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--top", type=int, default=40)
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else xplane.find_xplane(args.trace)
    t0 = time.perf_counter()
    by_device = scoped_ops(path) if path else None
    decode_s = time.perf_counter() - t0
    if not by_device:
        print(f"opscopes: no device operation with metadata stats under {args.trace}")
        return 1
    first = first_device(by_device)
    rows = table(by_device[first], args.program, args.depth)
    total = sum(r["seconds"] for r in rows) or 1.0
    by_part: Dict[str, float] = {}
    for r in rows:
        by_part[r["part"]] = by_part.get(r["part"], 0.0) + r["seconds"]
    print(f"{path}: {os.path.getsize(path)} bytes, {len(by_device[first])} operations on {first}, "
          f"decoded in {decode_s:.2f} s")
    print(f"{total:.6f} s of operations" + (f" in runs of {args.program}" if args.program else ""))
    print("  " + "  ".join(f"{p} {100 * by_part[p] / total:.1f}%" for p in PARTS if p in by_part))
    moved: Dict[str, float] = {}
    rx = re.compile(args.program) if args.program else None
    for op in by_device[first]:
        if part_of(op.tf_op, op.hlo_category) == "data_movement" and not (rx and not rx.search(op.program)):
            whose = part_of(op.consumer, "") if op.consumer else "no named user"
            moved[whose] = moved.get(whose, 0.0) + (op.end_s - op.start_s)
    if moved:
        print("  data_movement by its first named user's part: "
              + "  ".join(f"{p} {100 * v / total:.1f}%" for p, v in sorted(moved.items(), key=lambda kv: -kv[1])))
    print(f"{'seconds':>10} {'share':>6} {'count':>7} {'GB':>9} {'GB/s':>7} {'TFLOP/s':>8}  part           path")
    for r in rows[: args.top]:
        s = r["seconds"] or 1e-30
        print(f"{r['seconds']:10.6f} {100 * r['seconds'] / total:5.1f}% {r['count']:7d} {r['bytes'] / 1e9:9.3f} "
              f"{r['bytes'] / 1e9 / s:7.1f} {r['flops'] / 1e12 / s:8.2f}  {r['part']:<14} {r['path']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
