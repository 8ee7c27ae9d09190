"""``chipbench/opscopes.py`` on small traces recorded on the chip.

``data/tiny_annotated_v5e.xplane.pb`` (PR 25) is a trace of the program
*before* it named its own scopes: Flax's module paths are there, ``sample``
/ ``commit`` / ``optimizer`` are not. ``data/tiny_scoped_v5e.xplane.pb``
(``record_tiny_scoped.py``, PR 38) is the same traffic through the program
that names them. A file without metadata stats is made here, byte by byte.
"""

import importlib.util
import re
import shutil
from pathlib import Path

import pytest

from chipbench import opscopes, xplane
from chipbench.run import RunView

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[1]
ANNOTATED = str(DATA / "tiny_annotated_v5e.xplane.pb")
SCOPED = str(DATA / "tiny_scoped_v5e.xplane.pb")
DEVICE = "/device:TPU:0"
DECODE_PARTS = ("mixer", "ffn", "head", "glue", "data_movement")
READERS = (
    [f"decode_step_{p}_ms" for p in DECODE_PARTS] + ["decode_step_unscoped_pct", "prefill_mixer_ms", "prefill_ffn_ms"]
    + ["train_step_mixer_ms", "train_step_ffn_ms", "train_step_unscoped_pct"]
)


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", ROOT / "layer_metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _view(tmp_path, trace_file, **extra):
    """A ``RunView`` over one recorded trace, as a traced run leaves it."""
    shutil.copy(trace_file, tmp_path / Path(trace_file).name)
    return RunView({"trace_dir": str(tmp_path), "chunk_steps": 4, **extra}, {}, {}, {})


def _seconds(ops):
    return sum(op.end_s - op.start_s for op in ops)


# ---- the decoder

def test_the_decoder_finds_tf_op_in_the_event_metadata():
    (device,) = [p for p in opscopes._read_wire(ANNOTATED) if p.name == DEVICE]
    named = [m for m in device.event_metadata.values() if m.stats.get("tf_op")]
    assert (len(named), sum(1 for m in device.event_metadata.values() if m.stats.get("hlo_category"))) == (458, 1264)
    ops = opscopes.scoped_ops(ANNOTATED)[DEVICE]
    assert len(ops) == 6219 and ops == sorted(ops, key=lambda op: op.start_s)
    paths = {"/".join(opscopes.scope_path(op.tf_op)) for op in ops if op.program.startswith("jit_decode_chunk(")}
    for want in ("Llama/block_*/attn/paged_attention/", "Llama/block_*/moe/etd,edh->eth/", "Llama/block_*/attn_norm/",
                 "Llama/lm_head/", "Llama/embed/"):
        assert any(p.startswith(want) for p in paths), want
    # an instruction's stats travel with every execution of it
    kernel = [op for op in ops if "paged_attention" in op.tf_op]
    assert len(kernel) == 64 and {op.hlo_category for op in kernel} == {"custom-call"}
    assert all(op.tf_op.startswith("jit(decode_chunk)/while/body/closed_call/Llama/block_") for op in kernel)


def test_operations_are_put_to_the_module_run_that_contains_them():
    ops = opscopes.scoped_ops(ANNOTATED)[DEVICE]
    trace = xplane.load(ANNOTATED)
    for pattern, runs in ((opscopes.DECODE, 4), (opscopes.PREFILL, 4), (r"^jit_step\(", 3)):
        spans = trace.module_runs(pattern)
        assert len(spans) == runs == len(opscopes.whole_runs(ops, pattern))
        inside = [op for op in ops if re.search(pattern, op.program)]
        assert all(any(s <= op.start_s and op.end_s <= e + 1e-9 for s, e in spans) for op in inside)


def test_parts_sum_to_the_trace_s_own_totals_less_containers():
    ops = opscopes.scoped_ops(ANNOTATED)[DEVICE]
    leaves = [op for op in ops if not opscopes.is_container(op)]
    assert len(ops) - len(leaves) == 4  # the four chunks' ``while``
    by_part = {}
    for op in leaves:
        part = opscopes.part_of(op.tf_op, op.hlo_category)
        by_part[part] = by_part.get(part, 0.0) + (op.end_s - op.start_s)
    assert set(by_part) <= set(opscopes.PARTS)
    want = sum(s for name, s in xplane.load(ANNOTATED).op_totals(10 ** 6) if name not in xplane.CONTAINERS)
    # ProfileData cuts a duration to whole nanoseconds; the file holds picoseconds
    assert sum(by_part.values()) == pytest.approx(want, abs=1e-9 * len(leaves))
    assert sum(by_part.values()) >= want


def test_the_paged_kernel_s_time_lies_under_the_mixer():
    ops = opscopes.scoped_ops(ANNOTATED)[DEVICE]
    pattern = _reader("paged_attn_ms_per_step").KERNEL
    kernel = [op for op in ops if re.search(pattern, op.name)]
    assert {opscopes.part_of(op.tf_op, op.hlo_category) for op in kernel} == {"mixer"}
    want = xplane.total_length(xplane.load(ANNOTATED).ops_matching(pattern))
    assert _seconds(kernel) == pytest.approx(want, abs=1e-9 * len(kernel))


# ---- a file whose instructions carry no stats

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _bare_trace(path, with_category=False):
    """One chip, one run of ``jit_f(1)``, two operations."""
    stat_names = _field(5, _field(1, 9) + _field(2, _field(1, 9) + _field(2, b"hlo_category")))
    category = _field(5, _field(1, 9) + _field(5, b"loop fusion")) if with_category else b""
    metadata = b"".join(
        _field(4, _field(1, mid) + _field(2, _field(1, mid) + _field(2, name) + extra))
        for mid, name, extra in ((1, b"jit_f(1)", b""), (2, b"%fusion.1 = f32[8]{0} fusion()", category),
                                 (3, b"%copy.2 = f32[8]{0} copy()", b""))
    )

    def line(name, events):
        body = _field(2, name) + b"".join(_field(4, _field(1, m) + _field(2, o) + _field(3, d)) for m, o, d in events)
        return _field(3, body)

    plane = (_field(2, b"/device:TPU:0") + stat_names + metadata + line(b"XLA Modules", [(1, 1_000_000, 9_000_000)])
             + line(b"XLA Ops", [(2, 2_000_000, 3_000_000), (3, 6_000_000, 1_000_000)]))
    path.write_bytes(_field(1, plane) + _field(1, _field(2, b"/host:CPU")))
    return str(path)


def test_a_trace_without_metadata_stats_reads_none_not_zero(tmp_path):
    assert opscopes.scoped_ops(_bare_trace(tmp_path / "bare.xplane.pb")) is None
    run = RunView({"trace_dir": str(tmp_path), "chunk_steps": 4}, {}, {}, {})
    assert [_reader(name).read(run) for name in READERS] == [None] * len(READERS)
    # the same file with one category is a trace that says something
    described = opscopes.scoped_ops(_bare_trace(tmp_path / "bare.xplane.pb", with_category=True))
    assert [(op.program, op.hlo_category, op.run) for op in described[DEVICE]] == [("jit_f(1)", "loop fusion", 0), ("jit_f(1)", "", 0)]
    assert described[DEVICE][0].end_s - described[DEVICE][0].start_s == pytest.approx(3e-6)


def test_every_reader_reads_none_on_an_untraced_run():
    run = RunView({"chunk_steps": 4}, {}, {}, {})
    assert [_reader(name).read(run) for name in READERS] == [None] * len(READERS)


def _read_pb2(path):
    """What ``opscopes._read_wire`` returns, through TensorFlow's generated ``xplane_pb2``: the reference."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    planes = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:") and plane.name != opscopes.HLO_PLANE:
            planes.append(opscopes._Plane(plane.name, [], {}))
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        metadata = {}
        for mid, meta in plane.event_metadata.items():
            stats = {}
            for stat in meta.stats:
                kind = stat.WhichOneof("value")
                value = getattr(stat, kind) if kind else None
                stats[stat_names.get(stat.metadata_id, str(stat.metadata_id))] = (
                    stat_names.get(value, "") if kind == "ref_value" else value
                )
            metadata[mid] = opscopes._EventMeta(meta.name, stats)
        lines = [
            opscopes._Line(line.name, line.timestamp_ns, [(e.metadata_id, e.offset_ps, e.duration_ps) for e in line.events])
            for line in plane.lines if line.name in ("XLA Modules", "XLA Ops")
        ]
        planes.append(opscopes._Plane(plane.name, lines, metadata))
    return planes


@pytest.mark.parametrize("trace_file", [ANNOTATED, SCOPED, str(DATA / "tiny_v5e.xplane.pb")])
def test_the_wire_reader_and_xplane_pb2_agree(trace_file):
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    assert opscopes._scoped_ops(opscopes._read_wire(trace_file)) == opscopes._scoped_ops(_read_pb2(trace_file))


def _instruction(iid, name, opcode, operands=(), op_name=""):
    body = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if operands:
        body += _field(36, b"".join(_varint(o) for o in operands))  # packed, as proto3 writes it
    if op_name:
        body += _field(7, _field(2, op_name))
    return _field(2, body)


def test_an_unnamed_instruction_takes_its_first_named_user():
    # a weight's quarter prefetched and joined, then multiplied; a copy kept for the loop's next turn
    computation = b"".join([
        _instruction(1, b"p", b"parameter"),
        _instruction(2, b"slice-start.1", b"async-start", [1]),
        _instruction(3, b"slice-done.1", b"async-done", [2]),
        _instruction(4, b"custom-call.7", b"custom-call", [3]),
        _instruction(5, b"fusion.9", b"fusion", [4], b"jit(f)/while/body/closed_call/M/block_2/mlp/up/dot_general"),
        _instruction(6, b"copy-start.3", b"copy-start", [1]),
        _instruction(7, b"copy-done.3", b"copy-done", [6]),
        _instruction(8, b"tuple.1", b"tuple", [5, 7]),
    ])
    proto = _field(1, _field(1, b"jit_f") + _field(3, _field(1, b"body") + computation))
    want = "jit(f)/while/body/closed_call/M/block_2/mlp/up/dot_general"
    assert opscopes.consumers(proto) == {"slice-start.1": want, "slice-done.1": want, "custom-call.7": want}


def test_the_prefetch_of_the_scoped_trace_finds_its_users():
    ops = opscopes.scoped_ops(SCOPED)[DEVICE]
    moved = [op for op in ops if opscopes.part_of(op.tf_op, op.hlo_category) == "data_movement"]
    found = [op for op in moved if op.consumer]
    assert (len(moved), len(found)) == (1475, 853)
    assert all(op.consumer.startswith("jit(") and not op.tf_op.startswith("jit(") for op in found)
    # an instruction with a name stack of its own is never handed another's
    assert not any(op.consumer for op in ops if op.tf_op.startswith("jit("))
    in_step = [op for op in found if op.program.startswith("jit_step(")]
    assert {opscopes.part_of(op.consumer, "") for op in in_step} >= {"mixer", "ffn", "optimizer"}


# ---- from a name to a part

@pytest.mark.parametrize("tf_op,category,part", [
    ("jit(decode_chunk)/while/body/closed_call/Llama/block_3/attn/q/dot_general", "convolution fusion", "mixer"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/block_3/attn/gather", "loop fusion", "mixer"),
    ("jit(decode_chunk)/while/body/closed_call/OlmoHybrid/block_0/gdn/o_norm/rsqrt", "loop fusion", "mixer"),
    ("jit(decode_chunk)/while/body/closed_call/OlmoHybrid/block_0/gdn/state_update/gated_delta_step/pallas_call", "custom-call", "mixer"),
    ("jit(prefill)/GlmMoeLite/block_1/attn/expand/dot_general", "convolution fusion", "mixer"),
    ("jit(prefill)/GlmMoeLite/block_1/moe/gather/gather", "loop fusion", "ffn"),
    ("jit(prefill)/GlmMoeLite/block_1/shared_expert/up/dot_general", "convolution fusion", "ffn"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/block_3/moe/router/top_k", "sort", "ffn"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/block_3/attn_norm/rsqrt", "loop fusion", "glue"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/block_3/add", "loop fusion", "glue"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/embed/jit(_take)/gather", "loop fusion", "glue"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/convert_element_type", "loop fusion", "glue"),
    ("jit(decode_chunk)/while/body/closed_call/step_io/jit(_where)/select_n", "loop fusion", "glue"),
    ("jit(prefill)/commit/scatter", "loop fusion", "glue"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/lm_head/dot_general", "convolution fusion", "head"),
    ("jit(decode_chunk)/while/body/closed_call/Llama/final_norm/rsqrt", "loop fusion", "head"),
    ("jit(decode_chunk)/while/body/closed_call/sample/argmax", "loop fusion", "head"),
    ("jit(spec_chunk)/while/body/verify/Llama/block_0/mlp/up/dot_general", "convolution fusion", "ffn"),
    ("jit(spec_chunk)/while/body/verify/concatenate", "loop fusion", "glue"),
    ("jit(step)/jvp(ViT)/block_0/mlp/fc1/dot_general", "convolution fusion", "ffn"),
    ("jit(step)/transpose(jvp(ViT))/block_0/attn/qkv/dot_general", "convolution fusion", "mixer"),
    ("jit(step)/transpose(jvp(ViT))/ln_final/mul", "loop fusion", "head"),
    ("jit(step)/jvp(ViT)/patch_embed/conv_general_dilated", "convolution fusion", "glue"),
    ("jit(step)/jvp(loss)/reduce_sum", "loop fusion", "head"),
    ("jit(step)/optimizer/mul", "loop fusion", "optimizer"),
    ("jit(step)/while/body/grad_accumulate/add", "loop fusion", "optimizer"),
    ("jit(decode_chunk)/while/body/add", "loop fusion", "unscoped"),
    ("jit(decode_chunk)", "loop fusion", "unscoped"),
    ("jit(f)/while/body/closed_call/M/block_1/gdn/conv/mul;jit(f)/while/body/closed_call/M/block_1/mlp/up/dot_general", "", "mixer"),
    ("", "copy-done", "data_movement"),
    ("", "data formatting", "data_movement"),
    ("state['pool'][0][0]", "data formatting", "data_movement"),
    ("", "loop fusion", "unscoped"),
    ("", "", "unscoped"),
])
def test_part_of(tf_op, category, part):
    assert opscopes.part_of(tf_op, category) == part


# ---- the readers

def test_decode_parts_sum_to_the_step_s_operations_on_the_annotated_trace(tmp_path):
    run = _view(tmp_path, ANNOTATED)
    values = {name: _reader(name).read(run) for name in READERS}
    parts = [values[f"decode_step_{p}_ms"] for p in DECODE_PARTS]
    assert all(isinstance(v, float) and v > 0 for v in parts)
    ops = opscopes.for_run(run)
    whole = opscopes.whole_runs(ops, opscopes.DECODE)
    step_ms = 1e3 * sum(_seconds(r) for r in whole) / (len(whole) * 4)
    assert sum(parts) == pytest.approx(step_ms * (1 - values["decode_step_unscoped_pct"] / 100.0))
    # no more than the program's own time, and no less than the kernel the older reader times
    assert sum(parts) <= _reader("decode_step_device_ms").read(run) * len(whole) / len(whole)
    assert values["decode_step_mixer_ms"] >= _reader("paged_attn_ms_per_step").read(run)
    # that program named no scope of its own: sampling and the slot commit sit under the bare program
    assert values["decode_step_unscoped_pct"] == pytest.approx(2.85, abs=0.01)
    assert values["prefill_mixer_ms"] > values["prefill_ffn_ms"] > 0
    # the busiest program of this trace is the chunk, not a train step: its parts are read as such
    assert values["train_step_mixer_ms"] == pytest.approx(values["decode_step_mixer_ms"] * 4)
    assert opscopes.train_step_part_ms(run, "optimizer") is None  # no instruction of that part: None, never 0


def test_the_scoped_program_names_sample_commit_router_and_optimizer(tmp_path):
    ops = opscopes.scoped_ops(SCOPED)[DEVICE]

    def scopes(pattern):
        return {part for op in ops if re.search(pattern, op.program) for part in opscopes.scope_path(op.tf_op)[:-1]}

    # what survives fusion as an instruction's first name (the chunk's sampler
    # is fused into the head's matmul, ``gather`` / ``combine`` into the experts')
    assert {"step_io", "router", "group_rows", "experts"} <= scopes(opscopes.DECODE)
    assert {"sample", "commit", "step_io", "router", "group_rows", "experts"} <= scopes(opscopes.PREFILL)
    assert {"loss", "optimizer"} <= scopes(r"^jit_step\(")
    assert {opscopes.part_of(op.tf_op, op.hlo_category) for op in ops if "/optimizer/" in op.tf_op} == {"optimizer"}
    run = _view(tmp_path, SCOPED)
    # at this size the scan's own stacking and counter are 2 % of a step (0.005 % of Mixtral's);
    # the program before the scopes read 2.85 on the same traffic
    assert _reader("decode_step_unscoped_pct").read(run) == pytest.approx(2.02, abs=0.01)
    # the train step alone: its own reader finds the optimizer
    step = opscopes.whole_runs(ops, r"^jit_step\(")
    parts = opscopes.part_seconds(step)
    assert parts["optimizer"] > 0 and parts.get("unscoped", 0.0) / sum(parts.values()) < 0.05
