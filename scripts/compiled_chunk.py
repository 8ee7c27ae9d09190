"""What an engine's decode chunk compiles to, on a machine without a chip.

    python scripts/compiled_chunk.py chipbench/configs/olmo-hybrid-7b-int8.json \\
        [--match gdn] [--top 3] [--text chunk.hlo]
    python scripts/compiled_chunk.py chipbench/configs/mixtral-8x7b-int8.json \\
        --program prefill --bucket 256 [--match moe]
    python scripts/compiled_chunk.py chipbench/configs/glm-4.7-flash-int8.json [--match attn]
    python scripts/compiled_chunk.py chipbench/configs/sdar-30b-a3b-chat-int8.json [--match sample]

Builds the ``DecodeEngine`` a ``chipbench`` cell serves with (the
configuration's adapter and its ``serving`` block, as
``chipbench/adapters/llama_decoder.start_service`` does), compiles its decode
chunk for a DESCRIBED ``v5e`` (abstract parameters; nothing runs, no chip is
needed) and prints the operations of the chunk's loop body, one line a kind
(an instruction's name without its number): how many a step, their results'
bytes as the chip tiles them, the compiler's own estimate of their cycles,
and the largest few with the ``op_name`` that says which line of the model
made them. A ``copy`` or a ``fusion`` of a device trace is one of these.
``--text`` keeps the compiled module's text, and reads it back instead of
compiling if the file is there. A 32-layer model compiles in a quarter of a
minute. Where no TPU compiler is installed it says so and compiles nothing.
``--program prefill --bucket N`` lists the prefill program of one prompt
bucket the same way: it has no loop, so the whole entry computation is listed.
A step of the chunk of a module that generates by blocks (the SDAR
configuration) is one forward over every slot's open block.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import math
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")  # no chip is attached here
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4}
# dtype, dimensions, minor-to-major order, what follows the layout's colon
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\](?:\{([\d,]*)(?::([^}]*))?\})?")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+?)(?:\.\d+)* = (.*)$")
_NOT_WORK = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


def tiled_bytes(dtype: str, dims: str, order: str | None = None, tiles: str | None = None) -> int:
    """Bytes of an array in its layout: the minor axes padded to the tile
    (``T(8,128)``; a second ``(2,1)`` or ``(4,1)`` packs as many rows into a
    word, so the row count pads to that multiple of 8 as well)."""
    shape = [int(d) for d in dims.split(",") if d]
    order = [int(d) for d in (order or "").split(",") if d] or list(range(len(shape) - 1, -1, -1))
    physical = [shape[i] for i in reversed(order)]
    tile = re.search(r"T((?:\([\d,]+\))+)", tiles or "")
    if tile and physical:
        first, *packing = ([int(n) for n in t.split(",")] for t in re.findall(r"\(([\d,]+)\)", tile.group(1)))
        if packing and len(first) > 1:
            first[-2] *= packing[0][0]
        for axis, n in zip(range(len(physical) - len(first), len(physical)), first):
            if axis >= 0:
                physical[axis] = -(-physical[axis] // n) * n
    return math.prod(physical) * _BYTES.get(dtype, 4)


def result_bytes(result: str) -> int:
    """Bytes of an instruction's result type (a tuple's arrays together)."""
    return sum(tiled_bytes(*m.groups()) for m in _ARRAY.finditer(result))


def computation(text: str, entry: bool = False) -> list:
    """The lines of the largest ``while`` body of a compiled module, or of
    its entry computation (a prefill program's work is not in a loop)."""
    if entry:
        names = set(re.findall(r"^ENTRY %?([\w.-]+)", text, re.M))
    else:
        names = set(re.findall(r"\bwhile\(.*?body=%?([\w.-]+)", text))
    best, name, lines = [], None, []
    for line in text.splitlines():
        start = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$", line)
        if start:
            name, lines = start.group(1), []
        elif line.startswith("}"):
            if name in names and len(lines) > len(best):
                best = lines
            name = None
        elif name is not None:
            lines.append(line)
    return best


def compiled_chunk_text(config_path: str, program: str = "decode", bucket: int | None = None) -> str:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from unionml_tpu.ops import flash_attention, gated_delta, moe, paged_attention, sparse_attention
    from unionml_tpu.serving.engine import DecodeEngine

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:
        raise SystemExit(f"compiled_chunk: no TPU compiler here, nothing compiled ({exc!r})") from None
    chip = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)  # unreadable without the chip
    # off their CPU branch
    for module in (flash_attention, gated_delta, moe, paged_attention, sparse_attention):
        module._interpret = lambda: False

    cfg = json.loads(Path(config_path).read_text())
    built = importlib.import_module(f"chipbench.adapters.{cfg['family']}").build(cfg)
    s = cfg["serving"]
    engine = DecodeEngine(
        built["serve_module"], slots=s["slots"], max_new_tokens=s["max_new_tokens"],
        prompt_buckets=tuple(s["prompt_buckets"]), paged=True,
        kv_pool_bytes=int(s["kv_pool_bytes"]), kv_block_size=s["kv_block_size"],
    )
    try:
        def on_chip(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree,
            )

        params, state = built["abstract_serve_params"](), jax.eval_shape(engine._init_state)
        if program == "prefill":
            bucket = bucket or engine.buckets[0]
            if bucket not in engine.buckets:
                raise SystemExit(f"compiled_chunk: no bucket {bucket} among {engine.buckets}")
            compiled, args = engine._prefill, (
                params, state, jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((bucket // engine._kv_block_size,), jnp.int32),
                jax.ShapeDtypeStruct((bucket,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32),
                jax.eval_shape(lambda: jax.random.PRNGKey(0)),
                # a module that generates by blocks is also told the tokens asked
                *((jax.ShapeDtypeStruct((), jnp.int32),) if engine._blocks is not None else ()),
            )
        else:
            compiled, args = engine._decode_chunk, (
                params, state, jax.ShapeDtypeStruct((engine.slots,), jnp.bool_),
                jax.ShapeDtypeStruct(engine._table.shape, jnp.int32),
                jax.ShapeDtypeStruct((engine.chunk_steps, 2), jnp.uint32),
            )
        compiled = getattr(compiled, "__wrapped__", compiled)  # the tracker's wrapper
        return compiled.lower(*on_chip(args)).compile().as_text()
    finally:
        engine.close()


def report(text: str, match: str = "", top: int = 3, entry: bool = False) -> None:
    kinds = collections.defaultdict(list)
    for line in computation(text, entry):
        m = _INSTRUCTION.match(line)
        opcode = m and re.search(r" ([a-z][\w-]*)\(", m.group(2))
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1).split("closed_call/")[-1] if op_name else ""
        op_name = re.sub(r"block_\d+", "block_*", op_name)  # one line for the same operation of every layer
        if opcode and opcode.group(1) not in _NOT_WORK and match in op_name:
            result = m.group(2)[:opcode.start()]
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)  # where the compiler made a guess
            kinds[m.group(1)].append(
                (result_bytes(result), int(cycles.group(1)) if cycles else 0, result[:80], op_name[-60:])
            )
    print(f"{'a step':>7} {'MB in tiles':>12} {'kcycles':>9}  kind")
    print(f"{'':30s}  its largest results: count x MB, kcycles, type, op_name")
    by_cost = sorted(kinds.items(), key=lambda kv: (-sum(o[1] for o in kv[1]), -sum(o[0] for o in kv[1])))
    for kind, ops in by_cost:
        mb, kcycles = sum(o[0] for o in ops) / 1e6, sum(o[1] for o in ops) / 1e3
        print(f"{len(ops):7d} {mb:12.2f} {kcycles:9.1f}  {kind}")
        largest = sorted(collections.Counter(ops).items(), key=lambda c: -c[0][0])[:top]
        for (size, cycles, result, op_name), n in largest:
            print(f"{'':30s}  {n:4d} x {size / 1e6:8.3f} {cycles / 1e3:7.1f}  {result}  {op_name}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a chipbench/configs/*.json with a serving block")
    ap.add_argument("--match", default="", help="only operations whose op_name holds this")
    ap.add_argument("--top", type=int, default=3, help="largest results listed per kind")
    ap.add_argument("--text", help="the compiled module's text: read if the file is there, else written")
    ap.add_argument("--program", choices=("decode", "prefill"), default="decode")
    ap.add_argument("--bucket", type=int, help="the prefill program's prompt bucket (default: the smallest)")
    args = ap.parse_args()
    if args.text and Path(args.text).exists():
        text = Path(args.text).read_text()
    else:
        text = compiled_chunk_text(args.config, args.program, args.bucket)
        if args.text:
            Path(args.text).write_text(text)
    report(text, args.match, args.top, entry=args.program == "prefill")


if __name__ == "__main__":
    main()
