"""Every operation a traced program asks of the device has an owner: a
Flax module path or a ``jax.named_scope`` of ``DEVICE_SCOPE_NAMES``
(``scripts/lint_basics.py``; docs/observability.md "Device time by model
part"). The profiler stores an instruction's ``op_name`` as its ``tf_op``,
and ``chipbench/opscopes.py`` sums device time by it, so an instruction
outside every scope is device time nobody can aim at.

Each case compiles a tiny configuration on the CPU (through
``build_programs`` or a step factory) and reads ``op_name`` from the
compiled text. Instructions the compiler made up (no ``op_name``: layout
copies, fusion wrappers) and the bodies of reducers and comparators (an
``op_name`` without the program's ``jit(...)`` prefix) are not the
program's to name."""

import functools
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from unionml_tpu.models import ViT, ViTConfig, classification_step, lm_step
from unionml_tpu.models.generate import make_sampler
from unionml_tpu.models.glm_moe_lite import GlmMoeLite, GlmMoeLiteConfig
from unionml_tpu.models.keye_vl_moe import KeyeVLMoe, KeyeVLMoeConfig
from unionml_tpu.models.llama import Llama, LlamaConfig
from unionml_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
from unionml_tpu.models.sdar_moe import SdarMoe, SdarMoeConfig
from unionml_tpu.models.train import TrainState, adamw
from unionml_tpu.serving.programs import build_programs, generation_scheme

_LINT = Path(__file__).resolve().parents[2] / "scripts" / "lint_basics.py"
_spec = importlib.util.spec_from_file_location("lint_basics_for_scopes", _LINT)
_lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lint)
SCOPES = set(_lint.DEVICE_SCOPE_NAMES)

SLOTS, BUCKET, BLOCK, STEPS = 3, 32, 8, 2
# no work of their own: values passed along, and the containers whose
# bodies' instructions are listed themselves
NOT_WORK = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast", "while", "conditional", "call"}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.-]+ = .*?\s([\w-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# lax.scan's own loop: the counter, its test, the stacking of the
# per-step outputs. No line of the program makes them, so no scope can.
_SCAN_MACHINERY = {"add", "lt", "dynamic_update_slice", "dynamic_slice", "broadcast_in_dim"}


# what JAX puts into a name stack that is no line of the program: control
# flow, jax.numpy's own nested jits, and autodiff's marks round a component
_WRAPPER = re.compile(r"^(while|body|cond|closed_call|checkpoint|pjit|branch_\d+_fun|jit\(.*\))$")
_TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|custom_jvp|custom_vjp)\((.*)\)$")


def owner(op_name: str):
    """The first component of ``op_name`` below its program that is not a
    wrapper, with ``transpose(jvp(ViT))`` read as ``ViT``, and what follows
    it: a module class, a scope name, or the bare primitive of an operation
    nobody owns. ``None`` for a name of wrappers alone. The test's own
    reading of JAX's name stack: the benchmark's trace readers have theirs."""
    path = []
    for part in op_name.split("/")[1:]:
        while _TRANSFORM.match(part):
            part = _TRANSFORM.match(part).group(1)
        if part and not _WRAPPER.match(part):
            path.append(part)
    return (path[0], path[1:]) if path else (None, [])


def program_op_names(text: str):
    """``op_name`` of every instruction of compiled ``text`` that is work
    the program asked for."""
    names = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(1) in NOT_WORK:
            continue
        n = _OP_NAME.search(line)
        if not n or not n.group(1).startswith("jit("):
            continue
        # every operation of a jaxpr ends its name in its primitive; a
        # name of wrappers alone is the compiler's plumbing round a call
        # (its literal arguments broadcast, the CPU's float upcasts)
        if owner(n.group(1))[0] is None:
            continue
        names.append(n.group(1))
    return names


def unowned(names, model: str):
    """The ``op_name``s that lie under neither ``model`` nor a scope."""
    out = []
    for name in names:
        head, rest = owner(name)
        if head == model or head in SCOPES:
            continue
        if head in _SCAN_MACHINERY and not rest and re.search(r"/while/(body|cond)/[\w-]+$", name):
            continue
        out.append(name)
    return out


def scopes_under(names, component: str):
    """The scope names that occur directly below ``component`` in a path,
    with an operation below them (a path's last component is a primitive:
    ``attn/gather`` is ``lax.gather`` in ``attn``, not the scope)."""
    found = set()
    for name in names:
        parts = name.split("/")
        for i, part in enumerate(parts[:-2]):
            if part == component and parts[i + 1] in SCOPES:
                found.add(parts[i + 1])
    return found


def top_scopes(names):
    return {owner(n)[0] for n in names} & SCOPES


# ---- the families

FAMILIES = {
    "dense_llama": lambda: Llama(LlamaConfig.tiny(vocab_size=97)),
    "int8_moe_llama": lambda: Llama(
        LlamaConfig.tiny(vocab_size=97, num_experts=4, num_selected=2, quantized=True)
    ),
    "float_moe_llama": lambda: Llama(LlamaConfig.tiny(vocab_size=97, num_experts=4, num_selected=2)),
    "olmo_hybrid": lambda: OlmoHybrid(OlmoHybridConfig.tiny(vocab_size=97)),
    "glm_moe_lite": lambda: GlmMoeLite(GlmMoeLiteConfig.tiny(vocab_size=97)),
    "keye_vl_moe": lambda: KeyeVLMoe(KeyeVLMoeConfig.tiny(vocab_size=97, quantized=True)),
    "sdar_moe": lambda: SdarMoe(SdarMoeConfig.tiny(vocab_size=97, quantized=True)),
    "vit": lambda: ViT(ViTConfig.tiny()),
}
MOE_SCOPES = {"router", "group_rows", "gather", "experts", "combine"}


def _abstract_params(module, example):
    return jax.eval_shape(lambda: module.init(jax.random.PRNGKey(0), example)["params"])


def _served_text(module, program: str) -> str:
    progs = build_programs(
        module, slots=SLOTS, rows=2 * BUCKET, pool_blocks=16, block=BLOCK, chunk_steps=STEPS,
        sample=make_sampler(), eos_id=1, pad_id=0,
    )
    params = _abstract_params(module, jnp.zeros((1, 8), jnp.int32))
    state = jax.eval_shape(progs.init_state)
    key = jax.random.PRNGKey(0)
    if program == "decode_chunk":
        lowered = progs.decode_chunk.lower(
            params, state, jnp.ones((SLOTS,), bool), jnp.zeros((SLOTS, 2 * BUCKET // BLOCK), jnp.int32),
            jnp.stack([key] * STEPS),
        )
    else:
        lowered = progs.prefill.lower(
            params, state, jnp.int32(1), jnp.zeros((BUCKET // BLOCK,), jnp.int32),
            jnp.zeros((BUCKET,), jnp.int32), jnp.int32(5), key,
            # a module that generates by blocks is also told how many tokens are asked
            *((jnp.int32(8),) if generation_scheme(module) is not None else ()),
        )
    return lowered.compile().as_text()


def _train_text(module, accumulate_steps: int = 1) -> str:
    if isinstance(module, ViT):
        cfg = module.config
        example = jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.float32)
        batch = (example, jnp.zeros((2,), jnp.int32))
        step = classification_step(module, accumulate_steps=accumulate_steps)
    else:
        example = jnp.zeros((2, 9), jnp.int32)
        batch = example
        step = lm_step(module, accumulate_steps=accumulate_steps)
    if accumulate_steps > 1:
        batch = jax.tree_util.tree_map(lambda x: jnp.stack([x] * accumulate_steps), batch)
    params = _abstract_params(module, example[:, :8] if example.ndim == 2 else example)
    state = jax.eval_shape(
        lambda p: TrainState.create(apply_fn=module.apply, params=p, tx=adamw(1e-3)), params
    )
    return jax.jit(step).lower(state, batch).compile().as_text()


SERVED = [
    (family, program)
    for family in ("dense_llama", "int8_moe_llama", "olmo_hybrid", "glm_moe_lite", "keye_vl_moe", "sdar_moe")
    for program in ("decode_chunk", "prefill")
]


@functools.lru_cache(maxsize=None)
def _family_text(family: str, program: str) -> str:
    """One compile a served case, whichever tests read it."""
    return _served_text(FAMILIES[family](), program)


@pytest.mark.parametrize("family,program", SERVED)
def test_served_program_names_every_operation(family, program):
    module = FAMILIES[family]()
    names = program_op_names(_family_text(family, program))
    assert len(names) > 50
    assert unowned(names, type(module).__name__) == []
    # the work no module owns, where the readers look for it
    by_blocks = family == "sdar_moe"
    assert "step_io" in top_scopes(names)
    # (by blocks a prefill yields no token and samples nothing)
    assert ("sample" in top_scopes(names)) != (by_blocks and program == "prefill")
    if program == "prefill":
        assert "commit" in top_scopes(names)
    if by_blocks:
        assert scopes_under(names, "moe") == MOE_SCOPES
        # the confidences and the choice of the entries a forward decides
        assert scopes_under(names, "sample") == ({"unmask"} if program == "decode_chunk" else set())
    if family == "int8_moe_llama":
        assert scopes_under(names, "moe") == MOE_SCOPES
    if family == "glm_moe_lite":
        assert scopes_under(names, "moe") == MOE_SCOPES
        # absorbed where cached rows are read; this prefill is the cached form too
        assert "absorb" in scopes_under(names, "attn")
    if family == "olmo_hybrid":
        assert scopes_under(names, "gdn") == {"conv", "gates", "state_update"}
    if family == "keye_vl_moe":
        assert scopes_under(names, "moe") == MOE_SCOPES
        # topk 8 of a 64-row table and of a 32-token bucket: the selection runs
        # in both programs; only the decode step walks a pool with it
        walk = {"paged_sparse_attention"} if program == "decode_chunk" else set()
        assert scopes_under(names, "attn") == {"indexer", "select"} | walk


@pytest.mark.parametrize("family,program", [case for case in SERVED if case[0] != "sdar_moe"])
def test_the_choice_of_entries_reaches_no_other_familys_program(family, program):
    """``unmask`` has one caller, the chunk of a module that generates by
    blocks: no other served program holds an operation under it, nor the
    open block's state."""
    text = _family_text(family, program)
    parts = {part for name in program_op_names(text) for part in name.split("/")[:-1]}
    assert "unmask" not in parts and "blk_und" not in text


@pytest.mark.parametrize("family,program", [case for case in SERVED if case[0] != "keye_vl_moe"])
def test_the_selection_and_its_read_reach_no_other_familys_program(family, program):
    """The selection as a mask and the walk that takes it
    (``paged_sparse_attention``) have one caller, the family with an
    indexer: no other served program holds the kernel's name or an
    operation under ``indexer``, ``select`` or ``paged_sparse_attention``,
    so a change to that read cannot move their compiled programs."""
    text = _family_text(family, program)
    assert "paged_sparse_attention" not in text and "paged_index_scores" not in text
    parts = {part for name in program_op_names(text) for part in name.split("/")[:-1]}
    assert not parts & {"indexer", "select", "paged_sparse_attention", "paged_index_scores"}


def test_a_slot_rows_chunk_commits_nothing_but_a_prefill_does():
    """``commit`` is the prefill's: a decode chunk of the block pool writes
    its rows inside the model's own attention."""
    module = FAMILIES["dense_llama"]()
    assert "commit" not in top_scopes(program_op_names(_served_text(module, "decode_chunk")))


def test_glm_flash_prefill_expands_the_latents():
    module = GlmMoeLite(GlmMoeLiteConfig.tiny(vocab_size=97, prefill_impl="flash"))
    names = program_op_names(_served_text(module, "prefill"))
    assert unowned(names, "GlmMoeLite") == []
    assert "expand" in scopes_under(names, "attn")


TRAINED = [("dense_llama", 1), ("dense_llama", 2), ("float_moe_llama", 1), ("vit", 1)]


@pytest.mark.parametrize("family,accumulate_steps", TRAINED)
def test_train_step_names_every_operation(family, accumulate_steps):
    module = FAMILIES[family]()
    names = program_op_names(_train_text(module, accumulate_steps))
    assert len(names) > 50
    assert unowned(names, type(module).__name__) == []
    assert {"loss", "optimizer"} <= top_scopes(names)
    assert ("grad_accumulate" in top_scopes(names)) == (accumulate_steps > 1)
    if family == "float_moe_llama":
        # forward and backward of the dense dispatch, both under the block's moe
        assert scopes_under(names, "moe") == MOE_SCOPES


def test_owner_looks_through_autodiff_and_control_flow():
    backward = "jit(step)/transpose(jvp(ViT))/block_0/mlp/dot_general"
    assert owner(backward) == ("ViT", ["block_0", "mlp", "dot_general"])
    assert owner("jit(decode_chunk)/while/body/closed_call/sample/jit(_where)/select_n")[0] == "sample"
    assert owner("jit(decode_chunk)/while/body/add") == ("add", [])
    assert unowned(["jit(decode_chunk)/while/body/add", "jit(decode_chunk)/while/cond/lt"], "Llama") == []
    assert unowned(["jit(prefill)/add"], "Llama") == ["jit(prefill)/add"]


def test_the_lint_holds_the_readers_part_table_to_the_doc(tmp_path):
    """The scope names live in the lint's tuple, the doc's table and the
    trace readers' ``PART_TABLE`` (read as source, not imported): a name
    the table lost, or put under another part, fails the lint."""
    root = _LINT.parents[1]
    assert _lint.check_device_scope_names(root) == []
    assert set(_lint.documented_scope_parts((root / "docs/observability.md").read_text())) >= SCOPES
    for sub in ("docs", "chipbench", "unionml_tpu"):
        (tmp_path / sub).mkdir()
    (tmp_path / "docs/observability.md").write_text((root / "docs/observability.md").read_text())
    table = (root / _lint.PART_TABLE_MODULE).read_text()
    for old, new in (('"router", ', ""), ('"loss", ', ""), ('"step_io", ', '"step_io", "loss", ')):
        assert old in table
        table = table.replace(old, new)
    (tmp_path / _lint.PART_TABLE_MODULE).write_text(table)
    problems = _lint.check_device_scope_names(tmp_path)
    assert len(problems) == 2
    assert any("'router' under None" in p for p in problems)
    assert any("'loss' under 'glue'" in p and "'head'" in p for p in problems)
