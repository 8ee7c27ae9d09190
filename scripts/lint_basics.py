"""Dependency-free lint: the high-value correctness subset, stdlib-only.

The reference gates on full flake8/mypy; this image ships neither, so
this AST-based checker enforces the subset that catches real bugs and
runs anywhere (CI executes it alongside flake8 — flake8 remains the
richer gate where installed):

- F401-equivalent: unused imports (module scope, `__init__.py` exempt —
  package surfaces re-export),
- mutable default arguments,
- bare ``except:``,
- comparisons to ``None``/``True``/``False`` with ``==``/``!=``,
- f-strings without placeholders,
- tabs in indentation and trailing whitespace,
- lines over 110 columns (the codebase targets ~100; 110 is the hard
  stop so URLs/tables don't nag),
- bare ``time.time()`` in the serving layer and the execution engine
  (:data:`WALL_CLOCK_BANNED`): durations there MUST use
  ``time.monotonic()``/``time.perf_counter()`` — wall clock steps under
  NTP slew and breaks deadline/latency accounting. (``time.time()`` is
  fine elsewhere, e.g. epoch timestamps in logs.)
- direct ``cache[...]`` subscripts in ``unionml_tpu/serving/`` outside
  the block allocator module (:data:`CACHE_INDEX_BANNED` /
  :data:`CACHE_INDEX_EXEMPT`): since the paged-KV refactor
  (docs/performance.md), device KV rows are addressed through block
  tables — contiguous-row indexing of a cache object in serving code
  bypasses the allocator and silently breaks the paged layout. Route
  through the block-table API (``kv_pool.py`` + the engine's
  scatter/extract programs) instead.
- label-cardinality guard (repo-wide, when the default paths are
  linted): any ``unionml_*`` metric registered under ``unionml_tpu/``
  whose label schema contains a **request-derived** label name
  (:data:`REQUEST_DERIVED_LABELS` — tenant/rid/request ids) must live
  in the usage ledger module (:data:`REQUEST_LABEL_EXEMPT`), whose
  top-K rollup bounds the label's value set. Anywhere else, a
  request-derived label means unbounded series cardinality the moment
  a client controls the value — route the increment through
  ``UsageLedger.label_for`` instead (docs/observability.md "Usage
  metering & cost attribution").
- the decode engine's layering (repo-wide; :func:`check_engine_layering`):
  ``serving/programs.py`` imports nothing of the engine's host side
  (``engine``, ``scheduler``, ``kv_pool``, ``telemetry``, ``perf``) and
  no ``threading``, and ``serving/engine.py`` holds no ``jax.jit(``.
- metrics-doc drift (repo-wide, when the default paths are linted):
  every ``unionml_*`` metric registered under ``unionml_tpu/`` must be
  documented in ``docs/observability.md``, and every full metric name
  the doc mentions must exist in code — the by-hand doc table
  accumulated drift across PRs 1–4; this closes the loop both ways.

Usage: ``python scripts/lint_basics.py [paths...]`` (default: the
package, tests, benchmarks, scripts). Exits non-zero on findings.
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_PATHS = ["unionml_tpu", "tests", "benchmarks", "scripts", "bench.py",
                 "chip_smoke.py", "__graft_entry__.py"]
MAX_LINE = 110

# repo-relative prefixes where time.time() is banned (monotonic-clock
# territory: queue deadlines, latency splits, drain timers — and, since
# the goodput layer, every trainer path whose durations feed badput
# buckets: wall clock stepping under NTP would mis-attribute seconds).
# The checkpoint/ prefix covers async_writer.py: its save_ms/commit_ms
# split IS the checkpoint badput attribution, so a wall-clock duration
# there would corrupt the caller-stall vs background-commit story.
# The serving/ prefix covers router.py: the fleet router's ejection
# cooldowns, hedge delays, and backoff timers are exactly the durations
# an NTP step would corrupt into spurious ejections or storms.
# The serving/ prefix also covers scheduler.py: the preemptive
# scheduler's resume-wait spans and KV hold windows feed latency
# attribution and per-tenant billing — wall-clock stepping there would
# corrupt preemption accounting and the deficit queues' fairness.
WALL_CLOCK_BANNED = (
    "unionml_tpu/serving/",
    "unionml_tpu/execution.py",
    "unionml_tpu/goodput.py",
    "unionml_tpu/elastic.py",
    "unionml_tpu/data/pipeline.py",
    "unionml_tpu/checkpoint/",
)

# where direct `cache[...]` / `<expr>.cache[...]` subscripts are banned:
# serving-layer device KV goes through the block-table API so the paged
# and contiguous layouts cannot silently diverge. The allocator module
# itself is the one legitimate home for raw block addressing.
CACHE_INDEX_BANNED = ("unionml_tpu/serving/",)
CACHE_INDEX_EXEMPT = ("unionml_tpu/serving/kv_pool.py",)


class Checker(ast.NodeVisitor):
    def __init__(self, path: Path, src: str, ban_wall_clock: bool = False,
                 ban_cache_index: bool = False):
        self.path = path
        self.src = src
        self.ban_wall_clock = ban_wall_clock
        self.ban_cache_index = ban_cache_index
        self.problems: list = []
        self.imports: dict = {}       # name -> (lineno, spelled)
        self.used: set = set()

    def problem(self, lineno: int, msg: str):
        self.problems.append(f"{self.path}:{lineno}: {msg}")

    # -- imports ------------------------------------------------------- #

    def visit_Import(self, node: ast.Import):
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.imports[name] = (node.lineno, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module == "__future__":
            return  # compiler directive, never "used"
        for alias in node.names:
            if alias.name == "*":
                continue
            name = alias.asname or alias.name
            self.imports[name] = (node.lineno, alias.name)
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        root = node
        while isinstance(root, ast.Attribute):
            root = root.value
        if isinstance(root, ast.Name):
            self.used.add(root.id)
        self.generic_visit(node)

    # -- defaults / except / comparisons / f-strings ------------------- #

    def _check_defaults(self, node):
        for default in list(node.args.defaults) + list(node.args.kw_defaults):
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                self.problem(
                    default.lineno,
                    f"mutable default argument in {node.name}()",
                )

    def visit_FunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node):
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node):
        if node.type is None:
            self.problem(node.lineno, "bare except: (catch a class)")
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare):
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, (ast.Eq, ast.NotEq)) and isinstance(
                comp, ast.Constant
            ) and (comp.value is None or comp.value is True or comp.value is False):
                self.problem(
                    node.lineno,
                    f"comparison to {comp.value!r} with ==/!= (use is/is not)",
                )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        if (
            self.ban_wall_clock
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "time"
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "time"
        ):
            self.problem(
                node.lineno,
                "time.time() in serving/execution code — use "
                "time.monotonic()/time.perf_counter() for durations "
                "(wall clock steps under NTP)",
            )
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        if self.ban_cache_index:
            target = node.value
            name = (
                target.id if isinstance(target, ast.Name)
                else target.attr if isinstance(target, ast.Attribute)
                else None
            )
            if name == "cache":
                self.problem(
                    node.lineno,
                    "direct cache[...] indexing in serving code — device "
                    "KV rows are block-paged; go through the block-table "
                    "API (serving/kv_pool.py + the engine's "
                    "scatter/extract programs)",
                )
        self.generic_visit(node)

    def visit_JoinedStr(self, node: ast.JoinedStr):
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self.problem(node.lineno, "f-string without placeholders")
        self.generic_visit(node)

    def visit_FormattedValue(self, node: ast.FormattedValue):
        # do NOT descend into format_spec: "{x:.2e}" carries a nested
        # placeholder-free JoinedStr that is not a user f-string
        self.visit(node.value)

    # -- finish -------------------------------------------------------- #

    def report_unused_imports(self, tree: ast.Module):
        if self.path.name == "__init__.py":
            return
        # names exported via __all__ or re-exported strings count as used
        exported = set()
        for node in tree.body:
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "__all__"
                    for t in node.targets
                )
                and isinstance(node.value, (ast.List, ast.Tuple))
            ):
                exported |= {
                    e.value for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                }
        for name, (lineno, spelled) in self.imports.items():
            if name in self.used or name in exported:
                continue
            # "import x.y" spells a submodule import for side effects
            if "." in spelled and name == spelled.split(".")[0]:
                continue
            self.problem(lineno, f"unused import: {spelled}")


def check_file(path: Path) -> list:
    src = path.read_text(encoding="utf-8")
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    try:
        rel = path.resolve().relative_to(ROOT).as_posix()
    except ValueError:
        rel = path.as_posix()
    ban_wall_clock = any(
        rel == p or rel.startswith(p) for p in WALL_CLOCK_BANNED
    )
    ban_cache_index = any(
        rel == p or rel.startswith(p) for p in CACHE_INDEX_BANNED
    ) and rel not in CACHE_INDEX_EXEMPT
    checker = Checker(
        path, src, ban_wall_clock=ban_wall_clock,
        ban_cache_index=ban_cache_index,
    )
    checker.visit(tree)
    checker.report_unused_imports(tree)
    for i, line in enumerate(src.splitlines(), 1):
        if "\t" in line[: len(line) - len(line.lstrip())]:
            checker.problem(i, "tab in indentation")
        if line != line.rstrip():
            checker.problem(i, "trailing whitespace")
        if len(line) > MAX_LINE:
            checker.problem(i, f"line too long ({len(line)} > {MAX_LINE})")
    return checker.problems


METRICS_DOC = "docs/observability.md"
# a registration call looks like registry.counter("name", ...) /
# .gauge(...) / .histogram(...) — or the engine/batcher's local helper
# shorthands counter("name", ...) / hist("name", ...); the first
# positional string is the name either way
_METRIC_FACTORIES = ("counter", "gauge", "histogram", "hist")
# doc tokens that LOOK like metric names: the unionml_ prefix plus at
# least two more underscore-separated words (filters out module-ish
# mentions like `unionml_tpu.telemetry` → token "unionml_tpu" — while
# real metric names, `unionml_tpu_build_info` included, always qualify)
_DOC_METRIC_RE = re.compile(r"\bunionml(?:_[a-z0-9]+){2,}\b")
# histogram/counter exposition suffixes a doc may legitimately mention
_SERIES_SUFFIXES = ("_bucket", "_sum", "_count")


# request-derived label names: a client-controlled value minted into a
# label is unbounded cardinality — only the usage ledger's bounded
# top-K rollup may own such labels
REQUEST_DERIVED_LABELS = (
    "tenant", "rid", "request_id", "user", "user_id", "client",
    "client_id",
)
REQUEST_LABEL_EXEMPT = ("unionml_tpu/serving/usage.py",)


# the CLOSED trace-span-name vocabulary (the autoscaler's
# DECISION_REASONS pattern applied to spans): every literal span name
# recorded into the TraceRecorder must come from this set, and every
# name here must be documented in docs/observability.md — so the
# stitched fleet timeline's vocabulary stays a documented enum that
# OTLP consumers (grouping, alerting on span names) can rely on.
# Names recorded from variables (the goodput tracker's phase names are
# the BADPUT_CAUSES vocabulary, enforced at runtime) are not checkable
# statically and are skipped.
TRACE_SPAN_NAMES = (
    # engine request lifecycle
    "queue", "prefill", "harvest", "recover",
    # the dispatcher's host-side admission work inside prefill
    # (TraceRecorder.span: also on the profiler's clock as
    # engine.admit / engine.admit.enqueue)
    "admit", "admit.enqueue",
    # host spans that go to an open profiler session only
    # (TraceRecorder.span(None, ...); docs/observability.md "Host spans
    # on the profiler's clock") — chipbench/hostspans.py reads these
    "engine.pass", "engine.admit", "engine.admit.enqueue",
    "engine.dispatch_chunk", "engine.dispatch_chunk.enqueue",
    "engine.poll", "engine.harvest_wait", "engine.harvest_process",
    "train.feed_wait", "train.step",
    # micro-batcher
    "predict",
    # fleet router decision machinery (docs/observability.md
    # "Fleet observability")
    "pick", "attempt", "backoff", "hedge-lane",
    # disaggregated two-leg dispatch (docs/serving.md "Disaggregated
    # serving"): the prefill leg, the KV handoff between pools, and
    # the decode leg — all under one routing rid, joining the engine
    # prefill/prefix-splice families each leg records on its replica
    "prefill-leg", "handoff", "decode-leg",
    # rollout shadow dispatch (docs/robustness.md "Rollouts &
    # rollback"): the canary-side duplicate of a live request, on its
    # own timeline under the live request's trace id so
    # /debug/trace?rid=<live> stitches both paths
    "shadow",
)
# indexed span families (f-strings with a bounded constant prefix) and
# the transport server span (f"http {path}" — path is route-bounded)
TRACE_SPAN_PREFIXES = (
    "decode-chunk[", "prefill-chunk[", "prefix-splice[",
    "resume-wait[", "preempt[", "http ",
)
TRACE_SPAN_EXEMPT = (
    "unionml_tpu/telemetry.py",   # the recorder mechanism itself
)


def _span_name_literal(node: ast.Call):
    """The span-name argument of a ``record_span`` call when it is
    statically checkable: ``(kind, value)`` where kind is "const" for
    a string literal, "prefix" for an f-string's leading constant
    part, or None for a variable (skipped)."""
    if len(node.args) < 2:
        return None, None
    name_arg = node.args[1]
    if isinstance(name_arg, ast.Constant) and isinstance(
        name_arg.value, str
    ):
        return "const", name_arg.value
    if isinstance(name_arg, ast.JoinedStr):
        prefix = ""
        for value in name_arg.values:
            if isinstance(value, ast.Constant) and isinstance(
                value.value, str
            ):
                prefix += value.value
            else:
                break
        return "prefix", prefix
    return None, None


def check_span_names(package_root: Path) -> list:
    """Every literal span name at a ``record_span`` or ``span`` call
    site (the name, and ``span``'s ``annotation=``) must be
    in :data:`TRACE_SPAN_NAMES` (constants) or open with a
    :data:`TRACE_SPAN_PREFIXES` family (f-strings), and the whole
    vocabulary must be documented in docs/observability.md — the
    span-name twin of the metrics-doc drift check."""
    problems = []
    for path in sorted(package_root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            rel = path.resolve().relative_to(ROOT).as_posix()
        except ValueError:
            rel = path.as_posix()
        if rel in TRACE_SPAN_EXEMPT:
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # reported by the per-file checker
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("record_span", "span")
            ):
                continue
            kind, name = _span_name_literal(node)
            if kind is None:
                continue  # variable name: runtime-enforced vocabulary
            for kw in node.keywords:
                # span(rid, name, annotation=...): the profiler's name
                if kw.arg == "annotation" and isinstance(
                    kw.value, ast.Constant
                ) and kw.value.value not in TRACE_SPAN_NAMES:
                    kind, name = "const", kw.value.value
            if kind == "const" and name in TRACE_SPAN_NAMES:
                continue
            if kind == "prefix" and name and any(
                name.startswith(p) for p in TRACE_SPAN_PREFIXES
            ):
                # the f-string's constant prefix must COVER a family
                # prefix — the reverse test would let f"p{x}" ride in
                # on "preempt[" and silently widen the closed set
                continue
            problems.append(
                f"{path}:{node.lineno}: span name {name!r} is outside "
                "the closed TRACE_SPAN_NAMES/TRACE_SPAN_PREFIXES set "
                "(scripts/lint_basics.py) — span names are a "
                "documented enum; add it there AND to "
                f"{METRICS_DOC}, or reuse an existing name"
            )
    doc_path = ROOT / METRICS_DOC
    if doc_path.exists():
        doc_text = doc_path.read_text(encoding="utf-8")
        for name in TRACE_SPAN_NAMES + tuple(
            p.rstrip("[ ") for p in TRACE_SPAN_PREFIXES
        ):
            if name not in doc_text:
                problems.append(
                    f"{METRICS_DOC}: span name {name!r} from the "
                    "TRACE_SPAN_NAMES enum is not documented"
                )
    return problems


# the CLOSED device-scope vocabulary: every ``jax.named_scope("...")``
# literal in unionml_tpu/ names work that no Flax module owns, and a
# device trace carries it as each operation's ``tf_op`` — what
# chipbench/opscopes.py sums device time by (``part_of``). A name the
# readers do not know lands in their ``unscoped`` part, so the set is
# closed here and documented in docs/observability.md "Device time by
# model part".
DEVICE_SCOPE_NAMES = (
    # serving/programs.py: a decode step, a prefill, a speculative round
    "sample", "commit", "step_io", "draft", "verify", "accept",
    # models/train.py
    "loss", "optimizer", "grad_accumulate",
    # ops/moe.py, under a block's ``moe``
    "router", "group_rows", "gather", "experts", "combine",
    # models/olmo_hybrid.py GatedDeltaNet, under ``gdn``
    "conv", "gates", "state_update",
    # models/glm_moe_lite.py LatentAttention, under ``attn``
    "absorb", "expand",
    # models/keye_vl_moe.py IndexedSparseAttention and ops/sparse_attention.py,
    # under ``attn``; ops/paged_attention.py's plain form of the decode read
    "indexer", "select", "paged_sparse_attention",
    # serving/programs.py block_chunk, under ``sample``: the confidences and
    # the choice of the entries a denoising forward decides
    "unmask",
)
# scopes that stand in no row of ``PART_TABLE``: they are only ever opened
# under the module named here, whose row places them (``part_of`` reads the
# whole path), so the benchmark's table needs no edit for them
DEVICE_SCOPES_PLACED_BY_OWNER = {
    "indexer": "attn", "select": "attn", "paged_sparse_attention": "attn",
    "unmask": "sample",
}
_DEVICE_SCOPE_DOC_BEGIN = "<!-- DEVICE_SCOPE_NAMES:begin -->"
_DEVICE_SCOPE_DOC_END = "<!-- DEVICE_SCOPE_NAMES:end -->"
PART_TABLE_MODULE = "chipbench/opscopes.py"


def part_table_names(root: Path) -> dict:
    """{name: part} of the string literals in ``PART_TABLE`` of
    chipbench/opscopes.py, read from its source; empty where the file or
    the table is not there."""
    path = root / PART_TABLE_MODULE
    if not path.exists():
        return {}
    out = {}
    for node in ast.walk(ast.parse(path.read_text(encoding="utf-8"))):
        if not (
            isinstance(node, ast.Assign)
            and any(isinstance(t, ast.Name) and t.id == "PART_TABLE" for t in node.targets)
            and isinstance(node.value, ast.Tuple)
        ):
            continue
        for row in node.value.elts:
            if not (isinstance(row, ast.Tuple) and len(row.elts) == 2):
                continue
            for leaf in ast.walk(row.elts[1]):
                if isinstance(leaf, ast.Constant) and isinstance(leaf.value, str):
                    out.setdefault(leaf.value, row.elts[0].value)
    return out


def documented_scope_parts(table_text: str) -> dict:
    """{scope: part} of the doc's table: the names of a row's first cell
    beside those of its fourth, one part for all or one each."""
    out = {}
    for line in table_text.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) < 4:
            continue
        scopes = _BACKTICK_TOKEN_RE.findall(cells[0])
        parts = _BACKTICK_TOKEN_RE.findall(cells[3])
        if len(parts) == 1:
            parts = parts * len(scopes)
        out.update(zip(scopes, parts))
    return out


def check_device_scope_names(root: Path) -> list:
    """Every ``named_scope`` call in ``unionml_tpu/`` takes a string
    literal of :data:`DEVICE_SCOPE_NAMES`, every name of the set is
    documented between the markers of docs/observability.md, and the
    trace readers' ``PART_TABLE`` knows each name under the part the doc
    gives it: three lists that cannot drift."""
    problems = []
    for path in sorted((root / "unionml_tpu").rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # reported by the per-file checker
        for node in ast.walk(tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "named_scope"
            ):
                continue
            arg = node.args[0] if node.args else None
            name = arg.value if isinstance(arg, ast.Constant) else None
            if name not in DEVICE_SCOPE_NAMES:
                problems.append(
                    f"{path}:{node.lineno}: jax.named_scope({name!r}) is "
                    "outside the closed DEVICE_SCOPE_NAMES set "
                    "(scripts/lint_basics.py): the trace readers sum "
                    "device time by these names; add it there, to "
                    f"chipbench/opscopes.py and to {METRICS_DOC}, or "
                    "reuse an existing name (a literal, not a variable)"
                )
    doc_path = root / METRICS_DOC
    if doc_path.exists():
        doc_text = doc_path.read_text(encoding="utf-8")
        begin = doc_text.find(_DEVICE_SCOPE_DOC_BEGIN)
        end = doc_text.find(_DEVICE_SCOPE_DOC_END)
        if begin < 0 or end < begin:
            problems.append(
                f"{METRICS_DOC}: the DEVICE_SCOPE_NAMES markers "
                "(\"Device time by model part\") are missing"
            )
        else:
            documented = documented_scope_parts(doc_text[begin:end])
            table = part_table_names(root)
            for name in DEVICE_SCOPE_NAMES:
                if name not in documented:
                    problems.append(
                        f"{METRICS_DOC}: device scope {name!r} from "
                        "DEVICE_SCOPE_NAMES is not documented"
                    )
                elif table and name not in table and name in DEVICE_SCOPES_PLACED_BY_OWNER:
                    owner = DEVICE_SCOPES_PLACED_BY_OWNER[name]
                    if table.get(owner) != documented[name]:
                        problems.append(
                            f"{PART_TABLE_MODULE}: PART_TABLE puts {owner!r}, the owner of device "
                            f"scope {name!r}, under {table.get(owner)!r}, {METRICS_DOC} puts the "
                            f"scope under {documented[name]!r}"
                        )
                elif table and table.get(name) != documented[name]:
                    problems.append(
                        f"{PART_TABLE_MODULE}: PART_TABLE puts device scope "
                        f"{name!r} under {table.get(name)!r}, {METRICS_DOC} "
                        f"under {documented[name]!r}"
                    )
    return problems


# the decode engine's arrow points one way: serving/engine.py (host:
# queue, admission, dispatcher, harvester, recovery, stats) calls
# serving/programs.py (everything that is traced), never the reverse
PROGRAMS_MODULE = "unionml_tpu/serving/programs.py"
ENGINE_MODULE = "unionml_tpu/serving/engine.py"
_HOST_SIDE = {"engine", "scheduler", "kv_pool", "telemetry", "perf", "threading"}


def check_engine_layering(root: Path) -> list:
    """``serving/programs.py`` imports nothing of the host side and no
    ``threading``; ``serving/engine.py`` jits nothing itself."""
    problems = []
    tree = ast.parse((root / PROGRAMS_MODULE).read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [f"{node.module or ''}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            if set(name.split(".")) & _HOST_SIDE:
                problems.append(
                    f"{PROGRAMS_MODULE}:{node.lineno}: imports {name} — the "
                    "device programs know nothing of the engine's host side"
                )
    for lineno, line in enumerate(
        (root / ENGINE_MODULE).read_text().splitlines(), 1
    ):
        if "jax.jit(" in line:
            problems.append(
                f"{ENGINE_MODULE}:{lineno}: jax.jit( — traced code lives in "
                f"{PROGRAMS_MODULE}"
            )
    return problems


ROLLOUT_MODULE = "unionml_tpu/serving/rollout.py"
ROLLOUT_DOC = "docs/robustness.md"
# the doc's decision table is fenced by these markers so the reverse
# direction of the drift check has a bounded region to scan (free-text
# prose may mention a reason informally without being "the table")
_ROLLOUT_DOC_BEGIN = "<!-- ROLLOUT_REASONS:begin -->"
_ROLLOUT_DOC_END = "<!-- ROLLOUT_REASONS:end -->"
_BACKTICK_TOKEN_RE = re.compile(r"`([a-z0-9_]+)`")


def _module_tuple_literal(tree: ast.Module, name: str):
    """The string elements of a module-level ``NAME = (...)`` tuple
    assignment, or None when absent/not-a-literal."""
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets
            )
            and isinstance(node.value, (ast.Tuple, ast.List))
        ):
            return tuple(
                e.value for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return None


def check_rollout_reasons(root: Path) -> list:
    """Two-way drift check between the rollout controller's closed
    decision vocabulary (``ROLLOUT_DECISIONS``/``ROLLOUT_REASONS`` in
    serving/rollout.py) and the decision table in docs/robustness.md
    "Rollouts & rollback" — the DECISION_REASONS/span-name pattern
    applied to the rollout state machine, so an operator paging
    through ``unionml_rollout_decisions_total{decision,reason}`` can
    trust every label value has a documented row."""
    module_path = root / ROLLOUT_MODULE
    doc_path = root / ROLLOUT_DOC
    if not module_path.exists():
        return [f"{ROLLOUT_MODULE}: missing (rollout drift check needs it)"]
    try:
        tree = ast.parse(module_path.read_text(encoding="utf-8"))
    except SyntaxError:
        return []  # reported by the per-file checker
    reasons = _module_tuple_literal(tree, "ROLLOUT_REASONS")
    decisions = _module_tuple_literal(tree, "ROLLOUT_DECISIONS")
    problems = []
    if reasons is None or decisions is None:
        return [
            f"{ROLLOUT_MODULE}: ROLLOUT_REASONS/ROLLOUT_DECISIONS must "
            "be module-level literal tuples (the closed vocabulary the "
            "doc-drift check parses)"
        ]
    if not doc_path.exists():
        return [f"{ROLLOUT_DOC}: missing (rollout drift check needs it)"]
    doc_text = doc_path.read_text(encoding="utf-8")
    for value in decisions + reasons:
        if f"`{value}`" not in doc_text:
            problems.append(
                f"{ROLLOUT_MODULE}: rollout vocabulary value "
                f"{value!r} is not documented in {ROLLOUT_DOC}"
            )
    begin = doc_text.find(_ROLLOUT_DOC_BEGIN)
    end = doc_text.find(_ROLLOUT_DOC_END)
    if begin < 0 or end < 0 or end < begin:
        problems.append(
            f"{ROLLOUT_DOC}: decision table must be fenced by "
            f"{_ROLLOUT_DOC_BEGIN} / {_ROLLOUT_DOC_END} markers (the "
            "reverse drift direction scans that region)"
        )
        return problems
    known = set(decisions) | set(reasons)
    offset = doc_text[:begin].count("\n") + 1
    for lineno, line in enumerate(
        doc_text[begin:end].splitlines(), offset
    ):
        for token in _BACKTICK_TOKEN_RE.findall(line):
            if token not in known:
                problems.append(
                    f"{ROLLOUT_DOC}:{lineno}: decision-table token "
                    f"{token!r} is not in the ROLLOUT_DECISIONS/"
                    f"ROLLOUT_REASONS vocabulary ({ROLLOUT_MODULE})"
                )
    return problems


PERF_MODULE = "unionml_tpu/serving/perf.py"
PERF_DOC = "docs/observability.md"
_PERF_DOC_BEGIN = "<!-- PERF_REASONS:begin -->"
_PERF_DOC_END = "<!-- PERF_REASONS:end -->"


def check_perf_reasons(root: Path) -> list:
    """Two-way drift check between the serving perf watchdog's closed
    reasons vocabulary (``PERF_REGRESSION_REASONS`` in serving/perf.py)
    and the watchdog reasons table in docs/observability.md "Serving
    goodput & tail attribution" — the rollout-decision pattern applied
    to ``perf_regression`` flight events, so an operator filtering
    ``/debug/flight?kind=perf_regression`` can trust every ``reason``
    value has a documented row."""
    module_path = root / PERF_MODULE
    doc_path = root / PERF_DOC
    if not module_path.exists():
        return [f"{PERF_MODULE}: missing (perf-reasons drift check needs it)"]
    try:
        tree = ast.parse(module_path.read_text(encoding="utf-8"))
    except SyntaxError:
        return []  # reported by the per-file checker
    reasons = _module_tuple_literal(tree, "PERF_REGRESSION_REASONS")
    if reasons is None:
        return [
            f"{PERF_MODULE}: PERF_REGRESSION_REASONS must be a "
            "module-level literal tuple (the closed vocabulary the "
            "doc-drift check parses)"
        ]
    if not doc_path.exists():
        return [f"{PERF_DOC}: missing (perf-reasons drift check needs it)"]
    problems = []
    doc_text = doc_path.read_text(encoding="utf-8")
    for value in reasons:
        if f"`{value}`" not in doc_text:
            problems.append(
                f"{PERF_MODULE}: watchdog reason {value!r} is not "
                f"documented in {PERF_DOC}"
            )
    begin = doc_text.find(_PERF_DOC_BEGIN)
    end = doc_text.find(_PERF_DOC_END)
    if begin < 0 or end < 0 or end < begin:
        problems.append(
            f"{PERF_DOC}: watchdog reasons table must be fenced by "
            f"{_PERF_DOC_BEGIN} / {_PERF_DOC_END} markers (the reverse "
            "drift direction scans that region)"
        )
        return problems
    known = set(reasons)
    offset = doc_text[:begin].count("\n") + 1
    for lineno, line in enumerate(doc_text[begin:end].splitlines(), offset):
        for token in _BACKTICK_TOKEN_RE.findall(line):
            if token not in known:
                problems.append(
                    f"{PERF_DOC}:{lineno}: watchdog-reasons token "
                    f"{token!r} is not in the PERF_REGRESSION_REASONS "
                    f"vocabulary ({PERF_MODULE})"
                )
    return problems


# Closed flight-event vocabulary: every *literal* kind recorded via a
# ``*_flight_rec("kind", ...)`` / ``*flight*.record("kind", ...)`` call
# under unionml_tpu/ must be listed here AND in the fenced table in
# docs/observability.md — a postmortem filter (`/debug/flight?kind=`)
# and the fleet merge both key on these strings, so an undocumented or
# typo'd kind is an invisible event class. (Variable-kind pass-through
# sites — e.g. the rollout controller recording its decision enum — are
# covered by their own closed-set checks.)
FLIGHT_EVENT_KINDS = (
    # engine lifecycle
    "submit", "reject", "prefill", "decode", "finish", "drop",
    "promote", "preempt", "resume", "pool_pressure", "recovery",
    # micro-batcher
    "batch", "error",
    # fleet router / membership / dispatch
    "join", "leave", "rejoin", "drain", "eject", "probe", "route",
    "retry", "hedge",
    # autoscaler
    "scale_out", "scale_in", "scale_hold", "scale_reap",
    # disaggregated serving
    "handoff",
    # rollouts
    "rollout_shadow",
    # training goodput plane
    "train_compile", "step_time_anomaly", "step_time_regression",
    "straggler",
    # serving perf plane
    "perf_regression",
)
_FLIGHT_DOC_BEGIN = "<!-- FLIGHT_EVENT_KINDS:begin -->"
_FLIGHT_DOC_END = "<!-- FLIGHT_EVENT_KINDS:end -->"


def _flight_kind_literal(node: ast.Call):
    """The literal kind string of a flight-record call, or None when
    the call is not a flight record / the kind is not a literal."""
    func = node.func
    if not (isinstance(func, ast.Attribute) and node.args):
        return None
    if func.attr == "_flight_rec":
        pass
    elif func.attr == "record" and "flight" in ast.unparse(func.value):
        pass
    else:
        return None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


def check_flight_event_kinds(root: Path) -> list:
    """Both directions of the flight-event vocabulary contract: every
    literal kind recorded under unionml_tpu/ must be in
    ``FLIGHT_EVENT_KINDS``, and every backticked token in the fenced
    docs/observability.md table must be a known kind."""
    problems = []
    for path in sorted((root / "unionml_tpu").rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # reported by the per-file checker
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _flight_kind_literal(node)
            if kind is not None and kind not in FLIGHT_EVENT_KINDS:
                problems.append(
                    f"{path}:{node.lineno}: flight event kind {kind!r} "
                    "is not in FLIGHT_EVENT_KINDS (scripts/"
                    "lint_basics.py) — extend the closed vocabulary "
                    "and its docs/observability.md table"
                )
    doc_path = root / METRICS_DOC
    if not doc_path.exists():
        return problems + [
            f"{METRICS_DOC}: missing (flight-kind drift check needs it)"
        ]
    doc_text = doc_path.read_text(encoding="utf-8")
    begin = doc_text.find(_FLIGHT_DOC_BEGIN)
    end = doc_text.find(_FLIGHT_DOC_END)
    if begin < 0 or end < 0 or end < begin:
        problems.append(
            f"{METRICS_DOC}: flight-event kinds must be fenced by "
            f"{_FLIGHT_DOC_BEGIN} / {_FLIGHT_DOC_END} markers (the "
            "reverse drift direction scans that region)"
        )
        return problems
    region = doc_text[begin:end]
    offset = doc_text[:begin].count("\n") + 1
    for lineno, line in enumerate(region.splitlines(), offset):
        for token in _BACKTICK_TOKEN_RE.findall(line):
            if token not in FLIGHT_EVENT_KINDS:
                problems.append(
                    f"{METRICS_DOC}:{lineno}: flight-kind token "
                    f"{token!r} is not in FLIGHT_EVENT_KINDS "
                    "(scripts/lint_basics.py)"
                )
    for kind in FLIGHT_EVENT_KINDS:
        if f"`{kind}`" not in region:
            problems.append(
                f"{METRICS_DOC}: flight event kind {kind!r} is missing "
                "from the fenced FLIGHT_EVENT_KINDS table"
            )
    return problems


def _call_labelnames(node: ast.Call):
    """Constant label names of a metric registration call: the third
    positional arg or the ``labelnames`` kwarg, when it is a literal
    tuple/list of strings (the codebase's only registration idiom)."""
    label_arg = node.args[2] if len(node.args) >= 3 else None
    for kw in node.keywords:
        if kw.arg == "labelnames":
            label_arg = kw.value
    if not isinstance(label_arg, (ast.Tuple, ast.List)):
        return ()
    return tuple(
        e.value for e in label_arg.elts
        if isinstance(e, ast.Constant) and isinstance(e.value, str)
    )


def check_label_cardinality(package_root: Path) -> list:
    """Every ``unionml_*`` registration whose label schema contains a
    request-derived name must live in the ledger module — the single
    home of the bounded rollup that keeps such labels finite."""
    problems = []
    for path in sorted(package_root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            rel = path.resolve().relative_to(ROOT).as_posix()
        except ValueError:
            rel = path.as_posix()
        if rel in REQUEST_LABEL_EXEMPT:
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # reported by the per-file checker
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            func = node.func
            factory = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else None
            )
            if factory not in _METRIC_FACTORIES or not (
                isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("unionml_")
            ):
                continue
            bad = [
                label for label in _call_labelnames(node)
                if label in REQUEST_DERIVED_LABELS
            ]
            if bad:
                problems.append(
                    f"{path}:{node.lineno}: metric "
                    f"{node.args[0].value} takes request-derived "
                    f"label(s) {bad} outside the usage ledger — route "
                    "through UsageLedger's bounded top-K rollup "
                    "(unionml_tpu/serving/usage.py) so a client cannot "
                    "mint unbounded series"
                )
    return problems


def registered_metric_names(package_root: Path) -> dict:
    """``{metric_name: "file:line"}`` for every ``unionml_*`` metric
    registered under the package (AST walk: the first string argument
    of a ``.counter/.gauge/.histogram(...)`` call)."""
    names: dict = {}
    for path in sorted(package_root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"))
        except SyntaxError:
            continue  # reported by the per-file checker
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            func = node.func
            factory = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else None
            )
            if factory not in _METRIC_FACTORIES or not (
                isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
            ):
                continue
            name = node.args[0].value
            if name.startswith("unionml_"):
                names.setdefault(name, f"{path}:{node.args[0].lineno}")
    return names


def check_metrics_doc(root: Path) -> list:
    """Both directions of the metrics/doc contract: registered names
    must be documented; documented full names must be registered."""
    doc_path = root / METRICS_DOC
    if not doc_path.exists():
        return [f"{METRICS_DOC}: missing (metric drift check needs it)"]
    doc_text = doc_path.read_text(encoding="utf-8")
    registered = registered_metric_names(root / "unionml_tpu")
    problems = []
    for name, where in sorted(registered.items()):
        if name not in doc_text:
            problems.append(
                f"{where}: metric {name} is not documented in "
                f"{METRICS_DOC}"
            )
    known = set(registered)
    for name in known.copy():
        known.update(name + suffix for suffix in _SERIES_SUFFIXES)
    for lineno, line in enumerate(doc_text.splitlines(), 1):
        for token in _DOC_METRIC_RE.findall(line):
            if token not in known:
                problems.append(
                    f"{METRICS_DOC}:{lineno}: documented metric {token} "
                    "is not registered anywhere under unionml_tpu/"
                )
    return problems


def main(argv) -> int:
    paths = argv or DEFAULT_PATHS
    files: list = []
    for p in paths:
        path = (ROOT / p) if not Path(p).is_absolute() else Path(p)
        if path.is_dir():
            files.extend(sorted(path.rglob("*.py")))
        elif path.suffix == ".py" and path.exists():
            files.append(path)
        else:
            # a typo'd path must not green-light unlinted code
            print(f"lint_basics: path does not resolve: {p}")
            return 2
    problems: list = []
    for f in files:
        if "__pycache__" in f.parts:
            continue
        problems.extend(check_file(f))
    if paths is DEFAULT_PATHS or "unionml_tpu" in paths:
        # repo-wide contracts, meaningful only when the package is in
        # scope (a single-file lint must not fail on doc drift). The
        # default `make lint` target always lands here, so the
        # metrics↔docs drift check and the span-name enum run on
        # every lint, not just when someone remembers to ask.
        problems.extend(check_metrics_doc(ROOT))
        problems.extend(check_label_cardinality(ROOT / "unionml_tpu"))
        problems.extend(check_span_names(ROOT / "unionml_tpu"))
        problems.extend(check_device_scope_names(ROOT))
        problems.extend(check_engine_layering(ROOT))
        problems.extend(check_rollout_reasons(ROOT))
        problems.extend(check_perf_reasons(ROOT))
        problems.extend(check_flight_event_kinds(ROOT))
    for p in problems:
        print(p)
    print(f"lint_basics: {len(files)} files, {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
