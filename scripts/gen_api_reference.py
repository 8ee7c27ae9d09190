"""Generate docs/api_reference.md + docs/cli_reference.md from source.

The reference ships Sphinx-generated API and CLI references
(reference: docs/source/api_reference.rst, cli_reference.rst, conf.py).
This repo's environments don't carry Sphinx, so the generator is
stdlib-`inspect` over the public surface: module docstring, then every
public class/function with its signature and docstring summary. Runs
headless (no jax device init — signatures only) and deterministically;
`tests/unit/test_docs_reference.py` regenerates and diffs against the
committed files so they cannot go stale.

Usage::

    python scripts/gen_api_reference.py [--check]
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# public surface, in reading order (module, one-line section title)
MODULES = [
    ("unionml_tpu", "Package root"),
    ("unionml_tpu.dataset", "Dataset spec"),
    ("unionml_tpu.model", "Model spec"),
    ("unionml_tpu.stage", "Stages & workflows"),
    ("unionml_tpu.type_guards", "Type guards"),
    ("unionml_tpu.execution", "Execution engine"),
    ("unionml_tpu.elastic", "Elastic training"),
    ("unionml_tpu.diagnostics", "Diagnostics"),
    ("unionml_tpu.telemetry", "Telemetry"),
    ("unionml_tpu.goodput", "Training goodput"),
    ("unionml_tpu.exporters", "OTLP export"),
    ("unionml_tpu.slo", "SLO watchdog"),
    ("unionml_tpu.introspection", "Program introspection & profiling"),
    ("unionml_tpu.compile_cache", "Compile cache"),
    ("unionml_tpu.parallel", "Parallelism"),
    ("unionml_tpu.parallel.sharding", "Sharding config"),
    ("unionml_tpu.parallel.mesh", "Device meshes"),
    ("unionml_tpu.parallel.pipeline", "Pipeline parallelism"),
    ("unionml_tpu.ops", "TPU ops & kernels"),
    ("unionml_tpu.ops.paged_attention", "Paged attention"),
    ("unionml_tpu.models", "Model zoo"),
    ("unionml_tpu.models.generate", "Generation"),
    ("unionml_tpu.models.quantization", "Quantization"),
    ("unionml_tpu.data", "Data path"),
    ("unionml_tpu.checkpoint", "Checkpointing"),
    ("unionml_tpu.checkpoint.async_writer", "Async checkpointing"),
    ("unionml_tpu.remote", "Remote backend"),
    ("unionml_tpu.serving", "Serving"),
    ("unionml_tpu.serving.prefix_cache", "Prefix KV-cache"),
    ("unionml_tpu.serving.kv_pool", "Paged KV block pool"),
    ("unionml_tpu.serving.usage", "Usage metering & cost attribution"),
    ("unionml_tpu.serving.scheduler", "Preemptive scheduling & fairness"),
    ("unionml_tpu.serving.faults", "Serving fault tolerance"),
    ("unionml_tpu.serving.router", "Fleet router (cluster front door)"),
    ("unionml_tpu.serving.autoscaler", "SLO-driven fleet autoscaler"),
    ("unionml_tpu.serving.disagg", "Disaggregated prefill/decode serving"),
    ("unionml_tpu.serving.rollout", "Versioned rollouts & canary lifecycle"),
    ("unionml_tpu.serving.perf", "Serving goodput & perf watchdog"),
]


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [
        n for n, obj in vars(mod).items()
        if not n.startswith("_")
        and (inspect.isclass(obj) or inspect.isfunction(obj))
        and getattr(obj, "__module__", "").startswith(mod.__name__)
    ]


def _scrub(text: str) -> str:
    """Drop memory addresses (flax sentinel reprs leak into dataclass
    docstrings and signatures) so generation is deterministic."""
    import re

    return re.sub(r" at 0x[0-9a-f]+", "", text)


def _doc_summary(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    para = doc.split("\n\n", 1)[0].replace("\n", " ").strip()
    return _scrub(para)


def _signature(obj) -> str:
    try:
        return _scrub(str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def render_api() -> str:
    lines = [
        "# API reference",
        "",
        "<!-- GENERATED by scripts/gen_api_reference.py — do not edit by hand;",
        "     regenerate with `python scripts/gen_api_reference.py` -->",
        "",
        "Public surface of `unionml_tpu`, generated from source docstrings",
        "and signatures (the reference's Sphinx `api_reference.rst` analog).",
        "",
    ]
    for mod_name, title in MODULES:
        mod = importlib.import_module(mod_name)
        lines.append(f"## {title} — `{mod_name}`")
        lines.append("")
        summary = _doc_summary(mod)
        if summary:
            lines.append(summary)
            lines.append("")
        for name in _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None or not (inspect.isclass(obj) or callable(obj)):
                continue
            owner = getattr(obj, "__module__", mod_name)
            if not str(owner).startswith("unionml_tpu"):
                continue
            kind = "class" if inspect.isclass(obj) else "def"
            lines.append(f"### `{kind} {name}{_signature(obj)}`")
            lines.append("")
            doc = _doc_summary(obj)
            if doc:
                lines.append(doc)
                lines.append("")
            if inspect.isclass(obj):
                for mname, meth in inspect.getmembers(obj, inspect.isfunction):
                    if mname.startswith("_") or meth.__qualname__.split(".")[0] != obj.__name__:
                        continue
                    mdoc = _doc_summary(meth)
                    lines.append(f"- `{mname}{_signature(meth)}`" + (f" — {mdoc}" if mdoc else ""))
                lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def render_cli() -> str:
    import click

    from unionml_tpu.cli import app

    lines = [
        "# CLI reference",
        "",
        "<!-- GENERATED by scripts/gen_api_reference.py — do not edit by hand;",
        "     regenerate with `python scripts/gen_api_reference.py` -->",
        "",
        "`unionml-tpu` command surface (the reference's Sphinx",
        "`cli_reference.rst` analog; same seven verbs as `unionml`,",
        "reference: cli.py:26-212).",
        "",
    ]
    ctx = click.Context(app, info_name="unionml-tpu")
    for name in sorted(app.list_commands(ctx)):
        cmd = app.get_command(ctx, name)
        lines.append(f"## `unionml-tpu {name}`")
        lines.append("")
        if cmd.help:
            lines.append(inspect.cleandoc(cmd.help))
            lines.append("")
        lines.append("```")
        sub = click.Context(cmd, info_name=f"unionml-tpu {name}")
        lines.append(cmd.get_help(sub))
        lines.append("```")
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--check", action="store_true",
                        help="exit 1 if committed files are stale")
    args = parser.parse_args()

    outputs = {
        REPO / "docs" / "api_reference.md": render_api(),
        REPO / "docs" / "cli_reference.md": render_cli(),
    }
    stale = []
    for path, content in outputs.items():
        if args.check:
            if not path.exists() or path.read_text() != content:
                stale.append(str(path))
        else:
            path.write_text(content)
            print(f"wrote {path}")
    if stale:
        print(f"STALE (regenerate with scripts/gen_api_reference.py): {stale}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
