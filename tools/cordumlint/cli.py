"""cordumlint CLI.

Exit codes: 0 clean (or everything baselined), 1 active findings,
2 usage / configuration error.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from . import __version__, baseline as baseline_mod
from .core import all_rules, lint_paths
from .reporters import json_report, text_report

DEFAULT_BASELINE = "tools/cordumlint/baseline.json"
DEFAULT_CONFIG = "cordumlint.json"


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m tools.cordumlint",
        description="Control-plane-aware static analysis for cordum-tpu.",
    )
    p.add_argument("paths", nargs="*", default=["cordum_tpu"],
                   help="files or directories to lint (default: cordum_tpu)")
    p.add_argument("--root", default=".", help="repo root for relative paths")
    p.add_argument("--config", default=None,
                   help=f"config JSON (default: {DEFAULT_CONFIG} at root if present)")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--select", default="",
                   help="comma-separated rule ids to run (e.g. CL001,CL007)")
    p.add_argument("--ignore", default="", help="comma-separated rule ids to skip")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON path (default: {DEFAULT_BASELINE} at root)")
    p.add_argument("--no-baseline", action="store_true",
                   help="report grandfathered findings as active")
    p.add_argument("--write-baseline", action="store_true",
                   help="record current findings as grandfathered (needs --justification)")
    p.add_argument("--justification", default="",
                   help="why the baselined findings are acceptable (required with --write-baseline)")
    p.add_argument("--show-baselined", action="store_true",
                   help="include baselined findings in the report")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--write-obs-inventory", action="store_true",
                   help="regenerate the metric inventory section in "
                        "docs/OBSERVABILITY.md from the code (CL011 checks "
                        "against it)")
    p.add_argument("--version", action="version", version=f"cordumlint {__version__}")
    return p


def _load_config(root: Path, arg: str | None) -> dict:
    path = Path(arg) if arg else root / DEFAULT_CONFIG
    if not path.is_absolute():
        path = root / path
    if path.exists():
        return json.loads(path.read_text(encoding="utf-8"))
    if arg:  # explicitly requested but missing
        raise FileNotFoundError(f"config not found: {path}")
    return {}


def _write_obs_inventory(args, root: Path, config: dict) -> int:
    """Regenerate the CL011-checked metric inventory in docs/OBSERVABILITY.md
    from the same static collection the rule runs."""
    from .core import LintContext, _rel, collect_files
    from .program_rules import (
        INVENTORY_BEGIN, INVENTORY_END, MetricsConformance, render_inventory,
    )

    rule = MetricsConformance((config.get("rules", {}) or {}).get("CL011", {}))
    for f in collect_files(args.paths, root, config.get("exclude", ())):
        try:
            rule.collect(LintContext(f, _rel(f, root), f.read_text(encoding="utf-8")))
        except (SyntaxError, UnicodeDecodeError, OSError):
            continue
    doc = root / rule.doc_rel
    section = render_inventory(rule)
    text = doc.read_text(encoding="utf-8") if doc.exists() else ""
    if INVENTORY_BEGIN in text and INVENTORY_END in text:
        head, rest = text.split(INVENTORY_BEGIN, 1)
        tail = rest.split(INVENTORY_END, 1)[1]
        text = head + section + tail
    else:
        text = text.rstrip() + "\n\n## Metric inventory\n\n" + section + "\n"
    doc.write_text(text, encoding="utf-8")
    print(f"cordumlint: wrote {len(rule.defs)} metric families -> {doc}")
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    root = Path(args.root).resolve()

    try:
        config = _load_config(root, args.config)
    except (FileNotFoundError, json.JSONDecodeError) as e:
        print(f"cordumlint: {e}", file=sys.stderr)
        return 2

    if args.list_rules:
        for rule in all_rules(config):
            doc = (rule.__doc__ or "").strip().replace("\n    ", "\n  ")
            print(f"{rule.id} {rule.name}\n  {doc}\n")
        return 0

    if args.write_obs_inventory:
        return _write_obs_inventory(args, root, config)

    select = {s.strip().upper() for s in args.select.split(",") if s.strip()}
    ignore = {s.strip().upper() for s in args.ignore.split(",") if s.strip()}
    result = lint_paths(
        args.paths, root=root, config=config,
        select=select or None, ignore=ignore or None,
    )

    baseline_path = Path(args.baseline) if args.baseline else root / DEFAULT_BASELINE
    if not baseline_path.is_absolute():
        baseline_path = root / baseline_path

    if args.write_baseline:
        if not args.justification.strip():
            print(
                "cordumlint: --write-baseline requires --justification "
                "(why are these findings acceptable?)",
                file=sys.stderr,
            )
            return 2
        n = baseline_mod.write(baseline_path, result.findings, args.justification)
        print(f"cordumlint: baselined {n} finding(s) -> {baseline_path}")
        return 0

    if not args.no_baseline:
        try:
            doc = baseline_mod.load(baseline_path)
        except (ValueError, json.JSONDecodeError) as e:
            print(f"cordumlint: bad baseline: {e}", file=sys.stderr)
            return 2
        result.findings = baseline_mod.apply(result.findings, doc)

    report = text_report if args.format == "text" else json_report
    report(result, stream=sys.stdout, show_baselined=args.show_baselined)

    if result.parse_errors:
        return 2
    active = [f for f in result.findings if not f.baselined]
    return 1 if active else 0
