#!/usr/bin/env python
"""Docs health check: fail CI when the docs rot.

Four checks over README.md and docs/*.md:

1. markdown links: every relative `[text](path)` target exists;
2. inline code paths: every backtick-quoted repo path (`docs/...`,
   `tests/...`, `benchmarks/...`, `src/...`, or a `src/repro`-relative
   module path like `core/pipeline.py`, optionally with a `::symbol`
   suffix) resolves to a real file;
3. quickstart commands: every `PYTHONPATH=src python ...` command found
   in fenced code blocks is executed in --help / --list / compile-only
   form, so a renamed flag or moved entry point fails the check instead
   of rotting silently;
4. CLI flags: every `--flag` token the docs mention (in inline code or
   fenced blocks) must appear in a live `add_argument` definition in the
   repo's CLI sources (`src/repro/launch/*.py`, `benchmarks/*.py`,
   `tests/conftest.py`), in the spec flag table
   (`serving/spec.py::CLI_FLAGS` — `launch.serve` generates its argparse
   from it), or in the small argparse built-in allowlist — a renamed
   serving/benchmark knob fails the check instead of leaving the tuning
   guide pointing at a flag that no longer exists.

Plus structural checks:

5. flag<->spec three-way consistency: `serving.spec.CLI_FLAGS` (the
   single flag<->field table), the LIVE `launch.serve` argparse (built
   via `build_parser()`), and the `EngineSpec` dataclass fields must
   agree — every table flag is a real parser flag, every parser flag is
   either in the table or a declared workload flag, every table field is
   a real spec field, and every spec field is either in the table or in
   the declared no-flag set.  A knob added in one place but not the
   others fails CI.

6. benchmark scenarios: the scenario table in `docs/BENCHMARKS.md` and
   `benchmarks/run.py::BENCHES` must list the same names, both ways — a
   scenario added to the harness without a methodology row (or a
   documented scenario that was renamed/removed) fails CI.

Run locally:  python tools/check_docs.py
"""
from __future__ import annotations

import dataclasses
import os
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
DOC_FILES = [ROOT / "README.md", *sorted((ROOT / "docs").glob("*.md"))]

LINK_RE = re.compile(r"\[[^\]]+\]\(([^)#\s]+)\)")
CODEPATH_RE = re.compile(
    r"`((?:src|docs|tests|benchmarks|examples|tools|core|serving|models|"
    r"quant|launch|kernels|configs)/[A-Za-z0-9_./-]+\.(?:py|md|yml|yaml))"
    r"(?:::[A-Za-z0-9_.]+)?`")
FENCE_RE = re.compile(r"```[a-z]*\n(.*?)```", re.S)
INLINE_CODE_RE = re.compile(r"`([^`\n]+)`")
FLAG_RE = re.compile(r"(?<![\w/-])--[a-z][a-z0-9-]*")
FLAG_DEF_RE = re.compile(
    r"(?:add_argument|addoption)\(\s*['\"](--[a-z][a-z0-9-]*)['\"]")
# where CLI flags are defined (argparse entry points)
CLI_SOURCES = [*sorted((ROOT / "src" / "repro" / "launch").glob("*.py")),
               *sorted((ROOT / "benchmarks").glob("*.py")),
               ROOT / "chip_smoke.py", ROOT / "tests" / "conftest.py"]
# argparse/pytest built-ins the docs may reference without defining
FLAG_ALLOWLIST = {"--help"}


def known_cli_flags():
    flags = set(FLAG_ALLOWLIST)
    for src in CLI_SOURCES:
        flags.update(FLAG_DEF_RE.findall(src.read_text()))
    from repro.serving.spec import CLI_FLAGS
    flags.update(f.flag for f in CLI_FLAGS)
    return flags


def check_spec_cli_consistency(errors: list):
    """Check 5: the flag<->field table vs the LIVE launch.serve argparse
    vs the EngineSpec dataclass, three ways."""
    from repro.launch.serve import build_parser
    from repro.serving.spec import (CLI_FLAGS, NO_FLAG_FIELDS,
                                    WORKLOAD_FLAGS, EngineSpec)
    parser_flags = {s for a in build_parser()._actions
                    for s in a.option_strings if s.startswith("--")}
    table_flags = {f.flag for f in CLI_FLAGS}
    table_fields = [f.field for f in CLI_FLAGS]
    spec_fields = {f.name for f in dataclasses.fields(EngineSpec)}
    for fl in sorted(table_flags - parser_flags):
        errors.append(f"spec table flag {fl} not defined by "
                      f"launch.serve's argparse")
    for fl in sorted(parser_flags - table_flags - WORKLOAD_FLAGS):
        errors.append(f"launch.serve flag {fl} neither in "
                      f"serving.spec.CLI_FLAGS nor WORKLOAD_FLAGS")
    for fd in sorted(set(table_fields) - spec_fields):
        errors.append(f"spec table field {fd!r} is not an EngineSpec "
                      f"dataclass field")
    for fd in sorted(spec_fields - set(table_fields) - NO_FLAG_FIELDS):
        errors.append(f"EngineSpec field {fd!r} has no CLI flag and is "
                      f"not in NO_FLAG_FIELDS")
    dup = {f for f in table_fields if table_fields.count(f) > 1}
    if dup:
        errors.append(f"spec table maps multiple flags to field(s) "
                      f"{sorted(dup)}")


def check_bench_scenarios(errors: list):
    """Check 6: docs/BENCHMARKS.md's scenario table vs
    ``benchmarks/run.py::BENCHES``, both directions.  run.py's top level
    imports numpy/argparse only, so loading it here is cheap."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_pipo_bench_run", ROOT / "benchmarks" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bench_names = {b.__name__ for b in mod.BENCHES}
    # scenario-table rows: the only BENCHMARKS.md table whose first
    # column is a backticked identifier
    table_names = set(re.findall(
        r"^\|\s*`([a-z0-9_]+)`", (ROOT / "docs" / "BENCHMARKS.md")
        .read_text(), re.M))
    for n in sorted(bench_names - table_names):
        errors.append(f"benchmarks/run.py scenario {n!r} has no row in "
                      f"docs/BENCHMARKS.md's scenario table")
    for n in sorted(table_names - bench_names):
        errors.append(f"docs/BENCHMARKS.md scenario `{n}` is not in "
                      f"benchmarks/run.py BENCHES")


def doc_flags(text: str):
    """(flag, snippet) pairs from inline code spans and fenced blocks —
    prose is skipped so an em-dash or option-like phrase can't trip it."""
    out = []
    for block in FENCE_RE.findall(text):
        out += [(f, block.strip().splitlines()[0])
                for f in FLAG_RE.findall(block)]
    for span in INLINE_CODE_RE.findall(FENCE_RE.sub("", text)):
        out += [(f, span) for f in FLAG_RE.findall(span)]
    return out


def resolve_code_path(p: str):
    for base in (ROOT, ROOT / "src" / "repro"):
        if (base / p).exists():
            return base / p
    return None


def extract_commands(block: str):
    """`PYTHONPATH=src python ...` lines, with backslash continuations
    folded in."""
    out = []
    lines = block.splitlines()
    i = 0
    while i < len(lines):
        line = lines[i].strip()
        if line.startswith("PYTHONPATH=src python"):
            cmd = line
            while cmd.endswith("\\") and i + 1 < len(lines):
                i += 1
                cmd = cmd[:-1].rstrip() + " " + lines[i].strip()
            out.append(cmd.split(" # ")[0].rstrip())   # drop trailing comment
        i += 1
    return out


def check_file(md: Path, errors: list, cli_flags: set):
    text = md.read_text()
    rel = md.relative_to(ROOT)
    for m in LINK_RE.finditer(text):
        target = m.group(1)
        if target.startswith(("http://", "https://", "mailto:")):
            continue
        if not (md.parent / target).exists() and not (ROOT / target).exists():
            errors.append(f"{rel}: dead link -> {target}")
    for m in CODEPATH_RE.finditer(text):
        if resolve_code_path(m.group(1)) is None:
            errors.append(f"{rel}: dead code path -> `{m.group(1)}`")
    for flag, snippet in doc_flags(text):
        if flag not in cli_flags:
            errors.append(f"{rel}: unknown CLI flag {flag} "
                          f"(in `{snippet[:60]}`) — not defined by any "
                          f"argparse source")
    cmds = []
    for block in FENCE_RE.findall(text):
        cmds += extract_commands(block)
    return cmds


def dry_form(cmd: str):
    """Map a quickstart command to a cheap dry invocation (argparse
    --help exits before heavy imports; benchmarks use --list; a serve
    --plan-json command runs AS-IS — resolving the plan without
    building an engine is itself the dry-run, and it exercises the
    whole spec->plan path in docs CI)."""
    argv = cmd.split()
    assert argv[0] == "PYTHONPATH=src" and argv[1] == "python"
    rest = argv[2:]
    if rest[0] == "-m" and rest[1] == "pytest":
        return None                       # running the suite is CI's job
    if rest[0] == "-m" and rest[1] == "repro.launch.serve" \
            and "--plan-json" in rest:
        return [sys.executable, "-m", *rest[1:]]
    if rest[0] == "-m":
        return [sys.executable, "-m", rest[1], "--help"]
    if rest[0].endswith("benchmarks/run.py"):
        return [sys.executable, rest[0], "--list"]
    if rest[0].endswith(".py"):
        # plain script: syntax-check only (examples may run long)
        return [sys.executable, "-m", "py_compile", rest[0]]
    return None


def main() -> int:
    errors: list[str] = []
    commands: list[str] = []
    cli_flags = known_cli_flags()
    check_spec_cli_consistency(errors)
    check_bench_scenarios(errors)
    for md in DOC_FILES:
        if not md.exists():
            errors.append(f"missing doc file: {md.relative_to(ROOT)}")
            continue
        commands += check_file(md, errors, cli_flags)
    if not any(md.name == "ARCHITECTURE.md" for md in DOC_FILES):
        errors.append("docs/ARCHITECTURE.md missing")
    if not any(md.name == "BENCHMARKS.md" for md in DOC_FILES):
        errors.append("docs/BENCHMARKS.md missing")

    # the dry-runs only parse and resolve; they stay off any accelerator,
    # whose chip belongs to one process at a time
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    seen = set()
    for cmd in commands:
        dry = dry_form(cmd)
        if dry is None or tuple(dry) in seen:
            continue
        seen.add(tuple(dry))
        try:
            r = subprocess.run(dry, cwd=ROOT, capture_output=True,
                               text=True, env=env, timeout=180)
        except subprocess.TimeoutExpired:
            errors.append(f"quickstart dry-run timed out: {' '.join(dry)}")
            continue
        if r.returncode != 0:
            errors.append(f"quickstart dry-run failed ({' '.join(dry)}):\n"
                          f"{r.stderr.strip()[-400:]}")

    if errors:
        print("docs check FAILED:", file=sys.stderr)
        for e in errors:
            print(f"  - {e}", file=sys.stderr)
        return 1
    print(f"docs check OK: {len(DOC_FILES)} files, {len(seen)} quickstart "
          f"commands dry-run")
    return 0


if __name__ == "__main__":
    sys.exit(main())
