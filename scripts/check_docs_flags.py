#!/usr/bin/env python
"""Docs/CLI drift gate: every ``--flag`` a doc mentions must exist.

Scans the documentation surface (README.md, docs/*.md, tests/README.md)
for ``--flag`` tokens and checks each one against the union of flags
actually defined by ``add_argument`` calls in the CLI entry points
(``launch/serve.py``, ``launch/sharded_check.py``, ``launch/train.py``,
``launch/dryrun.py``, ``scripts/bench_smoke.py``,
``benchmarks/fig8_throughput.py``).  A flag that is renamed or removed
without updating the docs fails CI here, in the lint job, before the
test jobs spend minutes reaching it.

Pure stdlib + regex on source text: the lint job that runs this has no
jax installed, so the argparse definitions are scraped, not imported.

Exit status: 0 when every documented flag exists, 1 otherwise (the
unknown flags and the closest defined names are printed).
"""
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

CLI_SOURCES = [
    "chip_smoke.py",
    "src/repro/launch/serve.py",
    "src/repro/launch/sharded_check.py",
    "src/repro/launch/train.py",
    "src/repro/launch/dryrun.py",
    "scripts/bench_smoke.py",
    "benchmarks/fig8_throughput.py",
]

DOC_SOURCES = ["README.md", "tests/README.md"]

# non-argparse flags docs legitimately mention: tool flags (pytest,
# pip, XLA) that are not this repo's CLI surface
ALLOW = {
    "--xla_force_host_platform_device_count",
    "--upgrade",  # pip install --upgrade in quickstart snippets
    "-x", "-q", "-k", "-m",  # pytest short flags
}

FLAG_DEF_RE = re.compile(r"add_argument\(\s*['\"](--[A-Za-z][\w-]*)['\"]")
FLAG_USE_RE = re.compile(r"(?<![\w-])(--[A-Za-z][\w-]*)")


def defined_flags():
    flags = {}
    for rel in CLI_SOURCES:
        text = (REPO / rel).read_text()
        for m in FLAG_DEF_RE.finditer(text):
            flags.setdefault(m.group(1), []).append(rel)
            # argparse.BooleanOptionalAction also defines --no-<flag>
            if "BooleanOptionalAction" in text[m.end():m.end() + 80]:
                flags.setdefault("--no-" + m.group(1)[2:], []).append(rel)
    return flags


def doc_files():
    files = [REPO / rel for rel in DOC_SOURCES]
    files += sorted((REPO / "docs").glob("*.md"))
    return [f for f in files if f.exists()]


def main():
    defined = defined_flags()
    if not defined:
        print("check_docs_flags: no add_argument definitions found "
              "(CLI_SOURCES stale?)")
        return 1
    bad = []
    n_mentions = 0
    for doc in doc_files():
        for ln, line in enumerate(doc.read_text().splitlines(), 1):
            for m in FLAG_USE_RE.finditer(line):
                flag = m.group(1)
                n_mentions += 1
                if flag in defined or flag in ALLOW:
                    continue
                bad.append((doc.relative_to(REPO), ln, flag))
    if bad:
        print("check_docs_flags: documented flags that no CLI defines:")
        for rel, ln, flag in bad:
            near = [f for f in defined if flag[:5] in f] or sorted(defined)
            print(f"  {rel}:{ln}: {flag}  (defined flags include: "
                  f"{', '.join(near[:4])})")
        return 1
    print(f"check_docs_flags ok: {n_mentions} flag mentions across "
          f"{len(doc_files())} docs, all defined "
          f"({len(defined)} flags in {len(CLI_SOURCES)} CLI sources)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
