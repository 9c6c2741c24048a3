#!/usr/bin/env python
"""Doc-consistency CI check (wired into the examples-smoke job).

Three invariants keep the docs honest:

1. **API coverage** — every name in the ``__all__`` of ``repro``,
   ``repro.chain``, ``repro.chain.net``, ``repro.chain.workloads``,
   ``repro.core`` and ``repro.spans`` has a ``### `module.name` `` heading in
   ``docs/api.md`` (a new export without a doc entry fails CI; a doc
   entry for a removed export fails too).
2. **Docs execute** — every ```` ```python ```` block in README.md and
   ``docs/workloads.md`` runs, in order, in one shared namespace per
   file (a doctest-style session: later blocks may use names defined
   by earlier ones).  ``docs/api.md`` blocks are executed by the
   tier-1 suite (``tests/test_docs.py``) — they are numerous and
   belong with the fast feedback loop.
3. **No orphan docs** — every ``docs/*.md`` file must be claimed by an
   entry in ``DOC_CHECKS`` below; a doc nothing executes or
   cross-checks is a doc that silently rots.

Run it the way CI does::

    PYTHONPATH=src python scripts/check_docs.py
"""
from __future__ import annotations

import importlib
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "src"))

MODULES = ("repro", "repro.chain", "repro.chain.net",
           "repro.chain.workloads", "repro.core", "repro.spans")

# every file under docs/ must appear here, mapped to how it is kept
# honest: "blocks" (its ```python blocks execute in this script),
# "tier1" (executed/cross-checked by tests/test_docs.py), or a
# free-form justification string for genuinely static docs.
DOC_CHECKS = {
    "api.md": "tier1",      # coverage here + snippets in tests/test_docs.py
    "workloads.md": "blocks",
}


def check_api_coverage(api_md: Path = REPO / "docs" / "api.md"
                       ) -> list:
    """Names exported but undocumented, plus documented-but-not-exported
    headings (empty list == consistent)."""
    text = api_md.read_text()
    problems = []
    documented = set(re.findall(r"^###\s+`([\w.]+)`", text, re.M))
    exported = set()
    for modname in MODULES:
        mod = importlib.import_module(modname)
        for name in mod.__all__:
            exported.add(f"{modname}.{name}")
            if f"{modname}.{name}" not in documented:
                problems.append(
                    f"{modname}.{name} is exported in {modname}.__all__ "
                    f"but has no `### \\`{modname}.{name}\\`` entry in "
                    f"{api_md.relative_to(REPO)}")
    for heading in sorted(documented):
        modname = heading.rsplit(".", 1)[0]
        if modname in MODULES and heading not in exported:
            problems.append(
                f"{heading} is documented in {api_md.relative_to(REPO)} "
                f"but not exported from {modname}.__all__ (stale entry?)")
    return problems


def run_md_blocks(path: Path) -> list:
    """Execute every ```python block of ``path`` in one shared
    namespace, in order.  Returns a list of failure descriptions."""
    text = path.read_text()
    blocks = re.findall(r"```python\n(.*?)```", text, re.S)
    ns: dict = {}
    problems = []
    for i, block in enumerate(blocks):
        try:
            exec(compile(block, f"<{path.name} block {i}>", "exec"), ns)
        except Exception as e:                     # noqa: BLE001
            problems.append(
                f"{path.name} python block {i} failed: "
                f"{type(e).__name__}: {e}\n---\n{block}---")
    if not blocks:
        problems.append(f"{path.name} contains no ```python blocks")
    return problems


def run_readme_blocks(readme: Path = REPO / "README.md") -> list:
    """README's executable session (kept as its own entry point — the
    tier-1 suite calls it too)."""
    return run_md_blocks(readme)


def check_docs_coverage(docs_dir: Path = REPO / "docs") -> list:
    """Every docs/*.md must be claimed by DOC_CHECKS (and vice versa) —
    a doc no check executes or cross-references rots silently."""
    problems = []
    on_disk = {p.name for p in docs_dir.glob("*.md")}
    for name in sorted(on_disk - set(DOC_CHECKS)):
        problems.append(
            f"docs/{name} is not covered by any doc check — add it to "
            "DOC_CHECKS in scripts/check_docs.py (execute its blocks, "
            "or justify why it is static)")
    for name in sorted(set(DOC_CHECKS) - on_disk):
        problems.append(
            f"DOC_CHECKS claims docs/{name} but the file does not exist "
            "(stale entry in scripts/check_docs.py?)")
    return problems


def main() -> int:
    problems = check_api_coverage()
    n_api = len(problems)
    print(f"api coverage: {'OK' if not n_api else f'{n_api} problem(s)'} "
          f"({sum(len(importlib.import_module(m).__all__) for m in MODULES)}"
          " exported names checked)")
    readme_problems = run_readme_blocks()
    problems += readme_problems
    print(f"README blocks: "
          f"{'OK' if not readme_problems else 'FAILED'}")
    for name, how in DOC_CHECKS.items():
        if how != "blocks":
            continue
        doc_problems = run_md_blocks(REPO / "docs" / name)
        problems += doc_problems
        print(f"docs/{name} blocks: "
              f"{'OK' if not doc_problems else 'FAILED'}")
    coverage_problems = check_docs_coverage()
    problems += coverage_problems
    print(f"docs coverage: "
          f"{'OK' if not coverage_problems else 'FAILED'} "
          f"({len(DOC_CHECKS)} docs claimed)")
    for p in problems:
        print(f"  - {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
