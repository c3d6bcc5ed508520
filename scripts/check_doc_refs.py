#!/usr/bin/env python
"""Check that intra-repo file references in markdown docs resolve.

A reference is any backtick-quoted token that looks like a repo path:
it contains a ``/`` or ends in a known file suffix. Tokens containing
spaces, globs, or placeholders are ignored; a trailing ``:<line>`` is
stripped. Bare filenames (no ``/``) may live anywhere in the tree.

Usage: python scripts/check_doc_refs.py DOC.md [...]
Exits 1 listing broken references, 0 when everything resolves.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

SUFFIXES = (".py", ".md", ".json", ".yml", ".yaml", ".toml", ".txt")
ROOT = Path(__file__).resolve().parent.parent
SKIP_DIRS = {".git", "build", "__pycache__", ".pytest_cache"}


def iter_refs(text: str):
    for tok in re.findall(r"`([^`\n]+)`", text):
        tok = tok.strip().rstrip("/")
        tok = re.sub(r":\d+$", "", tok)          # path.py:123 → path.py
        if not re.fullmatch(r"[\w./-]+", tok) or tok.startswith("-"):
            continue
        if "/" in tok or tok.endswith(SUFFIXES):
            yield tok


def resolves(tok: str) -> bool:
    if (ROOT / tok).exists():
        return True
    if "/" not in tok:                           # bare filename: search tree
        for p in ROOT.rglob(tok):
            if not SKIP_DIRS.intersection(p.relative_to(ROOT).parts):
                return True
    return False


def main(argv: list[str]) -> int:
    if not argv:
        print(__doc__)
        return 2
    broken = []
    for doc in argv:
        text = Path(doc).read_text()
        for tok in sorted(set(iter_refs(text))):
            if not resolves(tok):
                broken.append(f"{doc}: `{tok}` does not resolve")
    for line in broken:
        print(line)
    if not broken:
        print(f"ok: all intra-repo references in {len(argv)} doc(s) "
              f"resolve")
    return 1 if broken else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
