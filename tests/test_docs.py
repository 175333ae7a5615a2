"""Every repo path a document names exists: a deletion that leaves a
sentence pointing at the deleted file fails here."""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md", "ARCHITECTURE.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, ROOT)
                 for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))))
BASES = ("", "spark_rapids_tpu", "benchmarks")


def _named_paths(text):
    """Words inside backticks (inline spans and fenced blocks) that end
    in .py, .json or .md, less a trailing `:line`, `:function` or
    `::test` suffix. Absolute paths and patterns (`*`, `<`, `{`, `$`)
    name nothing in the checkout."""
    spans = re.findall(r"```.*?```|`[^`\n]+`", text, flags=re.S)
    for word in " ".join(spans).replace("`", " ").split():
        word = re.sub(r"(?<=\.py)::?[\w:.\[\]-]+$", "",
                      word.strip("()[],;'\""))
        if (re.search(r"\.(py|json|md)$", word)
                and not re.search(r"[*<{$]", word)
                and not word.startswith(("/", "~"))):
            yield word


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        named = sorted(set(_named_paths(f.read())))
    assert named or doc == "docs/configs.md"   # a table of keys only
    dangling = [w for w in named
                if not any(os.path.exists(os.path.join(ROOT, b, w))
                           for b in BASES)]
    assert dangling == []
