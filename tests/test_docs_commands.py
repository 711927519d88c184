"""Every command and path a document gives resolves to a file in the tree:
a deleted module's command fails here for each document that still gives
it. Plain Python over the text; nothing is imported and nothing is run."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (["README.md", "COVERAGE.md"]
        + sorted(os.path.relpath(p, ROOT)
                 for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))
        + [".claude/skills/verify/SKILL.md"])

#: the USER's own training script in the launcher's examples
#: (`dstpu ... python train.py`): the one name that is not this repo's
USERS_OWN = {"train.py"}

_MODULE = re.compile(r"python3? -m (deepspeed_tpu(?:\.\w+)+)")
_SCRIPT = re.compile(r"python3? ((?!-)[\w./\-]+\.py)\b")
_TOOL = re.compile(r"(?<![\w/.\-])((?:scripts|bin)/\w[\w.\-]*)")
_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_SPAN = re.compile(r"`([^`]+)`")
_PATH = re.compile(r"^(?:deepspeed_tpu|benchmark|tests|scripts)/")


def _named(text):
    """(what the document wrote, the path it has to resolve to) pairs."""
    for mod in _MODULE.findall(text):
        yield f"python -m {mod}", mod.replace(".", "/")
    for path in _SCRIPT.findall(text):
        if path not in USERS_OWN:
            yield f"python {path}", path
    for path in _TOOL.findall(text):
        yield path, path
    for span in _SPAN.findall(_FENCE.sub("", text)):
        for word in span.split():
            if not _PATH.match(word) or re.search(r"[*<>{}]|\.\.\.", word):
                continue        # a glob, a <placeholder>, an elision
            yield word, re.sub(r"(::|:\d).*$", "", word)


def _resolves(path):
    path = os.path.join(ROOT, path.rstrip(".,;:)/"))
    return (os.path.exists(path) or os.path.exists(path + ".py")
            or os.path.exists(os.path.join(path, "__init__.py")))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_the_tree_holds(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = sorted({said for said, path in _named(text)
                      if not _resolves(path)})
    assert not missing, f"{doc} names what the tree does not hold: {missing}"
