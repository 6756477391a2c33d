"""Documents cannot name files that are gone.

Every back-ticked path in ``README.md``, ``docs/*.md`` and the verify
skill that ends in ``.py``, ``.json``, ``.jsonl`` or ``.md`` and holds
no wildcard or placeholder must exist in the tree: a path with a
directory in it relative to the repo root, the document's own directory
or the package (``serving/server.py``); a bare file name anywhere.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, REPO)
       for p in glob.glob(os.path.join(REPO, "docs", "*.md"))])

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w.\-/]+\.(?:py|jsonl?|md)$")
_LINE_SUFFIX = re.compile(r":[\d,\-]+$")

# Names a RUN writes into a directory the user chooses, which the
# documents describe and no checkout holds.
GENERATED = {
    "calibration.json",     # telemetry.calibration.save_calibration
    "autodist_meta.json",   # checkpoint.Saver, beside every saved step
    # the flight recorder's bundle (telemetry/flightrec.py)
    "MANIFEST.json", "verdicts.json", "hang.json", "schedule_ir.json",
    "events_tail.jsonl", "steps_tail.jsonl",
}
# The reference project's own tree, cited for parity: another checkout.
REFERENCE_PREFIXES = ("autodist/", "docs/usage/")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _, dirs, files in os.walk(REPO):
        # hidden directories are scratch (old checkouts among them)
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        names.update(files)
    return frozenset(names)


def cited_paths(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    for ticked in _TICKED.findall(text):
        for word in ticked.split():
            word = _LINE_SUFFIX.sub("", word.strip("(),;"))
            if _PATH.match(word) and not word.startswith(("/", "~")):
                yield word


def missing(document, basenames=None):
    basenames = basenames if basenames is not None else _basenames()
    roots = (REPO, os.path.join(REPO, os.path.dirname(document)),
             os.path.join(REPO, "autodist_tpu"))
    gone = []
    for path in sorted(set(cited_paths(document))):
        if path in GENERATED or path.startswith(REFERENCE_PREFIXES):
            continue
        if "/" not in path:
            found = path in basenames
        else:
            found = any(os.path.exists(os.path.join(r, path)) for r in roots)
        if not found:
            gone.append(path)
    return gone


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_exists(document):
    assert not missing(document), (
        f"{document} names files that are not in the tree")


def test_a_renamed_root_script_is_caught():
    """The README names ``chip_smoke.py``: without that file in the tree
    the check fails (so a rename has to take the documents along)."""
    assert "chip_smoke.py" in set(cited_paths("README.md"))
    assert "chip_smoke.py" in missing(
        "README.md", _basenames() - {"chip_smoke.py"})
