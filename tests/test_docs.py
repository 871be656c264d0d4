"""No document names a command that is not there.

The documents tell a reader what to run; a row that outlives its script
sends them to a file that is gone. Pure text and ``os.path``: no jax.
"""

import os
import re

import pytest

from speakingstyle_tpu.__main__ import COMMANDS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# `python x.py`, `python3 a/b.py`, `bash c.sh`: the interpreter's first
# argument, where it is a script path (``-m module`` and flags are not)
_SCRIPT = re.compile(
    r"(?<![\w/.-])(?:python3?|bash)\s+([^\s`'\"()|]+\.(?:py|sh))(?![\w.])"
)
# "`x.py --flag`": a script named in code font with a flag is a command too
_BACKTICKED = re.compile(r"`([^\s`]+\.(?:py|sh))\s+--?\w")
# `python -m speakingstyle_tpu <word>`; ``.obs.cli`` / ``.analysis.cli``
# are modules of their own and do not match (no space after the package)
_SUBCOMMAND = re.compile(r"python3?\s+-m\s+speakingstyle_tpu\s+(\S+)")
_PLACEHOLDER = ("<", "*", "…")


def _read(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("doc", [
    "README.md",
    "scripts/README.md",
    "ARCHITECTURE.md",
    ".claude/skills/verify/SKILL.md",
])
def test_documented_scripts_exist(doc):
    text = _read(doc)
    targets = [t for t in _SCRIPT.findall(text) + _BACKTICKED.findall(text)
               if not any(p in t for p in _PLACEHOLDER)]
    assert targets, f"{doc} names no script: the pattern has gone blind"
    bases = (ROOT, os.path.dirname(os.path.join(ROOT, doc)))
    missing = sorted({
        t for t in targets
        if not any(os.path.isfile(os.path.join(b, t)) for b in bases)
    })
    assert not missing, f"{doc} tells a reader to run {missing}: not there"


@pytest.mark.parametrize("doc", ["README.md", "scripts/README.md"])
def test_documented_commands_are_registered(doc):
    words = [w.strip("`.,;:)") for w in _SUBCOMMAND.findall(_read(doc))]
    named = [w for w in words if re.fullmatch(r"\w+", w)]
    assert named, f"{doc} names no `python -m speakingstyle_tpu` command"
    unknown = sorted(set(named) - set(COMMANDS))
    assert not unknown, (
        f"{doc} names {unknown}: not in speakingstyle_tpu.__main__.COMMANDS"
    )
