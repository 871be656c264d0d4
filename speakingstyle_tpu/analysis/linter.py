"""jaxlint driver: file walking, suppression handling, baseline compare.

The linter's contract with CI (tests/test_analysis.py makes it tier-1):

  * ``lint_paths(paths)`` -> findings, with per-line
    ``# jaxlint: disable=JL001[,JL004]`` (or bare ``disable``) and
    file-level ``# jaxlint: skip-file`` suppressions already applied.
  * Findings fingerprint as ``rule:path:context:detail`` — deliberately
    line-number-free, so unrelated edits don't churn the baseline.
  * ``compare_to_baseline`` is bidirectional: NEW findings fail, and
    STALE baseline entries (fixed code, unfixed baseline) also fail, so
    the committed baseline can never silently rot.
"""

import collections
import io
import json
import os
import time
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from speakingstyle_tpu.analysis.rules import RULES, Finding, ModuleInfo

import ast

_SKIP_DIRS = {
    "__pycache__", ".git", ".jax_cache", "artifacts", "node_modules",
    ".pytest_cache",
}

DEFAULT_BASELINE_NAME = "baseline.json"


def repo_root() -> str:
    """The directory containing the ``speakingstyle_tpu`` package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def default_baseline_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), DEFAULT_BASELINE_NAME
    )


def default_lockorder_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "lockorder.json"
    )


def default_lint_paths() -> List[str]:
    root = repo_root()
    out = []
    for rel in ("speakingstyle_tpu", "scripts", "tests", "chip_smoke.py"):
        p = os.path.join(root, rel)
        if os.path.exists(p):
            out.append(p)
    return out


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------


def _directives(source: str) -> Tuple[bool, Dict[int, Optional[set]]]:
    """Parse jaxlint comments. Returns (skip_file, {line: rules-or-None}).

    ``None`` as the rule set means "disable everything on this line".
    Uses the tokenizer so string literals containing 'jaxlint:' are not
    misread as directives.

    A directive that is the only thing on its line applies to the NEXT
    line instead — so long ``reason=`` clauses don't force overlong
    code lines.
    """
    skip_file = False
    per_line: Dict[int, Optional[set]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            text = tok.string.lstrip("#").strip()
            if not text.startswith("jaxlint:"):
                continue
            body = text[len("jaxlint:"):].strip()
            target = tok.start[0]
            if tok.line.lstrip().startswith("#"):
                target += 1   # standalone comment: guards the next line
            if body == "skip-file":
                skip_file = True
            elif body == "disable":
                per_line[target] = None
            elif body.startswith("disable="):
                spec = body[len("disable="):]
                # an optional trailing reason clause documents WHY a
                # deliberate pattern is suppressed:
                #   # jaxlint: disable=JL020 reason=single-reader stamp
                # (the concurrency rules require one; the reason text is
                # free-form and ends at end-of-comment)
                if " reason=" in spec:
                    spec = spec.split(" reason=", 1)[0]
                rules = {
                    r.strip().upper()
                    for r in spec.split(",")
                    if r.strip()
                }
                existing = per_line.get(target, set())
                per_line[target] = (
                    None if existing is None else existing | rules
                )
    except tokenize.TokenError:
        pass  # malformed tail; directives seen so far still apply
    return skip_file, per_line


def _suppressed(finding: Finding, per_line: Dict[int, Optional[set]]) -> bool:
    rules = per_line.get(finding.line, set())
    return rules is None or (rules and finding.rule in rules)


# ---------------------------------------------------------------------------
# linting
# ---------------------------------------------------------------------------


def lint_source(
    source: str,
    path: str = "<string>",
    select: Optional[Iterable[str]] = None,
    profile: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Lint one source string; ``path`` is used for reporting/fingerprints
    and for path-scoped rules (JL004 looks for ``training/``).

    ``profile``, if given, accumulates per-rule wall seconds
    (``--profile`` in the CLI).
    """
    skip_file, per_line = _directives(source)
    if skip_file:
        return []
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [
            Finding(
                rule="JL000",
                path=path,
                line=e.lineno or 0,
                context="<module>",
                detail="syntax error",
                message=f"could not parse: {e.msg}",
            )
        ]
    mod = ModuleInfo(path, source, tree)
    wanted = set(select) if select else set(RULES)
    findings: List[Finding] = []
    for code, rule in sorted(RULES.items()):
        if code not in wanted:
            continue
        t0 = time.perf_counter() if profile is not None else 0.0
        for f in rule(mod):
            if not _suppressed(f, per_line):
                findings.append(f)
        if profile is not None:
            profile[code] = (
                profile.get(code, 0.0) + time.perf_counter() - t0
            )
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames
                if d not in _SKIP_DIRS and not d.startswith(".")
            )
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)


def lint_paths(
    paths: Optional[Sequence[str]] = None,
    select: Optional[Iterable[str]] = None,
    root: Optional[str] = None,
    profile: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    """Lint files/trees; paths in findings are repo-root-relative."""
    root = root or repo_root()
    paths = list(paths) if paths else default_lint_paths()
    findings: List[Finding] = []
    for fpath in iter_py_files(paths):
        rel = os.path.relpath(os.path.abspath(fpath), root).replace(
            os.sep, "/"
        )
        try:
            with open(fpath, "r", encoding="utf-8") as fh:
                source = fh.read()
        except (OSError, UnicodeDecodeError):
            continue
        findings.extend(
            lint_source(source, rel, select=select, profile=profile)
        )
    return findings


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------


def findings_counter(findings: Iterable[Finding]) -> "collections.Counter":
    return collections.Counter(f.fingerprint for f in findings)


def load_baseline(path: Optional[str] = None) -> "collections.Counter":
    path = path or default_baseline_path()
    if not os.path.exists(path):
        return collections.Counter()
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    return collections.Counter(
        {entry["fingerprint"]: entry["count"] for entry in data["findings"]}
    )


def save_baseline(findings: Iterable[Finding], path: Optional[str] = None):
    path = path or default_baseline_path()
    counter = findings_counter(findings)
    data = {
        "comment": (
            "jaxlint tracked-but-allowed findings. Entries here are known "
            "hazards that are deliberate (rate-gated syncs, bucketed "
            "retraces) or pre-existing. Regenerate with "
            "`python scripts/lint_jax.py --update-baseline` and review the "
            "diff like code."
        ),
        "version": 1,
        "findings": [
            {"fingerprint": fp, "count": n}
            for fp, n in sorted(counter.items())
        ],
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(data, fh, indent=2)
        fh.write("\n")


def compare_to_baseline(
    findings: Iterable[Finding], baseline: "collections.Counter"
) -> Tuple[Dict[str, int], Dict[str, int]]:
    """-> (new findings over baseline, stale baseline entries), both as
    {fingerprint: count-delta}."""
    current = findings_counter(findings)
    new = {
        fp: n - baseline.get(fp, 0)
        for fp, n in current.items()
        if n > baseline.get(fp, 0)
    }
    stale = {
        fp: n - current.get(fp, 0)
        for fp, n in baseline.items()
        if n > current.get(fp, 0)
    }
    return new, stale
