"""jaxlint command line: ``python -m speakingstyle_tpu.analysis.cli``.

Exit codes: 0 = clean modulo the committed baseline; 1 = new findings
and/or stale baseline entries (both directions fail loudly); 2 = usage
error. ``scripts/lint_jax.py`` is the repo-root wrapper for CI.

The ``lockorder`` subcommand manages the static lock-order artifact
(``analysis/lockorder.json``, rule JL022):

    python -m speakingstyle_tpu.analysis.cli lockorder           # verify
    python -m speakingstyle_tpu.analysis.cli lockorder --write   # refresh

``--check`` also fails if the committed artifact is stale, same idiom
as the lint baseline.
"""

import argparse
import json
import sys
import time

from speakingstyle_tpu.analysis import linter
from speakingstyle_tpu.analysis.rules import RULES


def _print_rules():
    for code, rule in sorted(RULES.items()):
        doc = (rule.__doc__ or "").strip().splitlines()
        head = doc[0] if doc else ""
        print(f"{code}  {head}")
        for line in doc[1:]:
            print(f"       {line.strip()}")
        print()


def _load_lockorder(path: str):
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _lockorder_stale(path=None):
    """-> (message-or-None, artifact). Rebuilds the lock-order graph
    from source and compares with the committed file; any difference —
    including a cycle — is a failure message."""
    from speakingstyle_tpu.analysis import concurrency

    path = path or linter.default_lockorder_path()
    try:
        art = concurrency.lockorder_artifact(concurrency.tree_models())
    except ValueError as e:   # cycle: the artifact cannot exist
        return str(e), None
    committed = _load_lockorder(path)
    if committed is None:
        return (
            f"lockorder artifact missing/unreadable: {path} (run "
            "`python -m speakingstyle_tpu.analysis.cli lockorder "
            "--write` and commit it)"
        ), art
    if committed != art:
        return (
            "lockorder.json is STALE: lock acquisitions changed — "
            "regenerate with `python -m speakingstyle_tpu.analysis.cli "
            "lockorder --write` and review the diff like code"
        ), art
    return None, art


def _lockorder_main(argv) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m speakingstyle_tpu.analysis.cli lockorder",
        description="Build/verify the static lock-order artifact "
                    "(JL022).",
    )
    ap.add_argument(
        "--write", action="store_true",
        help="regenerate the committed artifact from source",
    )
    ap.add_argument(
        "--out", default=None,
        help=f"artifact path (default: {linter.default_lockorder_path()})",
    )
    args = ap.parse_args(argv)
    path = args.out or linter.default_lockorder_path()
    stale, art = _lockorder_stale(path)
    if art is None:   # cycle
        print(f"FAIL: {stale}", file=sys.stderr)
        return 1
    if args.write:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(art, fh, indent=2)
            fh.write("\n")
        print(
            f"lockorder written: {len(art['edges'])} edge(s), "
            f"{len(art['order'])} lock(s) -> {path}"
        )
        return 0
    if stale:
        print(f"FAIL: {stale}", file=sys.stderr)
        return 1
    print(
        f"OK: lockorder.json current ({len(art['edges'])} edge(s), "
        f"{len(art['order'])} lock(s), acyclic)"
    )
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "lockorder":
        return _lockorder_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="python -m speakingstyle_tpu.analysis.cli",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument(
        "paths", nargs="*",
        help="files/directories to lint (default: the repo's "
             "speakingstyle_tpu/, scripts/, tests/, chip_smoke.py)",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="CI mode: succeed only if clean modulo the baseline "
             "(stale baseline entries also fail)",
    )
    ap.add_argument(
        "--baseline", default=None,
        help=f"baseline file (default: {linter.default_baseline_path()})",
    )
    ap.add_argument(
        "--no-baseline", action="store_true",
        help="ignore the baseline: report every finding",
    )
    ap.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline from the current findings and exit 0",
    )
    ap.add_argument(
        "--select", default=None,
        help="comma-separated rule codes to run (default: all)",
    )
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument(
        "--profile", action="store_true",
        help="print per-rule wall time after linting",
    )
    ap.add_argument(
        "--time-budget", type=float, default=6.0, metavar="SECONDS",
        help="with --check: fail if the full-tree lint exceeds this "
             "CPU time (guards the single-pass refactor — the old "
             "flat scanner took ~7.5s; post-refactor is ~2.5s). "
             "0 disables. (default: %(default)s)",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        _print_rules()
        return 0

    select = None
    if args.select:
        select = {r.strip().upper() for r in args.select.split(",")}
        unknown = select - set(RULES)
        if unknown:
            print(f"unknown rules: {sorted(unknown)}", file=sys.stderr)
            return 2

    profile = {} if args.profile else None
    # the budget is held against the process's CPU time: it guards the
    # walk's cost, which a machine busy with other work does not change
    t_lint = time.process_time()
    findings = linter.lint_paths(
        args.paths or None, select=select, profile=profile
    )
    lint_secs = time.process_time() - t_lint
    if profile is not None:
        total = sum(profile.values())
        print(f"per-rule wall time ({total:.3f}s total):")
        for code, secs in sorted(
            profile.items(), key=lambda kv: -kv[1]
        ):
            print(f"  {code}  {secs * 1e3:8.1f} ms")

    if args.update_baseline:
        linter.save_baseline(findings, args.baseline)
        print(
            f"baseline updated: {len(findings)} findings -> "
            f"{args.baseline or linter.default_baseline_path()}"
        )
        return 0

    baseline = (
        linter.load_baseline(args.baseline)
        if not args.no_baseline
        else linter.findings_counter([])
    )
    new, stale = linter.compare_to_baseline(findings, baseline)

    by_fp = {}
    for f in findings:
        by_fp.setdefault(f.fingerprint, []).append(f)

    shown = 0
    for fp in sorted(new):
        for f in by_fp[fp][: new[fp]]:
            print(f"{f.path}:{f.line}: {f.rule} {f.message}")
            shown += 1
    baselined = len(findings) - shown
    if stale:
        print(
            f"\nSTALE baseline entries (fixed in code, still listed — run "
            "--update-baseline and commit the diff):",
            file=sys.stderr,
        )
        for fp in sorted(stale):
            print(f"  {fp} (x{stale[fp]})", file=sys.stderr)

    over_budget = (
        args.check and not args.paths and args.time_budget > 0
        and lint_secs > args.time_budget
    )
    if over_budget:
        print(
            f"\nlint CPU time {lint_secs:.2f}s exceeds the "
            f"{args.time_budget:.1f}s budget — the single-pass walk "
            "cache may have regressed (see --profile)",
            file=sys.stderr,
        )

    lockorder_msg = None
    if args.check and not args.paths:
        # CI gate over the whole tree: the committed lock-order
        # artifact must match what the source implies (JL022)
        lockorder_msg, _ = _lockorder_stale()
        if lockorder_msg:
            print(f"\n{lockorder_msg}", file=sys.stderr)

    failed = bool(new or stale or lockorder_msg or over_budget)
    summary = (
        f"{shown} finding(s) over baseline, {baselined} baselined, "
        f"{len(stale)} stale baseline entr{'y' if len(stale) == 1 else 'ies'}"
    )
    if args.check and not args.paths:
        summary += (
            ", lockorder stale" if lockorder_msg else ", lockorder current"
        )
    print(("FAIL: " if failed else "OK: ") + summary,
          file=sys.stderr if failed else sys.stdout)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
