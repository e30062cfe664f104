"""``python -m tools.jaxlint [paths...]`` — the command-line front-end.

Exit codes: 0 = clean (or every finding baselined/suppressed),
1 = at least one non-baselined finding, 2 = usage error.

``--format json`` emits one machine-readable object (file/line/col/
rule/severity/family/message records plus the summary, including
``summary_ms``/``link_ms`` pass timings and the summary-cache hit
counts) on stdout with the SAME exit codes, so CI renders findings as
annotations instead of scraping text; ``--jobs N`` fans per-file
analysis out over N workers with byte-identical output ordering.

v4 adds the two-pass linked analysis: ``--no-link`` falls back to the
v3 single-pass behavior (cross-module rules skipped), and
``--dump-summaries [MODULE]`` prints the linked export summaries pass
1 extracted — the debugging window into what the cross-module rules
actually saw.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Optional, Sequence

from tools.jaxlint import baseline as baseline_mod
from tools.jaxlint import core as core_mod
from tools.jaxlint import rules  # noqa: F401 — registers the rule set
from tools.jaxlint.core import REGISTRY, iter_python_files, run_paths

DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.json"
DEFAULT_CACHE = Path(".jaxlint_cache.json")
DEFAULT_PATHS = ("deeplearning4j_tpu", "tools")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.jaxlint",
        description="AST-based tracing-safety analyzer for this repo's "
                    "JAX invariants")
    ap.add_argument("paths", nargs="*", default=list(DEFAULT_PATHS),
                    help="files/dirs to lint (default: %(default)s)")
    ap.add_argument("--select", metavar="RULES",
                    help="comma-separated rule subset")
    ap.add_argument("--baseline", type=Path, default=DEFAULT_BASELINE,
                    metavar="FILE",
                    help="baseline JSON (default: %(default)s)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline file")
    ap.add_argument("--write-baseline", action="store_true",
                    help="snapshot current findings into the baseline "
                         "and exit 0")
    # a flag + a separate FILE option on purpose: an optional-argument
    # form (--cache [FILE]) would silently swallow the first positional
    # path as the cache filename and lint nothing
    ap.add_argument("--cache", action="store_true",
                    help=f"use the per-file result cache {DEFAULT_CACHE} "
                         "(gitignored)")
    ap.add_argument("--cache-file", type=Path, default=None,
                    metavar="FILE",
                    help="result cache at FILE (implies --cache)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the registered rules and exit")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    dest="fmt",
                    help="finding output format (default: %(default)s); "
                         "json emits file/line/rule/severity records for "
                         "CI annotation rendering, same exit codes")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="analyze N files concurrently (files are "
                         "independent; output order is deterministic "
                         "regardless of N)")
    ap.add_argument("--no-link", action="store_true",
                    help="skip pass 1 (summary extraction) and pass-2 "
                         "linking; cross-module rules don't run — the "
                         "v3 single-pass behavior")
    ap.add_argument("--dump-summaries", nargs="?", const="", default=None,
                    metavar="MODULE",
                    help="print the extracted (linked) export summary "
                         "of MODULE as JSON and exit — or every "
                         "summary in the run's closure when MODULE is "
                         "omitted (spell it --dump-summaries=MODULE "
                         "when positional paths follow)")
    args = ap.parse_args(argv)

    if args.jobs < 1:
        print(f"error: --jobs must be >= 1, got {args.jobs}",
              file=sys.stderr)
        return 2

    if args.list_rules:
        # grouped by family so the two PR 10 rule families read as the
        # units they ship as
        by_family: dict = {}
        for name in sorted(REGISTRY):
            by_family.setdefault(REGISTRY[name].family, []).append(name)
        for family in sorted(by_family):
            print(f"{family}:")
            for name in by_family[family]:
                rule = REGISTRY[name]
                print(f"  {name:30s} [{rule.severity}] "
                      f"{rule.description}")
        return 0

    select = [s.strip() for s in args.select.split(",") if s.strip()] \
        if args.select else None
    cache_path = args.cache_file if args.cache_file is not None \
        else (DEFAULT_CACHE if args.cache else None)

    if args.dump_summaries is not None:
        files = iter_python_files([Path(p) for p in args.paths])
        pass1 = core_mod._build_summaries(files, args.paths, cache_path)
        if args.dump_summaries:
            summ = pass1.linked.get(args.dump_summaries)
            if summ is None:
                print(f"error: no export summary for module "
                      f"{args.dump_summaries!r} in the scanned closure "
                      f"({len(pass1.linked)} modules); module names are "
                      "dotted, rooted at the repo",
                      file=sys.stderr)
                return 2
            print(json.dumps(summ, indent=2, sort_keys=True))
        else:
            print(json.dumps(pass1.linked, indent=2, sort_keys=True))
        return 0

    stats: dict = {}
    try:
        findings = run_paths(args.paths, select, cache_path=cache_path,
                             jobs=args.jobs, link=not args.no_link,
                             stats=stats)
    except KeyError as e:
        print(f"error: {e.args[0]}", file=sys.stderr)
        return 2

    if args.write_baseline:
        if select is not None:
            print("error: --write-baseline with --select would snapshot "
                  "a partial rule set (erasing other rules' entries); "
                  "run it without --select", file=sys.stderr)
            return 2
        scanned = {baseline_mod.norm_path(p.as_posix())
                   for p in iter_python_files(
                       [Path(p) for p in args.paths])}
        try:
            n = baseline_mod.save(args.baseline, findings,
                                  scanned_paths=scanned)
        except (OSError, ValueError) as e:
            print(f"error: baseline {args.baseline}: {e}", file=sys.stderr)
            return 2
        print(f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} to "
              f"{args.baseline}")
        return 0

    try:
        entries = [] if args.no_baseline \
            else baseline_mod.load(args.baseline)
    except (OSError, ValueError) as e:
        # a corrupt/mismatched baseline must be a clean usage
        # diagnostic, not a traceback
        print(f"error: baseline {args.baseline}: {e}", file=sys.stderr)
        return 2
    new, grandfathered = baseline_mod.apply(findings, entries)
    errors = sum(1 for f in new if f.severity == "error")
    warnings = len(new) - errors

    if args.fmt == "json":
        # one object, not a line stream: CI reads it with a single
        # json.load and renders per-record annotations
        print(json.dumps({
            "ok": not new,
            "errors": errors,
            "warnings": warnings,
            "baselined": len(grandfathered),
            "rules": len(REGISTRY) if select is None else len(select),
            "summary_ms": stats.get("summary_ms", 0.0),
            "link_ms": stats.get("link_ms", 0.0),
            "summaries_extracted": stats.get("summaries_extracted", 0),
            "summaries_cached": stats.get("summaries_cached", 0),
            "findings": [{
                "file": f.path, "line": f.line, "col": f.col,
                "rule": f.rule, "severity": f.severity,
                "family": getattr(REGISTRY.get(f.rule), "family",
                                  "framework"),
                "message": f.message,
            } for f in new],
        }, indent=2))
        return 1 if new else 0

    for f in new:
        print(f.render())
    if grandfathered:
        print(f"({len(grandfathered)} baselined finding"
              f"{'' if len(grandfathered) == 1 else 's'} not shown; "
              "see --baseline)")
    if new:
        print(f"jaxlint: {errors} error(s), {warnings} warning(s)")
        return 1
    print(f"jaxlint: ok ({len(REGISTRY) if select is None else len(select)}"
          f" rules, {len(findings) - len(new)} baselined)")
    return 0
