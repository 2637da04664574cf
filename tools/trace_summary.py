#!/usr/bin/env python
"""Per-stage round/bytes/latency table from a JSON-lines trace — and diffs.

The reading end of ``repro.obs`` (DESIGN.md §12).  A trace written by
``repro.obs.write_jsonl`` (e.g. by ``examples/obs_demo.py``) folds into the
stage table whose ``rounds`` column is the *measured* CostAccum delta and
whose ``declared`` column is the plan's round-bound schedule — equal rows
print ``OK``, so the paper's round bounds are checkable from telemetry
alone.  Traces from a ShardedEngine overlapped run additionally print a
``pipeline:`` footer: windows, rounds and hops, and the host's issue time
(DESIGN.md §13; the hop's device time comes from a profiler trace).  With
``--diff`` two traces are
compared stage by stage and semantic drift (round counts, communication,
drops — never wall time) is flagged.

Usage::

    python tools/trace_summary.py TRACE.jsonl            # table
    python tools/trace_summary.py TRACE.jsonl --json     # summary as JSON
    python tools/trace_summary.py A.jsonl --diff B.jsonl # A = baseline
"""
import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.obs import (diff_summaries, format_diff, format_table,  # noqa: E402
                       read_jsonl, summarize)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="JSON-lines trace file (write_jsonl)")
    ap.add_argument("--diff", metavar="OTHER",
                    help="second trace to compare against (trace = baseline)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary (or diff rows) as JSON")
    args = ap.parse_args(argv)

    summary = summarize(read_jsonl(args.trace))
    if args.diff:
        rows = diff_summaries(summary, summarize(read_jsonl(args.diff)))
        if args.json:
            print(json.dumps(rows, indent=2, sort_keys=True))
        else:
            print(format_diff(rows))
        return 1 if any(r["drift"] for r in rows) else 0

    if args.json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        print(format_table(summary))
    return 0 if summary["schedule_ok"] else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:      # e.g. `trace_summary.py T.jsonl | head`
        sys.exit(0)
