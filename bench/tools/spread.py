#!/usr/bin/env python3
"""Spreads of a cell's measurements, as its bounds are set from them.

    python3 bench/tools/spread.py chiprun_out/<file>.jsonl [...]

Reads the lines that ``sweep.py`` wrote (``measure_cell.sh`` order: one
warm-up run, two sets of 6 runs on the same seeds, traced runs, more
runs).  For each cell and each end-to-end metric it prints each set's
median and spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median), the
bound five times the wider spread would give, and every run's compared
numbers.
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    runs = defaultdict(list)
    for path in paths:
        with open(path) as f:
            for line in f:
                rec = json.loads(line)
                runs[rec["workload"]].append(rec)
    for cell, recs in runs.items():
        plain = [r for r in recs if r["trace"] == 0]
        print(f"== {cell}: {len(recs)} runs, "
              f"{sum(r['rc'] != 0 for r in recs)} failed, "
              f"{sum(bool(r['result'] and r['result']['correct']) for r in recs)}"
              f" correct, seeds {len({r['seed'] for r in recs})}")
        sets = [plain[1:7], plain[7:13]] if len(plain) >= 13 else []
        names = sorted({k for r in plain if r["result"]
                        for k in r["result"]["metrics"]})
        for name in names:
            row = []
            for s in sets:
                vals = [r["result"]["metrics"][name]["value"] for r in s
                        if r["result"]]
                if len(vals) >= 2:
                    row.append((statistics.median(vals), spread(vals)))
            if row:
                wide = max(sp for _m, sp in row)
                print(f"  {name}: " + "  ".join(
                    f"median {m:.6g} spread {sp:.4%}" for m, sp in row)
                    + f"  -> 5x wider spread {5 * wide:.4%}")
        for r in recs:
            res = r["result"] or {}
            print(f"  seed {r['seed']} trace {r['trace']} rc {r['rc']} "
                  f"wall {r['wall_s']:.1f}s correct {res.get('correct')} "
                  f"attempted {res.get('attempted')} "
                  f"metrics { {k: round(v['value'], 6) for k, v in res.get('metrics', {}).items()} } "
                  f"checks { {k: v['value'] for k, v in res.get('checks', {}).items()} } "
                  f"peak {res.get('device', {}).get('memory_peak_bytes')} "
                  f"busy {res.get('device', {}).get('busy_s')} "
                  f"window {res.get('device', {}).get('window_s')}")
            if r["rc"] != 0:
                print("    " + r["stderr_tail"][-1500:].replace("\n", "\n    "))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
