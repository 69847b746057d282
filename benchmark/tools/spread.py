"""Spreads of the runs `sets.sh` made, as the bound rule wants them: for
each metric and each set, the distance between the first and third
quartile (`statistics.quantiles(values, n=4)`) as a share of the median;
the wider of the two sets; five times that.

    python3 benchmark/tools/spread.py chiprun_out/logs/sets_<cell>.jsonl
"""
import json
import statistics
import sys


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(path):
    runs = [json.loads(ln) for ln in open(path) if ln.strip()]
    bad = [r for r in runs if not r["result"] or not r["result"]["correct"]]
    print("%d runs, %d without a correct result" % (len(runs), len(bad)))
    sets = {}
    for r in runs:
        if r["result"] and not r["trace"]:
            for name, m in r["result"]["metrics"].items():
                sets.setdefault(name, {}).setdefault(r["set"], []).append(
                    m["value"])
    for name, by_set in sorted(sets.items()):
        row = []
        for s, vals in sorted(by_set.items()):
            use = vals[1:] if name == "setup_s" and len(vals) > 3 else vals
            row.append((s, statistics.median(use), spread(use)
                        if len(use) >= 2 else float("nan"), len(use)))
        widest = max(r[2] for r in row)
        print("%-22s %s  widest %.4f  x5 = %.4f" % (name, "  ".join(
            "set %d: median %.6g spread %.4f (n=%d)" % r for r in row),
            widest, 5 * widest))
        for s, vals in sorted(by_set.items()):
            print("    set %d: %s" % (s, " ".join("%.6g" % v for v in vals)))
    for r in runs:
        if r["result"] and r["trace"]:
            print("traced:", json.dumps(r["result"])[:3000])


if __name__ == "__main__":
    main(sys.argv[1])
