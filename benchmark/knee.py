"""Read a sweep's lines and write a cell's rate: the knee is the highest
swept rate at which at least the mix's ``attainment`` share of requests met
both limits with nothing failed, and the cell runs at 0.8 of it.

    python -m benchmark.knee <sweep.json> <cells/<cell>.json> <attainment>
"""
import json
import sys


def main(argv):
    lines = [json.loads(x) for x in open(argv[1], encoding="utf-8") if x.startswith('{"rate')]
    good = [x["rate_rps"] for x in lines if x["met_share"] >= float(argv[3]) and not x["failed"]]
    if not good:
        print("no swept rate meets the limits", file=sys.stderr)
        return 1
    with open(argv[2], encoding="utf-8") as fh:
        cell = json.load(fh)
    cell["knee_rps"] = max(good)
    cell["rate_rps"] = round(0.8 * max(good), 3)
    with open(argv[2], "w", encoding="utf-8") as fh:
        json.dump(cell, fh, indent=2)
        fh.write("\n")
    print(f"knee {cell['knee_rps']} -> rate {cell['rate_rps']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
