#!/usr/bin/env python3
"""Set an open-loop cell's rate from the saturated cell's own reading:
four fifths of the median requests per second it finished, over the
runs kept in a ``sets.py`` log, to two significant digits.

    python benchmarks/tools/rate_from_sat.py chiprun_out/bench/<tag>.log \
        benchmarks/traffic/serve-chat-r80.json

Prints the runs it used; writes ``arrivals.rate_rps`` into the mix.
"""

from __future__ import annotations

import json
import math
import statistics
import sys


def main() -> int:
    log, mix_path = sys.argv[1], sys.argv[2]
    rates = []
    with open(log) as f:
        for line in f:
            if line.startswith('{"serve"'):
                rates.append(json.loads(line)["serve"]["finished_rps"])
    if not rates:
        print(f"no saturated runs in {log}", file=sys.stderr)
        return 1
    target = 0.8 * statistics.median(rates)
    digits = 1 - math.floor(math.log10(target))
    rate = round(target, digits)
    with open(mix_path) as f:
        mix = json.load(f)
    mix["arrivals"]["rate_rps"] = rate
    with open(mix_path, "w") as f:
        json.dump(mix, f, indent=1)
        f.write("\n")
    print(json.dumps({"finished_rps": rates,
                      "median": statistics.median(rates),
                      "rate_rps": rate}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
