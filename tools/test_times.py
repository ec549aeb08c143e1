"""Where tier-1's time goes: ``python tools/test_times.py <log>``.

The log is the driver's test command (``/root/TESTS_LAST_RUN.json``,
``commands``) with ``-v -v`` added and the seconds since the start before
each line::

    ... python -m pytest tests/ -q -v -v ... 2>&1 | python -u -c "
    import sys, time; t0 = time.time()
    for l in sys.stdin: print('%8.1f %s' % (time.time() - t0, l), end='')"

A case is charged the time between its worker's previous line and its own
(so a file's first case also pays its imports and module fixtures). Prints
a line a file (cases, worker seconds, when its worker started and ended
it), the sum over workers, and the 30 dearest cases.
"""

import collections
import re
import sys

LINE = re.compile(r"\s*([\d.]+) \[(gw\d+)\] \[ *\d+%\] (\w+) (\S+?)::(\S+)")


def main(path):
    last = collections.defaultdict(float)       # worker -> its last line's time
    files, cases = {}, []
    for line in open(path, errors="replace"):
        m = LINE.match(line)
        if not m:
            continue
        at, worker, _outcome, file, case = m.groups()
        at = float(at)
        cost, last[worker] = at - last[worker], at
        row = files.setdefault(file, [0, 0.0, at - cost, at])
        row[0], row[1], row[3] = row[0] + 1, row[1] + cost, at
        cases.append((cost, file, case))
    print(f"{'file':44}{'cases':>6}{'worker s':>10}{'from':>9}{'to':>9}")
    for file, (n, cost, start, end) in sorted(files.items(),
                                              key=lambda kv: -kv[1][1]):
        print(f"{file:44}{n:6d}{cost:10.1f}{start:9.1f}{end:9.1f}")
    print(f"{len(cases)} cases in {len(files)} files on {len(last)} workers: "
          f"{sum(c for c, _, _ in cases):.1f} worker seconds, "
          f"last line at {max(last.values(), default=0.0):.1f} s")
    for cost, file, case in sorted(cases, reverse=True)[:30]:
        print(f"{cost:8.1f} {file}::{case}")


if __name__ == "__main__":
    main(sys.argv[1])
