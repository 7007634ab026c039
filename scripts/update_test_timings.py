"""Write TEST_TIMINGS.md from a `pytest --durations=N` log.

The committed snapshot is the fast tier's time ledger (tests/conftest.py
documents the budget mechanism): when a new capability lands, regenerate
with `make test-timings` so its test-time cost is visible in the diff.
"""

import re
import sys
from datetime import date


def main(log_path: str) -> None:
    with open(log_path) as f:
        log = f.read()
    rows = re.findall(r"^\s*([0-9.]+)s\s+(call|setup|teardown)\s+(\S+)", log, re.M)
    # Final summary line: matches "N passed ..." AND "M failed, N passed ..."
    tail = re.search(
        r"^((?:\d+ \w+, )*\d+ (?:passed|failed|error\w*).* in [0-9.]+s.*)$",
        log,
        re.M,
    )
    # Wall time from the matched summary line itself (an earlier log line
    # like "retried in 0.5s" must not win).
    total = (
        re.search(r" in ([0-9.]+)s", tail.group(1)) if tail else None
    )
    wall = f"{float(total.group(1)):.0f} s wall" if total else "wall unknown"
    lines = [
        "# Fast-tier test timings (`pytest -m \"not slow\"`)",
        "",
        f"Snapshot: {date.today().isoformat()} — regenerate with `make test-timings`.",
        f"Result: {tail.group(1) if tail else 'unknown'} ({wall}; limit 1470 s)",
        "",
        "Taken on the CPU under the driver's command shape (`-p xdist -n 6 --dist",
        "loadfile`); the driver cuts its run at 1470 s (`timeout -k 10 1470`), and",
        "tests/conftest.py warns, listing offenders, when a fast-tier session",
        "exceeds that.  Timings depend on how warm tests/.jax_cache is: a cold run",
        "pays each unique program's compile once, a later run loads them",
        "(conftest.py).  A capability that adds a slower test than these either",
        "earns its seconds or takes a `slow` mark.",
        "",
        "| seconds | phase | test |",
        "|---|---|---|",
    ]
    for secs, phase, nodeid in rows:
        lines.append(f"| {secs} | {phase} | `{nodeid}` |")
    with open("TEST_TIMINGS.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote TEST_TIMINGS.md ({len(rows)} rows)")


if __name__ == "__main__":
    main(sys.argv[1])
