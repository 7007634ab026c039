"""Write TEST_TIMINGS.md from a `pytest --junitxml` file.

The committed snapshot is the fast tier's time ledger (tests/conftest.py
documents the budget mechanism): when a new capability lands, regenerate
with `make test-timings` so its test-time cost is visible in the diff.
The driver's own run of the tier writes such a file too (`/tmp/_t1.xml`,
/root/TESTS_LAST_RUN.json has the command): this script reads either.
"""

import sys
import xml.etree.ElementTree as ET
from collections import defaultdict
from datetime import date

TOP = 40


def _node_of(classname: str, name: str) -> tuple[str, str]:
    """('tests.unit.test_lint.TestBoundedQueues', 'test_x') ->
    ('tests/unit/test_lint.py', 'tests/unit/test_lint.py::TestBoundedQueues::test_x')."""
    parts = classname.split(".")
    module = max(i for i, p in enumerate(parts) if p.startswith("test_"))
    path = "/".join(parts[: module + 1]) + ".py"
    return path, "::".join([path, *parts[module + 1:], name])


def main(xml_path: str) -> None:
    suite = ET.parse(xml_path).getroot().find("testsuite")
    counts = {k: int(suite.get(k)) for k in ("tests", "errors", "failures", "skipped")}
    passed = counts["tests"] - counts["errors"] - counts["failures"] - counts["skipped"]
    wall = float(suite.get("time"))
    by_file: dict[str, list[float]] = defaultdict(list)
    tests = []
    for case in suite.iter("testcase"):
        seconds = float(case.get("time"))
        path, nodeid = _node_of(case.get("classname"), case.get("name"))
        by_file[path].append(seconds)
        tests.append((seconds, nodeid))
    total = sum(sum(v) for v in by_file.values())
    lines = [
        "# Fast-tier test timings (`pytest -m \"not slow\"`)",
        "",
        f"Snapshot: {date.today().isoformat()} — regenerate with `make test-timings`.",
        f"Result: {passed} passed, {counts['skipped']} skipped or xfailed, {counts['failures']} failed, "
        f"{counts['errors']} errors in {wall:.0f} s wall (limit 1470 s); {total:.0f} worker-seconds in all.",
        "",
        "Taken on the CPU under the driver's command shape (`-p xdist -n 6 --dist",
        "loadfile`); the driver cuts its run at 1470 s (`timeout -k 10 1470`), and",
        "tests/conftest.py warns, listing offenders, when a fast-tier session",
        "exceeds that.  A test's seconds are its setup, call and teardown, as the",
        "junit file counts them, on a worker that shares eight cores with five",
        "others: 2.5-3 times what the test takes alone.  A warm tests/.jax_cache",
        "moves them little (ROADMAP D19: the seconds are tracing, unrolled tiny",
        "layers and interpret-mode kernels, not XLA's compiles).  A capability",
        "that adds a slower test than these either earns its seconds or takes a",
        "`slow` mark.",
        "",
        "## By file",
        "",
        "| worker-seconds | tests | file |",
        "|---|---|---|",
    ]
    for path, secs in sorted(by_file.items(), key=lambda kv: -sum(kv[1])):
        lines.append(f"| {sum(secs):.0f} | {len(secs)} | `{path}` |")
    lines += ["", f"## The {TOP} slowest tests", "", "| seconds | test |", "|---|---|"]
    for seconds, nodeid in sorted(tests, reverse=True)[:TOP]:
        lines.append(f"| {seconds:.2f} | `{nodeid}` |")
    with open("TEST_TIMINGS.md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote TEST_TIMINGS.md ({len(by_file)} files, {len(tests)} tests)")


if __name__ == "__main__":
    main(sys.argv[1])
