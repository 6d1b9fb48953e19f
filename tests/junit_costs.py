"""What each test file cost a run: cpu-seconds summed a file from a junit file
(``--junitxml``), longest first. The tier-1 run hands a file to one worker
(``--dist loadfile``), so a file over some 150 s is split, and the files over
a minute are what ``conftest._LONGEST_FIRST`` lists. Not collected.

    python tests/junit_costs.py /tmp/_t1.xml [--over 60]
"""

import argparse
import collections
import xml.etree.ElementTree as ET


def file_costs(junit_path: str) -> dict[str, tuple[float, int]]:
    """{file relative to ``tests/``: (cpu-seconds, tests)}."""
    costs = collections.defaultdict(lambda: [0.0, 0])
    for case in ET.parse(junit_path).getroot().iter("testcase"):
        parts = case.get("classname").split(".")
        last = max(i for i, part in enumerate(parts) if part.startswith("test_"))
        name = "/".join(parts[1:last + 1]) + ".py"  # parts[0] is "tests"
        costs[name][0] += float(case.get("time") or 0)
        costs[name][1] += 1
    return {name: (seconds, n) for name, (seconds, n) in costs.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit")
    ap.add_argument("--over", type=float, default=0.0,
                    help="only the files over this many cpu-seconds")
    args = ap.parse_args()
    costs = file_costs(args.junit)
    print(f"{sum(s for s, _ in costs.values()):8.0f} cpu-s, {len(costs)} files")
    for name, (seconds, n) in sorted(costs.items(), key=lambda kv: -kv[1][0]):
        if seconds > args.over:
            print(f"{seconds:8.1f} {n:5d}  {name}")


if __name__ == "__main__":
    main()
