"""What each test file cost a run: cpu-seconds summed a file from a junit file
(``--junitxml``), longest first. The tier-1 run hands a file to one worker
(``tests/conftest.py``, whatever ``--dist`` says), so a file over 120 s is
split. A WHOLE run's table is also written to ``tests/junit_costs.txt``, which
is committed: ``conftest.recorded_order`` reads the next run's file order
from it. A junit file that leaves out a file the record names (and the tree
still has) is printed and the record left as it is. Not collected.

    python tests/junit_costs.py /tmp/_t1.xml [--over 60]
"""

import argparse
import collections
import pathlib
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


def left_out(record: pathlib.Path, costs: dict) -> list[str]:
    """The files ``record`` names, the tree beside it still has and ``costs``
    lacks: a part of the suite (three files run by hand, a run that was cut)
    would lose their place in the order, since a file the record does not
    name is collected first."""
    if not record.exists():
        return []
    names = [line.split()[2] for line in record.read_text().splitlines()[1:]]
    return [name for name in names
            if name not in costs and (record.parent / name).exists()]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("junit")
    ap.add_argument("--over", type=float, default=0.0,
                    help="only the files over this many cpu-seconds")
    args = ap.parse_args()
    costs = file_costs(args.junit)
    head = f"{sum(s for s, _ in costs.values()):8.0f} cpu-s, {len(costs)} files"
    rows = [
        (seconds, f"{seconds:8.1f} {n:5d}  {name}")
        for name, (seconds, n) in sorted(costs.items(), key=lambda kv: -kv[1][0])
    ]
    record = pathlib.Path(__file__).with_suffix(".txt")
    if not left_out(record, costs):
        record.write_text("\n".join([head, *(row for _, row in rows)]) + "\n")
    print("\n".join([head, *(row for s, row in rows if s > args.over)]))


if __name__ == "__main__":
    main()
