"""``chip_smoke.py --dry-run`` on the CPU's virtual devices (ISSUE 21), its
kernel phase: the kernels against their references, interpreted, at tiny
shapes. One subprocess test of 35-85 s in a file of its own, as the serving
phases of the same command are (``tests/test_chip_smoke_dry_run.py``): the two
were one test of 120-165 s, over the 120 cpu-seconds a file may cost a whole
run, and share nothing but the scorer's start.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestChipSmokeCommand:
    def test_dry_run_of_the_kernel_phase(self):
        r = subprocess.run(
            [sys.executable, "chip_smoke.py", "--dry-run", "--phases", "kernels"],
            cwd=REPO, capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
        lines = r.stdout.strip().splitlines()
        assert json.loads(lines[-1])["ok"] is True
        summary = json.loads(lines[-2].split("summary: ", 1)[1])
        assert summary["dry_run"] is True and summary["claim"] is None
        assert set(summary["phases"]) == {"native", "device", "kernels"}
        cases = summary["phases"]["kernels"]["cases"]
        assert cases and all(c["ok"] for c in cases.values())
