"""``chip_smoke.py --dry-run`` on the CPU's virtual devices (ISSUE 21), its
serving phases: one subprocess test of some 60-90 s, in a file of its own so
that a worker of the tier-1 run takes it alone. The kernel phase of the same
command (half of what was one test of 120-165 s) is
``tests/test_chip_smoke_dry_run_kernels.py``, the other bring-up invariants
are in ``tests/test_chip_bringup.py``.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, **env):
    return subprocess.run(
        [sys.executable, *cmd], cwd=REPO, capture_output=True, text=True,
        timeout=600, env={**os.environ, **env},
    )


class TestChipSmokeCommand:
    def test_dry_run_end_to_end_on_virtual_devices(self):
        """The explicit CPU rehearsal: same code, tiny preset, interpreter,
        four replicas on four virtual devices, then tp=4 vs tp=1."""
        r = _run(["chip_smoke.py", "--dry-run", "--phases", "serve,tp"])
        assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
        lines = r.stdout.strip().splitlines()
        last = json.loads(lines[-1])
        assert last["ok"] is True
        # never written under a device's name
        assert last["device"]["platform"] == "cpu"
        summary = json.loads(lines[-2].split("summary: ", 1)[1])
        assert summary["dry_run"] is True and summary["claim"] is None
        assert list(summary)[-1] == "claim"
        serve = summary["phases"]["serve"]
        assert serve["replicas"] == 4
        assert (serve["routing"]["routed_hit_rate"]
                > serve["routing"]["round_robin_hit_rate"])
        assert len({p["device"] for p in serve["placement"]}) == 4
        assert "tp" in summary["phases"] and "kernels" not in summary["phases"]
