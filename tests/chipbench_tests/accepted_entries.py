"""What a cell's test holds of the benchmark its PR found: the entries it
names, looked up by NAME (never by place), each as it stands, an end-to-end
metric's ``bound`` included, but for its ``workloads`` list, which a
``benchmark`` PR widens when a reader turns out to work in another cell
(whether an entry HAS a list is held). A ``benchmark`` PR that re-sets a
bound from its measured spreads changes the cell tests' digests in the same
commit and says why there (PR 55: ``ttft_ms_p50``, ``itl_ms_p50`` and
``out_tokens_per_s``, each from the spread of eighteen seeds: ``PERF.md`` 2).
An entry a ``benchmark`` PR retired (``decode_step_roofline``, PR 55) is
named by no test. No test of its own: the cell tests call ``digest``."""

import hashlib
import json

TOP = ("command", "paths", "run_seconds")
RE_SET = ("workloads",)


def held(bench: dict, accepted: dict) -> dict:
    """``accepted``: {section: "name name ..."}."""
    out = {key: bench[key] for key in TOP}
    for section, names in accepted.items():
        by_name = {entry["name"]: entry for entry in bench[section]}
        assert len(by_name) == len(bench[section])  # no name twice
        out[section] = {
            name: {**{k: v for k, v in by_name[name].items() if k not in RE_SET},
                   "listed": "workloads" in by_name[name]}
            for name in names.split()
        }
    return out


def digest(bench: dict, accepted: dict) -> str:
    text = json.dumps(held(bench, accepted), sort_keys=True)
    return hashlib.sha256(text.encode()).hexdigest()


def count(accepted: dict) -> int:
    return sum(len(names.split()) for names in accepted.values())
