"""What the readers PR 54 added share: the window's growth of the decode
tables' counter beside the contexts' (``Engine.step_stats``, on in the traced
run only, all replicas together), and a reader that is there under another
metric's name (``sibling``: a metric named ``<reader>.<cell's mix>`` whose
reader takes no suffix is a file of its own that reads what that one reads).
None where the program does not count (a program from before the counter,
as the parent of the PR that added it)."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE_KEYS = ("attn_ctx_tokens", "decode_table_slots")


def table_deltas(run):
    out = dict.fromkeys(TABLE_KEYS, 0)
    for after, before in zip(run.step_after, run.step_before):
        for key in TABLE_KEYS:
            if key not in after or key not in before:
                return None
            out[key] += after[key] - before[key]
    return out


def sibling(name: str):
    """``read`` of ``layer_metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "layer_metrics", f"{name}.py"),
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
