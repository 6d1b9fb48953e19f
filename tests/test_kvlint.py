"""kvlint static-analysis + locktrace runtime-harness tests.

Per-checker fixture snippets that MUST flag and MUST pass, suppression
semantics, the committed-tree gate (the whole package lints clean — the
same invariant CI enforces), and the locktrace regression suite including
a synthetic ABBA lock-order inversion the harness must detect.
"""

from __future__ import annotations

import textwrap
import threading
from pathlib import Path

import pytest

from llm_d_kv_cache_manager_tpu.utils import locktrace
from tools.kvlint.core import REPO_ROOT, lint_paths


def _mini_repo(tmp_path: Path, **files: str) -> Path:
    """Lay out a throwaway repo root with the given rel-path -> source."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    return tmp_path


def _lint(root: Path, rel: str, rule: str):
    return lint_paths([str(root / rel)], rules=[rule], repo_root=root)


# ---------------------------------------------------------------------------
# monotonic-time
# ---------------------------------------------------------------------------


class TestMonotonicTime:
    def test_flags_wall_clock_deadline(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                import time
                def wait(timeout):
                    deadline = time.time() + timeout
                    return deadline
                """
            },
        )
        findings = _lint(root, "pkg/mod.py", "monotonic-time")
        assert len(findings) == 1
        assert "time.monotonic" in findings[0].message

    def test_monotonic_passes(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                import time
                def wait(timeout):
                    return time.monotonic() + timeout
                """
            },
        )
        assert _lint(root, "pkg/mod.py", "monotonic-time") == []

    def test_line_suppression(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                import time
                def stamp():
                    # wall clock crosses the wire here
                    return time.time()  # kvlint: disable=monotonic-time
                """
            },
        )
        assert _lint(root, "pkg/mod.py", "monotonic-time") == []

    def test_file_suppression_requires_explicit_form(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                # this module is all wire timestamps
                # kvlint: disable-file=monotonic-time
                import time
                def a():
                    return time.time()
                def b():
                    return time.time()
                """
            },
        )
        assert _lint(root, "pkg/mod.py", "monotonic-time") == []

    def test_standalone_comment_covers_next_line_only(self, tmp_path):
        # The flake8 noqa-above-the-line habit must not silently become a
        # file-wide suppression: only the next line is covered.
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                import time
                def a():
                    # wall clock crosses the wire  # kvlint: disable=monotonic-time
                    return time.time()
                def b():
                    return time.time()
                """
            },
        )
        findings = _lint(root, "pkg/mod.py", "monotonic-time")
        assert len(findings) == 1
        assert findings[0].line == 7  # only b()'s call still flagged

    def test_suppressing_one_rule_keeps_others(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                "pkg/mod.py": """
                import time
                def stamp():
                    return time.time()  # kvlint: disable=lock-discipline
                """
            },
        )
        assert len(_lint(root, "pkg/mod.py", "monotonic-time")) == 1


# ---------------------------------------------------------------------------
# knob-default
# ---------------------------------------------------------------------------

_ALLOWLIST = "tools/kvlint/knob_allowlist.txt"


class TestKnobDefault:
    def test_flags_on_by_default_config_field(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/cfg.py": """
                class FooConfig:
                    fancy_mode: bool = True
                    safe_mode: bool = False
                """,
            },
        )
        findings = _lint(root, "pkg/cfg.py", "knob-default")
        assert len(findings) == 1
        assert "FooConfig.fancy_mode" in findings[0].message

    def test_allowlist_entry_passes(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "FooConfig.fancy_mode  # sizing, reviewed\n",
                "pkg/cfg.py": """
                class FooConfig:
                    fancy_mode: bool = True
                """,
            },
        )
        assert _lint(root, "pkg/cfg.py", "knob-default") == []

    def test_off_values_pass(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/cfg.py": """
                from typing import Optional
                class FooConfig:
                    a: int = 0
                    b: float = 0.0
                    c: Optional[str] = None
                    d: bool = False
                    e: str = ""
                    f: str = "off"
                    g: str = "auto"
                """,
            },
        )
        assert _lint(root, "pkg/cfg.py", "knob-default") == []

    def test_field_default_literal_checked(self, tmp_path):
        # field(default=True) is the same knob as `= True` — must not slip
        # through the Constant-only fast path.
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/cfg.py": """
                from dataclasses import dataclass, field
                @dataclass
                class FooConfig:
                    sneaky_on: bool = field(default=True)
                    composite: list = field(default_factory=list)
                """,
            },
        )
        findings = _lint(root, "pkg/cfg.py", "knob-default")
        assert len(findings) == 1
        assert "FooConfig.sneaky_on" in findings[0].message

    def test_mistyped_target_is_usage_error(self, tmp_path):
        with pytest.raises(SystemExit):
            lint_paths([str(tmp_path / "no_such_dir")], repo_root=tmp_path)

    def test_flags_env_literal_default(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/env.py": """
                import os
                FANCY = os.environ.get("FANCY_MODE", "1")
                PAGES = int(os.environ.get("PAGES", 0))
                """,
            },
        )
        findings = _lint(root, "pkg/env.py", "knob-default")
        assert len(findings) == 1
        assert "env:FANCY_MODE" in findings[0].message

    def test_env_bool_helper_on_default_flagged(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/env.py": """
                def _env_bool(name, default):
                    import os
                    return os.environ.get(name, default) not in ("0", "")
                PUBLISH = _env_bool("PUBLISH_STUFF", "1")
                QUIET = _env_bool("QUIET_STUFF", "0")
                """,
            },
        )
        findings = _lint(root, "pkg/env.py", "knob-default")
        assert len(findings) == 1
        assert "env:PUBLISH_STUFF" in findings[0].message

    def test_non_literal_default_defers_to_config(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _ALLOWLIST: "",
                "pkg/env.py": """
                import os
                def load(cfg):
                    cfg.depth = int(os.environ.get("DEPTH", cfg.depth))
                """,
            },
        )
        assert _lint(root, "pkg/env.py", "knob-default") == []


# ---------------------------------------------------------------------------
# wire-append-only
# ---------------------------------------------------------------------------

_MANIFEST = "tools/kvlint/wire_manifest.json"
_WIRE_MOD = "kvcache/transfer/protocol.py"


def _wire_repo(tmp_path: Path, body: str, manifest: str) -> Path:
    return _mini_repo(
        tmp_path, **{_MANIFEST: manifest, _WIRE_MOD: body}
    )


_WIRE_OK = """
import msgpack

def encode_request(name, hashes, extra=None):
    arr = ["Tag", name, hashes]
    if extra is not None:
        arr.append(extra)
    return msgpack.packb(arr)
"""

_WIRE_MANIFEST_OK = """
{"kvcache/transfer/protocol.py":
  {"encode_request": {"arr": ["'Tag'", "name", "hashes", "extra"]}}}
"""


class TestWireAppendOnly:
    def test_matching_manifest_passes(self, tmp_path):
        root = _wire_repo(tmp_path, _WIRE_OK, _WIRE_MANIFEST_OK)
        assert _lint(root, _WIRE_MOD, "wire-append-only") == []

    def test_reorder_flagged(self, tmp_path):
        reordered = _WIRE_OK.replace(
            '["Tag", name, hashes]', '["Tag", hashes, name]'
        )
        root = _wire_repo(tmp_path, reordered, _WIRE_MANIFEST_OK)
        findings = _lint(root, _WIRE_MOD, "wire-append-only")
        assert len(findings) == 1
        assert "reorders" in findings[0].message

    def test_positional_insertion_flagged(self, tmp_path):
        inserted = _WIRE_OK.replace(
            '["Tag", name, hashes]', '["Tag", name, "NEW", hashes]'
        )
        root = _wire_repo(tmp_path, inserted, _WIRE_MANIFEST_OK)
        findings = _lint(root, _WIRE_MOD, "wire-append-only")
        assert len(findings) == 1
        assert "reorders" in findings[0].message

    def test_new_trailing_field_requires_manifest_update(self, tmp_path):
        grown = _WIRE_OK + (
            "\n\ndef encode_request2(name, hashes, extra=None, trace=None):\n"
            "    arr = ['Tag', name, hashes]\n"
            "    if extra is not None:\n"
            "        arr.append(extra)\n"
            "    if trace is not None:\n"
            "        arr.append(trace)\n"
            "    return msgpack.packb(arr)\n"
        )
        manifest = _WIRE_MANIFEST_OK.replace(
            '"encode_request":',
            '"encode_request2": {"arr": ["\'Tag\'", "name", "hashes", '
            '"extra"]}, "encode_request":',
        )
        root = _wire_repo(tmp_path, grown, manifest)
        findings = _lint(root, _WIRE_MOD, "wire-append-only")
        assert len(findings) == 1
        assert "grew trailing" in findings[0].message
        assert "['trace']" in findings[0].message

    def test_unknown_builder_flagged(self, tmp_path):
        root = _wire_repo(
            tmp_path, _WIRE_OK, '{"kvcache/transfer/protocol.py": {}}'
        )
        findings = _lint(root, _WIRE_MOD, "wire-append-only")
        assert len(findings) == 1
        assert "not in" in findings[0].message

    def test_removed_field_flagged(self, tmp_path):
        shrunk = _WIRE_OK.replace('["Tag", name, hashes]', '["Tag", name]')
        # manifest still pins hashes at position 2
        manifest = _WIRE_MANIFEST_OK.replace(', "extra"', "")
        root = _wire_repo(tmp_path, shrunk, manifest)
        findings = _lint(root, _WIRE_MOD, "wire-append-only")
        assert len(findings) == 1

    def test_method_builders_extracted(self, tmp_path):
        body = """
        class Beat:
            def to_tagged_union(self):
                arr = ["Beat", self.n]
                if self.draining:
                    arr.append(True)
                return arr
        """
        manifest = (
            '{"kvcache/transfer/protocol.py": {"Beat.to_tagged_union":'
            ' {"arr": ["\'Beat\'", "self.n", "True"]}}}'
        )
        root = _wire_repo(tmp_path, textwrap.dedent(body), manifest)
        assert _lint(root, _WIRE_MOD, "wire-append-only") == []


# ---------------------------------------------------------------------------
# metric-pin
# ---------------------------------------------------------------------------

_METRIC_MOD = "kvcache/metrics/collector.py"
_DOCS = "docs/observability.md"


class TestMetricPin:
    def test_uncatalogued_name_flagged(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _DOCS: "| `kvcache_known_total` | counter | — | known |\n",
                _METRIC_MOD: 'NAME = "kvcache_mystery_total"\n',
            },
        )
        findings = _lint(root, _METRIC_MOD, "metric-pin")
        assert len(findings) == 1
        assert "kvcache_mystery_total" in findings[0].message

    def test_catalogued_name_passes(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _DOCS: "| `kvcache_known_total` | counter | — | known |\n",
                _METRIC_MOD: 'NAME = "kvcache_known_total"\n',
            },
        )
        assert _lint(root, _METRIC_MOD, "metric-pin") == []

    def test_stale_catalog_row_flagged_in_full_run(self, tmp_path):
        root = _mini_repo(
            tmp_path,
            **{
                _DOCS: (
                    "| `kvcache_known_total` | counter | — | known |\n"
                    "| `kvcache_gone_total` | counter | — | removed |\n"
                ),
                _METRIC_MOD: 'NAME = "kvcache_known_total"\n',
                # the reverse check only runs when every metric module is
                # in scope this invocation
                "server/serve.py": "x = 1\n",
                "llm_d_kv_cache_manager_tpu/obs/__init__.py": "",
            },
        )
        findings = lint_paths(
            [str(root)], rules=["metric-pin"], repo_root=root
        )
        assert len(findings) == 1
        assert "kvcache_gone_total" in findings[0].message


# ---------------------------------------------------------------------------
# lock-discipline
# ---------------------------------------------------------------------------


class TestLockDiscipline:
    def _repo(self, tmp_path, body):
        return _mini_repo(tmp_path, **{"pkg/mod.py": body})

    def test_unguarded_write_flagged(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._views = 0  # guarded_by: _lock
                def bump(self):
                    self._views += 1
            """,
        )
        findings = _lint(root, "pkg/mod.py", "lock-discipline")
        assert len(findings) == 1
        assert "_views" in findings[0].message

    def test_guarded_write_passes(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._views = 0  # guarded_by: _lock
                def bump(self):
                    with self._lock:
                        self._views += 1
            """,
        )
        assert _lint(root, "pkg/mod.py", "lock-discipline") == []

    def test_wrong_lock_flagged(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._other_lock = threading.Lock()
                    self._views = 0  # guarded_by: _lock
                def bump(self):
                    with self._other_lock:
                        self._views += 1
            """,
        )
        assert len(_lint(root, "pkg/mod.py", "lock-discipline")) == 1

    def test_holds_annotation_trusted(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._views = 0  # guarded_by: _lock
                def _bump_locked(self):  # kvlint: holds=_lock
                    self._views += 1
                def bump(self):
                    with self._lock:
                        self._bump_locked()
            """,
        )
        assert _lint(root, "pkg/mod.py", "lock-discipline") == []

    def test_condition_alias(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._mu = threading.Lock()
                    self._work = threading.Condition(self._mu)
                    self._q = []  # guarded_by: _mu|_work
                def put(self, x):
                    with self._work:
                        self._q.append(x)
                def snap(self):
                    with self._mu:
                        return list(self._q)
            """,
        )
        assert _lint(root, "pkg/mod.py", "lock-discipline") == []

    def test_sleep_under_lock_flagged(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading, time
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                def nap(self):
                    with self._lock:
                        time.sleep(1)
            """,
        )
        findings = _lint(root, "pkg/mod.py", "lock-discipline")
        assert len(findings) == 1
        assert "time.sleep" in findings[0].message

    def test_zmq_recv_under_lock_flagged(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.sock = None
                def pull(self):
                    with self._lock:
                        return self.sock.recv_multipart()
            """,
        )
        assert len(_lint(root, "pkg/mod.py", "lock-discipline")) == 1

    def test_jax_dispatch_under_lock_flagged(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            import jax
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                def ship(self, x):
                    with self._lock:
                        return jax.device_put(x)
            """,
        )
        findings = _lint(root, "pkg/mod.py", "lock-discipline")
        assert len(findings) == 1
        assert "dispatch" in findings[0].message

    def test_nested_with_on_held_lock_keeps_outer_hold(self, tmp_path):
        # Re-entering an already-held RLock inside a holds= method must not
        # clear the hold for the code after the inner block.
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.RLock()
                    self._views = 0  # guarded_by: _lock
                def helper(self):  # kvlint: holds=_lock
                    with self._lock:
                        self._views += 1
                    self._views += 1  # still under the caller's hold
            """,
        )
        assert _lint(root, "pkg/mod.py", "lock-discipline") == []

    def test_init_exempt(self, tmp_path):
        root = self._repo(
            tmp_path,
            """
            import threading
            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._views = 0  # guarded_by: _lock
                    self._views = 1
            """,
        )
        assert _lint(root, "pkg/mod.py", "lock-discipline") == []


# ---------------------------------------------------------------------------
# kernel-abi
# ---------------------------------------------------------------------------

_ABI_MANIFEST = "tools/kvlint/kernel_abi.json"
_KERNEL_MOD = "ops/paged_attention.py"

_KERNEL_OK = """
def paged_attention(q, k_pages, v_pages, bt, sl, k_scale=None, fresh_k=None):
    q_blocked = q.reshape(1, 2, 2, 8)
    inputs = [bt, sl, q_blocked, k_pages, v_pages]
    if k_scale is not None:
        inputs.append(k_scale)
    if fresh_k is not None:
        inputs.append(fresh_k.reshape(1, 2, 1, 8))
    grid_spec = PrefetchScalarGridSpec(num_scalar_prefetch=2, grid=(1,))
    return inputs, grid_spec
"""

_ABI_OK = """
{"ops/paged_attention.py":
  {"paged_attention": {
    "num_scalar_prefetch": 2,
    "operands": ["bt", "sl", "q_blocked", "k_pages", "v_pages",
                 "k_scale", "fresh_k"]}}}
"""


def _abi_repo(tmp_path: Path, body: str, manifest: str) -> Path:
    return _mini_repo(
        tmp_path, **{_ABI_MANIFEST: manifest, _KERNEL_MOD: body}
    )


class TestKernelAbi:
    def test_matching_pin_passes(self, tmp_path):
        root = _abi_repo(tmp_path, _KERNEL_OK, _ABI_OK)
        assert _lint(root, _KERNEL_MOD, "kernel-abi") == []

    def test_variant_tail_reorder_flagged(self, tmp_path):
        # Fresh operands appended before the scales: compiles fine, reads
        # scales as fresh K inside the kernel — exactly what the pin is for.
        swapped = _KERNEL_OK.replace(
            """    if k_scale is not None:
        inputs.append(k_scale)
    if fresh_k is not None:
        inputs.append(fresh_k.reshape(1, 2, 1, 8))""",
            """    if fresh_k is not None:
        inputs.append(fresh_k.reshape(1, 2, 1, 8))
    if k_scale is not None:
        inputs.append(k_scale)""",
        )
        root = _abi_repo(tmp_path, swapped, _ABI_OK)
        findings = _lint(root, _KERNEL_MOD, "kernel-abi")
        assert len(findings) == 1
        assert "operand order" in findings[0].message

    def test_seed_list_reorder_flagged(self, tmp_path):
        root = _abi_repo(
            tmp_path,
            _KERNEL_OK.replace(
                "[bt, sl, q_blocked, k_pages, v_pages]",
                "[bt, sl, q_blocked, v_pages, k_pages]",
            ),
            _ABI_OK,
        )
        assert len(_lint(root, _KERNEL_MOD, "kernel-abi")) == 1

    def test_unpinned_new_operand_flagged(self, tmp_path):
        grown = _KERNEL_OK.replace(
            "grid_spec = PrefetchScalarGridSpec",
            "inputs.append(bt)\n    grid_spec = PrefetchScalarGridSpec",
        )
        root = _abi_repo(tmp_path, grown, _ABI_OK)
        findings = _lint(root, _KERNEL_MOD, "kernel-abi")
        assert len(findings) == 1
        assert "update" in findings[0].message

    def test_prefetch_count_change_flagged(self, tmp_path):
        root = _abi_repo(
            tmp_path,
            _KERNEL_OK.replace("num_scalar_prefetch=2", "num_scalar_prefetch=3"),
            _ABI_OK,
        )
        findings = _lint(root, _KERNEL_MOD, "kernel-abi")
        assert len(findings) == 1
        assert "num_scalar_prefetch" in findings[0].message

    def test_pinned_function_removed_flagged(self, tmp_path):
        root = _abi_repo(
            tmp_path,
            _KERNEL_OK.replace("def paged_attention", "def renamed_attention"),
            _ABI_OK,
        )
        findings = _lint(root, _KERNEL_MOD, "kernel-abi")
        assert len(findings) == 1
        assert "no longer exists" in findings[0].message

    def test_committed_manifest_pins_the_real_kernel(self):
        import json

        manifest = json.loads(
            (REPO_ROOT / "tools/kvlint/kernel_abi.json").read_text()
        )
        pin = manifest["llm_d_kv_cache_manager_tpu/ops/paged_attention.py"][
            "paged_attention"
        ]
        # The scalar-prefetch operands (the layer word among them since
        # the full-context call walks a lane's pages, PR 57) lead in EVERY
        # variant, and the quantized scales sit between the pages and the
        # fresh tail. The one function holds the one ``pallas_call``.
        assert list(manifest["llm_d_kv_cache_manager_tpu/ops/paged_attention.py"]) == [
            "paged_attention"
        ]
        assert pin["num_scalar_prefetch"] == 3
        assert pin["operands"][:3] == ["layer_word", "block_tables", "seq_lens"]
        ops = pin["operands"]
        assert ops.index("k_scale") > ops.index("v_pages")
        assert ops.index("v_scale") < ops.index("fresh_k")


# ---------------------------------------------------------------------------
# committed tree stays clean (the CI gate invariant)
# ---------------------------------------------------------------------------


class TestCommittedTree:
    def test_package_lints_clean(self):
        findings = lint_paths(
            [str(REPO_ROOT / "llm_d_kv_cache_manager_tpu")],
            repo_root=REPO_ROOT,
        )
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_wire_manifest_covers_all_builders(self):
        # Both wire modules must have at least their known builders pinned;
        # an empty manifest section would make the rule vacuous.
        import json

        manifest = json.loads(
            (REPO_ROOT / "tools/kvlint/wire_manifest.json").read_text()
        )
        assert set(manifest) == {
            "kvcache/transfer/protocol.py",
            "kvcache/kvevents/events.py",
        }
        assert "encode_request" in manifest["kvcache/transfer/protocol.py"]
        assert (
            "EventBatch.to_payload" in manifest["kvcache/kvevents/events.py"]
        )


# ---------------------------------------------------------------------------
# locktrace runtime harness
# ---------------------------------------------------------------------------


@pytest.fixture
def traced():
    """Activate lock tracing for one test; restore the session's state
    after (a LOCKTRACE=1 run keeps tracing on for the remaining tests)."""
    locktrace.activate()
    try:
        yield
    finally:
        locktrace.reset()
        if not locktrace.enabled():
            locktrace.deactivate()


class TestLockTrace:
    def test_abba_inversion_detected(self, traced):
        """Seeded ABBA regression: two locks taken in opposite orders by
        two threads — no deadlock occurs (the threads run sequentially),
        but the harness must flag the order inversion."""
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def forward():
            with lock_a:
                with lock_b:
                    pass

        def backward():
            with lock_b:
                with lock_a:
                    pass

        for fn in (forward, backward):
            t = threading.Thread(target=fn)
            t.start()
            t.join()

        violations = locktrace.violations()
        assert len(violations) == 1
        assert violations[0].kind == "lock-order-cycle"
        assert "ABBA" in violations[0].message
        with pytest.raises(AssertionError):
            locktrace.assert_clean()
        locktrace.reset()
        locktrace.assert_clean()  # consumed: the autouse gate stays green

    def test_consistent_order_is_clean(self, traced):
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def nested():
            with lock_a:
                with lock_b:
                    pass

        for _ in range(2):
            t = threading.Thread(target=nested)
            t.start()
            t.join()
        locktrace.assert_clean()

    def test_rlock_reentrancy_not_a_cycle(self, traced):
        rl = threading.RLock()
        with rl:
            with rl:
                pass
        locktrace.assert_clean()

    def test_same_class_plain_lock_nesting_flagged(self, traced):
        """Two NON-reentrant locks born at the same allocation site (one
        lock class, two instances) nested inside each other: same instance
        would self-deadlock, two instances are an unordered pair — either
        way a violation."""

        def make():
            return threading.Lock()  # one allocation site = one lock class

        a, b = make(), make()
        with a:
            with b:
                pass
        assert [v.kind for v in locktrace.violations()] == [
            "lock-order-cycle"
        ]
        locktrace.reset()

    def test_guarded_attr_unguarded_mutation_detected(self, traced):
        class Obj:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = 0  # guarded_by: _lock

        obj = Obj()
        locktrace.guard_attrs(obj, obj._lock, "state")
        with obj._lock:
            obj.state = 1  # guarded: fine

        def rogue():
            obj.state = 2  # unguarded cross-thread write

        t = threading.Thread(target=rogue)
        t.start()
        t.join()
        violations = locktrace.violations()
        assert [v.kind for v in violations] == ["unguarded-mutation"]
        assert "state" in violations[0].message
        locktrace.reset()

    def test_guard_is_per_instance_not_per_lock_class(self, traced):
        """Two locks born at the same allocation site must not alias each
        other's holds: holding instance A's lock does not satisfy a guard
        on instance B's state."""

        class Obj:
            def __init__(self):
                self._lock = threading.Lock()  # one site, many instances
                self.state = 0

        a, b = Obj(), Obj()
        locktrace.guard_attrs(b, b._lock, "state")

        def rogue():
            with a._lock:  # the WRONG instance's lock
                b.state = 1

        t = threading.Thread(target=rogue)
        t.start()
        t.join()
        assert [v.kind for v in locktrace.violations()] == [
            "unguarded-mutation"
        ]
        locktrace.reset()

    def test_condition_event_queue_survive_tracing(self, traced):
        # The harness must not break stdlib primitives built on locks.
        import queue

        cond = threading.Condition()
        with cond:
            cond.notify_all()
        ev = threading.Event()
        ev.set()
        assert ev.is_set()
        q: "queue.Queue[int]" = queue.Queue()
        q.put(7)
        assert q.get() == 7
        locktrace.assert_clean()

    def test_index_hammer_under_tracing(self, traced):
        """The PR-3 concurrency hammer shape, run under the harness: the
        in-memory index's two-level locking must produce no order cycles."""
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock import (
            Key,
            PodEntry,
        )
        from llm_d_kv_cache_manager_tpu.kvcache.kvblock.in_memory import (
            InMemoryIndex,
        )

        index = InMemoryIndex()
        errors: list = []

        def worker(tid: int):
            try:
                for i in range(25):
                    key = Key("m", i % 7)
                    pod = f"pod{tid % 3}"
                    op = (tid + i) % 4
                    if op == 0:
                        index.add([key], [PodEntry(pod, None)])
                    elif op == 1:
                        index.lookup([key], set())
                    elif op == 2:
                        index.evict(key, [PodEntry(pod, None)])
                    else:
                        index.evict_pod(pod)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        locktrace.assert_clean()
