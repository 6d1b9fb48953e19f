"""``tools/warm_start_probe.py`` (PR 57): how JAX's compile events are
gathered a program, and that a script run under ``--events`` is listened to.
The phases themselves need the chip."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools import warm_start_probe as probe

TRACE, LOWER, RETRIEVAL, COMPILE = probe.EVENTS


@pytest.fixture
def programs(monkeypatch):
    import jax.monitoring
    from jax._src import compilation_cache as cc

    monkeypatch.setattr(cc, "decompress_executable", lambda blob: blob * 2)
    monkeypatch.setattr(
        jax.monitoring, "register_event_duration_secs_listener", lambda fn: None)
    return probe.Programs()


def test_a_nested_trace_reports_first_and_the_outermost_is_kept(programs):
    programs._on(TRACE, 0.25, fun_name="paged_attention")
    programs._on(TRACE, 0.5, fun_name="decode_steps")
    programs._on(LOWER, 1.0, fun_name="decode_steps")
    programs._on("/jax/some/other/event", 9.0)
    programs._on(COMPILE, 2.0, fun_name="jit(decode_steps)")
    assert programs.rows == [
        {"program": "jit(decode_steps)", "trace": 0.5, "lower": 1.0, "compile": 2.0}
    ]
    assert programs.pending == {}


def test_a_retrieval_belongs_to_the_compile_that_reports_after_it(programs):
    from jax._src import compilation_cache as cc

    assert cc.decompress_executable(b"abc") == b"abcabc"  # the entry passes through
    programs._on(RETRIEVAL, 0.125)
    programs._on(COMPILE, 0.25, fun_name="jit(step)")
    (row,) = programs.rows
    assert row["program"] == "jit(step)" and row["retrieval"] == 0.125
    assert (row["entry_bytes"], row["executable_bytes"]) == (3, 6)
    assert 0.0 <= row["load"] < 5.0  # since the entry was unpacked


def test_a_script_under_events_prints_a_line_a_program(tmp_path):
    script = tmp_path / "script.py"
    script.write_text(textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        assert sys.argv == [__file__, "--flag", "7"], sys.argv
        if __name__ == "__main__":
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """))
    out = subprocess.run(
        [sys.executable, probe.__file__, "--events", "--", str(script), "--flag", "7"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line.split(" ", 1)[1])
            for line in out.stdout.splitlines() if line.startswith("[program]")]
    assert any("lambda" in r["program"] and r["compile"] > 0 for r in rows)
