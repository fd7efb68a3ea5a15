"""The xplane trace reader (`utils/xplane.py`) must decode real
``jax.profiler.trace`` output — it is the op-attribution half of the
profiling story (SURVEY.md §5; VERDICT r3 #2) and has no external
dependency to fall back on (the image's tensorboard profile plugin
cannot load its own protos).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from replication_faster_rcnn_tpu.utils.xplane import (
    find_xplane_files,
    format_table,
    op_table,
    parse_xspace,
)


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def f(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    f(x)  # compile outside the trace
    with jax.profiler.trace(d):
        for _ in range(3):
            out = f(x)
        jax.block_until_ready(out)
    return d


class TestXplaneReader:
    def test_finds_and_parses_planes(self, trace_dir):
        files = find_xplane_files(trace_dir)
        assert files, "jax wrote no xplane file"
        planes = parse_xspace(files[0])
        assert planes
        named = [p for p in planes if p.name]
        assert named, "no plane decoded a name"
        # at least one plane carries events with metadata names
        assert any(p.event_names and p.lines for p in planes)

    def test_op_table_aggregates_durations(self, trace_dir):
        rows = op_table(trace_dir, top=50)
        assert rows
        assert all(r["total_ms"] >= 0 for r in rows)
        assert all(r["count"] >= 1 for r in rows)
        # sorted by total time descending
        totals = [r["total_ms"] for r in rows]
        assert totals == sorted(totals, reverse=True)
        # the traced jit function appears somewhere in the table
        assert any("f" in str(r["op"]) or "jit" in str(r["op"]).lower()
                   for r in rows)

    def test_plane_filter_and_empty(self, trace_dir):
        assert op_table(trace_dir, plane_filter="no-such-plane") == []
        host = op_table(trace_dir, plane_filter="host", top=5)
        assert len(host) <= 5

    def test_format_table(self, trace_dir):
        txt = format_table(op_table(trace_dir, top=5))
        assert "total_ms" in txt and txt.count("\n") <= 5
        assert format_table([]) == "(no events)"

    def test_cli_trace_summary(self, trace_dir, tmp_path, capsys):
        import json

        from replication_faster_rcnn_tpu import cli

        out_json = str(tmp_path / "ops.json")
        rc = cli.main(["trace-summary", trace_dir, "--top", "7",
                       "--json", out_json])
        assert rc == 0
        assert "total_ms" in capsys.readouterr().out
        with open(out_json) as f:
            data = json.load(f)
        assert data["ops"] and len(data["ops"]) <= 7

    def test_cli_trace_summary_missing_dir(self, tmp_path, capsys):
        from replication_faster_rcnn_tpu import cli

        rc = cli.main(["trace-summary", str(tmp_path / "nope")])
        assert rc == 1

    def test_truncated_file_raises_loudly(self, trace_dir, tmp_path):
        src = find_xplane_files(trace_dir)[0]
        with open(src, "rb") as f:
            data = f.read()
        bad = tmp_path / "t.xplane.pb"
        bad.write_bytes(data[: len(data) // 2])
        with pytest.raises(ValueError, match="truncated"):
            parse_xspace(str(bad))

    def test_xplane_import_is_jax_free(self):
        """`cli trace-summary` is documented as jax-free (it never takes
        the chip from a process that holds it); that holds only if
        importing the parser doesn't drag jax in (utils/__init__ must
        stay lazy)."""
        import subprocess
        import sys

        code = (
            "import sys; "
            "import replication_faster_rcnn_tpu.utils.xplane; "
            "sys.exit(1 if 'jax' in sys.modules else 0)"
        )
        r = subprocess.run([sys.executable, "-S", "-c", code],
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode == 0, "importing utils.xplane pulled in jax"
