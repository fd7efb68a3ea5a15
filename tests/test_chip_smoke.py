"""`chip_smoke.py` is the quickest proof that the system still starts on
the chip — so on a machine without one it must fail, not carry on: these
tests pin the two failure cases of its contract that a CPU host can see.
(The passing case needs a TPU; it is run through the chip tool.)"""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cwd, script):
    return subprocess.run(
        [sys.executable, script], cwd=cwd, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_without_a_tpu_exits_nonzero_naming_the_platform():
    r = _run(REPO, SMOKE)
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""  # no result line, nothing was run


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(SMOKE, tmp_path)
    r = _run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert "cannot import the program" in r.stderr
    assert '"ok"' not in r.stdout


def test_it_has_no_cpu_path_it_was_not_asked_for():
    """The only way past the platform check is the `--tiny` argument: no
    environment variable, and nothing that picks the CPU backend."""
    with open(SMOKE) as f:
        src = f.read()
    assert src.count("args.tiny") >= 1
    assert "os.environ" not in src
    assert "jax_platforms" not in src.split('"""', 2)[2]  # code, not docstring


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses any other last line: `ok` and `device`, and in
    `device` `platform`, `kind` (text) and `count` (a whole number). What
    else the smoke knows (claim, tiny, timings) goes to chip_smoke.json."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 0}
    )
    assert "\n" not in line
    out = json.loads(line)
    assert list(out) == ["ok", "device"] and out["ok"] is True
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with open(SMOKE) as f:
        src = f.read()
    # it is the last thing main() prints
    assert src.rstrip().split("return 0")[0].rstrip().endswith(
        "print(result_line(device), flush=True)"
    )
