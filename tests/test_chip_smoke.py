"""chip_smoke.py (ISSUE 22): the chip contract's CPU-checkable half.

Told nothing it must fail here (no TPU) without printing a result; told
``--cpu-rehearsal`` it walks the whole control flow at tiny size — the
trainer, both serve smokes, the probe child with every kernel phase under
the Pallas interpreter — and its last line parses to the result object.
(The four-virtual-device rehearsal of ``--chips 4`` is in
test_chip_smoke_multichip.py: its own file, so xdist's loadfile runs the
two long subprocesses side by side.)  None of this is a measurement: the
chip run is (``chiprun -- python3 chip_smoke.py``, CHANGES.md quotes it).
"""
import json
import os
import shutil
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, timeout=900):
    # the harness pins JAX_PLATFORMS=cpu; chip_smoke must not lean on it
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _result(proc):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_told_nothing_it_fails_without_a_tpu_and_prints_no_result():
    proc = _run([])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "not 'tpu'" in proc.stdout          # the trainer's own refusal


def test_alone_in_a_directory_it_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    for args in ([], ["--cpu-rehearsal"]):
        proc = _run(args, cwd=str(tmp_path), script=str(alone))
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


def test_the_parent_never_imports_jax():
    """One process per chip: the orchestrating half of the file must stay
    off jax and byol_tpu (which imports jax)."""
    import ast
    tree = ast.parse(open(SMOKE).read())
    top = [n for n in tree.body if isinstance(n, (ast.Import,
                                                  ast.ImportFrom))]
    names = {a.name.split(".")[0] for n in top if isinstance(n, ast.Import)
             for a in n.names} | {n.module.split(".")[0] for n in top
                                  if isinstance(n, ast.ImportFrom)}
    assert not names & {"jax", "byol_tpu", "numpy", "flax"}, names


def test_cpu_rehearsal_end_to_end():
    out = _result(_run(["--cpu-rehearsal"]))
    assert out == {"ok": True,
                   "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
