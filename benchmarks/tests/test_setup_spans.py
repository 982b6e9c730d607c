"""The set-up split's arithmetic (``lib/setup_spans.py``) and its six
readers: the cases tier-1 runs (``tests/test_setup_spans.py``), run here
among the benchmark's own tests too — and the split of one REAL tiny cell,
walked on the CPU, against what ``run.py`` and the driver say of the same
run."""
import json
import os
import re

from conftest import run_cell
from tests.test_setup_spans import *  # noqa: F401,F403

AFTER_RUN = '''
import json, os, sys
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run
rc = run.main()
from benchmarks.lib import setup_spans
print("SPLIT " + json.dumps(setup_spans.of_this_process()), flush=True)
sys.exit(rc)
'''


def test_split_of_a_real_tiny_cell_agrees_with_the_run(bench_copy):
    """``run.py``'s own line carries no ``setup.*`` off the chip; the
    reducer, asked directly in the same process, gives all six, and they
    fit what the run and its driver printed."""
    script = os.path.join(bench_copy, "benchmarks", "after_run_split.py")
    if not os.path.exists(script):
        with open(script, "w") as f:
            f.write(AFTER_RUN)
    rc, out, err = run_cell(bench_copy, "tiny_train", trace=1,
                            script="after_run_split.py")
    assert rc == 0, err[-2000:]
    line = json.loads([ln for ln in out if ln.startswith('{"correct"')][-1])
    assert not any(k.startswith("setup.") for k in line["metrics"])
    (split,) = [json.loads(ln[6:]) for ln in out if ln.startswith("SPLIT ")]
    assert set(split) == {"build_s", "init_s", "step_compile_s",
                          "other_compile_s", "cache_misses",
                          "unattributed_s"}
    assert all(v >= 0.0 for v in split.values())
    assert split["init_s"] <= split["build_s"]
    setup_s = line["traced_end_to_end"]["setup_s"]["value"]
    assert (split["build_s"] + split["step_compile_s"]
            + split["unattributed_s"]) <= setup_s
    # the driver's two perf_counter reads round lower().compile()
    (said,) = [float(m.group(1)) for ln in out for m in [re.search(
        r"step compiled in ([0-9.]+)s", ln)] if m]
    assert abs(split["step_compile_s"] - said) <= 0.15 * said + 0.1
