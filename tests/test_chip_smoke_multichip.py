"""chip_smoke.py --chips 4, rehearsed on four virtual CPU devices (ISSUE
22): the three-arm comparison — one-device mesh, data=4, data=4 with
--zero1 on — same seed and batch, one process driving all four devices.
Kept apart from test_chip_smoke.py so the two long subprocesses run on
different xdist workers."""
from tests.test_chip_smoke import _result, _run


def test_four_virtual_device_rehearsal_of_the_multichip_comparison():
    proc = _run(["--cpu-rehearsal", "--chips", "4"])
    out = _result(proc)
    assert out["ok"] is True and out["device"]["count"] == 4
    # only the three-arm comparison ran: no trainer, no server
    assert "== train" not in proc.stdout and "== serve" not in proc.stdout
    for arm in ("one-device:", "data=4:", "data=4 zero1:"):
        assert arm in proc.stdout
    assert "momentum leaves split 1/4 each over four distinct devices: ok" \
        in proc.stdout
